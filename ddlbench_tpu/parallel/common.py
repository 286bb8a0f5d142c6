"""Shared train-step machinery: loss, SGD with momentum/weight-decay, LR schedule.

Optimizer semantics follow the reference drivers: plain SGD+momentum
(benchmark/mnist/mnist_pytorch.py:153-156), imagenet adds weight decay 1e-4 and
step decay /10 every 30 epochs (benchmark/imagenet/imagenet_pytorch.py:44-50,
225-229). Implemented directly (not via optax) so the same update rule applies
unchanged to packed flat-vector stage parameters in the pipeline strategies.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ddlbench_tpu.telemetry import scopes


@scopes.scope(scopes.LOSS)
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       smoothing: float = 0.0) -> jax.Array:
    """Mean CE over valid label positions; works for classification
    (logits [B, C], labels [B]) and LM heads (logits [B, T, V], labels [B, T]).

    Positions with ``labels < 0`` are ignored (the seq2seq workload masks
    source-segment positions this way). ``smoothing`` is GNMT-style label
    smoothing (reference seq2seq/train/smoothing.py semantics: smoothed
    target = (1-s) on the gold label, s spread uniformly): loss_tok =
    (1-s)*NLL(gold) - s*mean_v(logp_v). For all-valid labels and s=0 this is
    the plain mean CE.
    """
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    if smoothing:
        nll = (1.0 - smoothing) * nll - smoothing * jnp.mean(logp, axis=-1)
    return jnp.sum(nll * mask) / jnp.maximum(1.0, jnp.sum(mask))


@scopes.scope(scopes.LOSS)
def correct_and_count(logits: jax.Array, labels: jax.Array):
    """(correct int32, valid-position count int32) for eval accumulation."""
    ok = (jnp.argmax(logits, axis=-1) == labels) & (labels >= 0)
    return (jnp.sum(ok.astype(jnp.int32)),
            jnp.sum((labels >= 0).astype(jnp.int32)))


def correct_topk(logits: jax.Array, labels: jax.Array, k: int = 5) -> jax.Array:
    """Count of valid positions whose label is in the top-k logits (prec@k,
    PipeDream eval parity — main_with_runtime.py:639-653).

    Tie handling matches torch.topk's selection order (value descending,
    index ascending): the label ranks after every strictly-greater logit and
    after equal logits at smaller class indices — so degenerate/constant
    logits report ~k/V, not 1.0.
    """
    k = min(k, logits.shape[-1])
    safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)
    higher = jnp.sum((logits > gold).astype(jnp.int32), axis=-1)
    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    tie_before = jnp.sum(
        ((logits == gold) & (idx < safe[..., None])).astype(jnp.int32), axis=-1
    )
    ok = (higher + tie_before < k) & (labels >= 0)
    return jnp.sum(ok.astype(jnp.int32))


def accum_loss_and_grads(model, params, model_state, x, y, compute_dtype,
                         aux_weight, smoothing, fused, accum_steps: int,
                         remat: bool = False, obj_scale=None):
    """K-way gradient accumulation: split the leading batch axis into K
    micro-steps, scan value_and_grad over them, and average the gradients
    weighted by each micro-step's valid-label count (exact K=1 equivalence;
    uniform weights — Horovod ``DistributedOptimizer(op=hvd.Average,
    backward_passes_per_step=K)`` semantics, imagenet_horovod.py:131-139 —
    whenever all labels are valid, which is every reference workload; the
    matching lr x K scaling lives in train/loop.py). BatchNorm state threads
    sequentially through the micro-steps, exactly as K separate batches
    would. Returns (loss, ce, (correct, valid), new_state, grads).
    """
    K = accum_steps
    B = x.shape[0]
    assert B % K == 0, f"batch {B} not divisible by grad_accum_steps {K}"
    # Micro-step losses are means over that step's VALID label positions, so
    # the K-step average only equals the K=1 full-batch gradient when every
    # micro-step has the same valid count. Weighting each micro-gradient by
    # its valid count restores exact K=1 equivalence for masked token/seq2seq
    # workloads; for image workloads (all labels valid — the only case the
    # reference's backward_passes_per_step ever sees) the weights are uniform
    # and this IS Horovod's equal-weight average.
    # Micro-step k takes every K-th row (reshape [B//K, K, ...], index axis
    # 1): with the batch sharded on axis 0 this keeps each micro-batch's rows
    # local to their device — Horovod's per-worker accumulation — whereas a
    # [K, B//K] leading split would put each micro-step on a fraction of the
    # devices and force a resharding collective per micro-step.
    xs = x.reshape(B // K, K, *x.shape[1:])
    ys = y.reshape(B // K, K, *y.shape[1:])

    from jax import lax

    def step(carry, k):
        st, gsum = carry
        xk = lax.dynamic_index_in_dim(xs, k, axis=1, keepdims=False)
        yk = lax.dynamic_index_in_dim(ys, k, axis=1, keepdims=False)

        def f(p):
            obj, ce, stats, new_st = loss_with_moe_aux(
                model, p, st, xk, yk, True, compute_dtype, aux_weight,
                smoothing, fused, remat)
            if obj_scale is not None:  # stability guard: loss scaling /
                obj = obj * obj_scale  # nan-grad poison carrier
            return obj, (ce, stats, new_st)

        (obj, (ce, (corr, valid), new_st)), g = jax.value_and_grad(
            f, has_aux=True)(params)
        wk = valid.astype(jnp.float32)
        gsum = jax.tree.map(lambda a, b: a + wk * b, gsum, g)
        return (new_st, gsum), (obj, ce, corr, valid)

    init = (model_state, jax.tree.map(jnp.zeros_like, params))
    (new_state, gsum), (objs, ces, corrs, valids) = lax.scan(
        step, init, jnp.arange(K))
    wks = valids.astype(jnp.float32)
    total = jnp.maximum(1.0, jnp.sum(wks))
    grads = jax.tree.map(lambda g: g / total, gsum)
    return (jnp.sum(objs * wks) / total, jnp.sum(ces * wks) / total,
            (jnp.sum(corrs), jnp.sum(valids)), new_state, grads)


def loss_and_grads(model, cfg, params, model_state, x, y, compute_dtype,
                   smoothing, obj_scale=None):
    """One-apply training loss + gradients, dispatching on
    cfg.grad_accum_steps (the shared core of the single/dp/tp/fsdp train
    steps). Returns (ce, (correct, valid), new_state, grads).

    ``obj_scale`` (stability guard) multiplies the training OBJECTIVE only
    — loss scaling plus the nan-grad poison carrier; the returned ``ce``
    metric and the gradients' downstream unscaling are the caller's."""
    if cfg.grad_accum_steps > 1:
        _, ce, stats, new_state, grads = accum_loss_and_grads(
            model, params, model_state, x, y, compute_dtype,
            cfg.moe_aux_weight, smoothing, cfg.fused_head_loss,
            cfg.grad_accum_steps, cfg.remat_layers, obj_scale=obj_scale)
        return ce, stats, new_state, grads

    def loss_fn(p):
        loss, ce, stats, new_state = loss_with_moe_aux(
            model, p, model_state, x, y, True, compute_dtype,
            cfg.moe_aux_weight, smoothing, fused=cfg.fused_head_loss,
            remat=cfg.remat_layers)
        if obj_scale is not None:
            loss = loss * obj_scale
        return loss, (ce, stats, new_state)

    (_, (ce, stats, new_state)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return ce, stats, new_state, grads


def make_optimizer(cfg):
    """(init, update) for cfg.resolved_optimizer(), torch semantics.

    * "sgd": torch.optim.SGD — buf = mu*buf + (grad + wd*p); p -= lr*buf
      (the reference's image drivers, mnist_pytorch.py:153-156).
    * "adam": torch.optim.Adam — the reference's translation runtime trains
      with AdamWithWeightStashing (runtime/adam.py,
      translation/main_with_runtime.py:251-256); weight decay is the L2 form
      (added to the gradient), betas/eps from cfg.

    State is a dict pytree ({"m"} or {"m", "v", "step"}) whose m/v leaves
    mirror params — so the same update serves per-layer pytrees AND the
    pipeline strategies' packed row vectors. ``init(params, step_like=None)``
    lets pipelines shape the step counter per stage row (e.g. [S, 1]) so
    every optimizer-state leaf shares the params' stage sharding; the update
    broadcasts it.
    """
    name = cfg.resolved_optimizer()
    mom = cfg.resolved_momentum()
    wd = cfg.resolved_weight_decay()
    b1, b2, eps = cfg.adam_beta1, cfg.adam_beta2, cfg.adam_eps

    zeros = lambda params: jax.tree.map(jnp.zeros_like, params)

    if name == "sgd":

        def init(params, step_like=None):
            return {"m": zeros(params)}

        @scopes.scope(scopes.OPTIMIZER)
        def update(params, grads, state, lr):
            def upd(p, g, m):
                g = g.astype(p.dtype)
                if wd:
                    g = g + wd * p
                m2 = mom * m + g
                return p - lr * m2, m2

            out = jax.tree.map(upd, params, grads, state["m"])
            new_p = jax.tree.map(lambda o: o[0], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            new_m = jax.tree.map(lambda o: o[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
            return new_p, {"m": new_m}

        return init, update

    def init(params, step_like=None):
        step = (jnp.zeros((), jnp.int32) if step_like is None
                else jnp.zeros(step_like, jnp.int32))
        return {"m": zeros(params), "v": zeros(params), "step": step}

    @scopes.scope(scopes.OPTIMIZER)
    def update(params, grads, state, lr):
        step = state["step"] + 1
        stepf = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** stepf
        bc2 = 1.0 - b2 ** stepf

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            if wd:
                g = g + wd * p
            m2 = b1 * m + (1.0 - b1) * g
            v2 = b2 * v + (1.0 - b2) * jnp.square(g)
            denom = jnp.sqrt(v2) / jnp.sqrt(bc2) + eps
            return p - (lr / bc1) * m2 / denom, m2, v2

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"m": pick(1), "v": pick(2), "step": step}

    return init, update


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_keepgrad(x, axis):
    """``lax.psum`` whose backward is the identity (pbroadcast semantics).

    Inside shard_map, differentiating a psum'd LOSS must seed each device's
    local backward with the replicated cotangent unchanged: every device
    already holds the same seed (e.g. 1/global_count), and the cross-device
    gradient sum happens once, explicitly, on the gradients themselves
    (psum_scatter in the dp sharded engine). Use this for aggregates whose
    cotangent is replicated (loss sums); aggregates with genuinely
    per-device partial cotangents (sync-BN batch statistics) need the
    mirrored reduction in models/layers.sync_batch_mean instead.
    """
    from jax import lax

    return lax.psum(x, axis)


def _psum_keepgrad_fwd(x, axis):
    from jax import lax

    return lax.psum(x, axis), None


def _psum_keepgrad_bwd(axis, _res, ct):
    # the primal was varying over the psum'd axes and the cotangent of the
    # (invariant) sum is not: custom_vjp wants the bwd output in the
    # primal's VMA type, and the cast to varying is the identity per device
    return (vary(ct, (axis,) if isinstance(axis, str) else tuple(axis)),)


psum_keepgrad.defvjp(_psum_keepgrad_fwd, _psum_keepgrad_bwd)


class FlatMeta(NamedTuple):
    """Packing recipe for one pytree <-> one flat f32 vector.

    ``length`` is the unpadded element count; ``padded`` rounds it up so a
    'data'-axis shard is a contiguous equal slice per device. The pad tail
    is mathematically inert through both SGD and Adam: zero params with
    zero grads update to zero (Adam's denominator bottoms out at eps).

    Bucketing (``--comm-buckets K``, the dp comm/compute-overlap engine):
    the flat vector is the concatenation of K contiguous, LEAF-ALIGNED
    buckets, each padded to a multiple of ``world`` so every bucket shards
    into equal contiguous per-device slices and can ride its own collective
    (the per-bucket reduce-scatters/all-gathers are what the latency-hiding
    scheduler interleaves with backward/forward compute).
    ``bucket_leaves[b]`` is the (start, stop) leaf range of bucket b,
    ``bucket_padded[b]`` its padded element count, ``bucket_offsets[b]``
    its start offset in the flat vector; ``padded == sum(bucket_padded)``.
    With one bucket the layout is EXACTLY the pre-bucketing one (single
    tail pad), so ``--comm-buckets 1`` compiles the same program as before.
    Bucketing only moves where pad zeros sit between leaves — never the
    leaf values or any reduction order within a bucket — which is what
    keeps the bucketed f32 path bitwise-pinned to the monolithic one.
    """

    treedef: object
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    length: int
    padded: int
    bucket_leaves: tuple = ((0, 0),)
    bucket_padded: tuple = (0,)
    bucket_offsets: tuple = (0,)

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_padded)


def _bucket_bounds(group_sizes, buckets: int):
    """Greedy contiguous split of ``group_sizes`` (elements per leaf group)
    into <= ``buckets`` groups-aligned chunks balancing element counts.
    Returns group-index boundaries [0, ..., len(group_sizes)]."""
    total = sum(group_sizes)
    buckets = max(1, min(buckets, len(group_sizes) or 1))
    bounds = [0]
    cum = 0  # elements before group i (boundary targets are cumulative)
    acc = 0  # elements in the currently-open bucket (must stay nonzero:
    #          an empty bucket would reduce-scatter a zero-size shard)
    for i, s in enumerate(group_sizes):
        remaining_groups = len(group_sizes) - i
        remaining_buckets = buckets - len(bounds) + 1
        # place boundary k where the CUMULATIVE element count crosses
        # k/buckets of the total (per-boundary fair-share target — a
        # per-bucket threshold drifts: one oversized bucket inflates
        # every later one), but never leave fewer groups than buckets
        # still to fill
        if (len(bounds) <= buckets - 1 and acc > 0
                and (cum >= total * len(bounds) / buckets
                     or remaining_groups <= remaining_buckets)):
            bounds.append(i)
            acc = 0
        cum += s
        acc += s
    bounds.append(len(group_sizes))
    return bounds


def flat_meta(params, world: int, buckets: int = 1,
              leaf_groups=None) -> FlatMeta:
    """Works on concrete leaves and jax.eval_shape ShapeDtypeStructs.

    ``buckets`` splits the packed vector into contiguous leaf-aligned
    buckets (see FlatMeta); ``leaf_groups`` optionally gives the leaf count
    of each alignment group (e.g. leaves per model layer) so bucket
    boundaries fall on LAYER boundaries — the backward then finishes a
    bucket's gradients as one contiguous stretch of layers unwinds. With
    no groups every leaf is its own group. ``buckets=1`` reproduces the
    pre-bucketing layout exactly.
    """
    import math

    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(math.prod(s) for s in shapes)
    length = int(sum(sizes))

    if leaf_groups is None:
        leaf_groups = [1] * len(leaves)
    assert sum(leaf_groups) == len(leaves), (leaf_groups, len(leaves))
    group_sizes = []
    li = 0
    for g in leaf_groups:
        group_sizes.append(int(sum(sizes[li:li + g])))
        li += g
    # empty-parameter groups (flatten/pool layers) can never host a
    # boundary worth having; merging them right keeps buckets non-trivial
    gbounds = _bucket_bounds(group_sizes, buckets)
    leaf_starts = [0]
    for g in leaf_groups:
        leaf_starts.append(leaf_starts[-1] + g)

    bucket_leaves, bucket_padded, bucket_offsets = [], [], []
    off = 0
    for b in range(len(gbounds) - 1):
        l0 = leaf_starts[gbounds[b]]
        l1 = leaf_starts[gbounds[b + 1]]
        blen = int(sum(sizes[l0:l1]))
        bpad = -(-blen // world) * world if blen else 0
        if bpad == 0 and bucket_leaves:
            # fold an empty bucket into its predecessor
            bucket_leaves[-1] = (bucket_leaves[-1][0], l1)
            continue
        bucket_leaves.append((l0, l1))
        bucket_padded.append(bpad)
        bucket_offsets.append(off)
        off += bpad
    if not bucket_leaves:  # degenerate: a model with zero parameters
        bucket_leaves, bucket_padded, bucket_offsets = [(0, 0)], [0], [0]
    padded = int(sum(bucket_padded))
    return FlatMeta(treedef, shapes, dtypes, sizes, length, padded,
                    tuple(bucket_leaves), tuple(bucket_padded),
                    tuple(bucket_offsets))


def pack_flat(tree, meta: FlatMeta) -> jax.Array:
    """Concatenate the tree's raveled f32 leaves into one [padded] vector
    (bucket-padded layout: each bucket's leaves then its pad zeros).

    The single-bucket path is kept byte-for-byte the pre-bucketing program
    (concat + one tail pad) — ``--comm-buckets 1`` must compile exactly
    the monolithic engine."""
    leaves = jax.tree.leaves(tree)
    if meta.num_buckets == 1:
        flat = jnp.concatenate([l.astype(jnp.float32).ravel()
                                for l in leaves])
        return jnp.pad(flat, (0, meta.padded - meta.length))
    parts = []
    for (l0, l1), bpad in zip(meta.bucket_leaves, meta.bucket_padded):
        parts.extend(l.astype(jnp.float32).ravel() for l in leaves[l0:l1])
        blen = int(sum(meta.sizes[l0:l1]))
        if bpad > blen:
            parts.append(jnp.zeros((bpad - blen,), jnp.float32))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)


def unpack_flat(flat: jax.Array, meta: FlatMeta):
    """Inverse of pack_flat (drops the pads, restores leaf dtypes).

    Each leaf is sliced from ITS bucket's stretch of the flat vector only —
    under the overlapped dp engine the buckets arrive as separate
    all-gathers, so this dataflow lets the forward's first layers start on
    early buckets while late buckets are still on the wire.
    """
    out = []
    for (l0, l1), boff in zip(meta.bucket_leaves, meta.bucket_offsets):
        off = boff
        for i in range(l0, l1):
            size, shape, dtype = meta.sizes[i], meta.shapes[i], meta.dtypes[i]
            out.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
    return jax.tree.unflatten(meta.treedef, out)


def bucket_content_lengths(meta: FlatMeta):
    """Unpadded element count of each bucket — the piece of the LOGICAL
    (concatenated-leaf, pad-free) vector that bucket b carries.

    Leaf-aligned metas (dp ``flat_meta``) sum their leaf sizes; row metas
    (``row_flat_meta``, empty ``sizes``) tile the contiguous [0, length)
    row, so a bucket's content is its overlap with that range. In both
    layouts ``flat = concat_b(logical[c_b:c_b+len_b] + zeros(pad_b))``
    with ``c_b = cumsum(len_b)`` — the invariant train/reshard.py's
    world-size permutation is built on.
    """
    if meta.sizes:
        return [int(sum(meta.sizes[l0:l1])) for l0, l1 in meta.bucket_leaves]
    return [max(0, min(meta.length, off + bp) - off)
            for off, bp in zip(meta.bucket_offsets, meta.bucket_padded)]


def bucket_slice(flat: jax.Array, meta: FlatMeta, b: int) -> jax.Array:
    """Bucket b's [bucket_padded[b]] stretch of a packed flat vector."""
    return flat[meta.bucket_offsets[b]:
                meta.bucket_offsets[b] + meta.bucket_padded[b]]


def unpack_buckets(bucket_arrays, meta: FlatMeta):
    """Pytree from per-bucket flat stretches (each [bucket_padded[b]]).

    The overlapped dp engine's forward: every bucket arrives as its own
    all-gather, and each leaf depends ONLY on its bucket's array — the
    dataflow that lets the first layers start on early buckets while late
    buckets are still on the wire.
    """
    out = []
    for (l0, l1), arr in zip(meta.bucket_leaves, bucket_arrays):
        off = 0
        for i in range(l0, l1):
            size, shape, dtype = meta.sizes[i], meta.shapes[i], meta.dtypes[i]
            out.append(arr[off:off + size].reshape(shape).astype(dtype))
            off += size
    return jax.tree.unflatten(meta.treedef, out)


def to_device_major(flat: jax.Array, meta: FlatMeta, world: int) -> jax.Array:
    """Bucket-layout [padded] vector -> the overlapped engine's DEVICE-MAJOR
    layout: concat over devices of (concat over buckets of that device's
    1/world bucket slice).

    This is the layout per-bucket ``psum_scatter`` outputs naturally produce
    when a device's shard is the concatenation of its bucket slices, and
    the layout the engine keeps params in BETWEEN steps (sharding P('data')
    makes device d own exactly its stretch). With one bucket it is the
    identity permutation.
    """
    parts = []
    for d in range(world):
        for b in range(meta.num_buckets):
            o = meta.bucket_offsets[b]
            bl = meta.bucket_padded[b] // world
            parts.append(flat[o + d * bl:o + (d + 1) * bl])
    return jnp.concatenate(parts) if parts else flat


def from_device_major(flat_dm: jax.Array, meta: FlatMeta,
                      world: int) -> jax.Array:
    """Inverse of :func:`to_device_major` (device-major -> bucket layout)."""
    shard_len = meta.padded // world
    parts = []
    for b in range(meta.num_buckets):
        bo = meta.bucket_offsets[b] // world
        bl = meta.bucket_padded[b] // world
        parts.extend(flat_dm[d * shard_len + bo:d * shard_len + bo + bl]
                     for d in range(world))
    return jnp.concatenate(parts) if parts else flat_dm


def row_flat_meta(length: int, world: int, buckets: int = 1) -> FlatMeta:
    """FlatMeta for an ALREADY-FLAT packed row (the pipeline strategies'
    [S, L] stage-parameter rows), sharded 1/world per device over the pipe
    mesh's 'data' axis in ``buckets`` contiguous pieces.

    The row has no pytree to align to (pack_stages already concatenated
    and padded the stage's leaves to a common L), so buckets are
    near-equal contiguous stretches, each padded-aligned to a multiple of
    ``world`` — the same per-bucket equal-slice property the dp engine's
    leaf-aligned buckets have, which is all to/from_device_major and the
    per-bucket psum_scatter/all_gather need. ``treedef``/``shapes`` are
    empty: unpacking goes through the stage unravels, not unpack_flat."""
    units = -(-max(1, length) // world)  # world-sized units in the row
    buckets = max(1, min(buckets, units))
    base, rem = divmod(units, buckets)
    bucket_padded = []
    bucket_offsets = []
    off = 0
    for b in range(buckets):
        u = base + (1 if b < rem else 0)
        bucket_padded.append(u * world)
        bucket_offsets.append(off)
        off += u * world
    return FlatMeta(None, (), (), (), int(length), int(off),
                    ((0, 0),) * buckets, tuple(bucket_padded),
                    tuple(bucket_offsets))


def device_major_perm(meta: FlatMeta, world: int):
    """Index permutation ``p`` with ``flat[p] == to_device_major(flat)``
    (and its inverse) as numpy arrays — the pipeline strategies apply the
    device-major relayout along the last axis of the packed [.., S, L]
    stage matrix via one jnp.take with a constant index vector."""
    import numpy as np

    idx = []
    for d in range(world):
        for b in range(meta.num_buckets):
            o = meta.bucket_offsets[b]
            bl = meta.bucket_padded[b] // world
            idx.extend(range(o + d * bl, o + (d + 1) * bl))
    perm = np.asarray(idx, np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size, dtype=np.int32)
    return perm, inv


def shard_bucket_slice(shard: jax.Array, meta: FlatMeta, world: int,
                       b: int) -> jax.Array:
    """Bucket b's segment of one device's [padded/world] shard.

    The sharded layout is per-bucket: a device's shard is the concatenation
    over buckets of its 1/world slice of each bucket, so bucket b occupies
    ``bucket_offsets[b]/world : (bucket_offsets[b]+bucket_padded[b])/world``
    of the local shard.
    """
    o = meta.bucket_offsets[b] // world
    return shard[o:o + meta.bucket_padded[b] // world]


# ---- int8 wire path (EQuARX-style block-scaled quantized collectives) ----


def sum_safe_qmax(world: int) -> int:
    """Largest per-device quantized magnitude whose WORLD-device sum still
    fits int8: the wire collective (psum / psum_scatter) accumulates IN
    int8, so each device may contribute at most 127 // world — e.g. +-15
    on an 8-way mesh, +-63 on a 2-way one. The lost bits are the price of
    summing on the wire (EQuARX pays the same with block headroom);
    stochastic rounding keeps the estimate unbiased regardless.
    """
    if world > 127:
        raise ValueError(
            f"int8 wire supports up to 127 devices (got {world}): the "
            f"in-dtype collective sum would overflow")
    return max(1, 127 // world)


def stochastic_round_int8(v: jax.Array, key, qmax: int = 127) -> jax.Array:
    """Unbiased stochastic rounding of ``v`` (already scaled into
    [-qmax, qmax]) to int8: floor(v) + Bernoulli(frac(v)).

    E[result] == v elementwise for any v in range, which is what keeps the
    quantized gradient sum an unbiased estimate of the f32 sum; the
    rounding noise is the ONLY stochastic element of the int8 wire and is
    fully determined by ``key`` (derived from the run seed + step counter +
    device/bucket indices in parallel/dp.py), so runs replay bitwise.
    The clip at ``qmax`` only defends against float-division round-off
    pushing an exact-absmax element one ulp past the bound — in-range
    values are never clipped, so no bias is introduced.
    """
    lo = jnp.floor(v)
    frac = v - lo
    u = jax.random.uniform(key, v.shape, dtype=jnp.float32)
    r = lo + (u < frac).astype(jnp.float32)
    return jnp.clip(r, -float(qmax), float(qmax)).astype(jnp.int8)


def quantize_int8(g: jax.Array, key, qmax: int = 127, absmax=None):
    """(q int8, scale f32): absmax-scaled stochastic int8 quantization.

    ``scale = absmax/qmax`` maps the largest-magnitude element to exactly
    +-qmax (representable, zero rounding error); an all-zero block gets
    scale 1 so the division below stays finite. ``absmax`` may be supplied
    by the caller (the dp engine psums a GLOBAL absmax so every device
    shares one scale — a per-device scale could not be summed on the
    wire). Dequantize with ``q.astype(f32) * scale`` — exact for values
    that are integer multiples of the scale (the absmax round-trip
    property pinned by tests/test_comm_overlap.py).
    """
    if absmax is None:
        absmax = jnp.max(jnp.abs(g))
    scale = jnp.where(absmax > 0,
                      absmax.astype(jnp.float32) / qmax, jnp.float32(1.0))
    return stochastic_round_int8(g / scale, key, qmax), scale


def opt_state_sharding(cfg, param_sharding, scalar_sharding):
    """Sharding pytree matching make_optimizer's state: m/v follow the
    params' sharding (which may itself be a pytree), step is scalar-like."""
    sh = {"m": param_sharding}
    if cfg.resolved_optimizer() == "adam":
        sh["v"] = param_sharding
        sh["step"] = scalar_sharding
    return sh


def step_decay_lr(base_lr: float, epoch, step_epochs: int, gamma: float):
    """Step decay /gamma every step_epochs (imagenet_pytorch.py:225-229)."""
    return base_lr * (gamma ** (epoch // step_epochs))


def gradual_warmup_lr(scaled_lr: float, world: int, epoch0: int, step: int,
                      steps_per_epoch: int, warmup_epochs: int) -> float:
    """Goyal-et-al gradual warmup (imagenet_horovod.py:258-275): during the
    first ``warmup_epochs`` the lr ramps linearly, at per-batch granularity,
    from base_lr to the full world-scaled ``scaled_lr`` (= base_lr * world).
    ``epoch0`` is 0-based. Returns scaled_lr untouched past the warmup.
    """
    if epoch0 >= warmup_epochs or world <= 1:
        return scaled_lr
    frac = epoch0 + (step + 1) / max(1, steps_per_epoch)
    lr_adj = (1.0 / world) * (frac * (world - 1) / warmup_epochs + 1.0)
    return scaled_lr * lr_adj


def cast_params(params, dtype, layers=None):
    """Cast floating-point leaves to the compute dtype (bf16 on TPU). With
    ``layers`` (the Layers whose per-layer list ``params`` is), what a layer
    names in ``f32_params`` stays as it is."""
    if dtype is None:
        return params
    cast = lambda tree: jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )
    if layers is None or not any(l.f32_params for l in layers):
        return cast(params)
    return [{k: v if k in l.f32_params else cast(v) for k, v in p.items()}
            if l.f32_params else cast(p) for l, p in zip(layers, params)]


def vary(v, axes):
    """Mark v as varying over any of `axes` it isn't already varying over.

    shard_map's VMA type system requires lax.switch branches and lax.scan
    carries to agree on varying-axes; constants (jnp.zeros) start invariant.
    """
    from ddlbench_tpu.compat import pcast_varying

    return pcast_varying(v, axes)


def cast_input(x, dtype):
    """Cast a batch to the compute dtype; integer inputs (token ids) pass
    through untouched."""
    if dtype is None or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(dtype)


def head_fusable(model) -> bool:
    """True when the model's last layer offers the fused projection+loss path
    (ops/fused_xent.py) — the LM heads of the token/seq2seq workloads."""
    return model.layers[-1].fused_loss is not None


def fused_slice_loss_sums(layers, params_cast, states, x_cast, labels,
                          smoothing: float, remat: bool = False):
    """Apply layers[:-1], then layers[-1].fused_loss (the fused projection+CE).

    The single home for the fused-head calling convention (also used by the
    pipeline strategies on their loss stage): the head layer must be
    stateless (true for lm_head) and its state entry is passed through
    unchanged. Returns (obj_sum, ce_sum, correct, new_states) — sums over
    valid label positions; callers normalize (and psum first under
    shard_map). Inputs must already be in the compute dtype.
    """
    from ddlbench_tpu.models.layers import apply_slice

    h, new_states = apply_slice(layers[:-1], params_cast[:-1], states[:-1],
                                x_cast, True, remat)
    # the head instance is applied here and not by apply_slice, so its
    # scope is opened here: the fused-xent kernels sit under
    # <head instance>/loss (projection and cross entropy are one kernel)
    with scopes.scope(layers[-1].name), scopes.scope(scopes.LOSS):
        obj_sum, ce_sum, correct = layers[-1].fused_loss(
            params_cast[-1], h, labels, smoothing)
    return obj_sum, ce_sum, correct, new_states + [states[-1]]


def fused_head_loss_sums(model, params_cast, model_state, x_cast, y,
                         smoothing: float, remat: bool = False):
    """Model-level wrapper of fused_slice_loss_sums; adds the valid count.

    Returns (obj_sum, ce_sum, correct, valid, new_state).
    """
    obj_sum, ce_sum, correct, new_state = fused_slice_loss_sums(
        model.layers, params_cast, model_state, x_cast, y, smoothing, remat)
    valid = jnp.sum((y >= 0).astype(jnp.int32))
    return obj_sum, ce_sum, correct, valid, new_state


def fused_slice_eval_sums(layers, params_cast, states, x_cast, labels):
    """Eval twin of fused_slice_loss_sums: apply layers[:-1] (eval mode),
    then layers[-1].fused_eval. Returns (ce_sum, correct, correct5, valid).
    """
    from ddlbench_tpu.models.layers import apply_slice

    h, _ = apply_slice(layers[:-1], params_cast[:-1], states[:-1], x_cast,
                       False)
    return layers[-1].fused_eval(params_cast[-1], h, labels)


def fused_head_eval_sums(model, params_cast, model_state, x_cast, y):
    """Model-level wrapper of fused_slice_eval_sums."""
    return fused_slice_eval_sums(model.layers, params_cast, model_state,
                                 x_cast, y)


def eval_metrics(model, cfg, params, model_state, x, y, compute_dtype):
    """Shared eval step core for single/dp/tp/fsdp: returns the metric dict
    {loss, correct, correct5, count}. Uses the fused head path (no [N, V]
    logits) when available and enabled."""
    from ddlbench_tpu.models.layers import apply_model, resolve_ties

    p = resolve_ties(model.ties, cast_params(params, compute_dtype,
                                             model.layers))
    xc = cast_input(x, compute_dtype)
    if cfg.fused_head_loss and model.layers[-1].fused_eval is not None:
        ce_sum, correct, correct5, count = fused_head_eval_sums(
            model, p, model_state, xc, y)
        loss = ce_sum / jnp.maximum(1.0, count.astype(jnp.float32))
        return {"loss": loss, "correct": correct, "correct5": correct5,
                "count": count}
    logits, _ = apply_model(model, p, model_state, xc, False)
    correct, count = correct_and_count(logits, y)
    return {
        "loss": cross_entropy_loss(logits, y),
        "correct": correct,
        "correct5": correct_topk(logits, y),
        "count": count,
    }


def loss_with_moe_aux(model, params, model_state, x, y, train, compute_dtype,
                      aux_weight, smoothing: float = 0.0, fused: bool = False,
                      remat: bool = False):
    """Apply the model and return (total_loss, ce, (correct, valid), new_state).

    total_loss = cross-entropy (optionally label-smoothed — the training
    objective) + aux_weight * (MoE router load-balance losses collected during
    the apply — zero for dense models). The returned ``ce`` is the *unsmoothed*
    CE so the headline loss metric stays comparable across configurations;
    (correct, valid) are the top-1 metric counts. With ``fused`` (and a model
    whose head supports it — see head_fusable) the projection+loss runs the
    chunked fused path and the full logits are never materialized.
    Shared by every strategy whose loss is computed from one traced apply
    (single/dp/tp/fsdp); sp/ep inline the same pattern because their aux terms
    need a psum over the shard_map axis first.
    """
    from ddlbench_tpu.models.layers import apply_model, resolve_ties
    from ddlbench_tpu.models.moe import collect_aux_losses

    remat = remat or model.remat_layers
    # a tied leaf goes to its reader HERE, inside what the caller
    # differentiates: both uses' gradients meet in the one leaf
    p = resolve_ties(model.ties, cast_params(params, compute_dtype,
                                             model.layers))
    xc = cast_input(x, compute_dtype)
    aux: list = []
    if fused and train and head_fusable(model):
        with collect_aux_losses(aux):
            obj_sum, ce_sum, correct, valid, new_state = fused_head_loss_sums(
                model, p, model_state, xc, y, smoothing, remat)
        denom = jnp.maximum(1.0, valid.astype(jnp.float32))
        obj, ce = obj_sum / denom, ce_sum / denom
    else:
        with collect_aux_losses(aux):
            logits, new_state = apply_model(model, p, model_state, xc, train,
                                            remat)
        ce = cross_entropy_loss(logits, y)
        obj = cross_entropy_loss(logits, y, smoothing) if smoothing else ce
        correct, valid = correct_and_count(logits, y)
    return (obj + aux_weight * sum(aux, jnp.float32(0.0)), ce,
            (correct, valid), new_state)
