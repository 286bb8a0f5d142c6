"""Functional flat-layer model representation.

The reference maintains THREE parallel model families per architecture because
each engine has different structural needs: idiomatic nn.Modules for
pytorch/horovod, flattened nn.Sequential with @skippable stash/pop residuals
for torchgpipe, and tracer-friendly module-only graphs for PipeDream
(SURVEY.md §2 B5-B7; gpipemodels/resnet/block.py:31-51 for the skip API).

Here a model is ONE flat ``list[Layer]``; residual blocks are single layers
(closures over their sub-params), so there is no stash/pop machinery, partitioning
a pipeline is slicing the list, and the same definition serves every strategy.

Each ``Layer`` is a pair of pure functions:

* ``init(key, in_shape) -> (params, state, out_shape)`` — shapes are per-example
  (no batch dim), NHWC.
* ``apply(params, state, x, train) -> (y, new_state)`` — x is batched [B, ...];
  ``state`` carries BatchNorm running statistics (functional analog of torch's
  buffers). In train mode BN uses batch statistics and returns updated running
  stats; in eval mode it uses running stats unchanged.

Everything is NHWC with HWIO kernels — the TPU-native convolution layout.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.telemetry import scopes

Params = Any
State = Any
Shape = Tuple[int, ...]

CONV_DIMS = ("NHWC", "HWIO", "NHWC")
BN_MOMENTUM = 0.1  # torch's default BatchNorm momentum
BN_EPS = 1e-5


class axis_context:
    """Trace-time marker that a named mesh axis is active for model applies.

    Subclasses declare their own class-level ``_stack``; entering pushes the
    axis name and ``current()`` peeks it. This is how one model definition
    serves multiple execution modes: sequence_parallel (ring attention,
    models/transformer.py) and expert_parallel (MoE all_to_all dispatch,
    models/moe.py) are both instances.
    """

    _stack: List[str]

    def __init__(self, axis: str):
        self.axis = axis

    def __enter__(self):
        type(self)._stack.append(self.axis)
        return self

    def __exit__(self, *exc):
        type(self)._stack.pop()
        return False

    @classmethod
    def current(cls):
        return cls._stack[-1] if cls._stack else None


class batch_parallel(axis_context):
    """Trace-time marker: the model is applied inside a shard_map whose
    named axis shards the BATCH dimension (the dp sharded-update engine,
    parallel/dp.py). batchnorm then computes cross-replica (global-batch)
    statistics explicitly via :func:`sync_batch_mean` — the same sync-BN
    semantics GSPMD derives automatically when the batch axis is sharded
    under one jit. The entry carries (axis_name, world) because the
    unbiased-variance correction needs the static global count."""

    _stack: List[Any] = []

    def __init__(self, axis: str, world: int):
        super().__init__(axis)
        self.world = int(world)

    def __enter__(self):
        type(self)._stack.append((self.axis, self.world))
        return self


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def sync_batch_mean(x, shape, axis, world):
    """Global-batch mean of ``x`` over all-but-last axes, f32-accumulated,
    inside a shard_map whose ``axis`` shards the leading (batch) dim.

    Mirrors the op order of GSPMD's partitioned ``jnp.mean(x, axes,
    dtype=f32)`` — local reduce, cross-replica sum, divide by the GLOBAL
    count — and defines the matching backward explicitly. The mean is
    invariant over ``axis``, so its cotangent arrives already summed over
    the devices (shard_map's VMA typing psums the per-device partials
    where the replicated stat meets device-varying rows); the backward
    divides it by the global count and broadcasts it over the local rows —
    the reduce/divide/broadcast sequence of the partitioned transpose.
    ``shape`` is the static LOCAL shape of x, ``world`` the axis size.
    """
    axes = tuple(range(len(shape) - 1))
    local = 1
    for a in axes:
        local *= shape[a]
    return lax.psum(jnp.sum(x, axis=axes, dtype=jnp.float32), axis) / (
        local * world)


def _sync_batch_mean_fwd(x, shape, axis, world):
    return sync_batch_mean(x, shape, axis, world), jnp.zeros((), x.dtype)


def _sync_batch_mean_bwd(shape, axis, world, res, ct):
    axes = tuple(range(len(shape) - 1))
    local = 1
    for a in axes:
        local *= shape[a]
    ct = ct / (local * world)
    bshape = [1] * len(shape)
    bshape[-1] = shape[-1]
    from ddlbench_tpu.compat import pcast_varying

    # the primal rows vary over ``axis``; so must their cotangent
    return (pcast_varying(
        jnp.broadcast_to(ct.reshape(bshape), shape).astype(res.dtype),
        (axis,)),)


sync_batch_mean.defvjp(_sync_batch_mean_fwd, _sync_batch_mean_bwd)


@dataclasses.dataclass(frozen=True)
class Layer:
    """One pipeline-atomic unit of a model.

    The three optional fields support KV-cached incremental decoding
    (models/decode.py) and default to None for layers that don't need them:

    * ``init_cache(params, batch, max_len, dtype) -> cache`` — allocate the
      layer's decode cache (e.g. K/V buffers for attention blocks).
    * ``prefill(params, state, cache, x, start) -> (y, cache)`` — process the
      whole decode prompt at once, populating the cache from position
      ``start``. Current implementations require ``start == 0`` (the prompt
      opens the stream); chunked prefill against an existing cache is future
      work. Layers without one are prefilled via ``apply``.
    * ``decode(params, state, cache, x, pos) -> (y, cache)`` — process ONE
      token (x is [B, 1, ...]) at dynamic position ``pos`` against the cache.
      Layers without one decode via ``apply`` (correct only for
      position-independent layers; position-dependent layers like embeddings
      must provide it).
    """

    name: str
    init: Callable[[jax.Array, Shape], Tuple[Params, State, Shape]]
    apply: Callable[[Params, State, jax.Array, bool], Tuple[jax.Array, State]]
    init_cache: Any = None
    prefill: Any = None
    decode: Any = None
    # True if ``apply`` on a single position equals its full-sequence result
    # (no position dependence, no cross-position mixing) — such layers can be
    # decoded via apply without a cache (e.g. the LM head).
    pointwise: bool = False
    # Output-head layers may provide a fused projection+loss path
    # ``fused_loss(params, x, labels, smoothing) -> (obj_sum, ce_sum, correct)``
    # that never materializes the [N, num_classes] logits (ops/fused_xent.py);
    # strategies use it on the training path when cfg.fused_head_loss is set.
    fused_loss: Any = None
    # Eval-side sibling: ``fused_eval(params, x, labels) ->
    # (ce_sum, correct, correct_top5, valid)`` — same fusion for the
    # validation metrics (incl. prec@5 with torch.topk tie order).
    fused_eval: Any = None
    # Per-example spatial factor for the analytic FLOP heuristic
    # (parallel/packing.layer_flop_costs): conv FLOPs ~ 2*params*H*W, read
    # from the layer's OUTPUT shape by default. Layers whose output shape
    # hides the compute geometry set this — packed composite spans
    # (models/branchy._packed_span) emit flat [N] boundaries whose spatial
    # would read as 1, underweighting convolutional spans by orders of
    # magnitude in the balanced stage split.
    cost_spatial: Any = None
    # Optional paged-KV-cache decode protocol (ops/paged_decode.py): the
    # copy-on-write fast path for beam search. Layers that allocate a cache
    # (init_cache) may also provide a PagedOps; cache-free decode layers
    # participate through their ordinary ``decode``.
    paged: Any = None
    # Optional continuous-batching serving protocol (serve/engine.py): a
    # ServeOps whose ops take per-ROW stream positions and go through a
    # shared free-list page pool. Pointwise layers participate through
    # ``apply``; everything else needs a ServeOps to be servable.
    serve: Any = None
    # Top-level keys of this layer's params that it computes with in float32
    # and that the step's cast to the compute dtype therefore leaves as they
    # are (parallel/common.cast_params, given the layers), e.g. a router's
    # weights.
    f32_params: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class PagedOps:
    """Paged-cache decode protocol (models/decode.py paged loops).

    Same shapes/positions as the dense protocol; ``reorder`` is the
    copy-on-write replacement for the full-cache gather in beam search, and
    ``decode`` must be traced inside a ``live_pages`` segment (the static
    page count the attention kernel grid needs)."""

    init_cache: Callable  # (params, batch, max_len, dtype) -> cache
    prefill: Callable  # (params, state, cache, x, start) -> (y, cache)
    decode: Callable  # (params, state, cache, x, pos) -> (y, cache)
    reorder: Callable  # (cache, parent, pos) -> cache


@dataclasses.dataclass(frozen=True)
class ServeOps:
    """Continuous-batching serving protocol (serve/engine.py).

    Unlike :class:`PagedOps` — whose rows march in lockstep through one
    shared position — serving rows are independent requests at per-row
    stream positions, borrowing K/V slots from a SHARED free-list pool
    (ops/paged_decode.py serve primitives). The engine owns ONE page table
    ([max_batch, n_pages] int32, slot 0 = scratch) shared by every layer:
    slot allocation is per-request across all layers at once, vLLM-style,
    so each layer indexes its own pool with the same table.

    * ``pool_init(params, n_pages, page, dtype) -> pool`` — the layer's
      slice of the shared pool ({} / None for cache-free layers).
    * ``prefill(p, s, pool, table, x, start, npl, page) -> (y, pool)`` —
      one page-aligned prompt chunk x [R, C] at positions
      [start, start + C) (``start`` dynamic, ``npl``/``page``/C static).
    * ``decode(p, s, pool, table, x, pos, npl, page) -> (y, pool)`` —
      one token per row, x [B, 1] at per-row positions ``pos`` [B].
    * ``verify`` (optional) — the speculative-decoding scoring pass:
      x [B, W] token spans at page-UNALIGNED per-row positions
      [pos0_r, pos0_r + W) (each row's pending token + its drafts). Same
      contract as ``decode`` — write the span's K/V through the table,
      then causal attention at absolute positions — but W positions per
      row in one call (ops/paged_decode.paged_table_span_write +
      per-row-start chunk attention). None = the layer cannot serve
      speculative traffic (the engine rejects the config at build).
    """

    pool_init: Any  # None for cache-free layers (e.g. the embedding)
    prefill: Callable
    decode: Callable
    verify: Any = None


@dataclasses.dataclass(frozen=True)
class Tie:
    """A leaf that a layer reads and another owns: ``layers[layer]`` computes
    with ``params[owner][owner_key]`` under its own top-level key ``key`` and
    holds no such leaf itself (a tied output head reads the embedding).
    Indices are list indices (-1: the last layer)."""

    layer: int
    key: str
    owner: int
    owner_key: str


@dataclasses.dataclass(frozen=True)
class LayerModel:
    """A named flat stack of layers plus metadata the strategies need."""

    name: str
    layers: List[Layer]
    in_shape: Shape  # (H, W, C) for images; (T,) for tokens
    num_classes: int  # classes, or vocab size for token models
    # "float" (images/features) or "tokens" (int32 ids into a vocab of
    # num_classes) — tells the profiler and tools how to synthesize inputs.
    input_kind: str = "float"
    # seq2seq models only: the prefix-LM source-segment length baked into the
    # attention masks (decode entry points validate against it).
    src_len: int | None = None
    # the strategies the model is brought up on (RunConfig.validate refuses
    # any other); None: every strategy its input kind allows.
    strategies: Tuple[str, ...] | None = None
    # leaves shared by two layers. The params tree holds each ONCE, at its
    # owner; a loss function puts it at its reader too INSIDE what it
    # differentiates (resolve_ties), so autodiff sums both uses into the one
    # leaf and the optimizer keeps one slot for it. Strategies that cut the
    # per-layer list into stages do not resolve ties: a model with ties
    # names the strategies that do in ``strategies``.
    ties: Tuple[Tie, ...] = ()
    # the model is built for inputs at which a layer's interior activations
    # do not fit beside the train state (models/smallthinker.py: one
    # 16,384-token sequence): the one-apply strategies checkpoint every layer
    # as under RunConfig.remat_layers, whatever that says.
    remat_layers: bool = False


def resolve_ties(ties: Sequence[Tie], params):
    """``params`` (a per-layer list, cast or not) with every tied leaf also
    where its reader looks for it; ``params`` itself when nothing is tied."""
    if not ties:
        return params
    out = list(params)
    for t in ties:
        out[t.layer] = dict(out[t.layer],
                            **{t.key: params[t.owner][t.owner_key]})
    return out


def init_model(model: LayerModel, key: jax.Array):
    """Initialize every layer; returns (params_list, state_list, shapes).

    ``shapes[i]`` is the per-example input shape of layer i; ``shapes[-1]`` is
    the final output shape. These boundary shapes drive pipeline activation
    buffers and the profiler's activation_size fields. A boundary that
    carries more than one array (models/zaya.py: the residual stream and the
    router's state) is a TUPLE of shapes here, as it is a tuple of arrays
    in ``apply_slice``; the pipeline strategies and the profiler take one
    array a boundary, so such a model names ``strategies`` that do not read
    these (``single``) and ``profiler.profile_model`` refuses it.
    """
    params, states, shapes = [], [], [model.in_shape]
    shape = model.in_shape
    for layer in model.layers:
        key, sub = jax.random.split(key)
        p, s, shape = layer.init(sub, shape)
        params.append(p)
        states.append(s)
        shapes.append(shape)
    return params, states, shapes


def apply_slice(layers: Sequence[Layer], params, states, x, train: bool,
                remat: bool = False):
    """Run ``layers`` in order. With ``remat`` each layer is wrapped in
    jax.checkpoint: the backward recomputes the layer instead of saving its
    interior activations, capping live memory at one layer's working set —
    at 8k context the XLA-attention score matrix is 2 GB/layer, so without
    this every layer's matrix is resident at once and a single v5e chip
    OOMs (perf_runs, round 3). FLOPs-for-HBM, the jax.checkpoint analog of
    the pipeline strategies' per-(microbatch, stage) cfg.remat_stages.

    What a rematerialized layer KEEPS besides its input: the values its
    kernels name for keeping (ops/flash_attention.REMAT_KEPT_NAMES: the
    flash forward's output and row logsumexp, H * dv / d of the layer's
    input), so the backward reads what the forward pass wrote and does not
    run the forward kernel a second time. A layer that traces no such kernel
    has no value of those names and lowers as under a bare jax.checkpoint."""
    if remat:
        from ddlbench_tpu.ops.flash_attention import REMAT_KEPT_NAMES

        keep = jax.checkpoint_policies.save_only_these_names(
            *REMAT_KEPT_NAMES)
    new_states = []
    for layer, p, s in zip(layers, params, states):
        # the layer instance's scope: every device op of this layer carries
        # layer.name on its op_name path (telemetry/scopes.py)
        with scopes.scope(layer.name):
            if remat:
                x, s2 = jax.checkpoint(
                    functools.partial(layer.apply, train=train),
                    policy=keep)(p, s, x)
            else:
                x, s2 = layer.apply(p, s, x, train)
        new_states.append(s2)
    return x, new_states


def apply_model(model: LayerModel, params, states, x, train: bool,
                remat: bool = False):
    return apply_slice(model.layers, params, states, x, train, remat)


# ---------------------------------------------------------------------------
# Parameter initializers (match torch defaults where the reference relies on
# them: kaiming-normal fan_out for convs, BN gamma=1 beta=0, linear kaiming-uniform).
# ---------------------------------------------------------------------------

def _conv_kernel_init(key, kh, kw, cin, cout):
    fan_out = kh * kw * cout
    std = math.sqrt(2.0 / fan_out)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _linear_init(key, cin, cout):
    bound = 1.0 / math.sqrt(cin)
    kw, kb = jax.random.split(key)
    w = jax.random.uniform(kw, (cin, cout), jnp.float32, -bound, bound)
    b = jax.random.uniform(kb, (cout,), jnp.float32, -bound, bound)
    return w, b


def _conv_out_hw(h, w, kh, kw, stride, padding):
    if padding == "SAME":
        return math.ceil(h / stride), math.ceil(w / stride)
    return (h - kh) // stride + 1, (w - kw) // stride + 1


# ---------------------------------------------------------------------------
# Stateless primitive helpers used *inside* composite layers.
# ---------------------------------------------------------------------------

@scopes.scope(scopes.CONV)
def conv2d(x, kernel, stride=1, padding="SAME", groups=1):
    return lax.conv_general_dilated(
        x,
        kernel.astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=CONV_DIMS,
        feature_group_count=groups,
    )


@scopes.scope(scopes.BN)
def batchnorm(p, s, x, train: bool):
    """Returns (y, new_state). p = {scale, bias}; s = {mean, var}.

    Statistics accumulate in float32 (f32-accumulated reductions over the bf16
    activations); normalization itself stays in the compute dtype so no f32
    copy of the activation tensor is ever materialized in HBM.
    """
    axes = tuple(range(x.ndim - 1))
    if train:
        sync = batch_parallel.current()
        # One-pass stats; the f32 converts fuse into the reductions (no f32
        # copy of x hits HBM, unlike a two-pass mean-then-var). Under a
        # batch_parallel axis (the dp sharded-update engine) the means are
        # explicit cross-replica psums over the global batch — the sync-BN
        # semantics the sharded-jit strategies get from GSPMD.
        if sync is not None:
            axis, world = sync
            mean = sync_batch_mean(x, x.shape, axis, world)
            mean2 = sync_batch_mean(lax.square(x.astype(jnp.float32)),
                                    x.shape, axis, world)
        else:
            world = 1
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            mean2 = jnp.mean(lax.square(x.astype(jnp.float32)), axis=axes,
                             dtype=jnp.float32)
        var = jnp.maximum(mean2 - lax.square(mean), 0.0)
        # Running var uses the unbiased estimator (torch BatchNorm semantics);
        # normalization below uses the biased batch var, also matching torch.
        n = (x.size // x.shape[-1]) * world
        unbiased = var * (n / max(1, n - 1))
        new_s = {
            "mean": (1 - BN_MOMENTUM) * s["mean"] + BN_MOMENTUM * mean,
            "var": (1 - BN_MOMENTUM) * s["var"] + BN_MOMENTUM * unbiased,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = lax.rsqrt(var + BN_EPS) * p["scale"]
    shift = p["bias"] - mean * inv
    y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
    return y, new_s


def bn_init(c):
    params = {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}
    state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
    return params, state


# ---------------------------------------------------------------------------
# Layer constructors.
# ---------------------------------------------------------------------------

def conv_bn(name: str, out_ch: int, kernel: int = 3, stride: int = 1,
            relu: bool = True, padding: str = "SAME", groups: int = 1) -> Layer:
    def init(key, in_shape):
        h, w, c = in_shape
        k = _conv_kernel_init(key, kernel, kernel, c // groups, out_ch)
        bn_p, bn_s = bn_init(out_ch)
        oh, ow = _conv_out_hw(h, w, kernel, kernel, stride, padding)
        return {"kernel": k, "bn": bn_p}, {"bn": bn_s}, (oh, ow, out_ch)

    def apply(p, s, x, train):
        y = conv2d(x, p["kernel"], stride, padding, groups)
        y, bn_s = batchnorm(p["bn"], s["bn"], y, train)
        if relu:
            y = jax.nn.relu(y)
        return y, {"bn": bn_s}

    return Layer(name, init, apply)


def max_pool(name: str, window: int = 2, stride: int | None = None, padding: str = "VALID") -> Layer:
    stride = stride or window

    def init(key, in_shape):
        h, w, c = in_shape
        oh, ow = _conv_out_hw(h, w, window, window, stride, padding)
        return {}, {}, (oh, ow, c)

    @scopes.scope(scopes.POOL)
    def apply(p, s, x, train):
        y = lax.reduce_window(
            x, -jnp.inf, lax.max,
            (1, window, window, 1), (1, stride, stride, 1), padding,
        )
        return y, s

    return Layer(name, init, apply)


def avg_pool(name: str, window: int = 3, stride: int = 1,
             padding: str = "SAME") -> Layer:
    """Average pooling (count includes SAME padding — torch
    count_include_pad=True, the AvgPool2d default the reference's models
    rely on)."""

    def init(key, in_shape):
        h, w, c = in_shape
        oh, ow = _conv_out_hw(h, w, window, window, stride, padding)
        return {}, {}, (oh, ow, c)

    @scopes.scope(scopes.POOL)
    def apply(p, s, x, train):
        y = lax.reduce_window(
            x, 0.0, lax.add,
            (1, window, window, 1), (1, stride, stride, 1), padding,
        ) / float(window * window)
        return y, s

    return Layer(name, init, apply)


def sep_conv_bn(name: str, out_ch: int, kernel: int = 3,
                stride: int = 1) -> Layer:
    """Depthwise-separable conv: relu -> depthwise kxk (stride) ->
    pointwise 1x1 -> BN — the NASNet cell operation (one pass of the
    paper's relu-sepconv-bn pair; the mini family applies it once)."""

    def init(key, in_shape):
        h, w, c = in_shape
        k1, k2 = jax.random.split(key)
        p = {"dw": _conv_kernel_init(k1, kernel, kernel, 1, c),
             "pw": _conv_kernel_init(k2, 1, 1, c, out_ch)}
        bn_p, bn_s = bn_init(out_ch)
        p["bn"] = bn_p
        oh, ow = _conv_out_hw(h, w, kernel, kernel, stride, "SAME")
        return p, {"bn": bn_s}, (oh, ow, out_ch)

    def apply(p, s, x, train):
        y = jax.nn.relu(x)
        y = conv2d(y, p["dw"], stride, groups=p["dw"].shape[-1])
        y = conv2d(y, p["pw"], 1)
        y, bn_s = batchnorm(p["bn"], s["bn"], y, train)
        return y, {"bn": bn_s}

    return Layer(name, init, apply)


def global_avg_pool(name: str = "gap") -> Layer:
    def init(key, in_shape):
        h, w, c = in_shape
        return {}, {}, (c,)

    @scopes.scope(scopes.POOL)
    def apply(p, s, x, train):
        return jnp.mean(x, axis=(1, 2)), s

    return Layer(name, init, apply)


def flatten(name: str = "flatten") -> Layer:
    def init(key, in_shape):
        return {}, {}, (int(math.prod(in_shape)),)

    def apply(p, s, x, train):
        return x.reshape(x.shape[0], -1), s

    return Layer(name, init, apply)


def dense(name: str, out_features: int, relu: bool = False, dropout: float = 0.0) -> Layer:
    """Linear layer over flattened features. Dropout is a no-op here (the
    benchmark protocol measures throughput; reference VGG classifiers carry
    Dropout but it does not change shapes/FLOPs materially) — documented
    deviation."""

    def init(key, in_shape):
        cin = int(in_shape[0]) if len(in_shape) == 1 else int(math.prod(in_shape))
        w, b = _linear_init(key, cin, out_features)
        return {"w": w, "b": b}, {}, (out_features,)

    @scopes.scope(scopes.FC)
    def apply(p, s, x, train):
        x = x.reshape(x.shape[0], -1)
        y = x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)
        if relu:
            y = jax.nn.relu(y)
        return y, s

    return Layer(name, init, apply)


# ---------------------------------------------------------------------------
# Residual blocks — each is ONE Layer (pipeline-atomic), so skip connections
# never cross stage boundaries and the reference's stash/pop machinery
# (gpipemodels/resnet/block.py:31-51) has no TPU analog to build.
# ---------------------------------------------------------------------------

def basic_block(name: str, out_ch: int, stride: int = 1) -> Layer:
    """ResNet BasicBlock: 3x3 -> 3x3 with identity/projection shortcut."""

    def init(key, in_shape):
        h, w, c = in_shape
        k1, k2, k3 = jax.random.split(key, 3)
        p = {
            "conv1": _conv_kernel_init(k1, 3, 3, c, out_ch),
            "conv2": _conv_kernel_init(k2, 3, 3, out_ch, out_ch),
        }
        s = {}
        p["bn1"], s["bn1"] = bn_init(out_ch)
        p["bn2"], s["bn2"] = bn_init(out_ch)
        if stride != 1 or c != out_ch:
            p["proj"] = _conv_kernel_init(k3, 1, 1, c, out_ch)
            p["bn_proj"], s["bn_proj"] = bn_init(out_ch)
        oh, ow = _conv_out_hw(h, w, 3, 3, stride, "SAME")
        return p, s, (oh, ow, out_ch)

    def apply(p, s, x, train):
        ns = {}
        y = conv2d(x, p["conv1"], stride)
        y, ns["bn1"] = batchnorm(p["bn1"], s["bn1"], y, train)
        y = jax.nn.relu(y)
        y = conv2d(y, p["conv2"], 1)
        y, ns["bn2"] = batchnorm(p["bn2"], s["bn2"], y, train)
        if "proj" in p:
            sc = conv2d(x, p["proj"], stride)
            sc, ns["bn_proj"] = batchnorm(p["bn_proj"], s["bn_proj"], sc, train)
        else:
            sc = x
        return jax.nn.relu(y + sc), ns

    return Layer(name, init, apply)


def bottleneck_block(name: str, mid_ch: int, stride: int = 1, expansion: int = 4) -> Layer:
    """ResNet Bottleneck: 1x1 -> 3x3 -> 1x1(x4) with projection shortcut."""
    out_ch = mid_ch * expansion

    def init(key, in_shape):
        h, w, c = in_shape
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {
            "conv1": _conv_kernel_init(k1, 1, 1, c, mid_ch),
            "conv2": _conv_kernel_init(k2, 3, 3, mid_ch, mid_ch),
            "conv3": _conv_kernel_init(k3, 1, 1, mid_ch, out_ch),
        }
        s = {}
        p["bn1"], s["bn1"] = bn_init(mid_ch)
        p["bn2"], s["bn2"] = bn_init(mid_ch)
        p["bn3"], s["bn3"] = bn_init(out_ch)
        if stride != 1 or c != out_ch:
            p["proj"] = _conv_kernel_init(k4, 1, 1, c, out_ch)
            p["bn_proj"], s["bn_proj"] = bn_init(out_ch)
        oh, ow = _conv_out_hw(h, w, 3, 3, stride, "SAME")
        return p, s, (oh, ow, out_ch)

    def apply(p, s, x, train):
        ns = {}
        y = conv2d(x, p["conv1"], 1)
        y, ns["bn1"] = batchnorm(p["bn1"], s["bn1"], y, train)
        y = jax.nn.relu(y)
        y = conv2d(y, p["conv2"], stride)
        y, ns["bn2"] = batchnorm(p["bn2"], s["bn2"], y, train)
        y = jax.nn.relu(y)
        y = conv2d(y, p["conv3"], 1)
        y, ns["bn3"] = batchnorm(p["bn3"], s["bn3"], y, train)
        if "proj" in p:
            sc = conv2d(x, p["proj"], stride)
            sc, ns["bn_proj"] = batchnorm(p["bn_proj"], s["bn_proj"], sc, train)
        else:
            sc = x
        return jax.nn.relu(y + sc), ns

    return Layer(name, init, apply)


def inverted_residual(name: str, out_ch: int, stride: int, expand: int) -> Layer:
    """MobileNetV2 inverted residual: 1x1 expand -> 3x3 depthwise -> 1x1 project,
    residual add when stride==1 and channels match."""

    def init(key, in_shape):
        h, w, c = in_shape
        hidden = c * expand
        k1, k2, k3 = jax.random.split(key, 3)
        p, s = {}, {}
        if expand != 1:
            p["expand"] = _conv_kernel_init(k1, 1, 1, c, hidden)
            p["bn_e"], s["bn_e"] = bn_init(hidden)
        # depthwise: HWIO with I=1, groups=hidden
        p["dw"] = _conv_kernel_init(k2, 3, 3, 1, hidden)
        p["bn_d"], s["bn_d"] = bn_init(hidden)
        p["project"] = _conv_kernel_init(k3, 1, 1, hidden, out_ch)
        p["bn_p"], s["bn_p"] = bn_init(out_ch)
        oh, ow = _conv_out_hw(h, w, 3, 3, stride, "SAME")
        return p, s, (oh, ow, out_ch)

    def apply(p, s, x, train):
        ns = {}
        y = x
        hidden_groups = p["dw"].shape[-1]
        if "expand" in p:
            y = conv2d(y, p["expand"], 1)
            y, ns["bn_e"] = batchnorm(p["bn_e"], s["bn_e"], y, train)
            y = jax.nn.relu6(y)
        y = conv2d(y, p["dw"], stride, groups=hidden_groups)
        y, ns["bn_d"] = batchnorm(p["bn_d"], s["bn_d"], y, train)
        y = jax.nn.relu6(y)
        y = conv2d(y, p["project"], 1)
        y, ns["bn_p"] = batchnorm(p["bn_p"], s["bn_p"], y, train)
        if stride == 1 and x.shape[-1] == y.shape[-1]:
            y = y + x
        return y, ns

    return Layer(name, init, apply)


def routing_counters(model_state):
    """The step's routing counters out of a model state, {} for a model
    without expert layers (whose state holds them under ``"moe"``,
    models/dropless.initial_counters): held slots summed over the layers,
    the load ratio of the most uneven layer, the fill of the fullest
    layer's common buffer and, where the layers count it, the mean weight
    of the one expert chosen."""
    found = [s["moe"] for s in model_state
             if isinstance(s, dict) and "moe" in s]
    if not found:
        return {}
    out = {"moe_held_slots": sum(c["held_slots"] for c in found),
           "moe_load_max_over_mean": jnp.max(jnp.stack(
               [c["load_max_over_mean"] for c in found]))}
    fill = [c["buffer_fill"] for c in found if "buffer_fill" in c]
    if fill:
        out["moe_buffer_fill"] = jnp.max(jnp.stack(fill))
    top1 = [c["top1_weight_mean"] for c in found if "top1_weight_mean" in c]
    if top1:
        out["moe_top1_weight_mean"] = sum(top1) / len(top1)
    return out


def param_count(params) -> int:
    return sum(math.prod(l.shape) for l in jax.tree.leaves(params))


def param_bytes(params) -> int:
    return sum(math.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(params))
