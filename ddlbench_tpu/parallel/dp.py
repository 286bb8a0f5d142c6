"""Data-parallel strategy — the reference's Horovod engine, TPU-native.

Reference mechanism (benchmark/mnist/mnist_horovod.py): hvd.init + one process
per GPU (:162-171), DistributedSampler batch sharding (:207-219), lr scaled by
world size (:226), rank-0 parameter/optimizer broadcast (:230-231),
DistributedOptimizer hooking an NCCL allreduce onto every gradient (:234-236),
and allreduced eval metrics via metric_average (:129-132).

TPU-native design: one jit over a 1-D 'data' mesh. The batch is sharded on the
leading axis; parameters are replicated. XLA's SPMD partitioner inserts the
gradient all-reduce over ICI automatically (the explicit analog of Horovod's
per-gradient NCCL hook), metric means are global by construction (allreduced
eval-metric parity), and the initial `device_put` of replicated params is the
broadcast-init. Helper processes, samplers, and hooks all disappear into the
compiled program.

Deviation (documented): BatchNorm statistics are computed over the *global*
batch (sync-BN) because the batch axis is sharded under one jit; Horovod
computes per-replica statistics. Throughput is unaffected; accuracy parity is
equal or better (SURVEY.md §7 "BatchNorm under pipeline/DP").

Sharded weight update (``--dp-shard-update``, ZeRO-1): with the flag on, the
train step runs under an explicit shard_map over the 'data' axis instead of
leaving the collective pattern to GSPMD: each device computes its batch
shard's partial gradients, the packed flat gradient vector reduce-scatters
(``lax.psum_scatter``) so every chip receives one contiguous 1/world slice
of the summed gradient, momentum/Adam state and the weight update live on
that slice only (the packed flat-vector optimizer of parallel/common.py
makes the shard a contiguous slice), and the updated parameter shard
all-gathers back to the replicated pytree at the shard_map boundary. Wire
bytes equal the replicated ring allreduce (RS + AG = 2(r-1)/r x P) but
optimizer-state memory and update FLOPs drop ~world x. BatchNorm runs
explicit cross-replica statistics (models/layers.batch_parallel), keeping
replicated dp's sync-BN semantics. ``--allreduce-dtype bf16`` additionally
casts the gradient partials to bfloat16 before the collective (EQuARX-style
compressed allreduce — dtype-narrowed ring collectives without block
rescaling), halving gradient wire bytes; it composes with or without the
sharded update (without, the engine runs an explicit bf16 ``lax.psum`` and
keeps the update replicated). Numerics: the f32 sharded update is pinned
bitwise-identical to replicated dp on the CPU mesh for non-BN models
(tests/test_dp_shard.py); BN models agree to float rounding only, because
GSPMD places the BN-backward cross-replica reductions around linear ops at
its own discretion while the explicit engine fixes them (sync_batch_mean).

Comm/compute overlap (``--comm-buckets K``, ISSUE 6): with K > 1 the packed
flat gradient splits into K contiguous, LAYER-ALIGNED buckets
(common.flat_meta's leaf_groups = leaves per model layer), each riding its
OWN collective. The per-bucket reduce-scatter depends only on that
bucket's layers' gradients, so under XLA's latency-hiding scheduler
(distributed.comm_flags) late buckets' wire time hides under earlier
layers' backward compute — the cross-replica sharded-weight-update
overlap, expressed as dataflow instead of a schedule. Combined with
``--dp-shard-update`` the engine goes fully OVERLAPPED: parameters stay
SHARDED between steps (TrainState.params is the flat device-major f32
vector, one contiguous shard per chip) and the forward all-gathers each
bucket just-in-time — every leaf depends only on its bucket's all-gather,
so the first layers start while late buckets are still in flight
(FSDP-style prefetch left to the scheduler). Bucketing only moves pad
zeros between leaves and never splits or reorders a reduction, so the f32
bucketed path is bitwise-pinned to the monolithic PR 3 engine and
``--comm-buckets 1`` compiles the exact PR 3 program.

Elastic world-invariant numerics (``--elastic-slices E``, ISSUE 12): the
local-sum + psum_scatter reduction above ties the f32 bits of every loss
and gradient to the WORLD SIZE (different batch partitions contract and
reduce in different orders), so an elastic run that shrinks 4 -> 2 chips
could never replay bitwise. With E set, the engine instead computes
gradients in E fixed slices of the GLOBAL batch (contiguous, E/world per
device) and reduces them over a canonical balanced binary tree: a
pairwise fold over each device's contiguous slices composes with a
recursive-doubling butterfly allreduce (log2(world) ppermute+add rounds;
IEEE addition is commutative, so every device lands on the SAME bits)
into one tree whose shape depends on E alone. Save at world N, reshard
(train/reshard.py), resume at world M: per-slice programs, tree, and
elementwise optimizer are all world-independent, so per-step losses and
materialized params are bitwise equal to the uninterrupted N-run
(tests/test_elastic.py). Exact-replay mode, not a fast path: the
butterfly ships log2(world) full vectors vs the ring's (world-1)/world,
and it is scoped to f32 wire, stateless (non-BN) models, and the sharded
update. Eval runs the same canonical reduction so validation losses
match across worlds too.

int8 wire (``--allreduce-dtype int8``, EQuARX-lite): per-bucket GLOBAL
absmax (lax.pmax) -> shared scale absmax/qmax with qmax = 127 // world
(the collective sums IN int8; see common.sum_safe_qmax) -> stochastic
rounding seeded from the run seed + a step counter in the optimizer dict
+ device + bucket indices (bitwise-reproducible runs) -> int8
psum/psum_scatter -> dequantize. Quarter gradient wire bytes vs f32;
accuracy is gated by the digits matrix (tools/accparity.py dp-int8 rows),
not claimed by construction.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import LayerModel, init_model
from ddlbench_tpu.ops.util import gspmd_jit
from ddlbench_tpu.parallel.common import make_optimizer
from ddlbench_tpu.parallel.single import TrainState
from ddlbench_tpu.telemetry import scopes


def make_data_mesh(num_devices: int, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    from ddlbench_tpu.distributed import make_mesh

    # DP allreduce tolerates DCN latency; the 'data' axis spans hosts.
    return make_mesh([("data", num_devices)], devices=devices, dcn_axis="data")


class DPStrategy:
    """strategy='dp': batch sharded over the 'data' mesh axis, params replicated."""

    def __init__(self, model: LayerModel, cfg: RunConfig, mesh: Optional[Mesh] = None):
        from ddlbench_tpu.guard import device_guard

        self.model = model
        self.cfg = cfg
        self.mesh = mesh or make_data_mesh(cfg.num_devices)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self._opt_init, opt_update = make_optimizer(cfg)
        self._opt_update = opt_update
        smooth = cfg.resolved_label_smoothing()
        guard = self._guard = device_guard(cfg)  # None = pre-guard program

        self._replicated = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P("data"))

        # Explicit collective engine (sharded weight update / compressed
        # allreduce): the train step is built by _build_explicit_engine
        # below instead of the GSPMD path; eval is identical either way.
        self.shard_update = bool(cfg.dp_shard_update)
        self.wire_dtype = jnp.dtype(cfg.resolved_allreduce_dtype())
        self._explicit = cfg.dp_explicit_collectives()
        self._flat_meta = None

        def train_step(ts: TrainState, x, y, lr):
            # MoE routing statistics are global-batch (dense semantics: the
            # batch axis is sharded under one jit). With grad_accum_steps > 1
            # this is Horovod backward_passes_per_step parity: K micro-steps
            # between optimizer updates. (GSPMD reduces each micro-gradient
            # inside the scan — the carry needs a concrete sharding — so the
            # wire cost is K allreduces; the explicit sharded engine below
            # halves that with K reduce-scatters.)
            from ddlbench_tpu.parallel.common import loss_and_grads

            # Stability guard (same shape as the single engine): scaled
            # objective, fused health pair, in-step skip-select. GSPMD
            # shards the norm reduction like any other reduction.
            smul, opt_in = None, ts.opt
            if guard is not None:
                opt_in, gstate = guard.split_opt(ts.opt)
                smul = guard.smul(gstate, lr)
            with gspmd_jit():  # auto-Pallas unsafe under GSPMD
                ce, (correct, valid), new_state, grads = loss_and_grads(
                    model, cfg, ts.params, ts.model_state, x, y,
                    self.compute_dtype, smooth, obj_scale=smul)
            if guard is not None:
                grads = guard.unscale(grads, smul)
                finite, gnorm = guard.health(ce, grads)
            params, opt = opt_update(ts.params, grads, opt_in, lr)
            if guard is not None:
                params, new_state, opt, gm = guard.commit(
                    finite, gnorm, gstate, (params, new_state, opt),
                    (ts.params, ts.model_state, opt_in))
            metrics = {
                "loss": ce,
                "accuracy": correct.astype(jnp.float32)
                / jnp.maximum(1.0, valid.astype(jnp.float32)),
            }
            if guard is not None:
                metrics.update(gm)
            return TrainState(params, new_state, opt), metrics

        def eval_step(ts: TrainState, x, y):
            from ddlbench_tpu.parallel.common import eval_metrics

            with gspmd_jit():
                return eval_metrics(model, cfg, self._params_pytree(ts),
                                    ts.model_state, x, y, self.compute_dtype)

        self._overlap = False  # _build_explicit_engine may flip it
        self.eval_step = None  # the elastic engine installs its own
        if self._explicit:
            self._build_explicit_engine(smooth)
        else:
            self.train_step = jax.jit(
                train_step,
                donate_argnums=(0,),
                in_shardings=(None, self._batch_sharding,
                              self._batch_sharding, None),
                out_shardings=None,
            )
        if self.eval_step is None:
            self.eval_step = jax.jit(
                eval_step,
                in_shardings=(None, self._batch_sharding,
                              self._batch_sharding),
            )
        self._materialize = jax.jit(self._params_pytree,
                                    out_shardings=self._replicated)

    def _params_pytree(self, ts: TrainState):
        """ts.params as the per-layer pytree — identity except under the
        overlapped engine, whose between-steps params are the flat
        device-major sharded vector (jit callers let XLA insert the
        gathers; GSPMD slices what each consumer needs)."""
        if not self._overlap:
            return ts.params
        from ddlbench_tpu.parallel.common import from_device_major, unpack_flat

        meta = self._flat_meta
        return unpack_flat(
            from_device_major(ts.params, meta, self.mesh.devices.size), meta)

    def materialize_params(self, ts: TrainState):
        """Replicated per-layer params pytree for host-side consumers
        (activation logging, tools) — the train loop calls this instead of
        touching ts.params so the overlapped engine's flat sharded state
        stays an implementation detail."""
        if not self._overlap:
            return ts.params
        return self._materialize(ts)

    # -- explicit collective engine (ZeRO-1 / compressed allreduce) --------

    def _local_loss_sums(self, params, state, x, y, smooth):
        """Local-shard (obj_sum, ce_sum, correct, valid, norm) mirroring
        loss_with_moe_aux's global computation op for op, so the explicit
        engine's partial gradients and metrics match the GSPMD path's.
        ``norm`` is the LOCAL loss normalizer contribution (float mask sum
        for the unfused CE, int valid count for the fused head — the two
        paths normalize with different dtypes in the replicated step)."""
        from ddlbench_tpu.models.layers import apply_model
        from ddlbench_tpu.parallel.common import (cast_input, cast_params,
                                                  correct_and_count,
                                                  fused_head_loss_sums,
                                                  head_fusable)

        cfg = self.cfg
        p = cast_params(params, self.compute_dtype)
        xc = cast_input(x, self.compute_dtype)
        if cfg.fused_head_loss and head_fusable(self.model):
            obj_sum, ce_sum, correct, valid, new_state = fused_head_loss_sums(
                self.model, p, state, xc, y, smooth)
            return obj_sum, ce_sum, correct, valid, valid, new_state
        logits, new_state = apply_model(self.model, p, state, xc, True)
        with scopes.scope(scopes.LOSS):
            lf = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(lf, axis=-1)
            maskf = (y >= 0).astype(jnp.float32)
            safe = jnp.maximum(y, 0)
            nll = -jnp.take_along_axis(logp, safe[..., None],
                                       axis=-1)[..., 0]
            ce_sum = jnp.sum(nll * maskf)
            if smooth:
                nll_s = ((1.0 - smooth) * nll
                         - smooth * jnp.mean(logp, axis=-1))
                obj_sum = jnp.sum(nll_s * maskf)
            else:
                obj_sum = ce_sum
            norm = jnp.sum(maskf)
        correct, valid = correct_and_count(logits, y)
        return obj_sum, ce_sum, correct, valid, norm, new_state

    def _build_explicit_engine(self, smooth):
        """Build train_step as one jit whose body is an explicit shard_map
        over 'data': per-device partial grads -> packed flat vector ->
        per-bucket psum_scatter (sharded update) or psum (replicated
        update), in self.wire_dtype on the wire -> packed-slice optimizer
        update -> params re-assembled at the sharding boundary (monolithic
        all-gather) or kept SHARDED between steps with per-bucket
        just-in-time all-gathers in the forward (the overlapped engine,
        --comm-buckets > 1 with --dp-shard-update)."""
        from jax import lax

        from ddlbench_tpu.compat import shard_map as _shard_map
        from ddlbench_tpu.models.layers import batch_parallel
        from ddlbench_tpu.parallel.common import (bucket_slice, flat_meta,
                                                  pack_flat, psum_keepgrad,
                                                  quantize_int8,
                                                  shard_bucket_slice,
                                                  sum_safe_qmax,
                                                  unpack_buckets, unpack_flat,
                                                  vary)

        cfg = self.cfg
        model = self.model
        mesh = self.mesh
        n = mesh.devices.size
        K = cfg.grad_accum_steps
        shard_update = self.shard_update
        wire = self.wire_dtype
        opt_update = self._opt_update
        overlap = self._overlap = cfg.dp_overlap_engine()
        int8_wire = wire == jnp.dtype(jnp.int8)

        abs_params, abs_state = jax.eval_shape(
            lambda k: init_model(model, k)[:2], jax.random.key(0))
        # Layer-aligned buckets: abs_params is the per-layer params list, so
        # each layer's leaves form one alignment group and bucket boundaries
        # fall on layer boundaries — the backward finishes a bucket's
        # gradients as one contiguous stretch of layers unwinds.
        leaf_groups = [len(jax.tree.leaves(p)) for p in abs_params]
        meta = flat_meta(abs_params, n, buckets=cfg.comm_buckets,
                         leaf_groups=leaf_groups)
        self._flat_meta = meta
        self._abs_params = abs_params
        self._leaf_groups = leaf_groups
        shard_len = meta.padded // n
        elastic = self._elastic = cfg.elastic_slices
        if elastic and jax.tree.leaves(abs_state):
            raise NotImplementedError(
                "elastic_slices (world-invariant reduction order) supports "
                "stateless (non-BN) models: batch statistics computed over "
                "per-slice sub-batches cannot be made world-invariant "
                f"({model.name} carries model state)")
        qmax = sum_safe_qmax(n) if int8_wire else None
        # int8 stochastic-rounding key root: run seed + a fixed tag keeping
        # the stream disjoint from data/init keys; the step counter
        # (optimizer dict "qstep"), device index, micro-step, and bucket
        # index fold in below — fully deterministic under the run seed.
        int8_key_root = (jax.random.fold_in(jax.random.key(cfg.seed), 0x1A8)
                         if int8_wire else None)

        def reduce_grads(g, qkey=None):
            """Partial gradient pytree -> REDUCED packed flat f32 vector:
            the wire-dtype cast (int8: global-absmax scaling + stochastic
            rounding), then per-bucket psum_scatter (sharded update: each
            device keeps one contiguous 1/world slice of EACH bucket,
            concatenated — the device-major layout) or psum (replicated
            update). Each bucket's collective depends only on its own
            layers' gradients, which is the whole overlap story: the
            latency-hiding scheduler starts late buckets' wire time while
            earlier layers' backward still computes. Within a bucket the
            reduction is the same elementwise cross-device sum as the
            monolithic path, so the f32 result is bitwise-pinned."""
            gf = pack_flat(g, meta)
            if meta.num_buckets == 1 and not int8_wire:
                # the exact PR 3 monolithic program (--comm-buckets 1)
                with scopes.scope(scopes.GRAD_SYNC), scopes.scope("bucket0"):
                    gw = gf.astype(wire)
                    if shard_update:
                        return lax.psum_scatter(
                            gw, "data", tiled=True).astype(jnp.float32)
                    return lax.psum(gw, "data").astype(jnp.float32)
            parts = []
            for b in range(meta.num_buckets):
                gb = bucket_slice(gf, meta, b)
                # grad_sync/bucket<b> on each bucket's collective: the device
                # trace tells the buckets apart by their op_name
                with scopes.scope(scopes.GRAD_SYNC), scopes.scope(f"bucket{b}"):
                    if int8_wire:
                        # one scale per bucket, shared across devices (pmax
                        # of the local absmaxes) — a per-device scale could
                        # not be summed on the wire
                        absmax = lax.pmax(jnp.max(jnp.abs(gb)), "data")
                        q, scale = quantize_int8(
                            gb, jax.random.fold_in(qkey, b), qmax=qmax,
                            absmax=absmax)
                        red = (lax.psum_scatter(q, "data", tiled=True)
                               if shard_update else lax.psum(q, "data"))
                        parts.append(red.astype(jnp.float32) * scale)
                    else:
                        gw = gb.astype(wire)
                        red = (lax.psum_scatter(gw, "data", tiled=True)
                               if shard_update else lax.psum(gw, "data"))
                        parts.append(red.astype(jnp.float32))
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        guard = self._guard

        def gather_params(pshard):
            """Overlapped forward: one all-gather PER BUCKET, each leaf
            sliced from its bucket's gathered stretch only — the first
            forward layer depends on bucket 0's all-gather alone, so
            compute starts while late buckets are still on the wire."""
            stretches = [
                lax.all_gather(shard_bucket_slice(pshard, meta, n, b),
                               "data", tiled=True)
                for b in range(meta.num_buckets)
            ]
            return unpack_buckets(stretches, meta)

        # -- elastic world-invariant reduction (--elastic-slices E) --------
        # The canonical tree: pairwise fold over each device's E/world
        # contiguous slice partials, then a recursive-doubling butterfly
        # across devices. Both halves compose into ONE balanced binary
        # tree over the E slice partials whose shape depends on E alone —
        # the property that makes f32 trajectories bitwise across world
        # sizes (module docstring; pinned by tests/test_elastic.py).

        def _stack_fold(v):
            """Balanced pairwise fold over the leading (slice) axis of a
            stacked array — the local half of the canonical tree. The
            slice count is a power of two (validate gates E and world)."""
            while v.shape[0] > 1:
                v = v[0::2] + v[1::2]
            return v[0]

        def _butterfly(tree):
            """Recursive-doubling allreduce: after log2(world) XOR-partner
            exchange rounds every device holds the balanced-tree sum —
            with IDENTICAL bits on every device, because a + b and b + a
            round identically (IEEE addition is commutative; only
            associativity fails)."""
            r = 1
            out = tree
            while r < n:
                perm = [(d, d ^ r) for d in range(n)]
                out = jax.tree.map(
                    lambda a: a + lax.ppermute(a, "data", perm), out)
                r <<= 1
            return out

        def _replicate0(x):
            """Force replicated VMA typing on a value the butterfly already
            made device-uniform, without perturbing its bits: psum of
            (x on device 0, zeros elsewhere) — adding zeros is exact in
            any association order."""
            keep = lax.axis_index("data") == 0
            return lax.psum(jnp.where(keep, x, jnp.zeros_like(x)), "data")

        def _own_shard(vec):
            """This device's device-major shard of a full bucket-layout
            vector (the butterfly leaves the FULL reduced vector on every
            device; the optimizer wants its 1/world slice of each
            bucket)."""
            d = lax.axis_index("data")
            parts = [lax.dynamic_slice_in_dim(
                vec, meta.bucket_offsets[b] + d * (meta.bucket_padded[b]
                                                   // n),
                meta.bucket_padded[b] // n)
                for b in range(meta.num_buckets)]
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        def _canonical_denom(y):
            # valid-label counts are small exact integers: their psum is
            # bitwise order-free, so the loss normalizer needs no tree
            return jnp.maximum(1.0, lax.psum(
                jnp.sum((y >= 0).astype(jnp.int32)),
                "data").astype(jnp.float32))

        def elastic_grads(params, state, x, y, smul):
            """(ce, correct, valid, new_state, grad_shard) with every f32
            reduction on the canonical E-leaf tree. Per-slice programs are
            shape-identical across world sizes (each slice is global_B/E
            rows), so save@N -> resume@M replays the same bits. The slices
            run under ONE lax.scan body — program size stays O(1) in E
            instead of unrolling E/world backward passes — and the scan
            only STACKS per-slice partials; the cross-slice reduction is
            the balanced fold below, never the scan's left-to-right carry."""
            k_local = elastic // n
            b = x.shape[0] // k_local
            denom = _canonical_denom(y)
            xs = x.reshape(k_local, b, *x.shape[1:])
            ys = y.reshape(k_local, b, *y.shape[1:])

            def slice_body(st, xy):
                xk, yk = xy

                def f(p):
                    obj_sum, ce_sum, correct, valid, _norm, new_st = \
                        self._local_loss_sums(p, st, xk, yk, smooth)
                    obj = obj_sum / denom
                    if smul is not None:  # guard: loss scale / poison
                        obj = obj * smul
                    return obj, (ce_sum, correct, valid, new_st)

                (_, (ce_sum, correct, valid, new_st)), g = \
                    jax.value_and_grad(f, has_aux=True)(params)
                return new_st, (pack_flat(g, meta), ce_sum, correct, valid)

            st, (gstack, ces, corrs, valids) = lax.scan(
                slice_body, state, (xs, ys))
            g_local, ce_local = _stack_fold(gstack), _stack_fold(ces)
            g_full, ce_tot = _butterfly((g_local, ce_local))
            ce = _replicate0(ce_tot) / denom
            # int sums are exact in any order — no tree needed
            return (ce, lax.psum(jnp.sum(corrs), "data"),
                    lax.psum(jnp.sum(valids), "data"), st,
                    _own_shard(g_full))

        def local_grads(params, state, x, y, smul, qkey=None):
            """(ce, correct, valid, new_state, g_reduced): psum'd metrics
            plus the reduced flat gradient (shard or full vector).
            Non-accum partials are pre-seeded by 1/global_count (the GSPMD
            backward's seed) and reduced once. Grad accumulation reduces
            EVERY micro-gradient inside the scan — mirroring the
            replicated step, whose scan carry forces GSPMD to allreduce
            each micro-gradient (one fused multiply-add per step on the
            reduced value; bitwise parity needs the same summation order)
            — and divides the reduced sum by the total weight at the end.
            Wire-wise this still halves replicated accum's cost: K
            reduce-scatters vs K full allreduces."""
            if K == 1:
                def loss_fn(p):
                    obj_sum, ce_sum, correct, valid, norm, new_state = \
                        self._local_loss_sums(p, state, x, y, smooth)
                    denom = jnp.maximum(
                        1.0, lax.psum(norm, "data").astype(jnp.float32))
                    obj = psum_keepgrad(obj_sum, "data") / denom
                    if smul is not None:  # guard: loss scale / poison
                        obj = obj * smul
                    return obj, (ce_sum, correct, valid, denom, new_state)

                (_, (ce_sum, correct, valid, denom, new_state)), g = \
                    jax.value_and_grad(loss_fn, has_aux=True)(params)
                ce = lax.psum(ce_sum, "data") / denom
                return (ce, lax.psum(correct, "data"),
                        lax.psum(valid, "data"), new_state,
                        reduce_grads(g, qkey))

            B = x.shape[0]
            assert B % K == 0, (
                f"local batch {B} not divisible by grad_accum_steps {K}")
            # Micro-step k takes every K-th local row — the same rows of
            # the global micro-batch that GSPMD keeps on this device
            # (common.accum_loss_and_grads's re-grouping, applied to the
            # local shard).
            xs = x.reshape(B // K, K, *x.shape[1:])
            ys = y.reshape(B // K, K, *y.shape[1:])

            def step(carry, k):
                st, gsum = carry
                xk = lax.dynamic_index_in_dim(xs, k, axis=1, keepdims=False)
                yk = lax.dynamic_index_in_dim(ys, k, axis=1, keepdims=False)

                def f(p):
                    obj_sum, ce_sum, correct, valid, norm, new_st = \
                        self._local_loss_sums(p, st, xk, yk, smooth)
                    denom = jnp.maximum(
                        1.0, lax.psum(norm, "data").astype(jnp.float32))
                    obj = psum_keepgrad(obj_sum, "data") / denom
                    if smul is not None:
                        obj = obj * smul
                    return obj, (ce_sum, correct, valid, denom, new_st)

                (_, (ce_sum, correct, valid, denom, new_st)), g = \
                    jax.value_and_grad(f, has_aux=True)(params)
                ce_k = lax.psum(ce_sum, "data") / denom
                wk = lax.psum(valid, "data").astype(jnp.float32)
                qk = (jax.random.fold_in(qkey, k) if qkey is not None
                      else None)
                gsum = gsum + wk * reduce_grads(g, qk)
                return (new_st, gsum), (ce_k, wk, lax.psum(correct, "data"),
                                        lax.psum(valid, "data"))

            gsum0 = jnp.zeros(
                (shard_len if shard_update else meta.padded,), jnp.float32)
            if shard_update:
                # psum_scatter outputs are device-varying; the scan carry
                # must start with matching varying-axes type
                gsum0 = vary(gsum0, ("data",))
            (new_state, gsum), (ces, wks, corrs, valids) = lax.scan(
                step, (state, gsum0), jnp.arange(K))
            total = jnp.maximum(1.0, jnp.sum(wks))
            ce = jnp.sum(ces * wks) / total
            return (ce, jnp.sum(corrs), jnp.sum(valids), new_state,
                    gsum / total)

        def local_step(params, state, opt, x, y, lr):
            gstate, smul, qstep, qkey = None, None, None, None
            if int8_wire:
                # the stochastic-rounding step counter rides in the opt dict
                # (split out before the optimizer update, advanced after —
                # the same pattern as the guard's scale state); it advances
                # on skipped steps too, keeping select's tree shapes simple
                qstep = opt["qstep"]
                opt = {k: v for k, v in opt.items() if k != "qstep"}
                qkey = jax.random.fold_in(int8_key_root, qstep)
                qkey = jax.random.fold_in(qkey, lax.axis_index("data"))
            if guard is not None:
                opt, gstate = guard.split_opt(opt)
                smul = guard.smul(gstate, lr)
            if overlap:
                # params arrive as this device's flat shard: just-in-time
                # per-bucket all-gather rebuilds the pytree for the forward
                pshard = params
                params = gather_params(pshard)
            # Differentiate w.r.t. a data-VARYING view of the params: the
            # gradient of a varying loss w.r.t. a replicated (invariant)
            # input is already its psum over 'data', and the engine's own
            # wire-dtype collective below must be the one reduction.
            gparams = jax.tree.map(lambda a: vary(a, ("data",)), params)
            if elastic:
                # world-invariant canonical-tree path (no BN — validated
                # at build, so no batch_parallel context is needed)
                ce, correct, valid, new_state, gr = elastic_grads(
                    gparams, state, x, y, smul)
            else:
                with batch_parallel("data", n):
                    ce, correct, valid, new_state, gr = local_grads(
                        gparams, state, x, y, smul, qkey)
            if guard is not None:
                # unscale AFTER the (wire-dtype) collective — the scaled
                # values are what rides the wire — then fuse the health
                # pair: the shard's sumsq psums to the global grad norm.
                gr = gr / smul
                sumsq = jnp.sum(jnp.square(gr))
                if shard_update:
                    sumsq = lax.psum(sumsq, "data")
                finite, gnorm = guard.finite(ce, jnp.sqrt(sumsq))
            metrics = {
                "loss": ce,
                "accuracy": correct.astype(jnp.float32)
                / jnp.maximum(1.0, valid.astype(jnp.float32)),
            }
            if guard is not None:
                new_gstate = guard.scaler_update(gstate, finite)
                metrics.update(guard.metrics(finite, gnorm, new_gstate))
            if shard_update:
                if overlap:
                    # params already flat+sharded between steps: the local
                    # shard IS the optimizer's parameter slice
                    ps = pshard
                else:
                    pf = pack_flat(params, meta)
                    ps = lax.dynamic_slice_in_dim(
                        pf, lax.axis_index("data") * shard_len, shard_len)
                new_ps, new_opt = opt_update(ps, gr, opt, lr)
                if guard is not None:
                    # skip-select covers the ZeRO-1 SHARDED slices too: the
                    # untouched old slice all-gathers back, so the
                    # re-assembled params are bitwise the pre-step ones
                    new_ps, new_state, new_opt = guard.select(
                        finite, (new_ps, new_state, new_opt),
                        (ps, state, opt))
                    new_opt = guard.fold_opt(new_opt, new_gstate)
                if qstep is not None:
                    new_opt = {**new_opt, "qstep": qstep + 1}
                # Monolithic engine: out_spec P('data') on the updated slice
                # re-assembles the flat parameter vector across devices —
                # the all-gather happens at the shard_map output boundary.
                # Overlapped engine: the slice STAYS the state (out spec
                # P('data') with no host unpack) and the NEXT step's forward
                # all-gathers it per bucket, just in time.
                return new_ps, new_state, new_opt, metrics
            # compressed allreduce with the replicated update: the explicit
            # psum already ran in the wire dtype; per-leaf optimizer step.
            new_params, new_opt = opt_update(
                params, unpack_flat(gr, meta), opt, lr)
            if guard is not None:
                new_params, new_state, new_opt = guard.select(
                    finite, (new_params, new_state, new_opt),
                    (params, state, opt))
                new_opt = guard.fold_opt(new_opt, new_gstate)
            if qstep is not None:
                new_opt = {**new_opt, "qstep": qstep + 1}
            return new_params, new_state, new_opt, metrics

        flat_spec = P("data") if shard_update else P()
        flat_sh = (NamedSharding(mesh, P("data")) if shard_update
                   else self._replicated)
        opt_specs = {"m": flat_spec}
        opt_shardings = {"m": flat_sh}
        if cfg.resolved_optimizer() == "adam":
            opt_specs.update(v=flat_spec, step=P())
            opt_shardings.update(v=flat_sh, step=self._replicated)
        if int8_wire:
            # replicated int32 stochastic-rounding step counter
            opt_specs.update(qstep=P())
            opt_shardings.update(qstep=self._replicated)
        if guard is not None:
            # dynamic loss-scale state: two replicated scalars in the dict
            opt_specs = guard.opt_state_spec(opt_specs, P())
            opt_shardings = guard.opt_state_spec(opt_shardings,
                                                 self._replicated)
        self._opt_shardings = opt_shardings

        sharded = _shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P("data") if overlap else P(), P(), opt_specs,
                      P("data"), P("data"), P()),
            out_specs=(P("data") if shard_update else P(), P(), opt_specs,
                       P()),
        )

        def step(ts: TrainState, x, y, lr):
            p_out, new_state, new_opt, metrics = sharded(
                ts.params, ts.model_state, ts.opt, x, y, lr)
            if shard_update and not overlap:
                p_out = unpack_flat(p_out, meta)
            # overlapped engine: p_out STAYS the flat device-major sharded
            # vector — no boundary all-gather; the next step (and eval /
            # materialize_params) gathers per bucket on demand
            return TrainState(p_out, new_state, new_opt), metrics

        param_out_sh = (NamedSharding(mesh, P("data")) if overlap
                        else self._replicated)
        jit_step = jax.jit(
            step,
            donate_argnums=(0,),
            in_shardings=(None, self._batch_sharding, self._batch_sharding,
                          None),
            out_shardings=(TrainState(param_out_sh, self._replicated,
                                      opt_shardings), None),
        )
        self._jit_train_step = jit_step  # introspection (tests, tools)
        mode = ("overlapped" if overlap
                else "sharded" if shard_update else "replicated")
        # Exact per-bucket wire-byte schedule: ring RS ships (n-1)/n of the
        # (padded) bucket in the wire dtype, the param AG the same fraction
        # in f32 (master weights), and the replicated engine's ring
        # ALLREDUCE ships 2(n-1)/n (RS + AG halves of the same ring —
        # matching comm_stats._ring_allreduce_bytes). The one host span of
        # the step, dp_explicit_update, carries the totals as arguments;
        # per-bucket device time is the grad_sync/bucket<b> scope on the
        # collectives themselves (reduce_grads), read from a device trace.
        wire_itemsize = 1 if int8_wire else jnp.dtype(wire).itemsize
        rs_scale = ((n - 1) / n if shard_update
                    else 2.0 * (n - 1) / n if n > 1 else 0.0)
        bucket_sched = [
            {"bucket": b, "offset": meta.bucket_offsets[b],
             "elems": meta.bucket_padded[b],
             "rs_wire_bytes": rs_scale * meta.bucket_padded[b]
             * wire_itemsize,
             "ag_wire_bytes": (n - 1) / n * meta.bucket_padded[b] * 4.0}
            for b in range(meta.num_buckets)
        ]
        self._bucket_schedule = bucket_sched
        span_args = {
            "mode": mode, "wire": str(jnp.dtype(wire)),
            "buckets": meta.num_buckets,
            "grad_wire_bytes": sum(sc["rs_wire_bytes"]
                                   for sc in bucket_sched),
            "param_wire_bytes": (sum(sc["ag_wire_bytes"]
                                     for sc in bucket_sched)
                                 if shard_update else 0.0)}

        def train_step(ts, x, y, lr):
            from ddlbench_tpu.telemetry import get_tracer

            # the update phase's dispatch on the host timeline
            with get_tracer().span("dp_explicit_update", **span_args):
                return jit_step(ts, x, y, lr)

        self.train_step = train_step

        if elastic:
            # eval on the same canonical tree: validation losses of an
            # elastic run are world-invariant too (chaosbench's trajectory
            # check compares the per-epoch valid records bitwise)
            def elastic_eval_local(params, state, x, y):
                k_local = elastic // n
                b = x.shape[0] // k_local
                xs = x.reshape(k_local, b, *x.shape[1:])
                ys = y.reshape(k_local, b, *y.shape[1:])

                def slice_body(_, xy):
                    ce_sum, c, c5, v = self._local_eval_sums(
                        params, state, *xy)
                    return 0, (ce_sum, c, c5, v)

                _, (ces, corrs, corr5s, cnts) = lax.scan(
                    slice_body, 0, (xs, ys))
                corr = jnp.sum(corrs)
                corr5 = jnp.sum(corr5s)
                ce_tot = _replicate0(_butterfly(_stack_fold(ces)))
                count = lax.psum(jnp.sum(cnts), "data")
                return {
                    "loss": ce_tot
                    / jnp.maximum(1.0, count.astype(jnp.float32)),
                    "correct": lax.psum(corr, "data"),
                    "correct5": lax.psum(corr5, "data"),
                    "count": count,
                }

            sharded_eval = _shard_map(
                elastic_eval_local, mesh=mesh,
                in_specs=(P(), P(), P("data"), P("data")), out_specs=P())

            def elastic_eval_step(ts, x, y):
                return sharded_eval(self._params_pytree(ts), ts.model_state,
                                    x, y)

            self.eval_step = jax.jit(
                elastic_eval_step,
                in_shardings=(None, self._batch_sharding,
                              self._batch_sharding))

    def _local_eval_sums(self, params, state, x, y):
        """Per-slice eval sums (ce_sum, correct, correct5, count) —
        common.eval_metrics' computation before normalization, so the
        elastic eval can reduce them on the canonical tree."""
        from ddlbench_tpu.models.layers import apply_model
        from ddlbench_tpu.parallel.common import (cast_input, cast_params,
                                                  correct_and_count,
                                                  correct_topk,
                                                  fused_head_eval_sums)

        cfg = self.cfg
        p = cast_params(params, self.compute_dtype)
        xc = cast_input(x, self.compute_dtype)
        if cfg.fused_head_loss and self.model.layers[-1].fused_eval \
                is not None:
            return fused_head_eval_sums(self.model, p, state, xc, y)
        logits, _ = apply_model(self.model, p, state, xc, False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        maskf = (y >= 0).astype(jnp.float32)
        safe = jnp.maximum(y, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        correct, valid = correct_and_count(logits, y)
        return jnp.sum(nll * maskf), correct, correct_topk(logits, y), valid

    def flat_meta_for_world(self, world: int, buckets: int):
        """The packed flat layout this MODEL would have at another world
        size — what train/reshard.py permutes an elastic checkpoint
        through (and verifies against the recorded layout)."""
        from ddlbench_tpu.parallel.common import flat_meta

        return flat_meta(self._abs_params, world, buckets=max(1, buckets),
                         leaf_groups=self._leaf_groups)

    def init(self, key) -> TrainState:
        from ddlbench_tpu.distributed import put_global_tree

        params, state, _ = init_model(self.model, key)
        int8_wire = self._explicit and self.wire_dtype == jnp.dtype(jnp.int8)
        if self._explicit and self.shard_update:
            # ZeRO-1: optimizer state lives on the packed flat vector, one
            # contiguous [padded/world] slice per device.
            opt = self._opt_init(
                jnp.zeros((self._flat_meta.padded,), jnp.float32))
            if int8_wire:
                opt = {**opt, "qstep": jnp.zeros((), jnp.int32)}
            if self._guard is not None:
                opt = self._guard.attach_opt_state(opt)
            if self._overlap:
                # overlapped engine: params live SHARDED between steps as
                # the flat device-major vector (broadcast-init parity still
                # holds — every host computes the same seed-deterministic
                # init, each device keeps its 1/world stretch)
                from ddlbench_tpu.parallel.common import (pack_flat,
                                                          to_device_major)

                meta = self._flat_meta
                pflat = to_device_major(pack_flat(params, meta), meta,
                                        self.mesh.devices.size)
                ts = TrainState(pflat, state, opt)
                shardings = TrainState(
                    NamedSharding(self.mesh, P("data")), self._replicated,
                    self._opt_shardings)
                return put_global_tree(ts, shardings)
            ts = TrainState(params, state, opt)
            shardings = TrainState(self._replicated, self._replicated,
                                   self._opt_shardings)
            return put_global_tree(ts, shardings)
        opt = self._opt_init(params)
        if int8_wire:
            opt = {**opt, "qstep": jnp.zeros((), jnp.int32)}
        if self._guard is not None:
            opt = self._guard.attach_opt_state(opt)
        ts = TrainState(params, state, opt)
        # Broadcast-init parity (mnist_horovod.py:230-231): replicate to the
        # mesh — identical on every host since init is seed-deterministic.
        shardings = TrainState(self._replicated, self._replicated,
                               self._replicated)
        if self.cfg.shard_opt_state:
            # ZeRO-1: optimizer state sharded over 'data' (largest divisible
            # dim per leaf), params replicated. Pure placement — XLA shards
            # the update math and all-gathers only the parameter delta. With
            # adam this drops the optimizer memory 2x*params -> 2x/world.
            from ddlbench_tpu.parallel.sharded import _leaf_spec

            n = self.mesh.devices.size

            def leaf_sh(x):
                return NamedSharding(
                    self.mesh, _leaf_spec(x, "data", n, prefer_last=False))

            shardings = TrainState(
                self._replicated, self._replicated,
                jax.tree.map(leaf_sh, ts.opt))
        return put_global_tree(ts, shardings)

    def shard_batch(self, x, y):
        from ddlbench_tpu.distributed import put_global_batch

        return (
            put_global_batch(x, self._batch_sharding),
            put_global_batch(y, self._batch_sharding),
        )

    @property
    def world_size(self) -> int:
        return self.mesh.devices.size
