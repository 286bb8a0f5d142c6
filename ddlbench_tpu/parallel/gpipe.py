"""Synchronous micro-batch pipeline parallelism — the reference's GPipe engine,
TPU-native.

Reference mechanism (benchmark/mnist/mnist_gpipe.py): flatten the model to
nn.Sequential, `balance_by_time` auto-partitions (:215-217), `GPipe(model,
balance, chunks=MICROBATCHES)` (:219) runs a clock-cycle schedule moving
micro-batch j through partition k with per-stage CUDA streams, stash/pop skip
connections across partitions, synchronous flush at the step end.

TPU-native design — the whole schedule is ONE compiled XLA program:

* mesh axes ``('data', 'stage')``; stage s's parameters live on its mesh row as
  a row of a packed ``[S, L]`` matrix (parallel/packing.py);
* `lax.scan` over the M + S - 1 clock ticks; each tick every device runs its
  stage via `lax.switch` and hands its activation to the right neighbor with
  `lax.ppermute` — the TPU analog of the reference's stream copies
  (SURVEY.md §3.4);
* the backward pipeline is not hand-written: `jax.grad` through the
  scan+ppermute forward yields the reversed schedule automatically (ppermute
  transposes to the opposite permutation), and `jax.checkpoint` on each stage
  reproduces torchgpipe's per-(microbatch, stage) activation checkpointing;
* hybrid PPxDP comes from the 'data' mesh axis: batches shard across it and
  shard_map's transpose machinery inserts the gradient all-reduce over ICI.

There is no stash/pop skip machinery: residual blocks are pipeline-atomic
layers (models/layers.py), so skips never cross a stage boundary.
"""

from __future__ import annotations

import math
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlbench_tpu.compat import shard_map as _shard_map

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import LayerModel, apply_slice, init_model
from ddlbench_tpu.parallel.common import (
    cast_input, cast_params, correct_and_count, correct_topk,
    cross_entropy_loss)
from ddlbench_tpu.parallel.packing import (
    balanced_stage_bounds,
    layer_flop_costs,
    pack_stages,
    pad_vec,
)


from ddlbench_tpu.parallel.common import vary as _vary_axes

_PIPE_AXES = ("data", "stage")


def _vary(v, axes=_PIPE_AXES):
    return _vary_axes(v, axes)


class PipeTrainState(NamedTuple):
    # V = cfg.virtual_stages model chunks per device; layouts:
    #   V=1: [S, L] f32, P('stage', None)        (row s = stage s)
    #   V>1: [V, S, L] f32, P(None, 'stage', None) (row [v, s] = chunk v*S+s)
    params: jax.Array
    model_state: jax.Array  # [S, Ls] / [V, S, Ls], same sharding as params
    # optimizer-state dict pytree (common.make_optimizer): m/v leaves mirror
    # params; the adam step counter is shaped [..., 1] per stage row so every
    # leaf shares the params' stage sharding
    opt: Any


def make_pipe_mesh(num_stages: int, dp_replicas: int,
                   devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    from ddlbench_tpu.distributed import make_mesh

    # 'stage' transfers are bandwidth-hungry: keep them on ICI; the 'data'
    # replica axis may span hosts over DCN.
    return make_mesh(
        [("data", dp_replicas), ("stage", num_stages)],
        devices=devices,
        dcn_axis="data",
    )


class GPipeStrategy:
    """strategy='gpipe': synchronous micro-batch pipeline over a 'stage' mesh axis."""

    def __init__(self, model: LayerModel, cfg: RunConfig,
                 mesh: Optional[Mesh] = None,
                 devices: Optional[Sequence[jax.Device]] = None,
                 stage_bounds: Optional[List[int]] = None):
        self.model = model
        self.cfg = cfg
        self.num_stages = cfg.resolved_stages()
        self.dp = max(1, cfg.dp_replicas)
        # Interleaved schedule (Megatron-style virtual stages): each device
        # owns V model chunks, chunk c = v*S + s living on device s. The
        # synchronous-pipeline bubble shrinks from (S-1) stage-times to
        # (S-1)/V; chunk handoffs become a ring rotation (every boundary is a
        # device boundary). V=1 is the classic schedule.
        self.vstages = max(1, getattr(cfg, "virtual_stages", 1))
        self.num_chunks = self.num_stages * self.vstages
        # Hybrid PP x ZeRO-1 (--dp-shard-update on gpipe): stage parameter
        # rows + optimizer state live SHARDED across the pipe mesh's
        # 'data' axis between steps (device-major bucketed flat layout,
        # parallel/common.py row_flat_meta); the forward all-gathers each
        # bucket just-in-time and the backward reduce-scatters per bucket
        # — optimizer bytes/chip drop /dp, the grad wire halves vs the
        # replicated pmean, and late buckets overlap the drain.
        # Elastic resume (train/reshard.py) reads pipe_shard/_row_meta/dp
        # off this strategy to reshard a checkpoint's rows between dp
        # replica counts (same stage split) — keep those names stable.
        self.pipe_shard = cfg.pipe_shard_engine()
        self.mesh = mesh or make_pipe_mesh(self.num_stages, self.dp, devices)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.mb, self.num_microbatches = cfg.resolved_batches()
        self._stage_bounds_override = stage_bounds
        self._built = False
        from ddlbench_tpu.guard import device_guard
        from ddlbench_tpu.parallel.common import make_optimizer

        self._opt_init, self._opt_update = make_optimizer(cfg)
        self._guard = device_guard(cfg)  # None = pre-guard program

    # -- initialization ----------------------------------------------------

    def _chunk_sharding_spec(self) -> P:
        # V=1: [S, L] rows over 'stage'; V>1: [V, S, L] middle axis over it.
        return P("stage", None) if self.vstages == 1 else P(None, "stage", None)

    def _param_spec(self) -> P:
        """Params (and optimizer m/v): the chunk spec, plus — hybrid
        PP x ZeRO-1 — the flat row axis sharded over 'data'."""
        if not self.pipe_shard:
            return self._chunk_sharding_spec()
        return (P("stage", "data") if self.vstages == 1
                else P(None, "stage", "data"))

    def init(self, key) -> PipeTrainState:
        params_list, state_list, shapes = init_model(self.model, key)
        S, V, C = self.num_stages, self.vstages, self.num_chunks
        bounds = getattr(self, "bounds", None)
        if bounds is None:
            if self._stage_bounds_override is not None:
                bounds = list(self._stage_bounds_override)
            else:
                costs = layer_flop_costs(params_list, shapes,
                                          self.model.layers)
                bounds = balanced_stage_bounds(costs, C)
            assert len(bounds) == C + 1 and bounds[0] == 0 and bounds[-1] == len(self.model.layers)
            self.bounds = bounds
            self.shapes = shapes

        params_mat, p_unravels, p_lens = pack_stages(
            [params_list[bounds[c]:bounds[c + 1]] for c in range(C)]
        )
        state_mat, s_unravels, s_lens = pack_stages(
            [state_list[bounds[c]:bounds[c + 1]] for c in range(C)]
        )
        if V > 1:
            # row c = v*S + s -> [v, s] (device s holds its V chunk rows)
            params_mat = params_mat.reshape(V, S, -1)
            state_mat = state_mat.reshape(V, S, -1)

        if self.pipe_shard and not self._built:
            from ddlbench_tpu.parallel.common import (device_major_perm,
                                                      row_flat_meta)

            self._row_meta = row_flat_meta(
                int(params_mat.shape[-1]), self.dp,
                max(1, self.cfg.comm_buckets))
            perm, inv = device_major_perm(self._row_meta, self.dp)
            self._row_perm = jnp.asarray(perm)
            self._row_inv = jnp.asarray(inv)

        if not self._built:
            self._p_unravels, self._p_lens = p_unravels, p_lens
            self._s_unravels, self._s_lens = s_unravels, s_lens
            # Per-device activation buffer: the largest activation crossing a
            # chunk boundary for one microbatch (per data replica). With V>1
            # every chunk boundary is a device boundary.
            interior = [
                self.mb * math.prod(shapes[bounds[c]]) for c in range(1, C)
            ]
            self._act_size = max(interior) if interior else 1
            self._build_steps()

        from ddlbench_tpu.distributed import put_global_batch

        if self.pipe_shard:
            # device-major bucketed relayout of every row, then the 'data'
            # axis shards each device's contiguous 1/dp stretch (the same
            # layout the per-bucket psum_scatter outputs produce — see
            # parallel/common.py to_device_major)
            pad = self._row_meta.padded - params_mat.shape[-1]
            params_mat = jnp.pad(
                params_mat,
                [(0, 0)] * (params_mat.ndim - 1) + [(0, pad)])
            params_mat = jnp.take(params_mat, self._row_perm, axis=-1)

        sharding = NamedSharding(self.mesh, self._chunk_sharding_spec())
        psharding = NamedSharding(self.mesh, self._param_spec())
        params_mat = put_global_batch(params_mat, psharding)
        state_mat = put_global_batch(state_mat, sharding)
        opt = self._opt_init(params_mat,
                             step_like=params_mat.shape[:-1] + (1,))
        if "step" in opt:
            opt = {**opt, "step": put_global_batch(opt["step"], sharding)}
        if self._guard is not None:
            opt = self._guard.attach_opt_state(opt)  # dynamic loss scale
        return PipeTrainState(params_mat, state_mat, opt)

    # -- stage branch construction ----------------------------------------

    def _make_branch(self, c: int, train: bool):
        """Branch for lax.switch: identical signature across chunks.

        ``c`` is the model-chunk index (= stage for V=1; c = v*S + s on
        device s for the interleaved schedule). ``m`` — the microbatch this
        chunk processes this tick — is computed by the caller's timetable.
        """
        C, M, mb, A = self.num_chunks, self.num_microbatches, self.mb, self._act_size
        layers = self.model.layers[self.bounds[c]:self.bounds[c + 1]]
        in_shape = self.shapes[self.bounds[c]]
        p_unravel, p_len = self._p_unravels[c], self._p_lens[c]
        s_unravel, s_len = self._s_unravels[c], self._s_lens[c]
        cdtype = self.compute_dtype
        num_classes = self.model.num_classes
        last = c == C - 1

        smooth = self.cfg.resolved_label_smoothing() if train else 0.0
        from ddlbench_tpu.models.moe import collect_aux_losses

        # Fused projection+CE on the loss stage: the [mb*T, vocab] logits
        # never materialize (ops/fused_xent.py); the eval twin also covers
        # the prec@5 metric.
        head = self.model.layers[-1]
        use_fused = (train and last and self.cfg.fused_head_loss
                     and head.fused_loss is not None)
        use_fused_eval = ((not train) and last and self.cfg.fused_head_loss
                          and head.fused_eval is not None)

        def branch(param_row, state_row, x_buf, x_in, ys, m):
            if c == 0:
                x = x_in
            else:
                x = x_buf[: mb * math.prod(in_shape)].reshape(mb, *in_shape)
            params = cast_params(p_unravel(param_row[:p_len]), cdtype)
            states = s_unravel(state_row[:s_len])
            # MoE router load-balance terms of THIS stage's layers are traced
            # into the branch, accumulated in the scan, and added to the
            # objective in _make_pipe_fn (empty for dense models).
            aux: list = []
            if use_fused:
                from ddlbench_tpu.parallel.common import fused_slice_loss_sums

                labels = lax.dynamic_index_in_dim(ys, m, keepdims=False)
                with collect_aux_losses(aux):
                    obj_sum, ce_sum, correct, new_states = (
                        fused_slice_loss_sums(layers, params, states,
                                              cast_input(x, cdtype), labels,
                                              smooth))
                aux_mb = sum(aux, jnp.float32(0.0))
                denom = jnp.maximum(
                    1.0, jnp.sum((labels >= 0).astype(jnp.float32)))
                ce = ce_sum / denom
                loss = obj_sum / denom
                correct5 = jnp.zeros((), jnp.int32)  # train path: discarded
                y_out = jnp.zeros((A,), cdtype)
                new_state_row = pad_vec(
                    ravel_pytree(new_states)[0].astype(jnp.float32),
                    state_row.shape[0],
                )
                return (_vary(y_out), _vary(new_state_row), _vary(loss),
                        _vary(ce), _vary(aux_mb), _vary(correct),
                        _vary(correct5))
            if use_fused_eval:
                from ddlbench_tpu.parallel.common import fused_slice_eval_sums

                labels = lax.dynamic_index_in_dim(ys, m, keepdims=False)
                with collect_aux_losses(aux):
                    ce_sum, correct, correct5, valid = fused_slice_eval_sums(
                        layers, params, states, cast_input(x, cdtype), labels)
                aux_mb = sum(aux, jnp.float32(0.0))
                denom = jnp.maximum(1.0, valid.astype(jnp.float32))
                ce = loss = ce_sum / denom
                return (_vary(jnp.zeros((A,), cdtype)), _vary(state_row),
                        _vary(loss), _vary(ce), _vary(aux_mb), _vary(correct),
                        _vary(correct5))
            with collect_aux_losses(aux):
                y, new_states = apply_slice(layers, params, states,
                                            cast_input(x, cdtype), train)
            aux_mb = sum(aux, jnp.float32(0.0))
            if last:
                labels = lax.dynamic_index_in_dim(ys, m, keepdims=False)
                # loss (the grad path) may be label-smoothed; ce is the
                # reported headline metric, comparable across strategies.
                ce = cross_entropy_loss(y, labels)
                loss = cross_entropy_loss(y, labels, smooth) if smooth else ce
                correct = correct_and_count(y, labels)[0]
                # prec@5 is eval-only; keep the (remat'd) train branch free of
                # the top-k compute — train_step discards it anyway
                correct5 = (jnp.zeros((), jnp.int32) if train
                            else correct_topk(y, labels))
                y_out = jnp.zeros((A,), cdtype)
            else:
                loss = jnp.zeros((), jnp.float32)
                ce = jnp.zeros((), jnp.float32)
                correct = jnp.zeros((), jnp.int32)
                correct5 = jnp.zeros((), jnp.int32)
                y_out = pad_vec(y.astype(cdtype), A)
            new_state_row = pad_vec(
                ravel_pytree(new_states)[0].astype(jnp.float32),
                state_row.shape[0],
            )
            # Constant-valued outputs (zeros) carry no varying-axes annotation;
            # normalize every output's VMA type so lax.switch branches agree.
            return (_vary(y_out), _vary(new_state_row), _vary(loss),
                    _vary(ce), _vary(aux_mb), _vary(correct), _vary(correct5))

        if train and self.cfg.remat_stages:
            branch = jax.checkpoint(branch)
        return branch

    # -- compiled steps ----------------------------------------------------

    def _build_steps(self):
        self._stage_sharding = NamedSharding(self.mesh, self._chunk_sharding_spec())
        self._param_sharding = NamedSharding(self.mesh, self._param_spec())
        self._batch_sharding = NamedSharding(self.mesh, P(None, "data"))
        self._materialize = None  # built lazily (hybrid engine only)
        self.train_step = self._make_train_step()
        self.eval_step = self._make_eval_step()
        self._built = True

    def materialize_params(self, ts: "PipeTrainState"):
        """The plain packed [.., S, L] stage-parameter matrix, replicated
        over 'data' — what host-side consumers (activation logging, tests,
        tools) read. Identity for the replicated engine; the hybrid
        PP x ZeRO-1 engine's between-steps params are the device-major
        padded sharded rows, so this inverts the relayout and drops the
        pad (one jitted gather)."""
        if not self.pipe_shard:
            return ts.params
        if self._materialize is None:
            inv, L = self._row_inv, self._row_meta.length

            def plain(p):
                return jnp.take(p, inv, axis=-1)[..., :L]

            self._materialize = jax.jit(
                plain, out_shardings=self._stage_sharding)
        return self._materialize(ts.params)

    def _make_pipe_fn(self, train: bool):
        """Synchronous fill-drain pipeline fwd (gpipe train fwd and all eval).

        The schedule is DATA (partition/schedule.py fill_drain_timetable):
        chunk c = v*S + s (on device s) runs microbatch m = g*S + r at tick
        t = g*S*V + v*S + s + r — conflict-free and dependency-correct
        (chunk c+1 runs exactly one tick after chunk c, so the handoff is
        always a one-step ring rotation, wrapping S-1 -> 0 between chunk
        groups). The scan body reads its (v, m, valid) triple from the
        table's forward_tick_arrays — the schedule-programmable runtime's
        autodiff mode (parallel/pipeline_rt.py module docstring); the
        backward half of the timetable is jax.grad through this scan,
        inheriting the same schedule reversed. Fill/drain cost is S-1
        CHUNK times instead of the classic (S-1) stage times — the
        interleaved-schedule bubble reduction — at the price of C-1
        rotations per microbatch. Requires M % S == 0 when V > 1.
        """
        S, M, A = self.num_stages, self.num_microbatches, self._act_size
        V, C = self.vstages, self.num_chunks
        mesh = self.mesh
        aux_w = self.cfg.moe_aux_weight if train else 0.0
        branches = [self._make_branch(c, train) for c in range(C)]
        if V == 1:
            perm = [(i, i + 1) for i in range(S - 1)]
        else:
            perm = [(i, (i + 1) % S) for i in range(S)] if S > 1 else []
        from ddlbench_tpu.partition.schedule import fill_drain_timetable

        tt = fill_drain_timetable(S, M, V)
        if train:
            # the TRAIN schedule drives --trace pipe_tick markers; eval
            # always runs fill-drain, and pipedream (async 1F1B train, no
            # static half-tick table) must not inherit this one
            self.timetable = tt
        tv_np, tm_np, tvalid_np = tt.forward_tick_arrays()
        t_v, t_m, t_valid = (jnp.asarray(tv_np), jnp.asarray(tm_np),
                             jnp.asarray(tvalid_np))
        gather_rows = self._make_gather_rows()

        def inner(params_rows, state_rows, xs, ys):
            # params_rows local: [1, L] (V=1) or [V, 1, L]; xs [M, mb, ...]
            # (hybrid PP x ZeRO-1: [1|V, 1?, L/dp] device-major shards,
            # rebuilt to full rows by the per-bucket just-in-time
            # all-gather below — whose TRANSPOSE under jax.grad is the
            # per-bucket psum_scatter that shards the gradients).
            # Mark everything varying over both mesh axes up front so all
            # switch branches produce identical VMA types; the pcast on
            # params transposes to the gradient psum over 'data' (the DP
            # all-reduce) in the backward pass.
            if V == 1:
                param_rows = _vary(params_rows)  # [1, L]
                state_rows = _vary(state_rows)
            else:
                param_rows = _vary(params_rows[:, 0])  # [V, L]
                state_rows = _vary(state_rows[:, 0])
            if gather_rows is not None:
                param_rows = _vary(gather_rows(param_rows))
            xs = _vary(xs)
            ys = _vary(ys)
            s_idx = lax.axis_index("stage")
            T = M * V + S - 1

            def body(carry, t):
                (x_buf, st_rows, loss_acc, ce_acc, aux_acc, corr_acc,
                 corr5_acc) = carry
                v = t_v[t, s_idx]
                valid = t_valid[t, s_idx]
                m = t_m[t, s_idx]
                chunk = v * S + s_idx
                param_row = lax.dynamic_index_in_dim(param_rows, v,
                                                     keepdims=False)
                st_row = lax.dynamic_index_in_dim(st_rows, v, keepdims=False)
                # This tick's input microbatch is picked OUT here, not
                # inside branch 0: an operand of the switch is saved per
                # tick for the backward, and the whole [M, mb, ...] input
                # stacked T times does not fit a chip (resnet50/imagenet
                # at the default 12 x 24: a 55 GB buffer on a 16 GB v5e).
                x_in = lax.dynamic_index_in_dim(xs, m, keepdims=False)
                (y_buf, new_st, loss_mb, ce_mb, aux_mb, corr_mb,
                 corr5_mb) = lax.switch(
                    chunk, branches, param_row, st_row, x_buf, x_in, ys, m
                )
                st_upd = lax.dynamic_update_index_in_dim(st_rows, new_st, v, 0)
                st_rows = jnp.where(valid, st_upd, st_rows)
                loss_acc = loss_acc + jnp.where(valid, loss_mb, 0.0)
                ce_acc = ce_acc + jnp.where(valid, ce_mb, 0.0)
                aux_acc = aux_acc + jnp.where(valid, aux_mb, 0.0)
                corr_acc = corr_acc + jnp.where(valid, corr_mb, 0)
                corr5_acc = corr5_acc + jnp.where(valid, corr5_mb, 0)
                if perm:
                    x_next = lax.ppermute(y_buf, "stage", perm)
                else:
                    x_next = y_buf
                return (x_next, st_rows, loss_acc, ce_acc, aux_acc, corr_acc,
                        corr5_acc), None

            init_carry = (
                _vary(jnp.zeros((A,), self.compute_dtype)),
                state_rows,
                _vary(jnp.zeros((), jnp.float32)),
                _vary(jnp.zeros((), jnp.float32)),
                _vary(jnp.zeros((), jnp.float32)),
                _vary(jnp.zeros((), jnp.int32)),
                _vary(jnp.zeros((), jnp.int32)),
            )
            (x_buf, st_rows, loss_acc, ce_acc, aux_acc, corr_acc,
             corr5_acc), _ = lax.scan(body, init_carry, jnp.arange(T))
            # Loss lives on the last chunk only; the MoE router aux terms live
            # on whichever chunks hold MoE layers — psum both and fold the
            # weighted aux into the training objective (dp-strategy parity;
            # the reported ce stays the bare metric).
            ce = lax.pmean(lax.psum(ce_acc, "stage") / M, "data")
            aux = lax.pmean(lax.psum(aux_acc, "stage") / M, "data")
            loss = lax.pmean(lax.psum(loss_acc, "stage") / M, "data")
            loss = loss + aux_w * aux
            correct = lax.psum(lax.psum(corr_acc, "stage"), "data")
            correct5 = lax.psum(lax.psum(corr5_acc, "stage"), "data")
            # Sync BN running stats across data replicas (sync-BN choice,
            # documented deviation — SURVEY.md §7).
            st_rows = lax.pmean(st_rows, "data")
            st_out = st_rows if V == 1 else st_rows[:, None]
            return loss, ce, st_out, correct, correct5

        spec = self._chunk_sharding_spec()
        return _shard_map(
            inner,
            mesh=mesh,
            in_specs=(self._param_spec(), spec, P(None, "data"),
                      P(None, "data")),
            out_specs=(P(), P(), spec, P(), P()),
        )

    def _make_gather_rows(self):
        """Hybrid PP x ZeRO-1: per-bucket just-in-time all-gather of the
        local [V?, L/dp] device-major param-row shards back to full plain
        rows, inside the shard_map. Each bucket rides its OWN all-gather
        so the first chunks' compute starts while late buckets are still
        on the wire; under jax.grad each gather transposes to that
        bucket's reduce-scatter, which is where the sharded gradients
        come from in autodiff mode. None when the engine is replicated."""
        if not self.pipe_shard:
            return None
        meta, dp = self._row_meta, self.dp

        def gather_rows(rows):  # [V?, L/dp] -> [V?, L_pad]
            parts = []
            for b in range(meta.num_buckets):
                o = meta.bucket_offsets[b] // dp
                ln = meta.bucket_padded[b] // dp
                parts.append(lax.all_gather(
                    rows[:, o:o + ln], "data", axis=1, tiled=True))
            return (jnp.concatenate(parts, axis=1) if len(parts) > 1
                    else parts[0])

        return gather_rows

    @property
    def _total_samples(self) -> int:
        return self.num_microbatches * self.mb * self.dp

    def _ts_sharding(self):
        sh = self._stage_sharding
        psh = self._param_sharding
        opt_sh = psh
        if self.pipe_shard or (self._guard is not None
                               and self._guard.dynamic):
            # hybrid: m/v ride the params' 'data'-sharded rows while the
            # adam step counter ([.., 1] per stage row) stays on the chunk
            # sharding; dynamic loss-scale scalars additionally break the
            # one-sharding-for-the-whole-subtree shorthand
            from ddlbench_tpu.parallel.common import opt_state_sharding

            opt_sh = opt_state_sharding(self.cfg, psh, sh)
            if self._guard is not None and self._guard.dynamic:
                opt_sh = self._guard.opt_state_spec(
                    opt_sh, NamedSharding(self.mesh, P()))
        return PipeTrainState(psh, sh, opt_sh)

    def _make_train_step(self):
        pipe_train = self._make_pipe_fn(train=True)
        guard = self._guard

        def train_step(ts: PipeTrainState, xs, ys, lr):
            gstate, smul, opt_in = None, None, ts.opt
            if guard is not None:
                opt_in, gstate = guard.split_opt(ts.opt)
                smul = guard.smul(gstate, lr)

            def loss_fn(params_mat):
                loss, ce, new_state, correct, _c5 = pipe_train(
                    params_mat, ts.model_state, xs, ys)
                if smul is not None:  # guard: loss scale / poison carrier
                    loss = loss * smul
                return loss, (ce, new_state, correct)

            (_, (ce, new_state, correct)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(ts.params)
            gm = None
            if guard is not None:
                grads = guard.unscale(grads, smul)
                finite, gnorm = guard.health(ce, grads)
            params, opt = self._opt_update(ts.params, grads, opt_in, lr)
            if guard is not None:
                params, new_state, opt, gm = guard.commit(
                    finite, gnorm, gstate, (params, new_state, opt),
                    (ts.params, ts.model_state, opt_in))
            # valid label positions (samples, or unmasked tokens for LM /
            # seq2seq workloads)
            valid = jnp.sum((ys >= 0).astype(jnp.float32))
            metrics = {
                "loss": ce,
                "accuracy": correct.astype(jnp.float32) / jnp.maximum(1.0, valid),
            }
            if gm is not None:
                metrics.update(gm)
            return PipeTrainState(params, new_state, opt), metrics

        return jax.jit(
            train_step,
            donate_argnums=(0,),
            in_shardings=(self._ts_sharding(), self._batch_sharding,
                          self._batch_sharding, None),
        )

    def _make_eval_step(self):
        pipe_eval = self._make_pipe_fn(train=False)

        def eval_step(ts, xs, ys):
            loss, _, _, correct, correct5 = pipe_eval(
                ts.params, ts.model_state, xs, ys)
            return {
                "loss": loss,
                "correct": correct,
                "correct5": correct5,
                "count": jnp.sum((ys >= 0).astype(jnp.int32)),
            }

        return jax.jit(
            eval_step,
            in_shardings=(self._ts_sharding(), self._batch_sharding,
                          self._batch_sharding),
        )

    # -- data placement ----------------------------------------------------

    def shard_batch(self, x, y):
        """Global batch [M*mb*dp, ...] -> [M, mb*dp, ...] sharded over 'data'."""
        from ddlbench_tpu.distributed import put_global_batch

        M, mb, dp = self.num_microbatches, self.mb, self.dp
        x = x.reshape(M, dp * mb, *x.shape[1:])
        y = y.reshape(M, dp * mb, *y.shape[1:])
        return (
            put_global_batch(x, self._batch_sharding),
            put_global_batch(y, self._batch_sharding),
        )

    @property
    def world_size(self) -> int:
        return self.mesh.devices.size
