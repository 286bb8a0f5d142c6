"""Tensor-parallel and FSDP/ZeRO strategies via sharding annotations.

Neither exists in the reference (SURVEY.md §2E marks TP and FSDP/ZeRO absent,
with TP "recommended — cheap under XLA SPMD"). Under XLA both modes are the
same program as data parallelism with different *placement annotations*; the
SPMD partitioner derives the collectives:

* `tp` (strategy='tp'): parameters sharded on their output-feature axis over a
  'model' mesh axis, batch replicated. XLA partitions every matmul/conv
  channel-wise and inserts the activation all-reduces — Megatron-style tensor
  parallelism without a single explicit collective in user code.
* `fsdp` (strategy='fsdp'): batch sharded over 'data' AND every parameter
  sharded over the same axis (largest divisible dimension). XLA all-gathers
  each layer's weights on use and reduce-scatters gradients — ZeRO-3
  semantics, weights live sharded in HBM.

Both reuse the single-device train-step math; only init/sharding differ.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import LayerModel, init_model
from ddlbench_tpu.ops.util import gspmd_jit
from ddlbench_tpu.parallel.common import make_optimizer, opt_state_sharding
from ddlbench_tpu.parallel.single import TrainState


def _leaf_spec(x: jax.Array, axis: str, size: int, prefer_last: bool) -> P:
    """Choose one divisible dimension to shard (None spec if nothing fits)."""
    if not hasattr(x, "shape") or x.ndim == 0:
        return P()
    dims = range(x.ndim - 1, -1, -1) if prefer_last else range(x.ndim)
    best = None
    for d in dims:
        if x.shape[d] % size == 0 and x.shape[d] >= size:
            if prefer_last:
                best = d
                break
            if best is None or x.shape[d] > x.shape[best]:
                best = d
    if best is None:
        return P()
    spec = [None] * x.ndim
    spec[best] = axis
    return P(*spec)


class _ShardedParamStrategy:
    """Shared machinery: single-step math + per-leaf parameter shardings."""

    axis_name: str
    batch_sharded: bool
    prefer_last: bool

    def __init__(self, model: LayerModel, cfg: RunConfig,
                 devices: Optional[Sequence[jax.Device]] = None):
        from ddlbench_tpu.distributed import make_mesh
        from ddlbench_tpu.guard import device_guard

        self.model = model
        self.cfg = cfg
        self.mesh = make_mesh([(self.axis_name, cfg.num_devices)],
                              devices=devices,
                              dcn_axis=self.axis_name if self.batch_sharded else None)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self._opt_init, opt_update = make_optimizer(cfg)
        n = self.mesh.devices.size
        guard = self._guard = device_guard(cfg)  # None = pre-guard program

        if self.batch_sharded:
            self._batch_sharding = NamedSharding(self.mesh, P(self.axis_name))
        else:
            self._batch_sharding = NamedSharding(self.mesh, P())

        smooth = cfg.resolved_label_smoothing()

        def train_step(ts: TrainState, x, y, lr):
            from ddlbench_tpu.parallel.common import loss_and_grads

            # Stability guard (ROADMAP item 4): tp/fsdp run the SAME
            # one-jit step shape as single/dp-GSPMD, so the guard wires in
            # identically — scaled objective, fused (finite, grad_norm)
            # health pair on the metrics path, anomalous updates dropped
            # in-step under skip / dynamic scaling. GSPMD keeps the
            # skip-select elementwise, so sharded params stay sharded.
            gstate, smul, opt_in = None, None, ts.opt
            if guard is not None:
                opt_in, gstate = guard.split_opt(ts.opt)
                smul = guard.smul(gstate, lr)
            with gspmd_jit():  # auto-Pallas unsafe under GSPMD
                ce, (correct, valid), new_state, grads = loss_and_grads(
                    model, cfg, ts.params, ts.model_state, x, y,
                    self.compute_dtype, smooth, obj_scale=smul)
            gm = None
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
            if gm is not None:
                metrics.update(gm)
            return TrainState(params, new_state, opt), metrics

        def eval_step(ts: TrainState, x, y):
            from ddlbench_tpu.parallel.common import eval_metrics

            with gspmd_jit():
                return eval_metrics(model, cfg, ts.params, ts.model_state,
                                    x, y, self.compute_dtype)

        self.train_step = jax.jit(
            train_step,
            donate_argnums=(0,),
            in_shardings=(None, self._batch_sharding, self._batch_sharding, None),
        )
        self.eval_step = jax.jit(
            eval_step,
            in_shardings=(None, self._batch_sharding, self._batch_sharding),
        )

    def _state_sharding(self, ts: TrainState):
        n = self.mesh.devices.size

        def leaf_sh(x):
            return NamedSharding(
                self.mesh, _leaf_spec(x, self.axis_name, n, self.prefer_last)
            )

        param_sh = jax.tree.map(leaf_sh, ts.params)
        opt_sh = opt_state_sharding(self.cfg, param_sh,
                                    NamedSharding(self.mesh, P()))
        if self._guard is not None:
            # dynamic loss-scale state: two replicated scalars in the dict
            opt_sh = self._guard.opt_state_spec(
                opt_sh, NamedSharding(self.mesh, P()))
        return TrainState(
            params=param_sh,
            model_state=jax.tree.map(
                lambda x: NamedSharding(self.mesh, P()), ts.model_state
            ),
            opt=opt_sh,
        )

    def init(self, key) -> TrainState:
        from ddlbench_tpu.distributed import put_global_tree

        params, state, _ = init_model(self.model, key)
        opt = self._opt_init(params)
        if self._guard is not None:
            opt = self._guard.attach_opt_state(opt)  # dynamic loss scale
        ts = TrainState(params, state, opt)
        return put_global_tree(ts, self._state_sharding(ts))

    def shard_batch(self, x, y):
        from ddlbench_tpu.distributed import put_global_batch

        return (
            put_global_batch(x, self._batch_sharding),
            put_global_batch(y, self._batch_sharding),
        )

    @property
    def world_size(self) -> int:
        return self.mesh.devices.size


class TPStrategy(_ShardedParamStrategy):
    """strategy='tp': Megatron-style tensor parallelism from annotations."""

    axis_name = "model"
    batch_sharded = False
    prefer_last = True


class FSDPStrategy(_ShardedParamStrategy):
    """strategy='fsdp': ZeRO-3 — batch and parameters sharded on 'data'."""

    axis_name = "data"
    batch_sharded = True
    prefer_last = False
