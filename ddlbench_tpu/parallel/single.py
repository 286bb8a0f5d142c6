"""Single-device strategy — the reference's PyTorch baseline.

Parity target: benchmark/mnist/mnist_pytorch.py (train loop :52-99, eval
:102-133): SGD+momentum cross-entropy training with per-interval throughput and
peak-memory logging. Here the entire step (fwd, bwd, update, metrics) is one
jitted function; donated arguments keep params in place in HBM.
"""

from __future__ import annotations

from typing import NamedTuple, Any

import jax
import jax.numpy as jnp

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import (LayerModel, init_model,
                                        routing_counters)
from ddlbench_tpu.parallel.common import make_optimizer


class TrainState(NamedTuple):
    params: Any
    model_state: Any  # BN running stats
    opt: Any  # optimizer-state dict pytree (common.make_optimizer)


class SingleStrategy:
    """strategy='single': one chip, no collectives."""

    def __init__(self, model: LayerModel, cfg: RunConfig):
        from ddlbench_tpu.guard import device_guard

        self.model = model
        self.cfg = cfg
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self._opt_init, opt_update = make_optimizer(cfg)
        smooth = cfg.resolved_label_smoothing()
        guard = self._guard = device_guard(cfg)  # None = pre-guard program

        def train_step(ts: TrainState, x, y, lr):
            from ddlbench_tpu.parallel.common import loss_and_grads

            if guard is None:
                ce, (correct, valid), new_state, grads = loss_and_grads(
                    model, cfg, ts.params, ts.model_state, x, y,
                    self.compute_dtype, smooth)
                params, opt = opt_update(ts.params, grads, ts.opt, lr)
            else:
                # Stability guard: scaled objective (loss scale x nan-grad
                # poison carrier), fused (finite, grad_norm) health pair on
                # the metrics path, anomalous updates dropped in-step under
                # skip / dynamic scaling.
                opt_in, gstate = guard.split_opt(ts.opt)
                smul = guard.smul(gstate, lr)
                ce, (correct, valid), new_state, grads = loss_and_grads(
                    model, cfg, ts.params, ts.model_state, x, y,
                    self.compute_dtype, smooth, obj_scale=smul)
                grads = guard.unscale(grads, smul)
                finite, gnorm = guard.health(ce, grads)
                params, opt = opt_update(ts.params, grads, opt_in, lr)
                params, new_state, opt, gm = guard.commit(
                    finite, gnorm, gstate, (params, new_state, opt),
                    (ts.params, ts.model_state, opt_in))
            # headline loss stays the CE term, comparable across strategies
            metrics = {
                "loss": ce,
                "accuracy": correct.astype(jnp.float32)
                / jnp.maximum(1.0, valid.astype(jnp.float32)),
            }
            if guard is not None:
                metrics.update(gm)
            # an expert model's routing counters (none for any other model)
            metrics.update(routing_counters(new_state))
            return TrainState(params, new_state, opt), metrics

        def eval_step(ts: TrainState, x, y):
            from ddlbench_tpu.parallel.common import eval_metrics

            return eval_metrics(model, cfg, ts.params, ts.model_state, x, y,
                                self.compute_dtype)

        self.train_step = jax.jit(train_step, donate_argnums=(0,))
        self.eval_step = jax.jit(eval_step)

    def init(self, key) -> TrainState:
        params, state, _ = init_model(self.model, key)
        opt = self._opt_init(params)
        if self._guard is not None:
            opt = self._guard.attach_opt_state(opt)  # dynamic loss scale
        return TrainState(params, state, opt)

    def shard_batch(self, x, y):
        return x, y

    @property
    def world_size(self) -> int:
        return 1
