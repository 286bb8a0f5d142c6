"""Window driver for ``kind: train`` mixes: the strategy's ``train_step``
fed by ``data/prefetch.Prefetcher``, as ``train/loop.py`` drives it.

Set-up builds ONE object — the compiled step with its state — seeds its
weights from ``--seed``, drives it through its first three steps on the
window's own call and feed (recording what the comparison needs), settles a
few more and hands the same object to the window. The rate is all samples of
all steps completed in the window over the window's seconds, the clock closed
on ``block_until_ready`` of the last step.
"""

from __future__ import annotations

import collections
import sys
import time
from typing import Dict

from benchmarks.harness import compare, weights
from benchmarks.harness.traffic import SeededBatches

CHECK_STEPS = 3
SETTLE_STEPS = 5
RUN_AHEAD = 2  # steps the host may dispatch ahead of the device


def hyperparameters(rc: Dict) -> Dict:
    hp = {"optimizer": rc["optimizer"], "lr": rc["lr"],
          "weight_decay": rc.get("weight_decay", 0.0)}
    if hp["optimizer"] == "sgd":
        hp["momentum"] = rc["momentum"]
    else:
        hp.update(beta1=rc.get("adam_beta1", 0.9),
                  beta2=rc.get("adam_beta2", 0.999),
                  eps=rc.get("adam_eps", 1e-8))
    return hp


def build(config: Dict, traffic: Dict):
    """The program's strategy for this cell, from the two data files."""
    from ddlbench_tpu import config as pcfg
    from ddlbench_tpu.parallel import make_strategy

    ds = config["dataset"]
    if ds["name"] not in pcfg.DATASETS:
        pcfg.DATASETS[ds["name"]] = pcfg.DatasetSpec(
            ds["name"], tuple(ds["sample_shape"]), ds["num_classes"],
            1 << 30, 1 << 20, kind=ds["kind"])
    cfg = pcfg.RunConfig(benchmark=ds["name"], arch=config["arch"],
                         **traffic["run_config"])
    cfg.validate()
    return cfg, make_strategy(cfg)


def seeded_state(strategy, seed: int, rules: Dict):
    """The strategy's train state with the benchmark's weights in it."""
    import jax

    names = [l.name for l in strategy.model.layers]
    ts = strategy.init(jax.random.key(0))
    specs = weights.flat_specs(ts.params, names)
    flat = weights.make_weights(seed, specs, rules)
    params = weights.unflatten(flat, ts.params, names)
    params = jax.device_put(
        params, jax.tree.map(lambda a: a.sharding, ts.params))
    return ts._replace(params=params), specs, names


def _leaf_norms(tree, names) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    n = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))(
            tree)
    return {k: float(v) for k, v in
            weights.flat_leaves(jax.device_get(n), names).items()}


def first_gradient(opt, p0, hp):
    """The gradient the optimizer got at step 1, from its state after it:
    SGD's momentum buffer is g + wd*p0, Adam's first moment (1-b1)*(g+wd*p0)."""
    import jax

    if hp["optimizer"] == "sgd":
        return jax.tree.map(lambda m, p: m - hp["weight_decay"] * p,
                            opt["m"], p0)
    return jax.tree.map(
        lambda m, p: m / (1.0 - hp["beta1"]) - hp["weight_decay"] * p,
        opt["m"], p0)


def reference_numbers(reference, config: Dict, hp: Dict, flat, batches,
                      rounding: str, rows=None):
    """Losses, first-gradient norms and change norms of ``CHECK_STEPS`` steps
    by the plain reference (``reference``: the module the configuration's
    file names). ``rows`` (a slice) plants the "part of the batch left out"
    fault: loss and gradients over those rows only."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import common

    rnd = common.ROUNDINGS[rounding]
    lg = lambda P, x, y: reference.loss_and_grads(P, x, y, config, rnd)
    if rows is not None:
        full = lg
        lg = lambda P, x, y: full(P, x[rows], y[rows])
    lg = jax.jit(lg)
    opt = jax.jit(lambda p, g, m, v, t: (
        common.sgd_momentum(p, g, m, hp) + (v,) if hp["optimizer"] == "sgd"
        else common.adam(p, g, m, v, t, hp)))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(a)))
                               for k, a in t.items()})
    p = flat
    m = v = {k: jnp.zeros_like(a) for k, a in flat.items()}
    losses, gnorm, g1, stats1 = [], None, None, None
    for t, (x, y) in enumerate(batches, start=1):
        loss, g, stats = lg(p, x, y)
        losses.append(float(loss))
        if gnorm is None:
            gnorm = {k: float(a) for k, a in jax.device_get(norms(g)).items()}
            g1, stats1 = g, jax.device_get(stats)
        p, m, v = opt(p, g, m, v, jnp.float32(t))
        del g
    delta = norms({k: p[k] - flat[k] for k in flat})
    return {"losses": losses, "grad_norm": gnorm, "grad": g1,
            "norm_var": stats1,
            "matrices": [k for k, a in flat.items() if a.ndim >= 2],
            "delta_norm": {k: float(a)
                           for k, a in jax.device_get(delta).items()}}


def gradient_differences(prog_grad, ref_grad) -> Dict[str, float]:
    """Per leaf, the norm of (program's first gradient - reference's)."""
    import jax
    import jax.numpy as jnp

    ref_grad = dict(ref_grad)
    like = {k: jax.device_put(jnp.asarray(prog_grad[k]), ref_grad[k].sharding)
            for k in ref_grad}
    d = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k])))
                              for k in b})(like, ref_grad)
    return {k: float(v) for k, v in jax.device_get(d).items()}


def first_steps(step_fn, stream, ts, hp, names, lr, mark=lambda _: None):
    """Drive ``ts`` through its first ``CHECK_STEPS`` steps on the window's
    own call and feed; returns what the comparison reads and the state."""
    import jax

    p0 = jax.tree.map(lambda a: a.copy(), ts.params)
    losses, grad_norm = [], None
    for i in range(CHECK_STEPS):
        ts, m = step_fn(ts, *next(stream).batch, lr)
        losses.append(m["loss"])
        if i == 0:
            g1 = first_gradient(ts.opt, p0, hp)
            grad_norm = _leaf_norms(g1, names)
            # the gradient itself waits on the host for the reference's, so
            # that the window's device memory is the program's alone
            grad = weights.flat_leaves(jax.device_get(g1), names)
            del g1
            # running variances of the normalization layers after one step
            norm_var = {k: v for k, v in weights.flat_leaves(
                jax.device_get(ts.model_state), names).items()
                if k.endswith("/var")}
            mark("first step (compile or cache load)")
    delta_norm = _leaf_norms(
        jax.tree.map(lambda a, b: a - b, ts.params, p0), names)
    return {"losses": [float(x) for x in losses], "grad_norm": grad_norm,
            "delta_norm": delta_norm, "grad": grad,
            "norm_var": norm_var}, ts


def run(rc) -> Dict:
    """``rc``: the run context of run_cell.py. Returns the outcome dict."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from ddlbench_tpu.data.prefetch import Prefetcher

    config, traffic = rc.config, rc.traffic
    run_cfg = traffic["run_config"]
    hp = hyperparameters(run_cfg)
    rc.mark("imports, backend")
    cfg, strategy = build(config, traffic)
    rc.mark("strategy built")
    ds = config["dataset"]
    global_batch = cfg.global_batch()
    data = SeededBatches(rc.seed, ds["kind"], tuple(ds["sample_shape"]),
                         config.get("vocab_size", ds["num_classes"]),
                         global_batch)
    if rc.chips > 1:
        data.lay_out_like(strategy.shard_batch)
    ts, specs, names = seeded_state(strategy, rc.seed, config["weights"])
    rc.mark("weights from the seed")
    lr = jnp.float32(hp["lr"])
    step_fn = strategy.train_step
    stream = Prefetcher(data, strategy.shard_batch,
                        depth=cfg.prefetch_depth).stream(epoch=0)

    prog, ts = first_steps(step_fn, stream, ts, hp, names, lr, rc.mark)
    for _ in range(SETTLE_STEPS):
        ts, m = step_fn(ts, *next(stream).batch, lr)
    jax.block_until_ready(ts)
    rc.mark("check and settle steps")

    # -- the window ------------------------------------------------------
    seconds = rc.window_seconds(traffic)
    pending = collections.deque()
    steps = 0
    rc.open_window()
    stall0 = stream.stall_s
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while True:
            with TraceAnnotation("bench/next_batch"):
                fetched = next(stream)
            with TraceAnnotation("bench/dispatch"):
                ts, m = step_fn(ts, *fetched.batch, lr)
            steps += 1
            pending.append(m["loss"])
            if len(pending) > RUN_AHEAD:
                with TraceAnnotation("bench/sync"):
                    jax.block_until_ready(pending.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench/sync"):
            jax.block_until_ready(ts)
    t1 = time.perf_counter()
    rc.close_window()
    window_s = t1 - t0
    stall_s = stream.stall_s - stall0
    stream.close()
    last_loss = float(m["loss"])
    memory_peak = rc.read_memory_peak()
    # how the window's own arguments lay over the chips: across chips
    # scopes.step_hlo partitions the step it lowers by these
    rc.step_shardings = jax.tree.map(lambda a: a.sharding,
                                     (ts, *fetched.batch))

    # -- free the program, then the reference ----------------------------
    del ts, m, pending, fetched, stream, strategy, step_fn
    flat = weights.make_weights(rc.seed, specs, config["weights"])
    batches = [data.batch(0, i) for i in range(CHECK_STEPS)]
    if rc.chips > 1:
        batches, flat = rc.spread(batches, flat)
    t_ref = time.perf_counter()
    ref = reference_numbers(rc.reference, config, hp, flat, batches,
                            "float32")
    print(f"train: {steps} steps in {window_s:.3f}s, input stall "
          f"{stall_s:.3f}s; reference {time.perf_counter() - t_ref:.1f}s; "
          f"last window loss {last_loss:.4f}", file=sys.stderr)
    prog["grad_diff"] = gradient_differences(prog.pop("grad"),
                                             ref.pop("grad"))
    numbers = compare.train_numbers(prog, ref, config["limits"])
    if last_loss != last_loss:
        numbers.append(compare.Compared("window_loss_finite", 1.0, 0.0))

    sample_shape = tuple(ds["sample_shape"])
    return {
        "end_to_end": {
            "train_samples_per_s_per_chip":
                steps * global_batch / window_s / rc.chips},
        "window_s": window_s, "attempted": steps, "failed": 0,
        "memory_peak_bytes": memory_peak, "numbers": numbers,
        "counters": {
            "steps": steps, "samples": steps * global_batch,
            "input_stall_s": stall_s,
            "model_flops": steps * global_batch
            * rc.reference.train_flops_per_sample(config, sample_shape)},
    }
