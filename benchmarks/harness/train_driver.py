"""Window driver for ``kind: train`` mixes: the strategy's ``train_step``
fed by ``data/prefetch.Prefetcher``, as ``train/loop.py`` drives it.

Set-up builds ONE object — the compiled step with its state — seeds its
weights from ``--seed``, drives it through its first three steps on the
window's own call and feed (recording what the comparison needs), settles a
few more and hands the same object to the window. The rate is all samples of
all steps completed in the window over the window's seconds, the clock closed
on ``block_until_ready`` of the last step.

What the check holds on the chip, in float32 copies of the parameters (P):
before the window closes, NOTHING. The check steps send the optimizer's first
moment after step 1 and the parameters after step 3 to the host and put
nothing on the chip, so the run's ``memory_peak_bytes`` is the program's
alone, at seeding too (the strategy's own initial values are dropped before
the seed's are made). After the window, with the program freed: ``flat`` is
made again from the seed, the program's first gradient and change are formed
from the two host copies and ``flat`` (3 P), then the reference flow holds
``flat`` (the caller's), ``p``, ``m``, ``v`` while ``loss_and_grads``
computes — 4 P and the reference's own working set — and the gradient with
them at the optimizer call, which donates ``p``, ``m``, ``v``: 5 P (SGD keeps
no ``v``: 3 and 4 P). Both first gradients wait on the host and meet one leaf
at a time.
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
    like = ts.params
    specs = weights.flat_specs(like, names)
    shardings = jax.tree.map(lambda a: a.sharding, like)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        like)
    # the strategy's own initial values go before the seed's are made: the
    # two are never on the chip together
    ts = ts._replace(params=None)
    flat = weights.make_weights(seed, specs, rules)
    params = jax.device_put(weights.unflatten(flat, like, names), shardings)
    return ts._replace(params=params), specs, names


def _leaf_norms(flat) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    n = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for k, a in t.items()})(flat)
    return {k: float(v) for k, v in jax.device_get(n).items()}


def first_gradient(moment, initial, hp):
    """The gradient the optimizer got at step 1, from its state after it:
    SGD's momentum buffer is g + wd*p0, Adam's first moment (1-b1)*(g+wd*p0).
    ``moment`` and ``initial`` (p0, read only where the weight decay is not
    0: x - 0*p0 is x) are flat dicts on the chip."""
    wd = hp["weight_decay"]
    if hp["optimizer"] == "sgd":
        first = lambda m: m
    else:
        first = lambda m: m / (1.0 - hp["beta1"])
    if not wd:
        return {k: first(m) for k, m in moment.items()}
    return {k: first(m) - wd * initial[k] for k, m in moment.items()}


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
    sgd = hp["optimizer"] == "sgd"
    # p, m and v are this flow's own and are donated: each step's take their
    # buffers. ``flat`` is the caller's, who reads it again, and never is.
    opt = jax.jit(lambda p, g, m, v, t: (
        common.sgd_momentum(p, g, m, hp) + (v,) if sgd
        else common.adam(p, g, m, v, t, hp)), donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(a)))
                               for k, a in t.items()})
    p = {k: a.copy() for k, a in flat.items()}
    m = {k: jnp.zeros_like(a) for k, a in flat.items()}
    v = {} if sgd else {k: jnp.zeros_like(a) for k, a in flat.items()}
    losses, gnorm, g1, stats1 = [], None, None, None
    for t, (x, y) in enumerate(batches, start=1):
        loss, g, stats = lg(p, x, y)
        losses.append(float(loss))
        if gnorm is None:
            gnorm = {k: float(a) for k, a in jax.device_get(norms(g)).items()}
            stats1 = jax.device_get(stats)
        p, m, v = opt(p, g, m, v, jnp.float32(t))
        if g1 is None:  # waits on the host, as the program's does
            g1 = jax.device_get(g)
        # the next gradient's buffer is taken when its call is made: this
        # one's is free only once the optimizer has ended
        jax.block_until_ready(p)
        del g
    del m, v
    delta = norms({k: p[k] - flat[k] for k in flat})
    return {"losses": losses, "grad_norm": gnorm, "grad": g1,
            "norm_var": stats1,
            "matrices": [k for k, a in flat.items() if a.ndim >= 2],
            "delta_norm": {k: float(a)
                           for k, a in jax.device_get(delta).items()}}


def gradient_differences(prog_grad, ref_grad) -> Dict[str, float]:
    """Per leaf, the norm of (program's first gradient - reference's). Both
    wait on the host; one leaf of each is on the chip at a time."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    return {k: float(diff(prog_grad[k], ref_grad[k])) for k in ref_grad}


def first_steps(step_fn, stream, ts, hp, names, lr, mark=lambda _: None):
    """Drive ``ts`` through its first ``CHECK_STEPS`` steps on the window's
    own call and feed; returns what ``check_numbers`` reads, all of it on the
    host, and the state. The check puts nothing on the chip here: the
    optimizer's first moment after step 1 and the parameters after the last
    step are read off the state, and nothing waits but for those steps."""
    import jax

    losses = []
    for i in range(CHECK_STEPS):
        ts, m = step_fn(ts, *next(stream).batch, lr)
        losses.append(m["loss"])
        if i == 0:
            moment = weights.flat_leaves(jax.device_get(ts.opt["m"]), names)
            # running variances of the normalization layers after one step
            norm_var = {k: v for k, v in weights.flat_leaves(
                jax.device_get(ts.model_state), names).items()
                if k.endswith("/var")}
            mark("first step (compile or cache load)")
    return {"losses": [float(x) for x in losses], "moment": moment,
            "params": weights.flat_leaves(jax.device_get(ts.params), names),
            "norm_var": norm_var}, ts


def check_numbers(held, flat, hp) -> Dict:
    """What the comparison reads of the program's first steps, formed once
    the program is freed from what ``first_steps`` held on the host and
    ``flat``, the parameters it started from (made again from the seed, laid
    out as the reference reads them): the first gradient, on the host again,
    its norm by leaf and the norm of each leaf's change."""
    import jax

    on_chip = lambda t: {k: jax.device_put(a, flat[k].sharding)
                         for k, a in t.items()}
    g1 = first_gradient(on_chip(held["moment"]), flat, hp)
    grad_norm, grad = _leaf_norms(g1), jax.device_get(g1)
    del g1
    delta_norm = _leaf_norms({k: a - flat[k]
                              for k, a in on_chip(held["params"]).items()})
    return {"losses": held["losses"], "grad_norm": grad_norm,
            "delta_norm": delta_norm, "grad": grad,
            "norm_var": held["norm_var"]}


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

    held, ts = first_steps(step_fn, stream, ts, hp, names, lr, rc.mark)
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
    prog = check_numbers(held, flat, hp)
    del held
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
