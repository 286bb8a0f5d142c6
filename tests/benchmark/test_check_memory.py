"""What the check holds on the device, in float32 copies of the parameters
(P), counted on the CPU at a tiny size. Nothing here is a device metric.

The rule (benchmarks/harness/train_driver.py): while the reference's
``loss_and_grads`` computes the harness holds at most 4 P (``flat``, ``p``,
``m``, ``v``), at the optimizer call at most 5 P (the gradient with them);
before the window the check puts nothing on the device: no array of a
parameter's shape beside the program's state while a check step runs, and at
seeding the strategy's own initial values are gone before the seed's are
made. The flow of the parent commit (PR 30) is kept below as the ORACLE of
the equality cases: the new flow's numbers have to equal its numbers to the
last digit, in one process on whatever machine runs this.

How the counting works: a ``jax.debug.callback`` inside the jitted function
runs while that function executes and sums ``jax.live_arrays()``. The CPU
backend honours donation (a donated argument ``is_deleted()`` from the call
on and is no live array), so at the optimizer the outputs that take the
donated buffers are added by their size. Python runs ahead of the device and
may have dropped the gradient by then: the count can only read low by that,
never high. Each counting case holds its instrument to a control of its own
(an undonated optimizer call; the parent's ``p0``).
"""

import importlib.util
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run_cell
from benchmarks.harness import manifest, train_driver, weights
from benchmarks.harness.traffic import SeededBatches
from benchmarks.reference import common

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ADAM = {"optimizer": "adam", "lr": 1e-3, "weight_decay": 0.0, "beta1": 0.9,
        "beta2": 0.999, "eps": 1e-8}
SGD = {"optimizer": "sgd", "lr": 0.01, "weight_decay": 1e-4, "momentum": 0.9}
SPECS = {"a/w": (64, 96), "a/b": (96,), "b/w": (96, 64), "b/scale": (64,)}


# -- the parent's flow (commit 1783fda): the oracle ------------------------
def parent_reference_numbers(reference, config, hp, flat, batches, rounding,
                             rows=None):
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


def parent_gradient_differences(prog_grad, ref_grad):
    ref_grad = dict(ref_grad)
    like = {k: jax.device_put(jnp.asarray(prog_grad[k]), ref_grad[k].sharding)
            for k in ref_grad}
    d = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k])))
                              for k in b})(like, ref_grad)
    return {k: float(v) for k, v in jax.device_get(d).items()}


def parent_first_steps(step_fn, stream, ts, hp, names, lr):
    def leaf_norms(tree):
        n = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
            t))(tree)
        return {k: float(v) for k, v in
                weights.flat_leaves(jax.device_get(n), names).items()}

    def first_gradient(opt, p0, hp):
        if hp["optimizer"] == "sgd":
            return jax.tree.map(lambda m, p: m - hp["weight_decay"] * p,
                                opt["m"], p0)
        return jax.tree.map(
            lambda m, p: m / (1.0 - hp["beta1"]) - hp["weight_decay"] * p,
            opt["m"], p0)

    p0 = jax.tree.map(lambda a: a.copy(), ts.params)
    losses, grad_norm = [], None
    for i in range(train_driver.CHECK_STEPS):
        ts, m = step_fn(ts, *next(stream).batch, lr)
        losses.append(m["loss"])
        if i == 0:
            g1 = first_gradient(ts.opt, p0, hp)
            grad_norm = leaf_norms(g1)
            grad = weights.flat_leaves(jax.device_get(g1), names)
            del g1
            norm_var = {k: v for k, v in weights.flat_leaves(
                jax.device_get(ts.model_state), names).items()
                if k.endswith("/var")}
    delta_norm = leaf_norms(jax.tree.map(lambda a, b: a - b, ts.params, p0))
    return {"losses": [float(x) for x in losses], "grad_norm": grad_norm,
            "delta_norm": delta_norm, "grad": grad,
            "norm_var": norm_var}, ts


# -- the reference flow -----------------------------------------------------
def live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


class Watched:
    """A stub reference whose ``loss_and_grads`` (a small two-layer net on
    the token ids) and, through ``common``, whose optimizer step record the
    live bytes each time they EXECUTE."""

    def __init__(self, monkeypatch):
        self.seen = []  # (where, live bytes)
        for name in ("adam", "sgd_momentum"):
            real = getattr(common, name)

            def watched(*args, _real=real):
                jax.debug.callback(lambda: self.seen.append(
                    ("opt", live_bytes())))
                return _real(*args)

            monkeypatch.setattr(common, name, watched)

    def loss_and_grads(self, P, x, y, config, rnd):
        jax.debug.callback(lambda: self.seen.append(("lg", live_bytes())))

        def loss(P):
            h = jnp.tanh(rnd(jax.nn.one_hot(x, 64)) @ rnd(P["a/w"]) + P["a/b"])
            return common.cross_entropy_sum(
                (h @ rnd(P["b/w"])) * P["b/scale"], y) / y.size

        value, grads = jax.value_and_grad(loss)(P)
        return value, grads, {}


def stub_inputs(seed=5):
    flat = weights.make_weights(seed, SPECS, {"matrix": 0.05})
    data = SeededBatches(seed, "tokens", (16,), 64, 4)
    return flat, [data.batch(0, i) for i in range(train_driver.CHECK_STEPS)]


@pytest.mark.parametrize("hp", [ADAM, SGD], ids=["adam", "sgd_wd"])
def test_copies_the_reference_flow_holds(monkeypatch, hp):
    """Over the three steps: at most 4 P while the reference computes and 5
    P at the optimizer call (SGD keeps no second moment: 3 and 4). The
    instrument's control: the same optimizer step called undonated shows its
    arguments alive while it runs, where the flow's own shows ``flat`` and
    the gradient alone."""
    ref = Watched(monkeypatch)
    flat, batches = stub_inputs()
    jax.block_until_ready((flat, batches))
    P = sum(a.nbytes for a in flat.values())
    base = live_bytes() - P  # the batches and whatever else the process has
    train_driver.reference_numbers(ref, {}, hp, flat, batches, "float32")
    jax.effects_barrier()
    sgd = hp["optimizer"] == "sgd"
    moments = 1 if sgd else 2
    held = {"lg": [], "opt": []}
    for where, live in ref.seen:
        held[where].append((live - base) / P)
    assert len(held["lg"]) == len(held["opt"]) == train_driver.CHECK_STEPS
    slack = 0.05  # scalars, the step number, a loss
    assert max(held["lg"]) <= 2 + moments + slack, held
    # flat and the gradient are alive; p, m, v are donated, and the outputs
    # that take their buffers are added by their size: 2 + (1 + moments) P
    assert max(held["opt"]) + 1 + moments <= 3 + moments + slack, held

    ref.seen.clear()
    p, g, m = ({k: a.copy() for k, a in flat.items()} for _ in range(3))
    v = {} if sgd else {k: a.copy() for k, a in flat.items()}
    jax.block_until_ready((p, g, m, v))
    jax.jit(lambda p, g, m, v, t: (
        common.sgd_momentum(p, g, m, hp) if sgd
        else common.adam(p, g, m, v, t, hp)))(p, g, m, v, jnp.float32(1))
    jax.effects_barrier()
    (_, live), = ref.seen
    assert (live - base) / P >= 3 + moments - slack


@pytest.mark.parametrize("hp", [ADAM, SGD], ids=["adam", "sgd_wd"])
def test_flat_is_the_callers_and_the_numbers_are_the_parents(monkeypatch, hp):
    """``flat`` is alive and unchanged after a call, a second call with it
    gives the same numbers, and they equal the parent's flow's to the last
    digit, the per-leaf gradient differences too."""
    ref = Watched(monkeypatch)
    flat, batches = stub_inputs()
    before = jax.device_get(flat)
    first, again = (train_driver.reference_numbers(
        ref, {}, hp, flat, batches, "float32") for _ in range(2))
    assert not any(a.is_deleted() for a in flat.values())
    for k, a in jax.device_get(flat).items():
        np.testing.assert_array_equal(a, before[k])
    parent = parent_reference_numbers(ref, {}, hp, flat, batches, "float32")
    control = train_driver.reference_numbers(ref, {}, hp, flat, batches,
                                             "float8_e4m3")
    for side in (again, parent):
        for key in ("losses", "grad_norm", "delta_norm", "matrices"):
            assert side[key] == first[key], key
        for k in flat:
            np.testing.assert_array_equal(side["grad"][k], first["grad"][k])
    # the first gradient waits on the host; the differences meet leaf by leaf
    assert all(isinstance(a, np.ndarray) for a in first["grad"].values())
    diff = train_driver.gradient_differences(control["grad"], first["grad"])
    assert diff == parent_gradient_differences(
        control["grad"], jax.device_put(first["grad"]))
    assert min(diff.values()) > 0


# -- the check steps ----------------------------------------------------------
def context(traffic, data, config, seed=3):
    man = manifest.Manifest()
    man.dir = os.path.join(DATA, data)
    man.index = dict(
        man.index,
        configs=[{"name": config, "file": os.path.relpath(
            os.path.join(man.dir, "configs", f"{config}.json"), man.root)}],
        workloads=[{"name": "tiny", "config": config, "traffic": traffic,
                    "chips": 1}])
    args = types.SimpleNamespace(workload="tiny", seed=seed, seconds=0.5,
                                 trace=0)
    return run_cell.RunContext(man, args, jax.devices())


def check_steps(monkeypatch, first_steps, rc):
    """``first_steps`` on the cell's own strategy and feed, with a step that
    looks around before it runs: the live arrays of a parameter matrix's
    shape that are neither its own arguments nor older than the check. On
    the CPU backend the host is the device and ``jax.device_get`` leaves its
    copy among the live arrays: what it leaves is on the host and is counted
    out. Seeding is watched the same way: the arrays of a parameter matrix's
    shape that are alive when the seed's weights are made."""
    from ddlbench_tpu.data.prefetch import Prefetcher

    config, traffic = rc.config, rc.traffic
    hp = train_driver.hyperparameters(traffic["run_config"])
    cfg, strategy = train_driver.build(config, traffic)
    ds = config["dataset"]
    data = SeededBatches(rc.seed, ds["kind"], tuple(ds["sample_shape"]),
                         config.get("vocab_size", ds["num_classes"]),
                         cfg.global_batch())
    live = lambda: {id(a): a for a in jax.live_arrays()}
    apart = set(live())  # older than the check, or on the host
    at_seeding = []
    real_make = weights.make_weights

    def make_weights(seed, specs, rules):
        shapes = [shape for shape in specs.values() if len(shape) >= 2]
        at_seeding.extend(a.shape for i, a in live().items()
                          if a.shape in shapes and i not in apart)
        return real_make(seed, specs, rules)

    monkeypatch.setattr(weights, "make_weights", make_weights)
    ts, specs, names = train_driver.seeded_state(strategy, rc.seed,
                                                 config["weights"])
    monkeypatch.setattr(weights, "make_weights", real_make)
    stream = Prefetcher(data, strategy.shard_batch,
                        depth=cfg.prefetch_depth).stream(epoch=0)
    matrices = sorted(a.shape for a in jax.tree.leaves(ts.params)
                      if a.ndim >= 2)
    real_get = jax.device_get

    def device_get(tree):
        before = set(live())
        out = real_get(tree)
        apart.update(set(live()) - before)
        return out

    monkeypatch.setattr(jax, "device_get", device_get)
    beside = []

    def step(ts, x, y, lr):
        own = {id(a) for a in jax.tree.leaves((ts, x, y, lr))}
        beside.append(sorted(
            a.shape for i, a in live().items()
            if a.shape in matrices and i not in own | apart))
        return strategy.train_step(ts, x, y, lr)

    try:
        prog, ts = first_steps(step, stream, ts, hp, names,
                               jnp.float32(hp["lr"]))
    finally:
        stream.close()
    monkeypatch.setattr(jax, "device_get", real_get)
    del ts
    if "grad" not in prog:  # what the check steps held on the host
        prog = train_driver.check_numbers(
            prog, real_make(rc.seed, specs, config["weights"]), hp)
    return prog, beside, matrices, sorted(at_seeding)


CELLS = {"adam": ("train-tiny", "tiny", "gpt2-tiny"),
         "sgd_wd": ("train-lenet", "third", "lenet-mnist")}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_no_copy_beside_the_state_and_the_parents_numbers(monkeypatch, cell):
    """While a check step runs no array of a parameter matrix's shape is
    live beside the program's state (what the check reads goes to the host
    and meets the seed's parameters after the window), where the parent's
    ``first_steps`` keeps ``p0`` there: the instrument's control. Losses,
    per-leaf gradient and change norms, the gradient itself and the running
    variances equal the parent's to the last digit."""
    prog, beside, _, _ = check_steps(monkeypatch, train_driver.first_steps,
                                     context(*CELLS[cell]))
    assert beside == [[]] * train_driver.CHECK_STEPS
    was, was_beside, matrices, _ = check_steps(
        monkeypatch, parent_first_steps, context(*CELLS[cell]))
    assert was_beside == [matrices] * train_driver.CHECK_STEPS
    for key in ("losses", "grad_norm", "delta_norm"):
        assert prog[key] == was[key], key
    assert max(prog["delta_norm"].values()) > 0
    for key in ("grad", "norm_var"):
        assert sorted(prog[key]) == sorted(was[key])
        for k, a in prog[key].items():
            np.testing.assert_array_equal(a, was[key][k])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_seeding_drops_the_strategys_own_values(monkeypatch, cell):
    """When the seed's weights are made the strategy's own initial values
    are gone: what is alive of a parameter matrix's shape is the optimizer's
    moments (two a matrix under Adam, one under SGD), not the parameters
    with them."""
    rc = context(*CELLS[cell])
    _, _, matrices, at_seeding = check_steps(
        monkeypatch, train_driver.first_steps, rc)
    moments = 1 if rc.traffic["run_config"]["optimizer"] == "sgd" else 2
    assert at_seeding == sorted(matrices * moments)


# -- the stub that sizes the next cut ---------------------------------------
STUB = os.path.join(DATA, "room_stub.py")


@pytest.mark.parametrize("accumulators", [1, 2])
def test_the_room_stub_sums_the_gradient_either_way(accumulators):
    """The stand-in reference at a tiny size: either way of summing over the
    sequences gives the loss and the gradient of the whole batch, and the
    reference flow follows it through its three steps."""
    spec = importlib.util.spec_from_file_location("room_stub", STUB)
    stub = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stub)
    layers, experts, hidden, rows = 2, 2, 16, 64
    flat = weights.make_weights(
        7, stub.specs(layers, experts, hidden, rows, attn=8, router=8),
        {"matrix": 0.2})
    data = SeededBatches(7, "tokens", (8,), rows, 3)
    batches = [data.batch(0, i) for i in range(train_driver.CHECK_STEPS)]
    config = {"accumulators": accumulators}
    ref = stub.reference(layers, experts)
    x, y = batches[0]
    loss, grads, _ = jax.jit(
        lambda P: ref.loss_and_grads(P, x, y, config))(flat)
    whole = stub.reference(layers, experts)  # one sequence a call, summed
    parts = [whole.loss_and_grads(flat, x[i:i + 1], y[i:i + 1], config)
             for i in range(len(x))]
    assert float(loss) == pytest.approx(
        sum(float(p[0]) for p in parts) / len(x), rel=1e-5)
    for k in flat:
        want = sum(p[1][k] for p in parts) / len(x)
        np.testing.assert_allclose(grads[k], want, rtol=1e-4, atol=1e-7)
    out = train_driver.reference_numbers(ref, config, stub.HP, flat, batches,
                                         "float32")
    assert np.isfinite(out["losses"]).all()
    assert min(out["delta_norm"].values()) > 0


def test_the_room_stub_refuses_without_a_tpu():
    """No chip, no reading: exit code 2, a message, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, STUB, "1,2,16,64", "2"], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 2
    assert done.stdout == ""
    assert "needs a TPU" in done.stderr
