"""CPU rehearsal of both window drivers at a tiny size, the controls, and
the timed path broken underneath.

``run_cell.py`` itself refuses a CPU (tests/benchmark/test_manifest.py); here
the harness's look for a chip is skipped and the rest of a run is driven:
the program's own strategy / server, the benchmark's weights and traffic
from the seed, the window, the plain reference and the comparison. Nothing
printed here is a device metric. The tiny configuration
(tests/benchmark/data/tiny) is the program's ``transformer_t`` in float32,
so its limits are float32 round-off, not the chip's.
"""

import os
import types

import jax
import pytest

from benchmarks import run_cell
from benchmarks.harness import compare, manifest, serve_driver, train_driver

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NUMBERS = {"loss_step1", "loss_step2", "loss_step3", "grad1_norm_gap",
           "delta3_norm_gap", "grad1_norm_gap_matrix",
           "delta3_norm_gap_matrix", "grad1_norm_gap_median",
           "delta3_norm_gap_median", "grad1_diff_median"}


def context(traffic, seed=3, seconds=0.5, chips=1, data="tiny",
            config="gpt2-tiny"):
    """A run context over a directory of test data files: the index entries
    a PR would add for them, nothing else."""
    man = manifest.Manifest()
    man.dir = os.path.join(DATA, data)
    man.index = dict(
        man.index,
        configs=[{"name": config, "file": os.path.relpath(
            os.path.join(man.dir, "configs", f"{config}.json"), man.root)}],
        workloads=[{"name": "tiny", "config": config,
                    "traffic": traffic, "chips": chips}])
    args = types.SimpleNamespace(workload="tiny", seed=seed, seconds=seconds,
                                 trace=0)
    rc = run_cell.RunContext(man, args, jax.devices())
    rc.read_memory_peak = lambda: 0  # the CPU backend keeps no such account
    rc.mark = lambda phase: None
    return rc


def verdict(out):
    return compare.report(out["numbers"]), {c.name: c.value
                                            for c in out["numbers"]}


def test_train_window_driver_rehearsal():
    rc = context("train-tiny")
    out = train_driver.run(rc)
    ok, by = verdict(out)
    assert ok, by
    assert set(by) == NUMBERS
    assert out["attempted"] == out["counters"]["steps"] > 0
    assert out["failed"] == 0 and rc.window_compiles == 0
    assert out["window_s"] >= 0.5
    assert out["end_to_end"]["train_samples_per_s_per_chip"] == pytest.approx(
        out["counters"]["samples"] / out["window_s"])
    assert rc.setup_s is not None


def broken_step(kind, chips=1):
    """A ``build`` whose strategy has the named fault planted in its
    ``train_step`` — the one entry the window drives."""
    real_build = train_driver.build

    def build(config, traffic):
        import jax.numpy as jnp

        cfg, strategy = real_build(config, traffic)
        step = strategy.train_step

        def stale(ts, x, y, lr):  # a step that returns its state unchanged
            _, m = step(jax.tree.map(lambda a: a.copy(), ts), x, y, lr)
            return ts, m

        def half(ts, x, y, lr):  # half the batch left out, mean over the rest
            h = x.shape[0] // 2
            return step(ts, jnp.concatenate([x[:h], x[:h]]),
                        jnp.concatenate([y[:h], y[:h]]), lr)

        def own_rows(ts, x, y, lr):  # the exchange between chips left out:
            n = x.shape[0] // chips  # every chip's gradient is chip 0's own
            return step(ts, *strategy.shard_batch(
                jnp.tile(x[:n], (chips, 1)), jnp.tile(y[:n], (chips, 1))), lr)

        strategy.train_step = {"stale": stale, "half": half,
                               "own_rows": own_rows}[kind]
        return cfg, strategy

    return build


@pytest.mark.parametrize("kind,traffic,chips", [
    ("stale", "train-tiny", 1), ("half", "train-tiny", 1),
    ("own_rows", "train-tiny-dp2", 2)])
def test_a_broken_train_step_is_not_correct(monkeypatch, kind, traffic, chips):
    monkeypatch.setattr(train_driver, "build", broken_step(kind, chips))
    ok, by = verdict(train_driver.run(context(traffic, chips=chips)))
    assert not ok, by
    if kind == "stale":  # an unmoved leaf reads 1 by the measure
        assert by["delta3_norm_gap"] == pytest.approx(1.0, abs=1e-3)


def test_the_sound_dp_step_is_correct():
    """The pair of the ``own_rows`` fault: the same two-chip cell, whole."""
    ok, by = verdict(train_driver.run(context("train-tiny-dp2", chips=2)))
    assert ok, by


def test_train_control_is_not_correct():
    """The reference at the control precision (float8_e4m3 operands), put in
    the program's place, fails at least one number."""
    rc = context("train-tiny")
    config, hp = rc.config, train_driver.hyperparameters(
        rc.traffic["run_config"])
    from benchmarks.harness import weights
    from benchmarks.harness.traffic import SeededBatches

    _, strategy = train_driver.build(config, rc.traffic)
    names = [l.name for l in strategy.model.layers]
    shapes = jax.eval_shape(strategy.init, jax.random.key(0)).params
    flat = weights.make_weights(3, weights.flat_specs(shapes, names),
                                config["weights"])
    data = SeededBatches(3, "tokens", (32,), config["vocab_size"], 4)
    batches = [data.batch(0, i) for i in range(train_driver.CHECK_STEPS)]
    ref, again, ctl = (
        train_driver.reference_numbers(rc.reference, config, hp, flat,
                                       batches, rounding)
        for rounding in ("float32", "float32",
                         config["precision"]["train"]["control"]))
    limits = config["limits"]
    for side in (again, ctl):
        side["grad_diff"] = train_driver.gradient_differences(side["grad"],
                                                              ref["grad"])
    assert compare.report(compare.train_numbers(again, ref, limits))
    numbers = compare.train_numbers(ctl, ref, limits)
    assert not compare.report(numbers), numbers


def test_a_third_configuration_of_a_new_family_is_files_only():
    """LeNet on MNIST: a configuration file, its plain reference with its
    FLOP function, and a traffic mix, all under tests/benchmark/data/third.
    The harness runs the cell, compares it and counts its FLOPs without
    having heard of the family."""
    rc = context("train-lenet", data="third", config="lenet-mnist")
    assert rc.reference.__file__.endswith("data/third/reference/lenet.py")
    out = train_driver.run(rc)
    ok, by = verdict(out)
    assert ok, by
    assert set(by) == NUMBERS
    c = out["counters"]
    per_image = 3 * 2 * (28 * 28 * 25 * 6 + 14 * 14 * 25 * 6 * 16
                         + 784 * 120 + 120 * 84 + 84 * 10)
    assert c["model_flops"] == c["samples"] * per_image
    harness = os.path.join(manifest.ROOT, "benchmarks", "harness")
    for name in os.listdir(harness):
        if name.endswith(".py"):
            text = open(os.path.join(harness, name)).read()
            assert "lenet" not in text and '"family"' not in text, name


def test_a_third_configuration_s_broken_step_is_not_correct(monkeypatch):
    monkeypatch.setattr(train_driver, "build", broken_step("half"))
    ok, by = verdict(train_driver.run(
        context("train-lenet", data="third", config="lenet-mnist")))
    assert not ok, by


def test_serve_window_driver_rehearsal():
    rc = context("serve-tiny")
    # the control wants some hundreds of tokens to show among; the tiny
    # mix's own 3 requests are a dozen
    rc.traffic["check_requests"] = 150
    out = serve_driver.run(rc, control="bfloat16")
    ok, by = verdict(out)
    assert ok, by
    assert by["served_logit_gap"] <= 1e-4 and by["never_finished"] == 0
    c = out["counters"]
    assert out["attempted"] == c["requests"] > 0 and out["failed"] == 0
    assert len(c["ttft_s"]) == c["requests"]
    assert c["out_tokens"] > 0 and c["decode_calls"] and c["prefill_calls"]
    assert all(n > 0 for _, n in c["prefill_calls"])
    assert rc.window_compiles == 0  # every reachable program was warmed
    e2e = out["end_to_end"]
    assert e2e["serve_out_tokens_per_s"] == pytest.approx(
        c["out_tokens"] / out["window_s"])
    assert 0 < e2e["serve_itl_p95_s"] < max(c["ttft_s"]) * 50
    # the control: the token bfloat16 puts first lies below the float32
    # reference's best somewhere among the compared positions
    assert out["control_gap"] > rc.config["limits"]["served_logit_gap"]


def test_an_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: every second emitted token + 1
    (every request of the tiny mix emits at least two)."""
    from ddlbench_tpu.serve.engine import ServeEngine

    real = ServeEngine._emit_token

    def emit(self, raw, rid, token_index):
        tok = real(self, raw, rid, token_index)
        return (tok + 1) % 250 if token_index % 2 == 1 else tok

    monkeypatch.setattr(ServeEngine, "_emit_token", emit)
    ok, by = verdict(serve_driver.run(context("serve-tiny")))
    assert not ok
    assert by["served_logit_gap"] > 1e-4


def test_reachable_page_counts_cover_what_the_mix_can_reach():
    mix = {"serve_config": {"page": 16, "prefill_chunk": 64, "max_len": 512},
           "prompt": [32, 128, 384], "output": [16, 64, 128],
           "max_total": 512}
    prefill, decode = serve_driver.reachable_page_counts(mix)
    assert prefill == list(range(2, 25))
    assert decode == list(range(3, 33))
