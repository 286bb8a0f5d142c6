"""The set-up metrics read from the program's compile tally
(``benchmarks/metrics/readers/setup_compiles.py``): events that ended
before the window opened count and later ones do not, a program without
the tally reads nothing, and the three entries are an addition that lists
neither cell whose roster accepted tests hold exact. CPU only."""

import sys
import time
import types

import jax
import numpy as np
import pytest

from benchmarks import run_cell
from benchmarks.harness import manifest
from ddlbench_tpu.telemetry import compiles
from ddlbench_tpu.telemetry.compiles import CompileTally

ROOT = manifest.ROOT
METRICS = ("setup_trace_lower_s.train", "setup_compile_load_s.train",
           "setup_cache_misses.train")
CELLS = ["resnet50-single", "gpt2s-train", "resnet50-dp4",
         "smallthinker-t16k-train"]
MAN = manifest.Manifest()


def read(name, ctx):
    spec = MAN.metric_file(name)
    return MAN.reader(spec).read(ctx, **spec.get("args", {}))


@pytest.fixture()
def fake_run(monkeypatch):
    """A context whose class lives in a module with its own
    ``T_PROCESS_START``, as ``run_cell.py``'s does, and a tally of the
    test's own in place of the process's."""
    mod = types.ModuleType("fake_run_cell")
    mod.T_PROCESS_START = time.perf_counter()
    exec("class Ctx:\n    setup_s = None\n", mod.__dict__)
    monkeypatch.setitem(sys.modules, "fake_run_cell", mod)
    tally = CompileTally()
    monkeypatch.setattr(compiles, "_TALLY", tally)
    return mod, mod.Ctx(), tally


def test_only_events_before_the_window_count(fake_run):
    mod, ctx, tally = fake_run
    gap = lambda: time.sleep(0.003)  # apart by more than an event lasts
    tally.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.001,
                      fun_name="train_step")
    gap()
    tally.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      0.002, fun_name="jit(train_step)")
    gap()
    tally.on_event("/jax/compilation_cache/cache_hits")
    tally.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                      0.001)
    tally.on_duration("/jax/core/compile/backend_compile_duration", 0.0015,
                      fun_name="jit(train_step)")
    gap()
    ctx.setup_s = time.perf_counter() - mod.T_PROCESS_START  # the window
    gap()
    # after the window: the reference flow, scopes.step_hlo
    tally.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.001,
                      fun_name="loss_and_grads")
    tally.on_event("/jax/compilation_cache/cache_misses")
    tally.on_duration("/jax/core/compile/backend_compile_duration", 2.0,
                      fun_name="jit(loss_and_grads)")
    # trace and lower one after the other; the load inside the backend's
    assert read("setup_trace_lower_s.train", ctx) == pytest.approx(0.003,
                                                                   abs=1e-6)
    assert read("setup_compile_load_s.train", ctx) == pytest.approx(0.0015)
    assert read("setup_cache_misses.train", ctx) == 0.0
    ctx.setup_s += 1.0
    assert read("setup_cache_misses.train", ctx) == 1.0
    assert read("setup_compile_load_s.train", ctx) >= 2.0


def test_nothing_to_read_leaves_the_metric_out(fake_run, monkeypatch):
    mod, ctx, tally = fake_run
    assert all(read(n, ctx) is None for n in METRICS)  # no window yet
    ctx.setup_s = 1.0
    assert read("setup_cache_misses.train", ctx) == 0.0
    # a tally that let events go cannot count them
    monkeypatch.setattr(compiles, "_CAPACITY", 1)
    full = CompileTally()
    for _ in range(2):
        full.on_event("/jax/compilation_cache/cache_misses")
    monkeypatch.setattr(compiles, "_TALLY", full)
    assert full.dropped == 1
    assert all(read(n, ctx) is None for n in METRICS)
    monkeypatch.setattr(compiles, "_TALLY", None)  # never installed
    assert all(read(n, ctx) is None for n in METRICS)
    # a program without the module at all: the parent's
    monkeypatch.setitem(sys.modules, "ddlbench_tpu.telemetry.compiles", None)
    assert all(read(n, ctx) is None for n in METRICS)


def test_the_run_context_s_window_is_on_the_tally_s_clock():
    """``run_cell.RunContext`` (what the drivers and the rehearsal tests
    build): a program compiled before ``open_window`` counts, one compiled
    after it does not."""
    compiles.install()
    args = types.SimpleNamespace(workload="gpt2s-train", seed=1, seconds=1,
                                 trace=0)
    rc = run_cell.RunContext(MAN, args, jax.devices())
    jax.jit(lambda x: x * 13.0 + 1.0)(np.ones(5, np.float32))
    rc.open_window()
    before = {n: read(n, rc) for n in METRICS}
    assert before["setup_compile_load_s.train"] > 0
    assert before["setup_trace_lower_s.train"] > 0
    assert before["setup_trace_lower_s.train"] \
        + before["setup_compile_load_s.train"] <= rc.setup_s
    jax.jit(lambda x: x * 17.0 - 1.0)(np.ones(5, np.float32))
    rc.close_window()
    assert {n: read(n, rc) for n in METRICS} == before


def test_the_three_entries_are_an_addition():
    assert manifest.check(MAN) == []
    assert manifest.against(MAN, ROOT) == []
    by_name = {m["name"]: m for m in MAN.index["per_layer"]}
    for name in METRICS:
        entry, spec = by_name[name], MAN.metric_file(name)
        assert entry["workloads"] == CELLS
        assert not {"kanana2-ep16-train", "zaya1-ep2-train"} \
            & set(entry["workloads"])
        assert (entry["source"], entry["layer"], entry["moves"],
                entry["better"]) == ("program_counter", "entry points",
                                     "setup_s", "lower")
        assert entry["unit"] == ("count" if "misses" in name else "s")
        assert spec["reader"] == "setup_compiles"
        for cell in CELLS:
            assert name in {m["name"] for m in MAN.per_layer_of(cell)}
