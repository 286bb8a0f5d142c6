"""jax's compile path, tallied (ddlbench_tpu/telemetry/compiles.py): what a
first jit with an empty persistent cache records, what the same program
records when it comes back from the cache, how a stamp splits the events,
that installing twice neither double-counts nor clears, and that the
tracer's ring gets ``compile/<kind>`` events only while it is on, and the
train loop's warm-up block split by it in the run summary. CPU."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.distributed import enable_compilation_cache
from ddlbench_tpu.telemetry import Tracer, compiles, get_tracer, set_tracer
from ddlbench_tpu.telemetry.compiles import CompileTally

CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes",
              "jax_enable_compilation_cache")


@pytest.fixture()
def fresh_cache(tmp_path):
    """The program's cache settings over an empty directory of the test's
    own, every program kept (as the benchmark keeps them); all restored."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    try:
        tally = compiles.install()
        enable_compilation_cache()
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        yield tally
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def gained(tally, t0, t1):
    """Per kind, the events that ended from ``t0`` to ``t1``."""
    kinds = ("trace", "lower", "backend", "cache_load", "cache_hit",
             "cache_miss")
    return {k: tally.count(k, t1) - tally.count(k, t0) for k in kinds}


def run_once(f):
    t0 = time.perf_counter_ns()
    f(np.arange(8, dtype=np.float32)).block_until_ready()
    return t0, time.perf_counter_ns()


def test_a_first_jit_and_its_cache_load(fresh_cache):
    """A first jit into an empty cache: trace, lower, a compile and one miss
    for each program compiled. After ``jax.clear_caches()`` the same
    function is traced and lowered again, and its program comes back from
    the persistent cache: a hit with load seconds, no new miss."""
    tally = fresh_cache
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 0.25)
    t0, t1 = run_once(f)
    cold = gained(tally, t0, t1)
    assert cold["trace"] >= 1 and cold["lower"] >= 1
    assert cold["backend"] >= 1
    assert cold["cache_miss"] == cold["backend"]
    assert cold["cache_hit"] == 0 and cold["cache_load"] == 0
    split = compiles.between(t0, t1)
    assert split["cache_misses"] == cold["cache_miss"]
    assert split["cache_hits"] == 0
    assert 0 < split["trace_lower_s"] and 0 < split["backend_s"]
    assert split["trace_lower_s"] + split["backend_s"] <= (t1 - t0) / 1e9

    jax.clear_caches()
    t2, t3 = run_once(f)
    warm = gained(tally, t2, t3)
    assert warm["trace"] >= 1 and warm["lower"] >= 1
    assert warm["cache_hit"] >= 1 and warm["cache_miss"] == 0
    assert warm["cache_load"] == warm["cache_hit"]
    load = tally.seconds("cache_load", t3) - tally.seconds("cache_load", t2)
    assert load > 0
    assert compiles.between(t2, t3)["cache_misses"] == 0
    assert compiles.between(t2, t3)["cache_hits"] == warm["cache_hit"]


def test_a_stamp_splits_the_events_and_nested_ones_count_once():
    tally = CompileTally()
    tally.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.002,
                      fun_name="inner")
    tally.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5,
                      fun_name="outer")
    tally.on_event("/jax/compilation_cache/cache_misses")
    tally.on_duration("/jax/core/compile/some_other_duration", 9.0)
    tally.on_event("/jax/compilation_cache/tasks_using_cache")
    mid = time.perf_counter_ns()
    time.sleep(0.002)
    tally.on_duration("/jax/core/compile/backend_compile_duration", 0.25,
                      fun_name="jit(step)")
    tally.on_event("/jax/compilation_cache/cache_misses")
    # events jax reports that the tally does not know are not kept
    assert len(tally.events()) == 5
    assert tally.count("cache_miss", until_ns=mid) == 1
    assert tally.count("cache_miss") == 2
    assert tally.count("backend", until_ns=mid) == 0
    assert tally.count("backend") == 1
    # the inner trace ended inside the outer one: 0.5 s, not 0.502
    assert tally.seconds("trace", until_ns=mid) == pytest.approx(0.5,
                                                                 abs=1e-3)
    assert tally.seconds(("trace", "backend"), until_ns=mid) \
        == tally.seconds("trace")
    assert tally.seconds("backend") == pytest.approx(0.25)
    assert tally.seconds(("trace", "backend")) >= 0.5
    assert tally.events()[-2][:3] == ("backend", "jit(step)", 0.25)


def test_a_full_tally_lets_the_oldest_go(monkeypatch):
    monkeypatch.setattr(compiles, "_CAPACITY", 3)
    tally = CompileTally()
    for i in range(5):
        tally.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.001,
                          fun_name=f"f{i}")
    assert tally.dropped == 2
    assert [e[1] for e in tally.events()] == ["f2", "f3", "f4"]
    assert tally.count("trace") == 3


def test_a_second_install_neither_registers_again_nor_clears():
    from jax._src import monitoring

    before = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    try:
        enable_compilation_cache()
        tally = compiles.installed()
        assert tally is not None
        jax.jit(lambda x: x + 1.0)(np.ones(3, np.float32)).block_until_ready()
        kept = tally.events()
        assert kept
        enable_compilation_cache()
        assert compiles.install() is tally and compiles.installed() is tally
        assert tally.events()[:len(kept)] == kept
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
    hooked = [f for f in monitoring._event_duration_secs_listeners
              if f == tally.on_duration]
    assert len(hooked) == 1
    assert len([f for f in monitoring._event_listeners
                if f == tally.on_event]) == 1
    # one compile, each of its events once
    seen = []

    def mine(event, duration, **_):
        if event in compiles.DURATION_KINDS:
            seen.append(compiles.DURATION_KINDS[event])

    monitoring.register_event_duration_secs_listener(mine)
    try:
        t0 = time.perf_counter_ns()
        jax.jit(lambda x: x * 5.0 - 1.0)(np.ones(3, np.float32))
        t1 = time.perf_counter_ns()
    finally:
        monitoring.unregister_event_duration_listener(mine)
    got = [e[0] for e in tally.events() if t0 < e[3] <= t1]
    assert sorted(got) == sorted(seen) and "backend" in got


def test_the_ring_gets_compile_events_only_while_it_is_on():
    tally = compiles.install()
    before = get_tracer()
    try:
        off = set_tracer(Tracer())
        jax.jit(lambda x: x * 7.0)(np.ones(4, np.float32))
        assert len(off) == 0
        on = set_tracer(Tracer()).enable()
        t0 = time.perf_counter_ns()
        jax.jit(lambda x: x * 11.0)(np.ones(4, np.float32))
        t1 = time.perf_counter_ns()
    finally:
        set_tracer(before)
    ring = on.events()
    names = {e[1] for e in ring}
    assert {"compile/trace", "compile/lower", "compile/backend"} <= names
    assert all(n.startswith("compile/") for n in names)
    kept = [e for e in tally.events() if t0 < e[3] <= t1
            and e[0] in ("trace", "lower", "backend", "cache_load")]
    assert len(ring) == len(kept)
    for phase, name, start, dur, _, _, args in ring:
        assert phase == "X" and dur >= 0 and t0 < start + dur <= t1
    lowered = [e for e in ring if e[1] == "compile/lower"]
    assert lowered[0][6] == {"fun": "jit(<lambda>)"}


def test_the_warm_up_block_is_split_in_the_run_summary(tmp_path, capsys):
    """``run_benchmark``'s summary keeps ``warmup_compile_s`` and adds what
    the tally says of that block; a traced run's Chrome trace has the block
    as a span and the compile path inside it."""
    from ddlbench_tpu.config import RunConfig
    from ddlbench_tpu.train.loop import run_benchmark

    compiles.install()
    path = tmp_path / "t.trace.json"
    result = run_benchmark(RunConfig(
        benchmark="mnist", strategy="single", arch="lenet", epochs=1,
        steps_per_epoch=2, batch_size=8, log_interval=1,
        compute_dtype="float32", trace=str(path)))
    capsys.readouterr()
    split = {k: result[k] for k in ("warmup_compile_s",
                                    "warmup_trace_lower_s",
                                    "warmup_backend_s", "warmup_cache_misses",
                                    "warmup_cache_hits")}
    assert split["warmup_trace_lower_s"] > 0
    assert split["warmup_backend_s"] > 0
    assert split["warmup_cache_misses"] >= 0 and split["warmup_cache_hits"] >= 0
    assert split["warmup_trace_lower_s"] + split["warmup_backend_s"] \
        <= split["warmup_compile_s"]
    events = json.load(open(path))["traceEvents"]
    (warm,) = [e for e in events if e["name"] == "warmup_compile"]
    inside = [e for e in events if e["name"].startswith("compile/")
              and warm["ts"] <= e["ts"] + e["dur"] <= warm["ts"] + warm["dur"]]
    assert {"compile/trace", "compile/lower", "compile/backend"} \
        <= {e["name"] for e in inside}
