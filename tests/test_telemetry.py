"""Step-level telemetry (ddlbench_tpu/telemetry/): tracer determinism and
thread-safety, Perfetto/Chrome export schema, percentile math, the new
epoch-line fields' scraper round-trip, and the metrics-neutrality pin
(losses bitwise identical with tracing on/off).

Tier-1-fast by design: tiny models, few steps — the subsystem touches the
hot path of every benchmark run, so the default gate must exercise it.
"""

import json
import threading

import pytest

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.telemetry import (StepLatencyStats, Tracer,
                                    export_chrome_trace, get_tracer,
                                    percentile, set_tracer)
from ddlbench_tpu.telemetry.export import chrome_trace_dict
from ddlbench_tpu.telemetry.stats import latency_summary

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    before = get_tracer()
    yield
    set_tracer(before)


# ---- tracer mechanics ----


def test_disabled_tracer_records_nothing():
    tr = Tracer()
    assert not tr.enabled
    with tr.span("x", epoch=1):
        pass
    tr.complete("y", 0, 10)
    tr.counter("c", 1.0)
    tr.instant("i")
    assert len(tr) == 0


def test_ring_off_span_is_a_cheap_profiler_annotation():
    """Ring off, no profiler session: a span is one inactive
    ``TraceAnnotation("ddl/<name>")`` — under 2 us (the prefetcher opens
    three per batch; the benchmark's cells run 7-10 batches a second)."""
    import time

    from jax.profiler import TraceAnnotation

    tr = Tracer()
    assert isinstance(tr.span("a"), TraceAnnotation)
    n = 20_000
    best = float("inf")
    for _ in range(5):  # the best of five: a loaded box must not fail this
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tr.span("batch_produce", epoch=1, step=2, train=True):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    assert best < 2000, f"{best:.0f} ns a span"
    assert len(tr) == 0


def test_spans_land_in_the_profiler_trace(tmp_path):
    """Under a profiler session the program's host spans are ``ddl/<name>``
    events of the same ``.xplane.pb`` as the device ops, ring on or off
    (the train loop's warm-up block is one); ``complete`` (a region already
    over, as jax's compile events are when reported) stays ring-only."""
    import glob

    import jax

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("ring_wait", step=1):
            pass
        tr.enable()
        with tr.span("step_sync"):
            pass
        with tr.span("warmup_compile"):
            pass
        tr.complete("compile/backend", 0, 10)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("ddl/")}
    assert names == {"ddl/ring_wait", "ddl/step_sync", "ddl/warmup_compile"}
    assert [e[1] for e in tr.events()] == ["step_sync", "warmup_compile",
                                           "compile/backend"]


def test_span_records_name_duration_and_args():
    tr = Tracer().enable()
    with tr.span("step", epoch=2, step=7):
        pass
    tr.complete("pre", 100, 250, {"k": "v"})
    events = tr.events()
    assert [e[1] for e in events] == ["step", "pre"]
    phase, name, t0, dur, tid, tname, args = events[0]
    assert phase == "X" and dur >= 0 and args == {"epoch": 2, "step": 7}
    assert tid == threading.get_ident() and tname == "MainThread"
    assert events[1][2:4] == (100, 150)


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = Tracer(capacity=8).enable()
    for i in range(20):
        tr.complete(f"e{i}", i, i + 1)
    assert len(tr) == 8
    assert tr.dropped_events == 12
    # the ring keeps the NEWEST window
    assert [e[1] for e in tr.events()] == [f"e{i}" for i in range(12, 20)]


def test_tracer_thread_safety_no_lost_events():
    tr = Tracer(capacity=100_000).enable()
    N, T = 500, 8

    def work(k):
        for i in range(N):
            with tr.span(f"t{k}", i=i):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tr.events()
    assert len(events) == N * T
    # per-thread event streams stay in per-thread program order
    for k in range(T):
        mine = [e for e in events if e[1] == f"t{k}"]
        assert [e[6]["i"] for e in mine] == list(range(N))


# ---- export schema ----


def test_chrome_trace_export_schema(tmp_path):
    tr = Tracer().enable()
    with tr.span("main_span"):
        pass

    def producer():
        with tr.span("producer_span"):
            pass

    t = threading.Thread(target=producer, name="fake-prefetch")
    t.start()
    t.join()
    tr.counter("depth", 3)
    tr.instant("mark")

    path = tmp_path / "out.trace.json"
    n = export_chrome_trace(tr, str(path))
    doc = json.load(open(path))  # valid JSON by construction
    events = doc["traceEvents"]
    assert n == 4  # spans + counter + instant; metadata excluded
    for e in events:
        assert {"ph", "pid", "tid", "name"} <= set(e)
        if e["ph"] != "M":
            assert "ts" in e
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0
    # one named track per thread
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {"MainThread", "fake-prefetch"}
    # main/producer spans land on different tracks
    tid_of = {e["args"]["name"]: e["tid"] for e in events if e["ph"] == "M"}
    spans = {e["name"]: e["tid"] for e in events if e["ph"] == "X"}
    assert spans["main_span"] == tid_of["MainThread"]
    assert spans["producer_span"] == tid_of["fake-prefetch"]
    assert doc["metadata"]["dropped_events"] == 0


def test_export_separates_reused_thread_ids():
    """OS thread idents are recycled after join — each (ident, name) pair
    must still get its own track (epoch-N prefetch producers)."""
    tr = Tracer().enable()
    tr.complete("a", 0, 1)
    ev = tr.events()[0]
    # forge a second thread with the SAME ident but a different name
    tr._append(("X", "b", 2, 1, ev[4], "other-thread", None))
    doc = chrome_trace_dict(tr)
    tids = {e["name"]: e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert tids["a"] != tids["b"]


# ---- percentile math ----


def test_percentile_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 4.0
    assert percentile(xs, 50) == 2.5
    assert percentile(xs, 25) == 1.75
    assert percentile(list(reversed(xs)), 50) == 2.5  # sorts internally
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 50) == 0.0
    with pytest.raises(ValueError):
        percentile(xs, 101)


def test_latency_summary_and_step_stats():
    stats = StepLatencyStats()
    for ep, times in ((1, [0.010, 0.020, 0.030]), (2, [0.040])):
        for t in times:
            stats.record_step(ep, t)
    stats.set_warmup(1.5)
    e1 = stats.epoch_summary(1)
    assert e1["steps"] == 3 and e1["p50_ms"] == pytest.approx(20.0)
    assert e1["max_ms"] == pytest.approx(30.0)
    assert stats.epoch_summary(3) is None
    run = stats.run_summary()
    assert run["steps"] == 4
    assert run["p50_ms"] == pytest.approx(25.0)  # over ALL steps, not means
    assert run["warmup_compile_s"] == 1.5
    assert latency_summary([])["steps"] == 0


# ---- end-to-end: epoch lines, JSONL, summary, scraper round-trip ----


def _tiny_cfg(**kw):
    # lenet, not resnet18: these tests pin TELEMETRY plumbing (span
    # vocabulary, JSONL/scraper round-trip, tracing neutrality), which is
    # arch-independent — the smallest conv net halves the compile bill of
    # the two heaviest tier-1 telemetry tests (ROADMAP item 5 budget)
    base = dict(benchmark="mnist", strategy="single", arch="lenet",
                epochs=2, steps_per_epoch=2, batch_size=8, log_interval=1,
                compute_dtype="float32")
    base.update(kw)
    return RunConfig(**base)


def test_run_emits_percentiles_everywhere(capsys, tmp_path):
    from ddlbench_tpu.tools.process_output import scrape
    from ddlbench_tpu.train.loop import run_benchmark
    from ddlbench_tpu.train.metrics import MetricLogger

    jsonl = tmp_path / "m.jsonl"
    cfg = _tiny_cfg()
    logger = MetricLogger(cfg.epochs, cfg.log_interval, jsonl_path=str(jsonl))
    result = run_benchmark(cfg, logger=logger)
    logger.close()
    text = capsys.readouterr().out

    # summary dict
    assert result["step_time_p50_ms"] > 0
    assert result["step_time_p95_ms"] >= result["step_time_p50_ms"]
    assert result["warmup_compile_s"] > 0

    # epoch lines -> scraper round-trip
    out = scrape(text)
    assert out["epochs"] == 2
    for ep in out["per_epoch"]:
        assert ep["step_time_p50_ms"] > 0
        assert ep["step_time_p95_ms"] >= ep["step_time_p50_ms"]

    # JSONL records
    records = [json.loads(l) for l in jsonl.read_text().splitlines()]
    epochs = [r for r in records if r["kind"] == "epoch"]
    assert len(epochs) == 2 and all("step_time_p50_ms" in r for r in epochs)
    summaries = [r for r in records if r["kind"] == "summary"]
    assert len(summaries) == 1 and "step_time_p95_ms" in summaries[0]


def test_scrape_epoch_line_with_all_suffixes():
    from ddlbench_tpu.tools.process_output import scrape

    out = scrape("epoch 2/3 done | 120.00 samples/sec | 8.33 sec | "
                 "input stall 12.5 ms | step p50 1.23 ms, p95 4.56 ms")
    ep = out["per_epoch"][0]
    assert ep["input_stall_ms"] == 12.5
    assert ep["step_time_p50_ms"] == 1.23
    assert ep["step_time_p95_ms"] == 4.56
    # old logs (no suffixes) still parse
    out = scrape("epoch 1/3 done | 10.00 samples/sec | 1.00 sec")
    assert "step_time_p50_ms" not in out["per_epoch"][0]


def test_valid_history_carries_top5():
    from ddlbench_tpu.train.metrics import MetricLogger

    lg = MetricLogger(2, 1)
    lg.valid_epoch(1, 2.0, 0.5, top5=0.9)
    lg.valid_epoch(2, 1.5, 0.6)
    s = lg.summary(0.6)
    assert s["valid_history"][0]["top5"] == 0.9
    assert "top5" not in s["valid_history"][1]


# ---- metrics neutrality: bitwise-identical losses with tracing on/off ----


def test_tracing_is_metrics_neutral(tmp_path, capsys):
    from ddlbench_tpu.train.loop import run_benchmark

    def losses(cfg):
        res = run_benchmark(cfg)
        capsys.readouterr()  # keep the log quiet between runs
        return [(h["epoch"], h["loss"], h["accuracy"])
                for h in res["valid_history"]]

    plain = losses(_tiny_cfg())
    traced = losses(_tiny_cfg(trace=str(tmp_path / "t.trace.json")))
    assert plain == traced  # bitwise: floats compared exactly

    # the traced run really did trace: spans from main loop AND producer
    doc = json.load(open(tmp_path / "t.trace.json"))
    span_threads = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(span_threads) >= 2
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train_step", "batch_produce", "ring_wait"} <= names
    # the global tracer is disabled again after the traced run
    assert not get_tracer().enabled
