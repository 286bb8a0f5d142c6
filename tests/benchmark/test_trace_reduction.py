"""The reduction from a trace to numbers: the interval arithmetic on
synthetic spans, and the whole reduction on an event list recorded on the
chip in PR 23 (two steps of gpt2s-train, cut out of a --trace 1 run)."""

import gzip
import json
import os

import pytest

from benchmarks.harness import intervals as iv
from benchmarks.harness import trace

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "data",
    "gpt2s-train.2steps.events.json.gz")


@pytest.mark.parametrize("spans,want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(3, 4), (0, 2), (1, 3.5)], [(0, 4)]),
    ([(0, 1), (2, 3), (2.5, 2.6)], [(0, 1), (2, 3)]),
    ([(1, 1), (2, 1)], []),
])
def test_union(spans, want):
    assert iv.union(spans) == want


def test_total_counts_overlaps_once():
    assert iv.total([(0, 2), (1, 3), (5, 6)]) == 4


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(2, 3), (5, 7)]),
    ([(0, 2), (4, 6)], [(1, 5)], [(1, 2), (4, 5)]),
    ([(0, 1)], [(1, 2)], []),
    ([(0, 5), (3, 8)], [(4, 9)], [(4, 8)]),
])
def test_intersect(a, b, want):
    assert iv.intersect(a, b) == want
    assert iv.intersect(b, a) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 2), (4, 6)], [(1, 5)], [(0, 1), (5, 6)]),
    ([(0, 4)], [(0, 4)], []),
    ([(0, 4)], [], [(0, 4)]),
    ([(0, 4), (6, 8)], [(3, 7)], [(0, 3), (7, 8)]),
])
def test_subtract(a, b, want):
    assert iv.subtract(a, b) == want


def test_gaps():
    assert iv.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert iv.gaps([], 0, 5) == [(0, 5)]


def test_subtract_plus_intersect_is_the_whole():
    a = [(0, 3), (4, 9), (10, 11)]
    b = [(1, 2), (2.5, 5), (8, 12)]
    assert iv.total(iv.subtract(a, b)) + iv.total(iv.intersect(a, b)) \
        == pytest.approx(iv.total(a))


def synthetic():
    """Two devices; a 10 s window. Device 0: compute [0,4), an all-reduce
    [3,6) (one second hidden under compute), compute [7,9). Device 1: busy
    [0,10) with no collective."""
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1", 0, 4], ["all-reduce.7", 3, 6],
                              ["flash_attn_fwd.3", 7, 9],
                              ["fusion.1", -5, -1]],  # before the window
            "/device:TPU:1": [["fusion.1", 0, 10]],
        },
        "host": [["bench/window", 0, 10], ["bench/dispatch", 0, 6.5],
                 ["bench/sync", 6.5, 10]],
    }


def test_reduce_synthetic():
    s = trace.reduce(synthetic())
    assert s.window_s == 10 and s.n_devices == 2
    # device 0 busy [0,6) + [7,9) = 8; device 1 busy 10
    assert s.busy_s == pytest.approx(9.0)
    # exposed: [4,6) on device 0, none on device 1 -> device 0's counts
    assert s.collective_exposed_s == pytest.approx(2.0)
    assert s.collective_s == pytest.approx(1.5)
    assert s.kernel_seconds(("flash_attn_fwd",)) == pytest.approx(1.0)
    assert s.op_seconds["fusion.1"] == pytest.approx((4 + 10) / 2)
    # device 0's gaps: [6,7) -> half under dispatch, half under sync: the
    # first to reach the larger cover takes it; [9,10) under sync
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(2.0)
    assert gaps["bench/sync"] >= 1.0


def test_async_collectives_are_collective_time_not_busy_time():
    ev = synthetic()
    ev["devices"]["/device:TPU:0"].append(
        [trace.ASYNC_PREFIX + "all-reduce-start.2", 6, 7])
    s = trace.reduce(ev)
    assert s.busy_s == pytest.approx(9.0)
    assert s.collective_exposed_s == pytest.approx(2.0 + 1.0)


def test_collective_exposed_share_is_of_the_chip_that_shows_most():
    from types import SimpleNamespace

    from benchmarks.harness import manifest

    man = manifest.Manifest()
    read = man.reader(man.metric_file("collective_exposed_share.train")).read
    # device 0 waits [4,6) of a 10 s window on its all-reduce; device 1 none
    assert read(SimpleNamespace(trace_summary=trace.reduce(synthetic()))) \
        == pytest.approx(20.0)
    one_chip = synthetic()
    one_chip["devices"]["/device:TPU:0"] = [["fusion.1", 0, 4]]
    assert read(SimpleNamespace(trace_summary=trace.reduce(one_chip))) is None


def test_reduce_needs_a_window_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"/device:TPU:0": []}, "host": []})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [["bench/window", 0, 1]]})


def test_short_name():
    assert trace.short_name(
        "%multiply_reduce_fusion.2 = (bf16[256]{0}) fusion(bf16[2] %x)") \
        == "multiply_reduce_fusion.2"
    assert trace.short_name("jvp_flash_attn_fwd_.12") \
        == "jvp_flash_attn_fwd_.12"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces_to_the_same_numbers_every_time(recorded):
    """Two steps of gpt2s-train on a TPU v5 lite (my chip run, PR 23)."""
    a, b = trace.reduce(recorded), trace.reduce(recorded)
    assert a == b
    assert a.n_devices == 1
    assert a.window_s == pytest.approx(0.312426854, abs=1e-9)
    assert a.busy_s == pytest.approx(0.31235718, abs=1e-8)
    assert 1.0 - a.busy_s / a.window_s == pytest.approx(2.23e-4, rel=0.01)
    assert a.collective_s == 0.0 and a.collective_exposed_s == 0.0


def test_recorded_trace_kernel_times_by_name(recorded):
    s = trace.reduce(recorded)
    flash = s.kernel_seconds(("flash_attn_fwd", "flash_attn_dq",
                              "flash_attn_dkv"))
    xent = s.kernel_seconds(("fused_xent_fwd", "fused_xent_dh",
                             "fused_xent_dw"))
    # 12 layers x 3 kernels x 2 steps; one fwd/dh/dw each x 2 steps
    n_flash = sum(1 for n, _, _ in recorded["devices"]["/device:TPU:0"]
                  if "flash_attn" in n)
    assert n_flash in range(72, 73 + 36)  # a step cut at the edge adds some
    assert flash == pytest.approx(0.0755, rel=0.05)
    assert xent == pytest.approx(0.0914, rel=0.05)
    assert s.top_ops(1)[0][0] == "transpose_jvp_fused_xent_dw__.1"
    assert s.idle_gaps[0][0] == "bench/sync"
