"""Async input pipeline (data/prefetch.py): determinism, backpressure,
shutdown hygiene, epoch-boundary ordering, stall accounting, CLI knobs.

Tier-1-fast by design (tiny models, few steps): the subsystem sits on the
hot path of every benchmark run, so the default gate must exercise it.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.data.prefetch import Prefetcher

pytestmark = pytest.mark.prefetch


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("ddlbench-prefetch") and t.is_alive()]


class _ScriptedData:
    """Deterministic (epoch, step)-addressed source that logs every call."""

    def __init__(self, steps=8, delay_s=0.0, fail_at=None):
        self._steps = steps
        self._delay_s = delay_s
        self._fail_at = fail_at
        self.calls = []

    def steps_per_epoch(self, train=True):
        return self._steps

    def batch(self, epoch, step, train=True):
        if self._fail_at is not None and step == self._fail_at:
            raise RuntimeError(f"scripted failure at step {step}")
        if self._delay_s:
            time.sleep(self._delay_s)
        self.calls.append((epoch, step, train))
        return (np.full((2, 2), epoch * 100 + step, np.float32),
                np.full((2,), step, np.int32))


def _identity_shard(x, y):
    return x, y


# ---- ring mechanics ----


def test_batches_arrive_in_order_and_threads_exit():
    data = _ScriptedData(steps=6)
    stream = Prefetcher(data, _identity_shard, depth=2).stream(1)
    got = [int(f.batch[0][0, 0]) for f in stream]
    assert got == [100 + s for s in range(6)]
    assert not _prefetch_threads()  # exhausted stream joined its producer


def test_bounded_queue_backpressure():
    """An unconsumed stream produces at most depth (queued) + 1 (in flight)
    batches — the ring really is bounded."""
    data = _ScriptedData(steps=32)
    stream = Prefetcher(data, _identity_shard, depth=2).stream(1)
    try:
        deadline = time.monotonic() + 5.0
        while len(data.calls) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # would overfill here if the ring were unbounded
        assert len(data.calls) == 3  # depth + 1
        consumed = sum(1 for _ in stream)
        assert consumed == 32 and len(data.calls) == 32
    finally:
        stream.close()
    assert not _prefetch_threads()


def test_close_mid_epoch_leaks_nothing():
    """Abandoning a stream mid-epoch (consumer exception path) joins the
    producer even while it is blocked on a full ring."""
    data = _ScriptedData(steps=64, delay_s=0.002)
    stream = Prefetcher(data, _identity_shard, depth=2).stream(1)
    with pytest.raises(RuntimeError, match="consumer blew up"):
        try:
            for i, _ in enumerate(stream):
                if i == 2:
                    raise RuntimeError("consumer blew up")
        finally:
            stream.close()
    assert not _prefetch_threads()
    assert len(data.calls) < 64  # production actually stopped early


def test_close_abandons_wedged_producer_after_grace():
    """A producer wedged INSIDE a fetch (a hung device_put) must not hang
    close(): the join is abandoned after the grace period so
    a propagating training exception still surfaces (daemon thread)."""
    release = threading.Event()

    class _WedgedData:
        def steps_per_epoch(self, train=True):
            return 4

        def batch(self, epoch, step, train=True):
            if step == 1:
                release.wait(30.0)  # simulates a hung device_put
            return np.zeros(1), np.zeros(1)

    stream = Prefetcher(_WedgedData(), _identity_shard, depth=2).stream(1)
    next(iter(stream))
    t0 = time.monotonic()
    stream.close(grace_s=0.3)
    assert time.monotonic() - t0 < 5.0  # returned despite the wedged fetch
    release.set()  # let the daemon thread finish so it doesn't linger
    deadline = time.monotonic() + 5.0
    while _prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _prefetch_threads()


def test_producer_exception_propagates_to_consumer():
    data = _ScriptedData(steps=8, fail_at=3)
    stream = Prefetcher(data, _identity_shard, depth=2).stream(1)
    seen = 0
    with pytest.raises(RuntimeError, match="scripted failure at step 3"):
        for _ in stream:
            seen += 1
    assert seen == 3
    assert not _prefetch_threads()


def test_epoch_boundary_ordering():
    """No epoch-E+1 batch is produced (let alone consumed) during epoch E."""
    data = _ScriptedData(steps=4)
    pf = Prefetcher(data, _identity_shard, depth=3)
    for _ in pf.stream(1):
        assert {e for e, _, _ in data.calls} == {1}
    assert [s for _, s, _ in data.calls] == [0, 1, 2, 3]
    for _ in pf.stream(2):
        pass
    assert [e for e, _, _ in data.calls] == [1, 1, 1, 1, 2, 2, 2, 2]
    assert not _prefetch_threads()


def test_sync_fallback_same_interface():
    """depth=0 (--no-prefetch) serves identical batches through the same
    stream interface, with no thread, counting the inline fetch as stall."""
    data = _ScriptedData(steps=3, delay_s=0.01)
    stream = Prefetcher(data, _identity_shard, depth=0).stream(1)
    got = [int(f.batch[0][0, 0]) for f in stream]
    assert got == [100, 101, 102]
    assert stream.stall_ms >= 30.0 * 0.5  # 3 x 10 ms inline fetches
    assert not _prefetch_threads()


def test_watchdog_heartbeat_eval_only():
    """Eval streams beat the watchdog (no per-step sync exists there); train
    streams do NOT — input-side kicks would postpone the armed watchdog's
    per-step device-hang deadline, which the loop's own float() syncs own."""
    class _WD:
        kicks = 0

        def kick(self):
            self.kicks += 1

    wd = _WD()
    pf = Prefetcher(_ScriptedData(steps=5), _identity_shard, depth=2,
                    watchdog=wd)
    for _ in pf.stream(1, train=False):
        pass
    assert wd.kicks >= 5  # at least one beat per consumed eval batch
    wd.kicks = 0
    for _ in pf.stream(1, train=True):
        pass
    assert wd.kicks == 0


# ---- loop integration: bitwise determinism + stall reporting ----


def _run(tmp_path, tag, prefetch_depth):
    from ddlbench_tpu.train.loop import run_benchmark
    from ddlbench_tpu.train.metrics import MetricLogger

    jsonl = tmp_path / f"{tag}.jsonl"
    cfg = RunConfig(benchmark="mnist", strategy="dp", arch="lenet",
                    num_devices=2, epochs=2, steps_per_epoch=4,
                    log_interval=2, batch_size=4, compute_dtype="float32",
                    prefetch_depth=prefetch_depth)
    logger = MetricLogger(cfg.epochs, cfg.log_interval, jsonl_path=str(jsonl))
    result = run_benchmark(cfg, logger=logger, warmup_steps=0)
    logger.close()
    records = [json.loads(l) for l in jsonl.read_text().splitlines()]
    return result, records


def test_prefetch_on_off_losses_bitwise_identical(tmp_path, devices):
    """Acceptance criterion: dp + synthetic on CPU, 2 epochs — every
    per-interval loss and the validation curve are bitwise identical with
    the async pipeline on vs --no-prefetch, and the per-epoch records
    report the input-stall metric."""
    res_on, rec_on = _run(tmp_path, "on", prefetch_depth=2)
    res_off, rec_off = _run(tmp_path, "off", prefetch_depth=0)

    def losses(recs, kind):
        return [r["loss"] for r in recs if r["kind"] == kind]

    on_losses = losses(rec_on, "train_interval")
    assert len(on_losses) == 4  # 2 intervals x 2 epochs
    assert on_losses == losses(rec_off, "train_interval")  # bitwise
    assert losses(rec_on, "valid") == losses(rec_off, "valid")
    assert res_on["valid_accuracy"] == res_off["valid_accuracy"]
    # input-stall accounting lands per epoch and in the summary
    for recs, res in ((rec_on, res_on), (rec_off, res_off)):
        stalls = [r["input_stall_ms"] for r in recs if r["kind"] == "epoch"]
        assert len(stalls) == 2 and all(s >= 0.0 for s in stalls)
        assert res["input_stall_ms_per_epoch"] >= 0.0
    assert not _prefetch_threads()


# ---- reporting plumbing ----


def test_epoch_line_and_scraper_roundtrip(capsys):
    from ddlbench_tpu.tools.process_output import scrape
    from ddlbench_tpu.train.metrics import MetricLogger

    lg = MetricLogger(total_epochs=1)
    lg.epoch_done(1, 120.0, 8.33, input_stall_ms=3.25)
    line = capsys.readouterr().out
    assert "| input stall 3.2 ms" in line
    out = scrape(line)
    assert out["per_epoch"][0]["input_stall_ms"] == 3.2
    assert out["per_epoch"][0]["samples_per_sec"] == 120.0
    # stall-less epoch lines (old logs) still parse
    lg.epoch_done(1, 120.0, 8.33)
    out2 = scrape(capsys.readouterr().out)
    assert "input_stall_ms" not in out2["per_epoch"][0]
    assert out2["per_epoch"][0]["epoch_seconds"] == 8.33


def test_cli_prefetch_flags():
    from ddlbench_tpu.cli import build_parser, config_from_args

    parser = build_parser()
    assert config_from_args(parser.parse_args([])).prefetch_depth == 2
    assert config_from_args(
        parser.parse_args(["--prefetch-depth", "5"])).prefetch_depth == 5
    assert config_from_args(
        parser.parse_args(["--no-prefetch"])).prefetch_depth == 0
    with pytest.raises(ValueError, match="prefetch_depth"):
        RunConfig(prefetch_depth=-1).validate()


def test_evaluate_on_device_accumulation_matches_host_math():
    """evaluate() now sums metrics as jax.Arrays with one epoch-end
    transfer; the result must equal the old per-step host accumulation."""
    from ddlbench_tpu.train.loop import evaluate

    per_step = [(1.5, 3, 5, 8), (0.5, 6, 7, 8), (2.0, 2, 4, 8)]

    class _Scripted:
        def __init__(self):
            self.i = 0

        def shard_batch(self, x, y):
            return x, y

        def eval_step(self, ts, x, y):
            loss, c, c5, n = per_step[self.i]
            self.i += 1
            return {"loss": jnp.float32(loss), "correct": jnp.int32(c),
                    "correct5": jnp.int32(c5), "count": jnp.int32(n)}

    class _Data:
        def steps_per_epoch(self, train=True):
            return len(per_step)

        def batch(self, epoch, step, train=True):
            return np.zeros((8, 1), np.float32), np.zeros((8,), np.int32)

    cfg = RunConfig(benchmark="mnist", strategy="single",
                    compute_dtype="float32")
    val = evaluate(cfg, _Scripted(), None, _Data(), 1)
    total = sum(n for _, _, _, n in per_step)
    assert val["accuracy"] == sum(c for _, c, _, _ in per_step) / total
    assert val["top5"] == sum(c5 for _, _, c5, _ in per_step) / total
    expect_loss = sum(l * n for l, _, _, n in per_step) / total
    assert abs(val["loss"] - expect_loss) < 1e-6


def test_jit_outputs_survive_recycled_host_buffers():
    """The invariant the zero-copy loader ring (native_loader) + execution
    barrier (ondisk.batch) rely on: jax may zero-copy ALIAS an aligned host
    numpy buffer (so no upload barrier can protect the raw device view),
    but jitted-pipeline OUTPUTS — including passthrough arguments, like the
    labels through _normalize — are fresh device buffers once execution
    completes, so recycling the source buffer afterwards cannot corrupt
    them. Uses 64-byte-aligned sources to force the aliasing path
    deterministically."""
    import jax as _jax

    def aligned(n, dtype, align=64):
        raw = np.zeros(n * np.dtype(dtype).itemsize + align, np.uint8)
        off = (-raw.ctypes.data) % align
        a = raw[off:off + n * np.dtype(dtype).itemsize].view(dtype)
        a[:] = np.arange(n, dtype=dtype)
        return a

    @_jax.jit
    def pipeline(img, lab):
        return img.astype(jnp.float32) / 255.0, lab

    imgs, labs = aligned(64, np.uint8), aligned(64, np.int32)
    x, y = pipeline(jnp.asarray(imgs), jnp.asarray(labs))
    _jax.block_until_ready((x, y))
    _jax.device_get(x.ravel()[0:1])
    _jax.device_get(y.ravel()[0:1])
    imgs[:] = 0
    labs[:] = 0  # recycle both ring buffers
    np.testing.assert_array_equal(np.asarray(y),
                                  np.arange(64, dtype=np.int32))
    np.testing.assert_allclose(
        np.asarray(x),
        np.arange(64, dtype=np.uint8).astype(np.float32) / 255.0)


def test_native_loader_ring_hands_out_buffers_without_copy(tmp_path):
    from ddlbench_tpu.config import DatasetSpec
    from ddlbench_tpu.data import native_loader

    if not native_loader.available():
        pytest.skip("native dataloader unavailable")
    spec = DatasetSpec("ringset", (4, 4, 1), 3, 24, 8)
    d = native_loader.generate_dataset(str(tmp_path), spec, "train", seed=2)
    loader = native_loader.NativeDataLoader(d, batch_size=8, seed=2,
                                            prefetch_depth=2)
    ring = [img for img, _ in loader._bufs]
    a, _ = loader.next()
    b, _ = loader.next()
    c, _ = loader.next()
    # zero-copy: the returned arrays ARE the preallocated ring buffers,
    # rotating so depth+1 consecutive batches never share storage
    assert all(any(x is buf for buf in ring) for x in (a, b, c))
    assert a is not b and b is not c and a is not c
    # wrap-around reuses the oldest buffer — the documented lifetime bound
    d2, _ = loader.next()
    assert d2 is a
    loader.close()
