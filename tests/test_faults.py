"""Fault injection + crash-consistent checkpointing (ISSUE 4 tentpole).

Three layers of pinning, all CPU-only and tier-1-fast (``chaos`` marker):

* commit-protocol units — a partially-written checkpoint (crash during
  save), an uncommitted directory (crash between the orbax write and the
  marker), truncation, and bit flips are each detected by ``latest_valid``,
  which falls back to the previous good checkpoint; retention GC bounds the
  window without ever dropping the newest committed state;
* in-process fault semantics — ``nan-loss`` drives the --nan-policy path at
  the injected step, ``prefetch-die`` surfaces promptly as a
  ``TrainingFailure`` with the producer's traceback chained, ``slow-host``
  delays the multihost init path, ``ckpt-corrupt`` damage is detected at
  resume and the run falls back and REPLAYS to the same trajectory;
* supervised kill/resume round-trips — ``tools/chaosbench.py`` SIGKILLs the
  real train CLI mid-run, auto-resumes it, and the recovered per-step
  loss trajectory matches an uninterrupted run bit-for-bit (single and
  gpipe), with recoveries/MTTR/overhead in the JSON report.
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.chaos

from ddlbench_tpu import faults
from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.train import checkpoint as ck
from ddlbench_tpu.train.loop import run_benchmark
from ddlbench_tpu.train.watchdog import TrainingFailure


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


def _cfg(ck_dir=None, **kw):
    base = dict(benchmark="mnist", strategy="single", arch="lenet",
                compute_dtype="float32", steps_per_epoch=4, log_interval=1,
                batch_size=8, checkpoint_dir=ck_dir)
    base.update(kw)
    return RunConfig(**base)


def _pvec(ts):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(ts.params)])


# ---- spec grammar ---------------------------------------------------------

def test_inject_spec_parsing():
    specs = faults.parse_injections(["kill@2:5", "nan-loss@1:0"])
    assert [(s.kind, s.epoch, s.step) for s in specs] == \
        [("kill", 2, 5), ("nan-loss", 1, 0)]
    for bad in ("kill", "kill@2", "kill@a:b", "tofu@1:1", "kill@-1:2"):
        with pytest.raises(ValueError):
            faults.parse_injections([bad])
    # RunConfig.validate rejects bad specs at config time, not mid-run
    with pytest.raises(ValueError, match="inject"):
        _cfg(inject=("explode@1:1",)).validate()
    _cfg(inject=("kill@1:1",)).validate()


def test_rearm_preserves_fired_state():
    faults.arm(["nan-loss@1:2"])
    assert faults.poison_loss(1, 2)
    faults.arm(["nan-loss@1:2"])  # run_benchmark re-arms what the CLI armed
    assert not faults.poison_loss(1, 2)  # each spec fires once per process
    faults.arm(["nan-loss@1:3"])  # a different spec set really re-arms
    assert faults.poison_loss(1, 3)


# ---- commit protocol ------------------------------------------------------

def _save_state():
    import jax.numpy as jnp

    return {"w": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones((3,))}


def test_partial_checkpoint_never_selected(tmp_path, capsys):
    d = str(tmp_path)
    state = _save_state()
    ck.save_checkpoint(d, 1, state, global_step=4, seed=1)
    # crash DURING the orbax write: only a .tmp directory exists
    os.makedirs(tmp_path / "epoch_2.tmp" / "state")
    (tmp_path / "epoch_2.tmp" / "state" / "data").write_bytes(b"torn")
    # crash BETWEEN the orbax write and the COMMIT marker
    os.makedirs(tmp_path / "epoch_3" / "state")
    (tmp_path / "epoch_3" / "state" / "data").write_bytes(b"unmarked")
    info = ck.latest_valid(d)
    assert info is not None and (info.epoch, info.step) == (1, None)
    out = capsys.readouterr().out
    assert "skipping epoch_3" in out and "no COMMIT marker" in out
    # the torn .tmp is not even a checkpoint name; nothing logs it
    assert "epoch_2" not in out


def test_legacy_checkpoint_accepted_and_not_gcd(tmp_path, capsys):
    """A pre-protocol checkpoint (orbax files directly under epoch_N, no
    COMMIT marker) is REAL user data: resume restores it (unverified, with
    a log) and retention GC treats it as a restorable keeper, never a
    crash remnant — under the new protocol a marker-less final-named dir
    cannot be a remnant (saves publish by atomic rename after the marker)."""
    d = str(tmp_path)
    state = _save_state()
    # legacy layout: orbax state directly at <dir>/epoch_1
    ckptr = ck._checkpointer()
    ckptr.save(os.path.join(d, "epoch_1"), state, force=True)
    ckptr.wait_until_finished()
    info = ck.latest_valid(d)
    assert info is not None and (info.epoch, info.step) == (1, None)
    assert "predates the commit protocol" in capsys.readouterr().out
    ep, restored = ck.restore_checkpoint(d, state)
    assert ep == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    # GC with room in the window keeps it; with a full window it ages out
    # like any other checkpoint (legitimate retention, not remnant sweeping)
    ck.save_checkpoint(d, 2, state, keep=2)
    assert set(os.listdir(d)) == {"epoch_1", "epoch_2"}
    ck.save_checkpoint(d, 3, state, keep=2)
    assert set(os.listdir(d)) == {"epoch_2", "epoch_3"}


def test_truncation_and_bitflip_detected(tmp_path, capsys):
    d = str(tmp_path)
    state = _save_state()
    ck.save_checkpoint(d, 1, state, seed=1)
    p2 = ck.save_checkpoint(d, 2, state, seed=1)
    assert ck.latest_valid(d).epoch == 2
    damaged = faults.corrupt_checkpoint(p2)  # truncate + flip a data file
    assert damaged and all("COMMIT" not in p for p in damaged)
    capsys.readouterr()
    info = ck.latest_valid(d)
    assert (info.epoch, info.step) == (1, None)
    out = capsys.readouterr().out
    assert "skipping epoch_2" in out and "mismatch" in out
    # restore_checkpoint(latest) follows the same fallback
    ep, _ = ck.restore_checkpoint(d, state)
    assert ep == 1


def test_torn_metadata_file_falls_back(tmp_path, capsys):
    """A checkpoint whose resume.json or logical.json is torn — while the
    orbax PAYLOAD still verifies — must be skipped by ``latest_valid``
    with a fallback to the previous good checkpoint: the commit manifest
    covers the metadata files, not just the payload (ISSUE 12 satellite;
    a torn logical.json would otherwise send an elastic resume through
    the wrong world shape)."""
    d = str(tmp_path)
    state = _save_state()
    logical = {"schema": 1, "kind": "replicated", "world": 4}
    ck.save_checkpoint(d, 1, state, seed=1, logical=logical)
    for victim in (ck.RESUME_META, ck.LOGICAL_META):
        p2 = ck.save_checkpoint(d, 2, state, seed=1, logical=logical)
        assert ck.latest_valid(d).epoch == 2
        # tear ONLY the metadata file; every orbax payload byte is intact
        meta_path = os.path.join(p2, victim)
        data = open(meta_path, "rb").read()
        with open(meta_path, "wb") as f:
            f.write(data[:max(1, len(data) // 2)])
        capsys.readouterr()
        info = ck.latest_valid(d)
        assert (info.epoch, info.step) == (1, None), victim
        out = capsys.readouterr().out
        assert "skipping epoch_2" in out and "mismatch" in out
        shutil.rmtree(p2)
    # and the surviving checkpoint's logical metadata reads back intact
    assert ck.load_logical(ck.latest_valid(d).path) == logical


def test_step_checkpoint_ordering_and_meta(tmp_path):
    d = str(tmp_path)
    state = _save_state()
    ck.save_checkpoint(d, 1, state, seed=7)
    ck.save_checkpoint(d, 2, state, step=1, global_step=5,
                       logger_state={"epoch_times": [1.0]}, seed=7)
    info = ck.latest_valid(d)
    assert (info.epoch, info.step) == (2, 1) and info.mid_epoch
    assert info.meta["global_step"] == 5
    assert info.meta["logger"]["epoch_times"] == [1.0]
    assert info.meta["seed"] == 7
    # the epoch-END checkpoint outranks any interior step of the same epoch
    ck.save_checkpoint(d, 2, state, seed=7)
    info = ck.latest_valid(d)
    assert (info.epoch, info.step) == (2, None)


def test_retention_gc(tmp_path):
    d = str(tmp_path)
    state = _save_state()
    for ep in range(1, 4):
        ck.save_checkpoint(d, ep, state, keep=2)
    names = {n for n in os.listdir(d)}
    assert names == {"epoch_2", "epoch_3"}
    # stale tmp + uncommitted dirs are swept too
    os.makedirs(tmp_path / "epoch_9.tmp")
    os.makedirs(tmp_path / "epoch_0")
    ck.save_checkpoint(d, 4, state, keep=2)
    assert set(os.listdir(d)) == {"epoch_3", "epoch_4"}
    with pytest.raises(ValueError):
        ck.gc_checkpoints(d, 0)


# ---- in-process fault semantics ------------------------------------------

def test_nan_loss_injection_drives_policy(tmp_path):
    with pytest.raises(TrainingFailure, match="interval ending step 3"):
        run_benchmark(_cfg(epochs=1, inject=("nan-loss@1:2",)),
                      warmup_steps=0)
    assert not faults.armed_specs()  # run_benchmark disarms in its finally
    res = run_benchmark(_cfg(epochs=1, inject=("nan-loss@1:2",),
                             nan_policy="warn"), warmup_steps=0)
    assert "samples_per_sec" in res


def test_prefetch_die_propagates_promptly(tmp_path):
    with pytest.raises(TrainingFailure,
                       match="prefetch producer failed") as ei:
        run_benchmark(_cfg(epochs=1, inject=("prefetch-die@1:1",),
                           prefetch_depth=2), warmup_steps=0)
    # the producer's original exception (and traceback) is CHAINED
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert "prefetch producer killed at epoch 1 step 1" in \
        str(ei.value.__cause__)


def test_dead_producer_without_delivery_fails_fast():
    """A producer that dies without managing an error delivery must not
    leave the consumer blocked on the ring forever."""
    from ddlbench_tpu.data.prefetch import Prefetcher

    class _Data:
        def steps_per_epoch(self, train=True):
            return 50

        def batch(self, epoch, step, train=True):
            return np.zeros(1), np.zeros(1)

    pf = Prefetcher(_Data(), lambda x, y: (x, y), depth=2)
    stream = pf.stream(1)
    next(iter(stream))
    # Simulate the undeliverable death: suppress the delivery path (an
    # instance attribute shadows the method for every FUTURE put), so the
    # producer exits silently on its next put instead of delivering —
    # the consumer must detect the dead thread, not block forever.
    stream._put = lambda item: False
    with pytest.raises(TrainingFailure, match="died without delivering"):
        for _ in stream:
            pass
    stream.close()


def test_slow_host_injection(monkeypatch):
    import time

    from ddlbench_tpu import distributed

    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setenv("DDLB_FAULT_SLOWHOST_S", "0.3")
    faults.arm(["slow-host@0:0"])
    t0 = time.monotonic()
    distributed.initialize()
    assert time.monotonic() - t0 >= 0.3
    # fires once: a second initialize pays nothing
    monkeypatch.setattr(distributed, "_initialized", False)
    t0 = time.monotonic()
    distributed.initialize()
    assert time.monotonic() - t0 < 0.25


def test_distributed_init_retries_with_backoff(monkeypatch, capsys):
    from ddlbench_tpu import distributed

    calls = []

    def flaky(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise ConnectionError(f"peer not up (attempt {len(calls)})")

    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    monkeypatch.setenv("DDLB_COORDINATOR", "127.0.0.1:9999")
    monkeypatch.setenv("DDLB_NUM_PROCESSES", "1")
    monkeypatch.setenv("DDLB_PROCESS_ID", "0")
    monkeypatch.setenv("DDLB_INIT_ATTEMPTS", "3")
    monkeypatch.setenv("DDLB_INIT_BACKOFF_S", "0.01")
    distributed.initialize()
    assert len(calls) == 3  # two failures, then the connect lands
    out = capsys.readouterr().out
    assert "attempt 1/3 failed" in out and "retrying in 0.0s" in out
    monkeypatch.setattr(distributed, "_initialized", False)
    # budget exhausted: the final error RAISES — a configured multi-host
    # run that could not join its world must not carry on single-process
    calls.clear()
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: (_ for _ in ()).throw(ConnectionError("still down")))
    with pytest.raises(ConnectionError, match="still down"):
        distributed.initialize()
    assert not distributed._initialized


# ---- resume semantics through the real loop ------------------------------

def test_resume_with_empty_checkpoint_dir_starts_fresh(tmp_path, capsys):
    """The supervisor passes --resume unconditionally; its very first
    attempt (nothing saved yet) must warn and start fresh, not crash."""
    res = run_benchmark(_cfg(str(tmp_path / "nope"), epochs=1, resume=True),
                        warmup_steps=0)
    assert "samples_per_sec" in res
    assert "no valid checkpoint" in capsys.readouterr().out


def test_mid_epoch_resume_bitwise_single(tmp_path):
    res_u = run_benchmark(_cfg(epochs=2), warmup_steps=0)
    d = str(tmp_path / "ck")
    run_benchmark(_cfg(d, epochs=2, checkpoint_every_steps=2),
                  warmup_steps=0)
    # simulate a crash after epoch 2's interior step checkpoint committed:
    # drop the epoch-2 end-of-epoch checkpoint, resume mid-epoch
    shutil.rmtree(os.path.join(d, "epoch_2"))
    res_r = run_benchmark(_cfg(d, epochs=2, resume=True,
                               checkpoint_every_steps=2), warmup_steps=0)
    np.testing.assert_array_equal(_pvec(res_r["train_state"]),
                                  _pvec(res_u["train_state"]))
    assert res_r["valid_accuracy"] == res_u["valid_accuracy"]
    # the restored metric-logger counters cover the WHOLE trajectory
    assert [h["epoch"] for h in res_r["valid_history"]] == [1, 2]


def test_ckpt_corrupt_injection_falls_back_and_replays(tmp_path, capsys):
    """A corrupted newest checkpoint is detected at resume; the run falls
    back to the previous good one and REPLAYS to the identical state."""
    res_u = run_benchmark(_cfg(epochs=2), warmup_steps=0)
    d = str(tmp_path / "ck")
    run_benchmark(_cfg(d, epochs=2, inject=("ckpt-corrupt@2:0",)),
                  warmup_steps=0)
    capsys.readouterr()
    res_r = run_benchmark(_cfg(d, epochs=2, resume=True), warmup_steps=0)
    out = capsys.readouterr().out
    assert "skipping epoch_2" in out
    assert "resumed from" in out and "epoch 1" in out
    np.testing.assert_array_equal(_pvec(res_r["train_state"]),
                                  _pvec(res_u["train_state"]))


# ---- supervised kill/resume round-trips (subprocess) ---------------------

def _chaos_args(tmp_path, strategy_args, kills=1):
    from ddlbench_tpu.tools import chaosbench

    return chaosbench._parse_args([
        "--kills", str(kills), "--platform", "cpu",
        "-b", "mnist", "-m", "lenet", "--steps-per-epoch", "4",
        "-e", "2", "--batch-size", "8", "--log-interval", "1",
        "--checkpoint-every-steps", "2",
        "--workdir", str(tmp_path / "w"), "--keep-workdir",
        "--skip-verify", *strategy_args])


def _inprocess_baseline_jsonl(tmp_path, **cfg_kw):
    """The uninterrupted reference trajectory, produced in-process (cheaper
    than a third child: the bitwise claim is about values, not processes)."""
    from ddlbench_tpu.train.metrics import MetricLogger

    path = str(tmp_path / "baseline.jsonl")
    cfg = _cfg(epochs=2, **cfg_kw)
    logger = MetricLogger(cfg.epochs, cfg.log_interval, jsonl_path=path)
    try:
        run_benchmark(cfg, logger=logger, warmup_steps=0)
    finally:
        logger.close()
    return path


@pytest.mark.parametrize("strategy_args,cfg_kw", [
    (["-f", "single", "-g", "1"], {}),
    # the gpipe variant's two CLI children each pay a pipeline compile
    # (~38 s total on the 1-core CPU mesh) while exercising the SAME
    # supervision path as [single]; gpipe's own resume state is pinned by
    # test_resume — slow-marked for the tier-1 budget (ROADMAP item 5)
    pytest.param(
        ["-f", "gpipe", "-g", "2", "--",
         "--stages", "2", "--micro-batch-size", "4",
         "--num-microbatches", "2"],
        dict(strategy="gpipe", num_devices=2, num_stages=2,
             micro_batch_size=4, num_microbatches=2, batch_size=None),
        marks=pytest.mark.slow),
])
def test_kill_resume_roundtrip_supervised(tmp_path, strategy_args, cfg_kw):
    """SIGKILL the real train CLI mid-run, auto-resume via the chaosbench
    supervisor, and pin the recovered per-step loss trajectory to the
    uninterrupted run bit-for-bit (single + one pipeline strategy)."""
    from ddlbench_tpu.tools import chaosbench

    args = _chaos_args(tmp_path, strategy_args)
    report = chaosbench.run_chaos(args)
    assert report["completed"], report
    assert report["kills"] == 1 and report["recoveries"] == 1
    assert report["restarts"] >= 1
    # bench.py-style measurement fields are present and sane
    assert report["mttr_s_mean"] > 0
    assert report["checkpoint_overhead_pct"] is not None
    assert report["checkpoint_save_s"] > 0
    assert report["steps_lost_per_kill"][0] is not None
    assert 0 <= report["steps_lost_per_kill"][0] < 2  # K=2 bounds the loss
    # bitwise trajectory vs an uninterrupted in-process reference
    baseline = _inprocess_baseline_jsonl(tmp_path, **cfg_kw)
    match, mismatches = chaosbench.verify_trajectory(
        baseline, str(tmp_path / "w" / "chaos.jsonl"))
    assert match, mismatches


def test_kill_schedule_deterministic():
    from ddlbench_tpu.tools.chaosbench import kill_schedule

    assert kill_schedule(2, 2, 6) == [(1, 4), (2, 2)]
    assert kill_schedule(2, 2, 6) == kill_schedule(2, 2, 6)
    # tiny runs collapse duplicates instead of double-killing one boundary
    pts = kill_schedule(5, 1, 3)
    assert len(set(pts)) == len(pts)
    # kills never schedule at the very first boundary (nothing to recover)
    assert all((e, s) != (1, 0) for e, s in kill_schedule(3, 1, 4))
