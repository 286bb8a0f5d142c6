#!/usr/bin/env python
"""Chaos benchmark: kill/preempt/restart supervision + recovery measurement.

The reference suite cannot answer "what happens when a worker dies?" — its
only failure handling is a 2-hour process-group timeout and a pkill script
(SURVEY.md §5.3). This tool makes recovery a *benchmark dimension*: it runs
the train CLI as a child process under a supervisor that

1. schedules ``--kills N`` deterministic SIGKILL injections and
   ``--preempts N`` graceful SIGTERM preemptions (``--inject kill@E:S`` /
   ``preempt@E:S``, one per attempt, spread evenly over the run's global
   steps), plus explicit ``--reshape shrink@E:S:M`` / ``grow@E:S:M``
   world RESHAPES: the child is gracefully preempted at (E, S) (``--inject
   shrink@E:S`` — a checkpoint carrying the logical world-shape metadata
   commits) and every later attempt runs at ``--devices M`` with the
   per-device batch rescaled so the GLOBAL batch is preserved and
   ``--elastic-resume`` reshards the ZeRO-1 flat state (train/reshard.py),
2. relaunches the child with ``--resume`` after every death, with
   exponential backoff and a bounded restart budget (a crash-looping run
   must not spin forever; an exhausted budget exits nonzero),
3. verifies the interrupted trajectory against an uninterrupted baseline
   run **bit-for-bit** (per-step train losses via ``--log-interval 1``
   JSONL records and per-epoch validation loss/accuracy — synthetic data is
   (epoch, step)-addressed, so any divergence means state was lost), and
4. emits a bench.py-style JSON line: recoveries, MTTR (child death -> the
   resumed child's "resumed from" line) split between SIGKILL deaths,
   graceful preemptions (exit code guard/preempt.py PREEMPT_EXIT_CODE with
   a committed checkpoint — counted separately from hard crashes), and
   world reshapes (``mttr_reshape_s`` — death at world N to resumed at
   world M, the reshape recovery time), steps lost per kill, checkpoint
   write overhead (telemetry spans from each attempt's ``--trace``),
   post-reshape trajectory divergence (max |loss delta| vs the baseline
   over the records at/after the first reshape — 0.0 for f32 elastic
   runs), and the stability-guard event counts scraped from the
   children's ``guard:`` lines (anomalies detected / steps skipped /
   rewinds / loss-scale backoffs).

Elastic example (dp ZeRO-1, shrink 4 -> 2 mid-run)::

    python -m ddlbench_tpu.tools.chaosbench --kills 0 \
        --reshape shrink@2:1:2 --platform cpu -b mnist -m lenet \
        -f dp -g 4 --batch-size 2 --steps-per-epoch 4 -e 2 \
        --checkpoint-every-steps 2 -- --dp-shard-update --elastic-slices 4

   The baseline runs uninterrupted at world 4; trajectory_match pins the
   reshaped run's per-step losses to it bitwise (--elastic-slices is what
   makes the f32 reduction order world-invariant — parallel/dp.py).

Usage (CPU smoke)::

    python -m ddlbench_tpu.tools.chaosbench --kills 2 --preempts 1 \
        --platform cpu -b mnist -m lenet --steps-per-epoch 6 -e 2 \
        --batch-size 8 --checkpoint-every-steps 2 --json chaos.json

Any flags after ``--`` are passed through to the train CLI verbatim (e.g.
``-- --anomaly-policy skip --inject nan-grad@1:3`` for an anomaly mix).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from ddlbench_tpu.guard.preempt import PREEMPT_EXIT_CODE


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="chaosbench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--kills", type=int, default=1,
                   help="number of SIGKILL injections to schedule")
    p.add_argument("--preempts", type=int, default=0,
                   help="number of graceful SIGTERM preemptions to "
                        "schedule (interleaved with the kills; the child "
                        "commits a checkpoint and exits with the distinct "
                        "graceful code)")
    p.add_argument("--reshape", action="append", default=[],
                   metavar="KIND@E:S:M",
                   help="elastic world reshape (repeatable): shrink@E:S:M "
                        "or grow@E:S:M gracefully preempts the child at "
                        "epoch E step S and restarts it (and every later "
                        "attempt) at --devices M with --elastic-resume, "
                        "per-device batch rescaled so the global batch is "
                        "preserved (requires -f dp; pass --dp-shard-update "
                        "--elastic-slices E after -- for the bitwise "
                        "trajectory pin)")
    p.add_argument("--restart-budget", type=int, default=None,
                   help="max child relaunches (default: kills + preempts "
                        "+ reshapes + 3)")
    p.add_argument("--backoff-base-s", type=float, default=0.5,
                   help="restart backoff base (doubles per consecutive "
                        "restart, capped by --backoff-max-s)")
    p.add_argument("--backoff-max-s", type=float, default=8.0)
    p.add_argument("-b", "--benchmark", default="mnist")
    p.add_argument("-m", "--model", default="lenet")
    p.add_argument("-f", "--framework", default="single")
    p.add_argument("-g", "--devices", type=int, default=1)
    p.add_argument("-e", "--epochs", type=int, default=2)
    p.add_argument("--steps-per-epoch", type=int, default=6,
                   help="fixed steps/epoch (required: the kill schedule and "
                        "steps-lost accounting are computed from it)")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--log-interval", type=int, default=1,
                   help="1 = per-step loss records (the bitwise trajectory "
                        "check compares every overlapping step)")
    p.add_argument("--dtype", default="float32",
                   help="float32 default: the bitwise check is the point")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--checkpoint-every-steps", type=int, default=2)
    p.add_argument("--keep-checkpoints", type=int, default=None)
    p.add_argument("--platform", default=None,
                   help="forwarded to the train CLI (e.g. cpu)")
    p.add_argument("--workdir", default=None,
                   help="scratch dir for checkpoints/logs (default: a "
                        "fresh chaosbench_runs/<pid> dir, removed unless "
                        "--keep-workdir)")
    p.add_argument("--keep-workdir", action="store_true")
    p.add_argument("--json", default=None, help="also write the report here")
    p.add_argument("--skip-verify", action="store_true",
                   help="skip the uninterrupted baseline run (no bitwise "
                        "trajectory check, no overhead denominator A/B)")
    p.add_argument("train_args", nargs="*", default=[],
                   help="extra flags after -- forwarded to the train CLI")
    return p.parse_args(argv)


def kill_schedule(kills: int, epochs: int, steps_per_epoch: int
                  ) -> List[Tuple[int, int]]:
    """Evenly spaced (epoch, step) kill points over the run's global steps.

    Deterministic by construction (no RNG): chaos runs are reproducible
    benchmark configurations, not fuzzing.
    """
    total = epochs * steps_per_epoch
    points = []
    for k in range(1, kills + 1):
        g = max(1, min(total - 1, round(k * total / (kills + 1))))
        points.append((g // steps_per_epoch + 1, g % steps_per_epoch))
    # collapse duplicates from tiny runs while preserving order
    seen, out = set(), []
    for pt in points:
        if pt not in seen:
            seen.add(pt)
            out.append(pt)
    return out


def event_schedule(kills: int, preempts: int, epochs: int,
                   steps_per_epoch: int) -> List[Tuple[str, int, int]]:
    """Deterministic (kind, epoch, step) schedule: kills and graceful
    preemptions interleaved over the evenly-spaced disruption points."""
    points = kill_schedule(kills + preempts, epochs, steps_per_epoch)
    events, k_left, p_left, want_kill = [], kills, preempts, True
    for e, s in points:
        pick_kill = (want_kill and k_left > 0) or p_left <= 0
        if pick_kill:
            events.append(("kill", e, s))
            k_left -= 1
        else:
            events.append(("preempt", e, s))
            p_left -= 1
        want_kill = not want_kill
    return events


def _global_step(epoch: int, step: int, steps_per_epoch: int) -> int:
    return (epoch - 1) * steps_per_epoch + step


def parse_reshapes(specs: List[str]) -> List[Tuple[str, int, int, int]]:
    """``shrink@E:S:M`` / ``grow@E:S:M`` -> (kind, epoch, step, devices)."""
    out = []
    for raw in specs:
        try:
            kind, rest = raw.split("@", 1)
            e_s, s_s, m_s = rest.split(":")
            e, s, m = int(e_s), int(s_s), int(m_s)
        except ValueError:
            raise ValueError(
                f"bad --reshape spec {raw!r}: expected shrink@E:S:M or "
                f"grow@E:S:M (e.g. shrink@2:1:2)")
        if kind not in ("shrink", "grow"):
            raise ValueError(
                f"--reshape kind must be shrink or grow, got {kind!r}")
        if e < 1 or s < 0 or m < 1:
            raise ValueError(f"--reshape {raw!r}: E >= 1, S >= 0, M >= 1")
        out.append((kind, e, s, m))
    return out


def merge_schedule(events: List[Tuple[str, int, int]],
                   reshapes: List[Tuple[str, int, int, int]],
                   steps_per_epoch: int) -> List[Tuple]:
    """One pending list, ordered by global step (kills/preempts keep their
    relative order; a reshape at the same boundary as a kill would race
    the SIGKILL against the SIGTERM, so duplicates are rejected)."""
    merged = list(events) + list(reshapes)
    merged.sort(key=lambda t: _global_step(t[1], t[2], steps_per_epoch))
    seen = set()
    for t in merged:
        pt = (t[1], t[2])
        if pt in seen:
            raise ValueError(
                f"disruption schedule collision at epoch {t[1]} step "
                f"{t[2]}: move the --reshape point off the kill/preempt "
                f"grid")
        seen.add(pt)
    return merged


# Stability-guard event lines (train/loop.py + guard/policy.py print these
# with stable prefixes precisely so the supervisor can aggregate them).
_GUARD_COUNTED = {
    "steps_skipped": re.compile(r"guard: dropped (\d+) non-finite"),
    "loss_scale_backoffs": re.compile(r"guard: loss-scale backoff x(\d+)"),
    "warned_steps": re.compile(
        r"guard: WARNING non-finite gradients \((\d+) step"),
}
_GUARD_FLAGGED = {
    "spikes": re.compile(r"guard: grad-norm spike"),
    "rewinds": re.compile(r"guard: rewinding to the last valid checkpoint"),
}


def guard_events(lines: List[str]) -> Dict[str, int]:
    """Aggregate guard event counts from one attempt's output lines."""
    out = {k: 0 for k in (*_GUARD_COUNTED, *_GUARD_FLAGGED)}
    for line in lines:
        for key, pat in _GUARD_COUNTED.items():
            m = pat.search(line)
            if m:
                out[key] += int(m.group(1))
        for key, pat in _GUARD_FLAGGED.items():
            if pat.search(line):
                out[key] += 1
    out["anomalies_detected"] = sum(
        out[k] for k in ("steps_skipped", "loss_scale_backoffs", "spikes",
                         "rewinds", "warned_steps"))
    return out


def _train_argv(args, ckpt_dir: Optional[str], jsonl: str,
                trace: Optional[str], inject: List[str],
                resume: bool, devices: Optional[int] = None,
                batch_size: Optional[int] = None,
                elastic: bool = False) -> List[str]:
    argv = [sys.executable, "-m", "ddlbench_tpu.cli",
            "-b", args.benchmark, "-m", args.model, "-f", args.framework,
            "-g", str(devices if devices is not None else args.devices),
            "-e", str(args.epochs),
            "--steps-per-epoch", str(args.steps_per_epoch),
            "--batch-size",
            str(batch_size if batch_size is not None else args.batch_size),
            "--log-interval", str(args.log_interval),
            "--dtype", args.dtype, "--seed", str(args.seed),
            "--jsonl", jsonl]
    if elastic:
        argv += ["--elastic-resume"]
    if args.platform:
        argv += ["--platform", args.platform]
    if ckpt_dir:
        argv += ["--checkpoint-dir", ckpt_dir,
                 "--checkpoint-every-steps", str(args.checkpoint_every_steps)]
        if args.keep_checkpoints:
            argv += ["--keep-checkpoints", str(args.keep_checkpoints)]
    if resume:
        argv += ["--resume"]
    if trace:
        argv += ["--trace", trace]
    for spec in inject:
        argv += ["--inject", spec]
    argv += list(args.train_args)
    return argv


class AttemptResult:
    def __init__(self):
        self.rc: Optional[int] = None
        self.wall_s = 0.0
        self.resumed_line: Optional[str] = None
        self.resumed_at: Optional[float] = None  # monotonic
        self.died_at: Optional[float] = None
        self.lines: List[str] = []


def _run_attempt(argv: List[str], log_path: str) -> AttemptResult:
    """Launch one child; stream stdout (timestamping the recovery line)."""
    res = AttemptResult()
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        assert proc.stdout is not None
        for line in proc.stdout:
            log.write(line)
            res.lines.append(line.rstrip("\n"))
            if line.startswith("resumed from") and res.resumed_at is None:
                res.resumed_at = time.monotonic()
                res.resumed_line = line.strip()
        res.rc = proc.wait()
    res.died_at = time.monotonic()
    res.wall_s = res.died_at - t0
    return res


def _result_device(lines: List[str]) -> Optional[Dict[str, Any]]:
    """The ``device`` record ({platform, kind, count}) of a child's
    ``result:`` line — what jax reported in the process that ran."""
    for line in reversed(lines):
        if line.startswith("result: "):
            return json.loads(line[len("result: "):]).get("device")
    return None


def _parse_resumed_global(line: Optional[str], steps_per_epoch: int
                          ) -> Optional[int]:
    """'resumed from <dir> epoch E[ step S (mid-epoch)]' -> resumed global step."""
    if not line:
        return None
    toks = line.split()
    try:
        ep = int(toks[toks.index("epoch") + 1])
        if "step" in toks:
            return _global_step(ep, int(toks[toks.index("step") + 1]) + 1,
                                steps_per_epoch)
        return ep * steps_per_epoch
    except (ValueError, IndexError):
        return None


def _span_seconds(trace_path: str, names: Tuple[str, ...]) -> Dict[str, float]:
    """Total duration (s) of the named complete-spans in a Chrome trace."""
    totals = {n: 0.0 for n in names}
    try:
        with open(trace_path) as f:
            events = json.load(f).get("traceEvents", [])
    except (OSError, ValueError):
        return totals
    for ev in events:
        if ev.get("ph") == "X" and ev.get("name") in totals:
            totals[ev["name"]] += ev.get("dur", 0) / 1e6
    return totals


def _jsonl_trajectory(path: str) -> Tuple[Dict, Dict]:
    """(train, valid) maps from a metrics JSONL; last write wins, so a
    chaos run's re-executed steps are compared at their FINAL values."""
    train: Dict[Tuple[int, float], float] = {}
    valid: Dict[int, Tuple[float, float]] = {}
    try:
        with open(path) as f:
            for raw in f:
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue
                if rec.get("kind") == "train_interval":
                    train[(rec["epoch"], rec["progress_pct"])] = rec["loss"]
                elif rec.get("kind") == "valid":
                    valid[rec["epoch"]] = (rec["loss"], rec["accuracy"])
    except OSError:
        pass
    return train, valid


def verify_trajectory(baseline_jsonl: str, chaos_jsonl: str
                      ) -> Tuple[bool, List[str]]:
    """Bit-for-bit comparison (exact float equality — no tolerance: the
    commit protocol's claim is bitwise resume, not approximate resume)."""
    return _verify_maps(_jsonl_trajectory(baseline_jsonl),
                        _jsonl_trajectory(chaos_jsonl))


def _verify_maps(baseline: Tuple[Dict, Dict], chaos: Tuple[Dict, Dict]
                 ) -> Tuple[bool, List[str]]:
    b_train, b_valid = baseline
    c_train, c_valid = chaos
    mismatches = []
    for key, loss in sorted(b_train.items()):
        if key not in c_train:
            mismatches.append(f"missing train record {key}")
        elif c_train[key] != loss:
            mismatches.append(
                f"train loss @ {key}: {c_train[key]!r} != {loss!r}")
    for ep, lv in sorted(b_valid.items()):
        if ep not in c_valid:
            mismatches.append(f"missing valid record epoch {ep}")
        elif c_valid[ep] != lv:
            mismatches.append(
                f"valid @ epoch {ep}: {c_valid[ep]!r} != {lv!r}")
    return not mismatches, mismatches


def run_chaos(args) -> Dict[str, Any]:
    # absolute: orbax rejects relative checkpoint paths at RESTORE time,
    # which otherwise burns the whole restart budget on the default workdir
    workdir = os.path.abspath(
        args.workdir or os.path.join("chaosbench_runs", str(os.getpid())))
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    reshapes = parse_reshapes(getattr(args, "reshape", []))
    if reshapes and args.framework != "dp":
        raise ValueError(
            "--reshape changes the dp world size; run it with -f dp "
            "(--dp-shard-update after -- for the ZeRO-1 reshard path)")
    # the GLOBAL batch is the invariant across a reshape: the data stream
    # is (epoch, step)-addressed at that batch, so the per-device batch
    # rescales with each new world
    global_batch = args.batch_size * args.devices
    elastic_slices = None
    if "--elastic-slices" in args.train_args:
        # the child's elastic gates must hold at EVERY scheduled world, or
        # each post-reshape relaunch dies in RunConfig.validate and the
        # supervisor burns the whole restart budget on a usage error
        elastic_slices = int(args.train_args[
            args.train_args.index("--elastic-slices") + 1])
    for kind, e, s, m in reshapes:
        if global_batch % m:
            raise ValueError(
                f"--reshape {kind}@{e}:{s}:{m}: global batch "
                f"{global_batch} must divide by the new device count {m}")
        if elastic_slices is not None and \
                (m & (m - 1) or elastic_slices % m):
            raise ValueError(
                f"--reshape {kind}@{e}:{s}:{m}: the child's "
                f"--elastic-slices {elastic_slices} needs a power-of-two "
                f"device count dividing it; {m} fails that gate")
    schedule = merge_schedule(
        event_schedule(args.kills, getattr(args, "preempts", 0),
                       args.epochs, args.steps_per_epoch),
        reshapes, args.steps_per_epoch)
    budget = (args.restart_budget if args.restart_budget is not None
              else len(schedule) + 3)

    # The supervisor never touches a jax backend: a chip belongs to one
    # process at a time, and the children it spawns are the ones that need
    # it. The device the run executed on is read from the completing
    # child's ``result:`` line instead (report["device"], below).
    from ddlbench_tpu.distributed import RECORD_SCHEMA_VERSION

    report: Dict[str, Any] = {
        "schema_version": RECORD_SCHEMA_VERSION,
        "metric": "chaosbench_recovery",
        "benchmark": args.benchmark, "arch": args.model,
        "framework": args.framework,
        "epochs": args.epochs, "steps_per_epoch": args.steps_per_epoch,
        "checkpoint_every_steps": args.checkpoint_every_steps,
        "kills_scheduled": [f"{t[0]}@{t[1]}:{t[2]}" for t in schedule
                            if t[0] == "kill"],
        "preempts_scheduled": [f"{t[0]}@{t[1]}:{t[2]}" for t in schedule
                               if t[0] == "preempt"],
        "reshapes_scheduled": [f"{k}@{e}:{s}:{m}"
                               for k, e, s, m in reshapes],
        "restart_budget": budget,
    }

    # -- baseline: uninterrupted, checkpoint-free (overhead denominator +
    # -- the bitwise trajectory reference) ---------------------------------
    baseline_jsonl = os.path.join(workdir, "baseline.jsonl")
    if not args.skip_verify:
        print(f"chaosbench: baseline run (uninterrupted, no checkpoints)",
              flush=True)
        base = _run_attempt(
            _train_argv(args, None, baseline_jsonl, None, [], resume=False),
            os.path.join(workdir, "baseline.log"))
        if base.rc != 0:
            report["error"] = f"baseline run failed (rc={base.rc})"
            print(json.dumps(report), flush=True)
            return report
        report["baseline_wall_s"] = round(base.wall_s, 3)

    # -- chaos run: supervised kill/preempt/restart loop -------------------
    chaos_jsonl = os.path.join(workdir, "chaos.jsonl")
    pending = list(schedule)
    attempts: List[AttemptResult] = []
    mttr_s: List[float] = []  # hard-kill MTTRs (legacy field name)
    mttr_preempt_s: List[float] = []  # graceful-preemption MTTRs
    mttr_reshape_s: List[float] = []  # world-reshape recovery times
    steps_lost: List[int] = []
    recoveries = restarts = 0
    kills_fired = preempts_fired = reshapes_fired = graceful_exits = 0
    consecutive_failures = 0
    save_s = restore_s = 0.0
    last_death: Optional[float] = None
    death_kind: Optional[str] = None
    killed_at: Optional[Tuple[int, int]] = None
    guard_totals: Dict[str, int] = {}
    completed = False
    cur_devices, cur_batch = args.devices, args.batch_size
    elastic = bool(reshapes)  # harmless on non-reshaped attempts

    while True:
        attempt_no = len(attempts)
        inject = [f"{pt[0]}@{pt[1]}:{pt[2]}" for pt in pending[:1]]
        trace = os.path.join(workdir, f"attempt_{attempt_no}.trace.json")
        argv = _train_argv(args, ckpt_dir, chaos_jsonl, trace, inject,
                           resume=True, devices=cur_devices,
                           batch_size=cur_batch, elastic=elastic)
        print(f"chaosbench: attempt {attempt_no} (devices {cur_devices})"
              + (f" (pending {inject[0]})" if inject
                 else " (no more disruptions)"),
              flush=True)
        res = _run_attempt(argv,
                           os.path.join(workdir, f"attempt_{attempt_no}.log"))
        attempts.append(res)
        spans = _span_seconds(trace, ("checkpoint_save",
                                      "checkpoint_restore"))
        save_s += spans["checkpoint_save"]
        restore_s += spans["checkpoint_restore"]
        for key, v in guard_events(res.lines).items():
            guard_totals[key] = guard_totals.get(key, 0) + v

        if res.resumed_at is not None and last_death is not None:
            mttr = res.resumed_at - last_death
            (mttr_preempt_s if death_kind == "preempt"
             else mttr_reshape_s if death_kind == "reshape"
             else mttr_s).append(mttr)
            recoveries += 1
            resumed_g = _parse_resumed_global(res.resumed_line,
                                              args.steps_per_epoch)
            if resumed_g is not None and killed_at is not None and \
                    steps_lost and steps_lost[-1] is None:
                steps_lost[-1] = _global_step(*killed_at,
                                              args.steps_per_epoch) - resumed_g
            last_death, death_kind = None, None

        if res.rc == 0:
            completed = True
            break
        if res.rc == -signal.SIGKILL and pending and \
                pending[0][0] == "kill" and \
                any(l.startswith("fault-inject: kill") for l in res.lines):
            killed_at = pending.pop(0)[1:]
            kills_fired += 1
            steps_lost.append(None)  # filled in by the next resume line
            last_death, death_kind = res.died_at, "kill"
            consecutive_failures = 0
        elif res.rc == PREEMPT_EXIT_CODE and \
                any(l.startswith("preempt: checkpoint committed")
                    for l in res.lines):
            # graceful exit: the child committed its preemption checkpoint
            # and exited with the distinct code — an EXPECTED eviction, not
            # a crash (counted, timed, and budgeted separately)
            # pop the scheduled spec only when the INJECTED preemption
            # actually fired (kill-branch parity): a stray external SIGTERM
            # also exits 75 with a committed line, but must not consume the
            # scheduled disruption point
            if pending and pending[0][0] in ("shrink", "grow") and \
                    any(l.startswith(f"fault-inject: {pending[0][0]}")
                        for l in res.lines):
                # world RESHAPE: the child committed its logical-metadata
                # checkpoint; every attempt from here runs at the new
                # world, per-device batch rescaled so the global batch —
                # the (epoch, step) data-addressing invariant — holds
                kind, e, s, m = pending.pop(0)
                reshapes_fired += 1
                cur_devices, cur_batch = m, global_batch // m
                print(f"chaosbench: reshape {kind}@{e}:{s} -> devices "
                      f"{m} (batch {cur_batch}/device, elastic resume)",
                      flush=True)
                last_death, death_kind = res.died_at, "reshape"
            else:
                if pending and pending[0][0] == "preempt" and \
                        any(l.startswith("fault-inject: preempt")
                            for l in res.lines):
                    pending.pop(0)
                    preempts_fired += 1
                last_death, death_kind = res.died_at, "preempt"
            graceful_exits += 1
            consecutive_failures = 0
        else:
            consecutive_failures += 1
            print(f"chaosbench: unexpected child exit rc={res.rc}",
                  flush=True)
        restarts += 1
        if restarts > budget:
            report["error"] = (f"restart budget ({budget}) exhausted after "
                               f"{len(attempts)} attempts")
            break
        delay = min(args.backoff_max_s,
                    args.backoff_base_s * 2 ** consecutive_failures)
        print(f"chaosbench: restarting in {delay:.2f}s", flush=True)
        time.sleep(delay)

    chaos_wall = sum(a.wall_s for a in attempts)
    report.update({
        "completed": completed,
        "device": _result_device(attempts[-1].lines) if completed else None,
        "attempts": len(attempts),
        "restarts": restarts,
        # fired counts, not args.kills: tiny runs collapse duplicate
        # disruption points, and the report must agree with mttr/steps_lost
        "kills": kills_fired,
        "preempts": preempts_fired,
        "reshapes": reshapes_fired,
        "final_devices": cur_devices,
        "graceful_exits": graceful_exits,
        "recoveries": recoveries,
        "mttr_s": [round(t, 3) for t in mttr_s],
        "mttr_s_mean": round(sum(mttr_s) / len(mttr_s), 3) if mttr_s else None,
        "mttr_preempt_s": [round(t, 3) for t in mttr_preempt_s],
        "mttr_preempt_s_mean": (round(sum(mttr_preempt_s)
                                      / len(mttr_preempt_s), 3)
                                if mttr_preempt_s else None),
        "mttr_reshape_s": [round(t, 3) for t in mttr_reshape_s],
        "mttr_reshape_s_mean": (round(sum(mttr_reshape_s)
                                      / len(mttr_reshape_s), 3)
                                if mttr_reshape_s else None),
        "steps_lost_per_kill": steps_lost,
        "guard": guard_totals,
        "chaos_wall_s": round(chaos_wall, 3),
        "checkpoint_save_s": round(save_s, 3),
        "checkpoint_restore_s": round(restore_s, 3),
        "checkpoint_overhead_pct": (
            round(100.0 * save_s / chaos_wall, 2) if chaos_wall else None),
    })

    if not args.skip_verify and completed:
        b_train, b_valid = _jsonl_trajectory(baseline_jsonl)
        c_train, c_valid = _jsonl_trajectory(chaos_jsonl)
        match, mismatches = _verify_maps((b_train, b_valid),
                                         (c_train, c_valid))
        report["trajectory_match"] = match
        if not match:
            report["trajectory_mismatches"] = mismatches[:20]
        if reshapes:
            # post-reshape trajectory divergence: max |loss delta| vs the
            # baseline over records at/after the FIRST reshape point —
            # 0.0 for f32 elastic runs (the headline reshape number next
            # to mttr_reshape_s; nonzero quantifies drift when a run
            # reshapes without --elastic-slices)
            spe = args.steps_per_epoch
            e0, s0 = reshapes[0][1], reshapes[0][2]
            g0 = _global_step(e0, s0, spe)
            div = 0.0
            for (ep, prog), loss in b_train.items():
                g = (ep - 1) * spe + round(prog * spe / 100.0) - 1
                if g >= g0 and (ep, prog) in c_train:
                    div = max(div, abs(c_train[(ep, prog)] - loss))
            for ep, (l, _a) in b_valid.items():
                if ep >= e0 and ep in c_valid:
                    div = max(div, abs(c_valid[ep][0] - l))
            report["post_reshape_divergence"] = div

    print(json.dumps(report), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
    if not args.keep_workdir and args.workdir is None:
        shutil.rmtree(workdir, ignore_errors=True)
    return report


def main(argv=None) -> int:
    args = _parse_args(argv)
    try:
        report = run_chaos(args)
    except ValueError as e:
        # schedule-construction errors (--reshape grammar, batch/world
        # divisibility, kill-point collisions) are usage errors, not bugs
        print(f"chaosbench: {e}", file=sys.stderr, flush=True)
        return 2
    # nonzero whenever no run COMPLETED (e.g. the restart budget was
    # exhausted on a crash-looping child), an error was recorded, or the
    # recovered trajectory diverged — supervisor callers key off this
    ok = bool(report.get("completed")) and "error" not in report and \
        report.get("trajectory_match", True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
