"""Planner-quality harness: `--plan auto` predictions vs measured reality.

For each (model, world) pair this tool runs the whole `--plan auto` loop —
profile the model, solve the dp/pp/tp mix + stage split + schedule
(partition/planner.py), rewrite the config onto the winning engines — then
EXECUTES the winner and times real steps, printing one JSON row per point:

    {"arch": "resnet18", "benchmark": "cifar10", "world": 4,
     "pp": 2, "dp": 2, "tp": 1, "schedule": "1f1b", "bounds": [0, 5, 9],
     "predicted_ms": N, "measured_ms": N, "err_frac": N,
     "peak_bytes_per_chip": N, "candidates": N, "feasible": N}

``err_frac = (measured - predicted) / measured`` is the planner's
prediction error — the number that makes planner quality a reported figure
instead of a claim. On the CPU mesh the ABSOLUTE error is expected to be
large with ``--profile-mode flops`` (the cost model prices a TPU v5e); use
``--profile-mode time`` (the default here) so per-layer costs are measured
on the machine that executes them and the error mostly reflects the
schedule/communication model. On-chip rows: not measured.

Usage:
    python -m ddlbench_tpu.tools.planbench \
        [--pairs lenet:mnist,resnet18:cifar10,transformer_s:synthtext] \
        [--worlds 2,4] [--micro-batch 4] [--num-microbatches 8] \
        [--steps 8] [--warmup 2] [--profile-mode time] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys

DEFAULT_PAIRS = "lenet:mnist,resnet18:cifar10,transformer_s:synthtext"


def bench_pair(arch: str, benchmark: str, world: int, args,
               audit_manifests=None) -> dict:
    """One (model, world) row: solve, execute, compare."""
    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.config import RunConfig
    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.parallel.api import make_strategy
    from ddlbench_tpu.partition.planner import (_apply_rewrite,
                                                plan_for_config)
    from ddlbench_tpu.tools.timing import timed_steps

    cfg0 = RunConfig(
        benchmark=benchmark, strategy="gpipe", arch=arch,
        num_devices=world, plan="auto", profile_mode=args.profile_mode,
        micro_batch_size=args.micro_batch,
        num_microbatches=args.num_microbatches,
        compute_dtype=args.dtype, steps_per_epoch=args.steps)
    plan, rewrite, _ = plan_for_config(cfg0)
    w = plan.winner
    cfg = _apply_rewrite(cfg0, rewrite)
    row = {
        "arch": arch, "benchmark": benchmark, "world": world,
        "pp": w.pp, "dp": w.dp, "tp": w.tp, "schedule": w.schedule,
        "bounds": list(w.bounds) if w.bounds else None,
        "strategy": cfg.strategy,
        "predicted_ms": round(w.step_time_ms, 4),
        "peak_bytes_per_chip": round(w.peak_bytes_per_chip, 1),
        "candidates": len(plan.candidates),
        "feasible": sum(1 for c in plan.candidates if c.feasible),
    }
    strategy = make_strategy(cfg)
    data = make_synthetic(cfg.dataset(), cfg.global_batch(),
                          steps_per_epoch=args.steps)
    ts = strategy.init(jax.random.key(cfg.seed))
    lr = jnp.float32(cfg.resolved_lr())

    def run_step(x, y):
        nonlocal ts
        ts, m = strategy.train_step(ts, *strategy.shard_batch(x, y), lr)
        return m

    dt = timed_steps(run_step, data.batch, args.steps, args.warmup)
    measured = 1000.0 * dt / args.steps
    row["measured_ms"] = round(measured, 4)
    row["err_frac"] = round((measured - w.step_time_ms) / measured, 4) \
        if measured > 0 else None
    if audit_manifests is not None:
        # compiled-program audit for the winner: manifest + comm_stats
        # tie-out, plus the planner's per-stage HBM-model signed error vs
        # memory_analysis() (recorded into partition.json when the run
        # has a persisted plan — here it rides the row)
        from ddlbench_tpu.telemetry.audit import (planner_stage_hbm_audit,
                                                  lower_manifest,
                                                  reconcile_train,
                                                  record_hbm_audit)

        x0, y0 = data.batch(0, 0)
        # some engines wrap their jit in a telemetry-span function; lower
        # the underlying executable either way (bench.py idiom)
        jit_step = getattr(strategy, "_jit_train_step", None) \
            or strategy.train_step
        man = lower_manifest(
            jit_step, (ts, *strategy.shard_batch(x0, y0), lr),
            f"plan/{arch}:{benchmark}@{world}",
            mesh=getattr(strategy, "mesh", None))
        man["reconcile"] = reconcile_train(strategy, man)
        hbm = planner_stage_hbm_audit(w.as_record(), man, world)
        man["hbm_audit"] = hbm
        audit_manifests.append(man)
        if hbm is not None:
            row["hbm_err_frac_per_stage"] = [
                round(s["err_frac"], 4) if s["err_frac"] is not None
                else None for s in hbm["stages"]]
            record_hbm_audit(cfg, hbm)
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pairs", default=DEFAULT_PAIRS,
                   help="comma list of arch:benchmark pairs to sweep")
    p.add_argument("--worlds", default="2,4",
                   help="comma list of chip counts per pair")
    p.add_argument("--micro-batch", type=int, default=4,
                   help="pre-plan micro-batch (the gpipe batch grammar the "
                        "plan preserves: global = micro x microbatches)")
    p.add_argument("--num-microbatches", type=int, default=8)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--profile-mode", default="time",
                   choices=("flops", "time"),
                   help="time (default) measures per-layer costs on THIS "
                        "machine, so err_frac reflects the schedule model "
                        "rather than the TPU constants; flops is the "
                        "deterministic device-free mode")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--audit", default=None, metavar="PATH",
                   help="also emit the winner's compiled-program audit "
                        "manifest per point (telemetry/audit.py) — "
                        "includes the planner's per-stage HBM error vs "
                        "memory_analysis() — into one ledger JSON")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)

    import jax

    from ddlbench_tpu.distributed import record_provenance

    prov = record_provenance(args.platform, "planbench")
    print(json.dumps({"provenance": {**prov,
                                     "platform_arg": args.platform}}),
          flush=True)
    avail = len(jax.devices())
    rows = []
    audit_manifests = [] if args.audit else None
    for pair in args.pairs.split(","):
        arch, benchmark = pair.strip().split(":")
        for world in (int(v) for v in args.worlds.split(",")):
            if world > avail:
                print(json.dumps({"arch": arch, "world": world, "error":
                                  f"{world} devices exceed the {avail} "
                                  f"attached"}), flush=True)
                continue
            try:
                row = bench_pair(arch, benchmark, world, args,
                                 audit_manifests)
            except ValueError as e:  # e.g. branchy arch, no feasible mix
                row = {"arch": arch, "benchmark": benchmark,
                       "world": world, "error": str(e)}
            row = {**row, "schema_version": prov["schema_version"],
                   "jax_backend": prov["jax_backend"],
                   "cpu_fallback": prov["cpu_fallback"]}
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.audit:
        from ddlbench_tpu.telemetry.audit import write_manifests

        write_manifests(args.audit, audit_manifests,
                        header={**prov, "tool": "planbench"})
        print(json.dumps({"audit": args.audit,
                          "programs": len(audit_manifests)}), flush=True)
    good = [r for r in rows if "err_frac" in r and r["err_frac"] is not None]
    if good:
        errs = sorted(abs(r["err_frac"]) for r in good)
        print(json.dumps({
            "summary": {
                "points": len(good),
                "abs_err_frac_p50": round(errs[len(errs) // 2], 4),
                "abs_err_frac_max": round(errs[-1], 4),
            }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
