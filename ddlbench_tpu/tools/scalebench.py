"""Scaling-curve harness: strategy throughput vs chip count.

The north-star measurement (BASELINE.md): ResNet-50/ImageNet images/sec/chip
and DP-vs-pipeline scaling efficiency from 1 to N chips. This tool sweeps
strategies over growing device counts on whatever mesh exists — the real TPU
slice when one is attached, or the virtual CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=N) for harness validation —
and prints one JSON line per (strategy, n_devices) point:

    {"strategy": "dp", "devices": 4, "samples_per_sec": N,
     "per_chip": N, "efficiency": N}

``efficiency`` is per-chip throughput relative to the 1-chip single-strategy
anchor (the reference's scaling-efficiency definition; weak scaling — the
global batch grows with the chip count for dp/fsdp, stays per-pipeline for
gpipe/pipedream).

Usage:
    python -m ddlbench_tpu.tools.scalebench [-b imagenet] [-m resnet50]
        [--devices 1,2,4,8] [--strategies dp,gpipe,pipedream]
        [--steps 10] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys


def opt_state_bytes_per_chip(ts) -> int:
    """ACTUAL optimizer-state bytes resident on one chip: the summed
    addressable-shard bytes of every ``ts.opt`` leaf on device 0 —
    replicated leaves count in full, ZeRO-1-sharded leaves count their
    1/world slice, so the hybrid PP x ZeRO-1 memory win is a countable
    JSON field instead of a claim."""
    import jax

    opt = getattr(ts, "opt", None)
    if opt is None:
        return 0
    d0 = jax.devices()[0]
    total = 0
    for leaf in jax.tree.leaves(opt):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += sum(sh.data.nbytes for sh in leaf.addressable_shards
                     if sh.device == d0)
    return int(total)


def _run_point(cfg, steps: int, warmup: int, repeats: int = 1):
    import statistics

    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.parallel.api import make_strategy
    from ddlbench_tpu.tools.timing import timed_steps

    strategy = make_strategy(cfg)
    data = make_synthetic(cfg.dataset(), cfg.global_batch(),
                          steps_per_epoch=steps)
    ts = strategy.init(jax.random.key(cfg.seed))
    opt_bytes = opt_state_bytes_per_chip(ts)
    lr = jnp.float32(cfg.resolved_lr())

    def run_step(x, y):
        nonlocal ts
        ts, m = strategy.train_step(ts, *strategy.shard_batch(x, y), lr)
        return m

    # Median of ``repeats`` timed loops: a scaling CURVE amplifies
    # per-point noise into fake efficiency cliffs. Warmup (compile) is paid
    # once; later loops reuse the jitted step.
    dts = [timed_steps(run_step, data.batch, steps, warmup)
           for _ in range(max(1, repeats))]
    return steps * cfg.global_batch() / statistics.median(dts), opt_bytes


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-b", "--benchmark", default="imagenet")
    p.add_argument("-m", "--model", default="resnet50")
    p.add_argument("--devices", default=None,
                   help="comma list of chip counts (default: 1,2,4,... up to "
                        "the attached device count)")
    p.add_argument("--strategies", default="dp,gpipe,pipedream")
    p.add_argument("--batch-size", type=int, default=None,
                   help="per-device batch for dp; global for pipelines")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--repeats", type=int, default=1,
                   help="timed loops per point; the reported figure is the "
                        "median")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--dp-shard-update", action="store_true",
                   help="run the dp points with the explicit sharded weight "
                        "update (ZeRO-1; parallel/dp.py) — A/B against a "
                        "plain run to price the reduce-scatter/all-gather "
                        "pattern")
    p.add_argument("--allreduce-dtype", default="f32",
                   choices=("f32", "float32", "bf16", "bfloat16", "int8"),
                   help="wire dtype for dp's gradient collectives "
                        "(bf16 = compressed allreduce, int8 = absmax + "
                        "stochastic rounding at quarter bytes)")
    p.add_argument("--comm-buckets", type=int, default=1,
                   help="dp points: layer-aligned gradient buckets for "
                        "comm/compute overlap (1 = monolithic)")
    from ddlbench_tpu.partition.schedule import PIPE_SCHEDULES

    p.add_argument("--pipe-schedule", default="fill-drain",
                   choices=PIPE_SCHEDULES,
                   help="gpipe points: pipeline timetable executed by the "
                        "schedule runtime (parallel/pipeline_rt.py) — the "
                        "round-10 A/B column; analytic bubble fractions "
                        "ride the JSON points for comparison against the "
                        "telemetry/bubble.py measured value")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="gpipe points: model chunks per device (fill-drain "
                        "interleaving, or the interleaved-1f1b schedule)")
    p.add_argument("--dp-replicas", type=int, default=1,
                   help="pipeline points: data replicas per stage on the "
                        "2-D pipe mesh (stages = devices/replicas). With "
                        "--dp-shard-update, gpipe points run the hybrid "
                        "PP x ZeRO-1 engine — opt_state_bytes_per_chip in "
                        "the JSON is where the memory win shows up")
    p.add_argument("--audit", default=None, metavar="PATH",
                   help="also emit the compiled-program audit manifest per "
                        "point (telemetry/audit.py: flops/HBM/per-"
                        "collective ledger + comm_stats tie-outs) into one "
                        "ledger JSON — the tools/auditbench.py diff "
                        "substrate")
    from ddlbench_tpu.distributed import (add_platform_arg, apply_comm_flags,
                                          apply_platform)

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)
    if args.comm_buckets > 1:
        apply_comm_flags(args.platform)

    import jax

    from ddlbench_tpu.config import RunConfig
    from ddlbench_tpu.distributed import enable_compilation_cache

    enable_compilation_cache()
    # Backend provenance header: one JSON line recording what jax ACTUALLY
    # selected (shared helper — distributed.record_provenance: adds
    # schema_version and refuses an unrequested CPU), so every scalebench
    # artifact self-identifies.
    from ddlbench_tpu.distributed import record_provenance

    prov = record_provenance(args.platform, "scalebench")
    print(json.dumps({"provenance": {**prov, "platform_arg": args.platform}}),
          flush=True)
    audit_manifests = []
    failed = 0  # a failed point keeps the sweep alive but not exit code 0
    avail = len(jax.devices())
    if args.devices:
        counts = [int(c) for c in args.devices.split(",")]
    else:
        counts = [c for c in (1, 2, 4, 8, 16, 32) if c <= avail]
    bad = [c for c in counts if c > avail]
    if bad:
        p.error(f"device counts {bad} exceed the {avail} attached devices")

    # 1-chip anchor: the single strategy (the reference's baseline driver)
    anchor_cfg = RunConfig(
        benchmark=args.benchmark, strategy="single", arch=args.model,
        batch_size=args.batch_size, compute_dtype=args.dtype,
        steps_per_epoch=args.steps)
    anchor, anchor_opt = _run_point(anchor_cfg, args.steps, args.warmup,
                                    args.repeats)
    print(json.dumps({"strategy": "single", "devices": 1,
                      "schema_version": prov["schema_version"],
                      "samples_per_sec": round(anchor, 2),
                      "per_chip": round(anchor, 2), "efficiency": 1.0,
                      "opt_state_bytes_per_chip": anchor_opt}),
          flush=True)

    for strat in args.strategies.split(","):
        strat = strat.strip()
        for n in counts:
            # n == 1 is a legitimate point too (1-stage pipelines measure
            # the microbatching overhead vs the single anchor)
            kw = dict(benchmark=args.benchmark, strategy=strat,
                      arch=args.model, num_devices=n,
                      compute_dtype=args.dtype, steps_per_epoch=args.steps,
                      batch_size=args.batch_size)
            if strat not in ("dp", "fsdp"):
                kw["num_stages"] = n
            point = {"strategy": strat, "devices": n}
            if strat in ("gpipe", "pipedream") and args.dp_replicas > 1:
                if n % args.dp_replicas:
                    print(json.dumps({**point, "error":
                                      f"{n} devices not divisible by "
                                      f"--dp-replicas {args.dp_replicas}"}),
                          flush=True)
                    failed += 1
                    continue
                kw["num_stages"] = n // args.dp_replicas
                kw["dp_replicas"] = args.dp_replicas
                point["dp_replicas"] = args.dp_replicas
            if strat == "gpipe" and (args.pipe_schedule != "fill-drain"
                                     or args.virtual_stages > 1):
                kw["pipe_schedule"] = args.pipe_schedule
                kw["virtual_stages"] = args.virtual_stages
                point["pipe_schedule"] = args.pipe_schedule
                point["virtual_stages"] = args.virtual_stages
            if strat == "gpipe":
                # hybrid PP x ZeRO-1 on/off is an A/B column: the flag
                # rides every gpipe point so the JSON rows pair up
                kw["dp_shard_update"] = args.dp_shard_update
                kw["comm_buckets"] = (args.comm_buckets
                                      if args.dp_shard_update else 1)
                point["dp_shard_update"] = args.dp_shard_update
                if args.dp_shard_update:
                    point["comm_buckets"] = kw["comm_buckets"]
            if strat == "dp" and (args.dp_shard_update
                                  or args.comm_buckets > 1
                                  or args.allreduce_dtype not in
                                  ("f32", "float32")):
                kw["dp_shard_update"] = args.dp_shard_update
                kw["allreduce_dtype"] = args.allreduce_dtype
                kw["comm_buckets"] = args.comm_buckets if n > 1 else 1
                point["dp_shard_update"] = args.dp_shard_update
                point["allreduce_dtype"] = args.allreduce_dtype
                point["comm_buckets"] = kw["comm_buckets"]
            cfg = RunConfig(**kw)
            try:
                cfg.validate()
                if "pipe_schedule" in point:
                    # analytic bubble rides the point for the round-10
                    # report table; inside the try so an infeasible
                    # (schedule, S, M) point records its error like any
                    # other instead of killing the sweep
                    from ddlbench_tpu.partition.schedule import (
                        bubble_is_estimate, schedule_bubble_fraction)

                    _, chunks_b = cfg.resolved_batches()
                    point["bubble_analytic"] = round(
                        schedule_bubble_fraction(
                            args.pipe_schedule, cfg.resolved_stages(),
                            chunks_b, args.virtual_stages), 4)
                    if bubble_is_estimate(args.pipe_schedule,
                                          cfg.resolved_stages(), chunks_b,
                                          args.virtual_stages):
                        point["bubble_analytic_is_lower_bound"] = True
                ips, opt_bytes = _run_point(cfg, args.steps, args.warmup,
                                            args.repeats)
                if args.audit:
                    from ddlbench_tpu.telemetry.audit import \
                        audit_train_config

                    man, _ = audit_train_config(
                        cfg, name=f"scale/{strat}@{n}")
                    audit_manifests.append(man)
                    point["audit_tie_ok"] = man["reconcile"].get("ok")
                    point["audit_tieable"] = man["reconcile"]["tieable"]
            except Exception as e:  # point failures shouldn't kill the sweep
                print(json.dumps({**point, "error": str(e)[:200]}),
                      flush=True)
                failed += 1
                continue
            print(json.dumps({
                **point,
                "schema_version": prov["schema_version"],
                "samples_per_sec": round(ips, 2),
                "per_chip": round(ips / n, 2),
                "efficiency": round(ips / n / anchor, 4),
                "opt_state_bytes_per_chip": opt_bytes,
            }), flush=True)
    if args.audit:
        from ddlbench_tpu.telemetry.audit import write_manifests

        write_manifests(args.audit, audit_manifests,
                        header={**prov, "tool": "scalebench"})
        print(json.dumps({"audit": args.audit,
                          "programs": len(audit_manifests)}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
