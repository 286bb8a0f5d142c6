#!/usr/bin/env python
"""Headline benchmark: ResNet-50 / synthetic ImageNet throughput on one chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N}

Baseline for vs_baseline: the reference framework's hardware is a GTX 1080 Ti
(run_template.sh:416-419); the commonly reported ResNet-50/ImageNet fp32
training throughput for that card is ~200 images/sec (batch 32). The reference
repo publishes no numbers of its own (BASELINE.md), so vs_baseline =
value / 200.0 against that documented figure.

The number is a device number or nothing: with no TPU backend the script
exits nonzero before it runs a step.

Usage: python bench.py [--quick] [--batch-size N] [--steps N] [--arch resnet50]
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

REFERENCE_1080TI_RESNET50_IPS = 200.0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="resnet50")
    p.add_argument("--benchmark", default="imagenet")
    p.add_argument("-f", "--framework", default="single",
                   choices=("single", "dp"),
                   help="single (the 1-chip headline) or dp — multi-chip "
                        "rounds A/B the dp engine variants through the "
                        "same timed harness")
    p.add_argument("-g", "--devices", type=int, default=1,
                   help="chips for -f dp (batch-size stays per-device)")
    p.add_argument("--dp-shard-update", action="store_true",
                   help="dp only: explicit ZeRO-1 sharded weight update")
    p.add_argument("--allreduce-dtype", default="f32",
                   choices=("f32", "float32", "bf16", "bfloat16", "int8"),
                   help="dp only: gradient-collective wire dtype")
    p.add_argument("--comm-buckets", type=int, default=1,
                   help="dp only: layer-aligned gradient buckets for "
                        "comm/compute overlap (parallel/dp.py; 1 = "
                        "monolithic collectives)")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--repeats", type=int, default=3,
                   help="timed loops; the reported value is the median")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="async input pipeline depth (data/prefetch.py); "
                        "0 = synchronous batch generation on the timed path")
    p.add_argument("--quick", action="store_true", help="tiny run for smoke testing")
    p.add_argument("--audit", default=None, metavar="PATH",
                   help="write the compiled step's audit manifest here "
                        "(telemetry/audit.py: flops / HBM components / "
                        "collective ledger + comm_stats tie-out) — reuses "
                        "the timed executable, zero extra compiles")
    args = p.parse_args()

    if args.quick:
        args.batch_size, args.steps, args.warmup = 32, 5, 2

    from ddlbench_tpu.distributed import (RECORD_SCHEMA_VERSION,
                                          apply_comm_flags,
                                          backend_provenance,
                                          enable_compilation_cache)

    if args.comm_buckets > 1:
        # async-collective overlap flags: must precede the first backend
        # touch (libtpu reads LIBTPU_INIT_ARGS when it initializes)
        apply_comm_flags()
    enable_compilation_cache()
    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU backend, jax found "
              f"{jax.default_backend()!r} ({jax.devices()}); no number is "
              f"reported from any other device", file=sys.stderr)
        return 1

    from ddlbench_tpu.config import RunConfig, device_peaks
    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.parallel.api import make_strategy

    cfg = RunConfig(
        benchmark=args.benchmark,
        strategy=args.framework,
        arch=args.arch,
        num_devices=args.devices,
        batch_size=args.batch_size,
        compute_dtype=args.dtype,
        steps_per_epoch=args.steps,
        dp_shard_update=args.dp_shard_update,
        allreduce_dtype=args.allreduce_dtype,
        comm_buckets=args.comm_buckets,
    )
    cfg.validate()
    strategy = make_strategy(cfg)
    global_batch = cfg.global_batch()
    data = make_synthetic(cfg.dataset(), global_batch, steps_per_epoch=args.steps)
    ts = strategy.init(jax.random.key(cfg.seed))
    lr = jnp.float32(cfg.resolved_lr())

    # AOT-compile once: the same executable serves warmup, the timed loop,
    # and the roofline cost analysis (no second compile). Measurement
    # discipline (warmup >= 1, chained train state, block_until_ready at
    # the close) lives in tools/timing.
    from ddlbench_tpu.data.prefetch import Prefetcher
    from ddlbench_tpu.telemetry.stats import percentile
    from ddlbench_tpu.tools.timing import timed_steps_prefetched

    x, y = data.batch(0, 0)
    # the dp explicit-collective engine wraps its jit in a telemetry-span
    # function; AOT-lower the underlying executable either way
    jit_step = getattr(strategy, "_jit_train_step", None) or strategy.train_step
    step_fn = jit_step.lower(ts, x, y, lr).compile()

    def run_step(bx, by):
        nonlocal ts
        ts, m = step_fn(ts, bx, by, lr)
        return m

    # The timed loop rides the same async input pipeline as training, so the
    # headline number includes (and reports) any input-boundedness.
    prefetcher = Prefetcher(data, strategy.shard_batch,
                            depth=args.prefetch_depth)
    runs = sorted((timed_steps_prefetched(run_step, prefetcher, args.warmup)
                   for _ in range(max(1, args.repeats))),
                  key=lambda r: r[0])
    # the median-dt RUN, keeping its own stall/step-latency figures —
    # mixing medians of the series could pair a throughput with another
    # run's stall
    dt, stall_s, steps_run, step_s = runs[len(runs) // 2]

    # steps_run, not args.steps: the timed loop drives one full epoch of the
    # stream, and the two agree only while make_synthetic keeps train_size an
    # exact multiple of the batch
    ips = steps_run * global_batch / dt
    n_chips = max(1, cfg.num_devices)
    device = jax.devices()[0]
    record = {
        "metric": f"{args.arch}_{args.benchmark}_images_per_sec_per_chip",
        "value": round(ips / n_chips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / n_chips / REFERENCE_1080TI_RESNET50_IPS,
                             3),
        # Input-boundedness next to samples/sec: the timed loop is one
        # epoch, so this is directly comparable across runs.
        "input_stall_ms_per_epoch": round(stall_s * 1e3, 2),
        # Step-latency percentiles + stall fraction (telemetry/stats.py):
        # a tight p50 with stall_frac near 0 is compute-bound; a large
        # stall_frac says the input pipeline is the regime, regardless of
        # what samples/sec alone suggests.
        "step_time_p50_ms": round(percentile([t * 1e3 for t in step_s], 50), 3),
        "step_time_p95_ms": round(percentile([t * 1e3 for t in step_s], 95), 3),
        "stall_frac": round(stall_s / dt, 4) if dt else 0.0,
        "prefetch_depth": args.prefetch_depth,
        "strategy": args.framework,
        "devices": n_chips,
        # dp engine variant under measurement (A/B provenance)
        **({"dp_shard_update": True} if args.dp_shard_update else {}),
        **({"allreduce_dtype": cfg.resolved_allreduce_dtype()}
           if cfg.resolved_allreduce_dtype() != "float32" else {}),
        **({"comm_buckets": args.comm_buckets}
           if args.comm_buckets > 1 else {}),
        # the device the measurement ran on, as jax reports it
        "platform": device.platform,
        "device_kind": device.device_kind,
        **{k: v for k, v in backend_provenance(what="bench").items()
           if k in ("jax_backend", "jax_device_count")},
        "schema_version": RECORD_SCHEMA_VERSION,
    }
    import datetime

    record["measured_at"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    # Roofline context: XLA's own cost analysis of the compiled step vs the
    # published peaks of the device that ran it (config.DEVICE_PEAKS — an
    # unknown device_kind raises rather than borrowing another chip's).
    peaks = device_peaks(device.device_kind)
    cost = step_fn.cost_analysis()
    sec_per_step = dt / steps_run  # same denominator as the headline ips
    record["mfu"] = round(
        cost["flops"] / sec_per_step / peaks.peak_flops, 4)
    record["hbm_util"] = round(
        cost["bytes accessed"] / sec_per_step / peaks.hbm_bandwidth, 4)
    if args.audit:
        # full audit manifest from the SAME executable the loop timed —
        # the collective ledger and comm_stats tie-out ride the run free
        from ddlbench_tpu.telemetry.audit import (program_manifest,
                                                  reconcile_train,
                                                  write_manifests)

        man = program_manifest(
            step_fn, f"bench/{args.framework}/{args.arch}@{n_chips}",
            mesh=getattr(strategy, "mesh", None))
        man["reconcile"] = reconcile_train(strategy, man)
        write_manifests(args.audit, [man],
                        header={"tool": "bench",
                                "schema_version": RECORD_SCHEMA_VERSION,
                                "platform": record["platform"]})
        record["audit"] = args.audit
        record["audit_tie_ok"] = man["reconcile"].get("ok")
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
