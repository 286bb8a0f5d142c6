"""Hetero-conveyor A/B microbenchmark: flat-axis engine vs regular grid.

The uneven-replication engine (parallel/hetero.py) runs R ppermute rounds of
a max-interior-activation buffer per tick plus a gradient ring per sync —
wire traffic the regular 2-D ('data','stage') mesh does not pay. This tool
quantifies that overhead where the two engines are comparable: a UNIFORM
replication plan (e.g. 2,2), which both can execute at the same topology and
global batch. It also runs one genuinely uneven plan (e.g. 1,3) for the
capability-side number (no uniform-mesh comparator exists there — the
reference executes such plans via round-robin + LCM,
pipedream-fork/runtime/runtime.py:663-690).

Each point prints one JSON line:

    {"engine": "hetero"|"grid", "plan": [2,2], "samples_per_sec": N,
     "ms_per_step": N, "peak_bytes_in_use": N|null}

and a final {"comparison": ...} line with the hetero/grid throughput ratio.
Needs sum(plan) attached devices; with fewer, or when any point fails, the
exit code is nonzero.

A chip belongs to one process at a time, so the default
subprocess-per-point mode keeps the PARENT off jax entirely (no backend
touch, no device query — those would hold the chip its children need) and
reads platform and device facts from the children's records.

Usage:
    python -m ddlbench_tpu.tools.heterobench [-b mnist] [-m resnet18]
        [--plan 2,2] [--uneven 1,3] [--steps 10] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys


def _peak_bytes():
    import jax

    # the CPU backend keeps no memory statistics (memory_stats() is None)
    return (jax.local_devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")


def _run_engine(strategy, cfg, steps, warmup):
    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.tools.timing import timed_steps

    data = make_synthetic(cfg.dataset(), cfg.global_batch(),
                          steps_per_epoch=steps)
    ts = strategy.init(jax.random.key(cfg.seed))
    lr = jnp.float32(cfg.resolved_lr())

    def run_step(x, y):
        nonlocal ts
        ts, m = strategy.train_step(ts, *strategy.shard_batch(x, y), lr)
        return m

    return timed_steps(run_step, data.batch, steps, warmup)


def _measure(engine_name, plan, cfg, strategy, steps, warmup):
    import jax

    dt = _run_engine(strategy, cfg, steps, warmup)
    rec = {
        "engine": engine_name,
        "plan": list(plan),
        "samples_per_sec": round(steps * cfg.global_batch() / dt, 2),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "peak_bytes_in_use": _peak_bytes(),
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(rec), flush=True)
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-b", "--benchmark", default="mnist")
    p.add_argument("-m", "--model", default="resnet18")
    p.add_argument("-f", "--framework", default="pipedream",
                   choices=("gpipe", "pipedream"))
    p.add_argument("--plan", default="2,2",
                   help="uniform replication plan for the A/B (hetero vs grid)")
    p.add_argument("--uneven", default="1,3",
                   help="uneven plan measured hetero-only ('' to skip)")
    p.add_argument("--micro-batch-size", type=int, default=None)
    p.add_argument("--num-microbatches", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--only", default=None,
                   choices=("hetero", "grid", "uneven"),
                   help="measure one point in THIS process (used by the "
                        "subprocess-per-point default so peak_bytes_in_use "
                        "is per-engine, not a process-lifetime max)")
    p.add_argument("--in-process", action="store_true",
                   help="run all points in one process (faster; memory "
                        "figures then reflect the process max, reported as "
                        "null past the first point)")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    plan = tuple(int(r) for r in args.plan.split(","))
    uneven = tuple(int(r) for r in args.uneven.split(",")) if args.uneven else ()
    points = ["hetero", "grid"] + (["uneven"] if uneven else [])
    if not (args.only or args.in_process):
        # subprocess per point: fresh process => per-engine peak memory.
        # Nothing above or inside _run_children touches a jax backend.
        return _run_children(args, plan, points)

    apply_platform(args.platform)

    import jax

    from ddlbench_tpu.config import RunConfig
    from ddlbench_tpu.distributed import enable_compilation_cache
    from ddlbench_tpu.models.zoo import get_model
    from ddlbench_tpu.parallel.api import make_strategy
    from ddlbench_tpu.parallel.hetero import (
        HeteroGPipeStrategy,
        HeteroPipeDreamStrategy,
    )

    enable_compilation_cache()
    need = max(sum(plan), sum(uneven) if uneven else 0)
    avail = len(jax.devices())
    if avail < need:
        print(json.dumps({
            "error": f"needs {need} devices, {avail} attached",
            "platform": jax.devices()[0].platform,
        }), flush=True)
        return 1

    hetero_cls = (HeteroGPipeStrategy if args.framework == "gpipe"
                  else HeteroPipeDreamStrategy)

    import math

    def base_cfg(repl):
        cfg = RunConfig(
            benchmark=args.benchmark, strategy=args.framework,
            arch=args.model, num_devices=sum(repl),
            stage_replication=tuple(repl),
            micro_batch_size=args.micro_batch_size,
            num_microbatches=args.num_microbatches,
            compute_dtype=args.dtype, steps_per_epoch=args.steps)
        if args.micro_batch_size is None:
            # replicas split each microbatch's rows: round the default
            # micro-batch down to a multiple of lcm(repl) so every plan in
            # the A/B is executable at (nearly) the same global batch
            l = math.lcm(*repl)
            mb, _ = cfg.resolved_batches()
            cfg = cfg.replace(micro_batch_size=max(l, mb // l * l))
        return cfg

    def model_of(cfg):
        return get_model(cfg.arch, cfg.benchmark,
                         attention_backend=cfg.attention_backend)

    def run_point(which):
        """Measure one engine point in this process; returns its record."""
        if which == "uneven":
            cfg = base_cfg(uneven)
            cfg.validate()
            return _measure("hetero", uneven, cfg,
                            hetero_cls(model_of(cfg), cfg),
                            args.steps, args.warmup)
        cfg = base_cfg(plan)
        cfg.validate()
        if which == "hetero":
            # conveyor engine constructed directly — the strategy factory
            # rewrites uniform plans onto the grid (api.py:122-134)
            strat = hetero_cls(model_of(cfg), cfg)
        else:
            # the same topology on the regular 2-D mesh (make_strategy's pick)
            strat = make_strategy(cfg)
        return _measure(which, plan, cfg, strat, args.steps, args.warmup)

    if args.only:
        run_point(args.only)
        return 0

    records = {}
    for i, which in enumerate(points):
        rec = run_point(which)
        if i > 0:
            # memory_stats peaks are a process-lifetime max: only the
            # first point's figure is attributable to its engine
            rec["peak_bytes_in_use"] = None
        records[which] = rec
    _print_comparison(records, plan)
    return 0


def _print_comparison(records, plan) -> None:
    print(json.dumps({
        "comparison": "hetero/grid",
        "plan": list(plan),
        "throughput_ratio": round(
            records["hetero"]["samples_per_sec"]
            / records["grid"]["samples_per_sec"], 4),
        "platform": records["hetero"]["platform"],
    }), flush=True)


def _run_children(args, plan, points) -> int:
    """One child process per point, each re-entering this tool with
    ``--only``. The parent never touches a jax backend; a failed point
    prints its error row and makes the exit code nonzero."""
    import subprocess

    base_argv = [sys.executable, "-m", "ddlbench_tpu.tools.heterobench",
                 "-b", args.benchmark, "-m", args.model,
                 "-f", args.framework, "--plan", args.plan,
                 "--uneven", args.uneven or "",
                 "--steps", str(args.steps),
                 "--warmup", str(args.warmup), "--dtype", args.dtype]
    if args.micro_batch_size is not None:
        base_argv += ["--micro-batch-size", str(args.micro_batch_size)]
    if args.num_microbatches is not None:
        base_argv += ["--num-microbatches", str(args.num_microbatches)]
    if args.platform:
        base_argv += ["--platform", args.platform]
    records, rc = {}, 0
    for which in points:
        out = subprocess.run(base_argv + ["--only", which],
                             capture_output=True, text=True)
        line = next((ln for ln in out.stdout.splitlines()
                     if ln.startswith("{")), None)
        if out.returncode or line is None:
            print(json.dumps({"engine": which, "error":
                              (out.stderr or line or "no output")[-300:]}),
                  flush=True)
            rc = 1
            continue
        records[which] = json.loads(line)
        print(line, flush=True)
    if rc == 0:
        _print_comparison(records, plan)
    return rc


if __name__ == "__main__":
    sys.exit(main())
