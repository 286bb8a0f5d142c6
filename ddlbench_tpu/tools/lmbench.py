"""LM-workload single-chip microbenchmark: tokens/sec across optimization
knobs.

Sweeps the training step of a token workload over the framework's two kernel
knobs — attention backend (Pallas flash vs XLA) and the fused LM-head loss
(ops/fused_xent.py) vs full-logits — so the kernel wins can be quantified on
real hardware in one command. The CNN analog is bench.py (the headline
driver-recorded number); this is the transformer-side companion used for
PERF.md measurements.

Each configuration prints one JSON line:

    {"config": "flash+fused", "tokens_per_sec": N, "ms_per_step": N, ...}

Sync discipline follows bench.py (tools/timing.py): chain the train state
through all steps and close the clock on jax.block_until_ready.

Usage:
    python -m ddlbench_tpu.tools.lmbench [-m transformer_s] [-b synthtext]
        [--batch-size 16] [--steps 20] [--dtype bfloat16] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--model", default="transformer_s")
    p.add_argument("-b", "--benchmark", default="synthtext")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--label-smoothing", type=float, default=None)
    p.add_argument("--configs", default=None,
                   help="comma list among flash+fused,flash+logits,"
                        "xla+fused,xla+logits,auto (default: the four "
                        "forced cells; 'auto' measures the length-based "
                        "dispatch a default run gets)")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)

    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.distributed import (backend_provenance,
                                          enable_compilation_cache)

    enable_compilation_cache()
    # actual-backend record on every row (distributed.backend_provenance,
    # which refuses to measure on a CPU nobody asked for)
    prov = backend_provenance(args.platform, "lmbench")

    from ddlbench_tpu.config import DATASETS, RunConfig
    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.distributed import is_tpu_backend
    from ddlbench_tpu.parallel.api import make_strategy

    token_benchmarks = sorted(
        n for n, s in DATASETS.items() if s.kind in ("tokens", "seq2seq"))
    if (args.benchmark not in DATASETS
            or DATASETS[args.benchmark].kind not in ("tokens", "seq2seq")):
        p.error(f"-b {args.benchmark!r} is not a token workload; lmbench "
                f"sweeps token workloads (pick one of {token_benchmarks})")

    all_configs = {
        "flash+fused": ("flash", True),
        "flash+logits": ("flash", False),
        "xla+fused": ("xla", True),
        "xla+logits": ("xla", False),
        # what a default run actually gets: the length-based dispatch
        # (ops/flash_attention.py flash_dispatch) + fused head. Not in
        # the default sweep (it duplicates one of the forced cells); use
        # --configs auto to check the dispatch picks the winning backend.
        "auto": ("auto", True),
    }
    on_tpu = is_tpu_backend()
    if args.configs:
        names = [c.strip() for c in args.configs.split(",") if c.strip()]
        unknown = [c for c in names if c not in all_configs]
        if unknown:
            p.error(f"unknown --configs {unknown}; choose from "
                    f"{sorted(all_configs)}")
    else:
        # flash off-TPU means interpret mode (minutes per step) — skip it
        sweep = [n for n in all_configs if n != "auto"]
        names = sweep if on_tpu else ["xla+fused", "xla+logits"]

    def run_config(name: str, remat: bool):
        attn, fused = all_configs[name]
        cfg = RunConfig(
            benchmark=args.benchmark,
            strategy="single",
            arch=args.model,
            batch_size=args.batch_size,
            compute_dtype=args.dtype,
            attention_backend=attn,
            fused_head_loss=fused,
            remat_layers=remat,
            label_smoothing=args.label_smoothing,
            steps_per_epoch=args.steps,
        )
        strategy = make_strategy(cfg)
        spec = cfg.dataset()
        B = cfg.global_batch()
        data = make_synthetic(spec, B, steps_per_epoch=args.steps)
        ts = strategy.init(jax.random.key(cfg.seed))
        lr = jnp.float32(cfg.resolved_lr())

        from ddlbench_tpu.tools.timing import timed_steps

        def run_step(x, y, _s=strategy):
            nonlocal ts
            ts, m = _s.train_step(ts, x, y, lr)
            return m

        dt = timed_steps(run_step, data.batch, args.steps, args.warmup)

        tokens = args.steps * B * spec.seq_len
        print(json.dumps({
            "config": name,
            "model": args.model,
            "benchmark": args.benchmark,
            "batch": B,
            "seq_len": spec.seq_len,
            "remat": remat,
            "tokens_per_sec": round(tokens / dt, 1),
            "ms_per_step": round(1000 * dt / args.steps, 2),
            **prov,
        }), flush=True)

    def is_oom(e: BaseException) -> bool:
        msg = str(e)
        return ("RESOURCE_EXHAUSTED" in msg or "Ran out of memory" in msg
                or "out of memory" in msg.lower())

    ok = 0
    for name in names:
        # An OOM in one configuration must not lose the others' numbers
        # (measured on-chip: at T=8192 the XLA-attention configs exceed one
        # v5e's HBM — every layer's [B, H, T, T] score matrix stays live into
        # the backward — while the flash configs fit). Record the OOM as a
        # data point, then retry that cell with per-layer rematerialization
        # (cfg.remat_layers), which caps live activations at one layer.
        # MoE archs cannot remat (config.validate: the router aux-loss side
        # channel cannot escape a checkpointed trace) — no retry for them.
        attempts = (False,) if "moe" in args.model else (False, True)
        for remat in attempts:
            try:
                run_config(name, remat)
                ok += 1
                break
            except Exception as e:  # noqa: BLE001 — sweep must survive a cell
                if not is_oom(e):
                    raise
                print(json.dumps({
                    "config": name, "model": args.model,
                    "benchmark": args.benchmark, "remat": remat,
                    "error": "hbm-oom",
                    "detail": str(e).splitlines()[0][:200],
                    **prov,
                }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
