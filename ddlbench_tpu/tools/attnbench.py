"""Attention-kernel microbenchmark: flash (Pallas) vs XLA across sequence
lengths.

What re-measures ``flash_pays_off`` (ops/flash_attention.py): one
fwd+bwd jitted step per (backend, T) cell over the bare attention primitive,
so the crossover where the kernel's grid/stream overhead stops paying for
its HBM savings can be re-measured when shapes, kernels, or hardware change.
One JSON line per T:

    {"T": 1024, "B": 16, ..., "flash_ms": N, "xla_ms": N, "flash_speedup": N}

Sync discipline follows tools/timing.py: chain nothing (the primitive is
stateless) and close each timed region on jax.block_until_ready.

``--tiles 256x256,512x512`` sweeps the flash kernels' (block_q x block_k)
instead — the evidence behind flash_attention's default blocks and behind
its resident budget (``--design`` forces either grid design where the shape
rule would pick). It reads DEVICE time per kernel from a profiler trace (a
host clock around a 1 ms kernel times the dispatch), one JSON line per
(T, tile):

    {"T": 1024, ..., "block_q": 512, "block_k": 512, "design": "auto",
     "kernel_ms": {"flash_attn_fwd": N, "flash_attn_dq_dkv": N}}

Usage:
    python -m ddlbench_tpu.tools.attnbench [--seq-lens 128,256,512,1024]
        [--batch 16] [--heads 8] [--head-dim 64] [--v-dim 64] [--prefix 0]
        [--steps 50] [--tiles 128x128,256x256,512x512]
        [--design auto|resident|streaming]
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time


def flash_kernel_ms(fn, xs, steps: int) -> dict:
    """{flash kernel name: device ms per call of fn} from a profiler trace."""
    import collections
    import glob
    import re
    import shutil
    import tempfile

    import jax

    jax.block_until_ready(fn(*xs))
    trace_dir = tempfile.mkdtemp(prefix="attnbench_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(steps):
            out = fn(*xs)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
        ns = collections.defaultdict(int)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    m = re.search(r"flash_attn_(fwd|dq_dkv|dq|dkv)", e.name)
                    if m:
                        ns[m.group(0)] += e.duration_ns
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {k: round(v / steps * 1e-6, 4) for k, v in sorted(ns.items())}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-lens", default="128,256,512,768,1024,2048")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64,
                   help="the q/k width")
    p.add_argument("--v-dim", type=int, default=None,
                   help="the v/o width (default: --head-dim); latent "
                        "attention is 192 / 128")
    p.add_argument("--prefix", type=int, default=0,
                   help="prefix-LM visible-prefix length (seq2seq shape)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--repeats", type=int, default=1,
                   help="timed loops per cell; the reported ms is the median")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--tiles", default="",
                   help="BQxBK,...: sweep the flash kernels' blocks (TPU "
                        "only), device ms per kernel from a trace")
    p.add_argument("--design", default="auto",
                   choices=("auto", "resident", "streaming"),
                   help="with --tiles: the flash kernels' grid design; auto "
                        "is the shape rule's pick")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)

    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.distributed import enable_compilation_cache, is_tpu_backend
    from ddlbench_tpu.models.transformer import causal_attention

    enable_compilation_cache()
    backends = ("flash", "xla") if is_tpu_backend() else ("xla",)
    dtype = jnp.dtype(args.dtype)
    v_dim = args.head_dim if args.v_dim is None else args.v_dim
    stream = {"auto": None, "resident": False, "streaming": True}[args.design]
    tiles = [tuple(int(b) for b in t.split("x"))
             for t in args.tiles.split(",") if t]
    if tiles and not is_tpu_backend():
        p.error("--tiles times the compiled kernels: needs a TPU backend")

    def timed_once(f, *xs):
        jax.block_until_ready(f(*xs))
        t0 = time.perf_counter()
        for _ in range(args.steps):
            o = f(*xs)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / args.steps

    def timed(f, *xs):
        import statistics
        return statistics.median(
            timed_once(f, *xs) for _ in range(max(1, args.repeats)))

    for T in (int(t) for t in args.seq_lens.split(",")):
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (args.batch, args.heads, T, d), dtype)
                   for kk, d in zip(ks, (args.head_dim, args.head_dim, v_dim)))

        def loss(q, k, v, backend):
            out = causal_attention(q, k, v, prefix_len=args.prefix,
                                   backend=backend)
            return jnp.sum(out.astype(jnp.float32))

        row = {"T": T, "B": args.batch, "H": args.heads,
               "dh": args.head_dim, "dv": v_dim, "prefix": args.prefix,
               "dtype": args.dtype, "repeats": args.repeats}
        for bq, bk in tiles:
            from ddlbench_tpu.ops.flash_attention import flash_attention

            g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, 0, 0, args.prefix, bq, bk, False, stream
                ).astype(jnp.float32)), argnums=(0, 1, 2)))
            print(json.dumps({**row, "block_q": bq, "block_k": bk,
                              "design": args.design, "kernel_ms": flash_kernel_ms(
                                  g, (q, k, v), args.steps)}), flush=True)
        if tiles:
            continue
        for mode in backends:
            g = jax.jit(jax.value_and_grad(
                functools.partial(loss, backend=mode), argnums=(0, 1, 2)))
            row[f"{mode}_ms"] = round(timed(g, q, k, v) * 1e3, 3)
        if "flash_ms" in row and "xla_ms" in row:
            row["flash_speedup"] = round(row["xla_ms"] / row["flash_ms"], 3)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
