"""Attention-kernel microbenchmark: flash (Pallas) vs XLA across sequence
lengths.

The evidence behind ``FLASH_AUTO_MIN_SEQ`` (models/transformer.py): one
fwd+bwd jitted step per (backend, T) cell over the bare attention primitive,
so the crossover where the kernel's grid/stream overhead stops paying for
its HBM savings can be re-measured when shapes, kernels, or hardware change.
One JSON line per T:

    {"T": 1024, "B": 16, ..., "flash_ms": N, "xla_ms": N, "flash_speedup": N}

Sync discipline follows tools/timing.py: chain nothing (the primitive is
stateless) and close each timed region on jax.block_until_ready.

Usage:
    python -m ddlbench_tpu.tools.attnbench [--seq-lens 128,256,512,1024]
        [--batch 16] [--heads 8] [--head-dim 64] [--prefix 0] [--steps 50]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-lens", default="128,256,512,768,1024,2048")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--prefix", type=int, default=0,
                   help="prefix-LM visible-prefix length (seq2seq shape)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--repeats", type=int, default=1,
                   help="timed loops per cell; the reported ms is the median")
    p.add_argument("--dtype", default="bfloat16")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)

    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.distributed import enable_compilation_cache, is_tpu_backend
    from ddlbench_tpu.models.transformer import (causal_attention,
                                                 set_attention_backend)

    enable_compilation_cache()
    backends = ("flash", "xla") if is_tpu_backend() else ("xla",)
    dtype = jnp.dtype(args.dtype)

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
        q, k, v = (jax.random.normal(kk, (args.batch, args.heads, T,
                                          args.head_dim), dtype) for kk in ks)

        def loss(q, k, v):
            out = causal_attention(q, k, v, prefix_len=args.prefix)
            return jnp.sum(out.astype(jnp.float32))

        row = {"T": T, "B": args.batch, "H": args.heads,
               "dh": args.head_dim, "prefix": args.prefix,
               "dtype": args.dtype, "repeats": args.repeats}
        for mode in backends:
            set_attention_backend(mode)
            try:
                g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
                row[f"{mode}_ms"] = round(timed(g, q, k, v) * 1e3, 3)
            finally:
                set_attention_backend("auto")
        if "flash_ms" in row and "xla_ms" in row:
            row["flash_speedup"] = round(row["xla_ms"] / row["flash_ms"], 3)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
