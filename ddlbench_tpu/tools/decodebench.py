"""Inference (decode) throughput microbenchmark.

GNMT-analog inference measurement (the reference benchmarks only training;
its translation runtime ships beam-search inference without a throughput
harness — SURVEY.md §2 C13). Measures tokens/sec for greedy and beam decode
on a seq2seq model, KV-cached (models/decode.py) vs the full-forward
reference path, printing one JSON line per configuration:

    {"tool": "decodebench", "mode": "greedy", "cached": true,
     "tokens_per_sec": N, "ms_per_token": M, ...}

Usage:
    python -m ddlbench_tpu.tools.decodebench [-m seq2seq_s] [-b synthmt]
        [--batch 8] [--beam 4] [--repeats 3] [--skip-uncached] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _bench(fn, repeats: int):
    import jax

    jax.block_until_ready(fn())  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("-m", "--model", default="seq2seq_s")
    p.add_argument("-b", "--benchmark", default="synthmt")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--beam", type=int, default=4)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--total-len", type=int, default=None,
                   help="decode out to this stream length (default: the "
                        "benchmark's full seq_len; lower it for long-context "
                        "specs where the compile of thousands of decode "
                        "steps would dominate)")
    p.add_argument("--cache-dtype", default="float32",
                   help="KV-cache storage dtype for the paged variants "
                        "(bfloat16 halves cache traffic; scores stay f32)")
    p.add_argument("--skip-uncached", action="store_true",
                   help="skip the slow full-forward reference path")
    p.add_argument("--chunk-prefill", action="store_true",
                   help="also bench the serving chunk-prefill attention "
                        "(ops/paged_decode.paged_chunk_attention): Pallas "
                        "kernel vs gathered-page XLA rows over "
                        "--chunk-sizes x --chunk-pages")
    p.add_argument("--chunk-sizes", default="16,32",
                   help="chunk-prefill query lengths C to sweep (the Pallas "
                        "kernel refuses a C x page score product over its "
                        "VMEM budget — ops/paged_decode.py)")
    p.add_argument("--chunk-pages", default="4,16",
                   help="live page counts to sweep for the chunk rows")
    p.add_argument("--chunk-heads", type=int, default=8)
    p.add_argument("--chunk-dh", type=int, default=64)
    p.add_argument("--chunk-page-size", type=int, default=32,
                   help="positions per page for the chunk rows")
    p.add_argument("--kv-dtype", default=None,
                   help="comma list among float32,bfloat16,int8: serving-"
                        "pool dtype sweep rows — paged flash-decode and "
                        "chunk-prefill attention, Pallas fused-dequant "
                        "kernel vs XLA reference per dtype (kernel rows "
                        "skipped-with-provenance off-TPU)")
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
    prov = backend_provenance(args.platform, "decodebench")

    from ddlbench_tpu.config import DATASETS
    from ddlbench_tpu.models import init_model
    from ddlbench_tpu.models.zoo import get_model
    import ddlbench_tpu.models.seq2seq as s2s

    spec = DATASETS[args.benchmark]
    model = get_model(args.model, spec)
    params, state, _ = init_model(model, jax.random.key(0))
    # seq2seq: prompt = the source segment. Token (causal-LM) benchmarks:
    # prompt = half the stream — the long-context decode shape where the
    # paged cache pays most (live pages vs masked full length).
    causal = spec.kind == "tokens"
    T = min(args.total_len or spec.seq_len, spec.seq_len)
    S = T // 2 if causal else spec.src_len
    src = jax.random.randint(jax.random.key(1), (args.batch, S), 0,
                             spec.num_classes, jnp.int32)
    new_tokens = (T - S) * args.batch

    import ddlbench_tpu.models.decode as dec

    # "paged": copy-on-write page-table cache + live-page flash decode
    # (ops/paged_decode.py) — the round-4 fast path; "cached": dense KV
    # cache with the full gather-per-expansion; "full": the full-forward
    # reference loop.
    runs = [("greedy", "paged"), ("beam", "paged"),
            ("greedy", "cached"), ("beam", "cached")]
    if not args.skip_uncached:
        runs += [("greedy", "full"), ("beam", "full")]

    for mode, variant in runs:
        cached = variant != "full"
        if variant == "paged" and not dec.supports_paged(model):
            print(json.dumps({"tool": "decodebench", "mode": mode,
                              "variant": "paged",
                              "skipped": f"{args.model} lacks paged support",
                              **prov}),
                  flush=True)
            continue
        if causal and variant == "full":
            # the full-forward reference loop is seq2seq-specific; the
            # causal cached path is pinned against it in tests instead
            print(json.dumps({"tool": "decodebench", "mode": mode,
                              "variant": "full",
                              "skipped": "full-forward loop is seq2seq-only",
                              **prov}),
                  flush=True)
            continue
        if variant == "paged" or causal:
            cdt = jnp.dtype(args.cache_dtype if variant == "paged"
                            else "float32")
            paged = variant == "paged"
            if mode == "greedy":
                fn = jax.jit(lambda: dec.greedy_decode(
                    model, params, state, src, T, dtype=cdt, paged=paged))
            else:
                fn = jax.jit(lambda: dec.beam_search_decode(
                    model, params, state, src, T, beam=args.beam,
                    dtype=cdt, paged=paged)[0])
        elif mode == "greedy":
            fn = jax.jit(lambda: s2s.greedy_decode(
                model, params, state, src, T, use_cache=cached))
        else:
            fn = jax.jit(lambda: s2s.beam_search_decode(
                model, params, state, src, T, beam=args.beam,
                use_cache=cached)[0])
        dt = _bench(fn, args.repeats)
        print(json.dumps({
            "tool": "decodebench",
            "platform": jax.devices()[0].platform,
            **prov,
            "model": args.model,
            "benchmark": args.benchmark,
            "mode": mode,
            "variant": variant,
            "cache_dtype": (args.cache_dtype if variant == "paged"
                            else "float32"),
            "cached": cached,
            "batch": args.batch,
            "prompt_len": S,
            "total_len": T,
            "beam": args.beam if mode == "beam" else 1,
            "new_tokens": new_tokens,
            "tokens_per_sec": round(new_tokens / dt, 2),
            "ms_per_token": round(1000.0 * dt / max(1, T - S), 3),
        }), flush=True)

    if args.chunk_prefill:
        _chunk_prefill_rows(args, prov)
    if args.kv_dtype:
        _kv_dtype_rows(args, prov)
    return 0


def _chunk_prefill_rows(args, prov) -> None:
    """Kernel-vs-XLA rows for the serving chunk-prefill attention: one row
    per (chunk size C, live page count) x {chunk-kernel, chunk-xla} over a
    synthetic serving pool (shuffled free-list table, the layout the
    engine produces). The kernel variant is the Pallas multi-query
    flash-decode analog and only compiles on TPU — elsewhere the row is
    recorded as skipped, with the same backend provenance as every other
    row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddlbench_tpu.distributed import is_tpu_backend
    from ddlbench_tpu.ops.paged_decode import paged_chunk_attention

    H, dh, page = args.chunk_heads, args.chunk_dh, args.chunk_page_size
    chunks = [int(x) for x in args.chunk_sizes.split(",")]
    pages = [int(x) for x in args.chunk_pages.split(",")]
    for C in chunks:
        for npl in pages:
            pool_pages = npl + 2  # slot 0 scratch + headroom
            kk = jax.random.normal(jax.random.key(10),
                                   (pool_pages, page, H, dh), jnp.float32)
            vv = jax.random.normal(jax.random.key(11),
                                   (pool_pages, page, H, dh), jnp.float32)
            perm = np.random.default_rng(0).permutation(
                np.arange(1, pool_pages))[:npl]
            cache = {"pool_k": kk, "pool_v": vv,
                     "table": jnp.asarray(perm[None, :], jnp.int32)}
            q = jax.random.normal(jax.random.key(12), (1, H, C, dh),
                                  jnp.float32)
            # chunk start = the last page (the serving frontier shape)
            start = jnp.int32((npl - 1) * page)
            for variant, use_kernel in (("chunk-kernel", True),
                                        ("chunk-xla", False)):
                base = {"tool": "decodebench", "variant": variant,
                        "chunk": C, "pages": npl, "page": page,
                        "heads": H, "dh": dh, **prov}
                if use_kernel and not is_tpu_backend():
                    print(json.dumps({
                        **base,
                        "skipped": "Pallas chunk kernel needs a TPU "
                                   "backend (XLA row is the CPU path)",
                    }), flush=True)
                    continue
                fn = jax.jit(lambda q=q, cache=cache, start=start,
                             uk=use_kernel: paged_chunk_attention(
                                 q, cache, start, npl, page=page,
                                 use_kernel=uk))
                dt = _bench(fn, args.repeats)
                print(json.dumps({
                    **base,
                    "tokens_per_sec": round(C / dt, 2),
                    "us_per_chunk": round(1e6 * dt, 2),
                }), flush=True)


def _kv_dtype_rows(args, prov) -> None:
    """KV-pool dtype sweep for the serving attention hot path: one row per
    (dtype, op in {decode, chunk}, variant in {kernel, xla}) over a
    synthetic shuffled-free-list pool at the ``--chunk-*`` shapes. The
    int8 pool is built through the real write primitive
    (paged_table_chunk_write — per-page scale sidecar + stochastic
    rounding), so the kernel rows measure the FUSED-dequant read path the
    serving engine compiles, not a hand-rolled stand-in. Kernel rows off
    TPU record skipped-with-provenance, the same contract as every other
    decodebench row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddlbench_tpu.distributed import is_tpu_backend
    from ddlbench_tpu.ops.paged_decode import (paged_attention,
                                               paged_chunk_attention,
                                               paged_table_chunk_write,
                                               serve_pool_init)

    H, dh, page = args.chunk_heads, args.chunk_dh, args.chunk_page_size
    C = int(args.chunk_sizes.split(",")[0])
    npl = max(int(x) for x in args.chunk_pages.split(","))
    dtypes = [s.strip() for s in args.kv_dtype.split(",") if s.strip()]
    for name in dtypes:
        if name not in ("float32", "bfloat16", "int8"):
            print(json.dumps({"tool": "decodebench", "variant": "kv-dtype",
                              "kv_dtype": name,
                              "error": "unknown dtype (float32|bfloat16|"
                                       "int8)", **prov}), flush=True)
            continue
        dt = jnp.dtype(name)
        pool_pages = npl + 2  # slot 0 scratch + headroom
        perm = np.random.default_rng(0).permutation(
            np.arange(1, pool_pages))[:npl]
        table = jnp.asarray(perm[None, :], jnp.int32)
        pool = serve_pool_init(pool_pages, page, H, dh, dt)
        cache = {**pool, "table": table}
        # fill the live pages through the real page-aligned write path
        kk = jax.random.normal(jax.random.key(20), (1, npl * page, H, dh),
                               jnp.float32)
        vv = jax.random.normal(jax.random.key(21), (1, npl * page, H, dh),
                               jnp.float32)
        cache = jax.jit(lambda c, k, v: paged_table_chunk_write(
            c, k, v, jnp.int32(0), page))(cache, kk, vv)
        q1 = jax.random.normal(jax.random.key(22), (1, H, dh), jnp.float32)
        qC = jax.random.normal(jax.random.key(23), (1, H, C, dh),
                               jnp.float32)
        pos = jnp.asarray([npl * page - 1], jnp.int32)
        start = jnp.asarray([(npl - 1) * page], jnp.int32)
        ops = [
            ("decode", 1, lambda uk: paged_attention(
                q1, cache, pos, npl, page=page, use_kernel=uk)),
            ("chunk", C, lambda uk: paged_chunk_attention(
                qC, cache, start, npl, page=page, use_kernel=uk)),
        ]
        for op_name, toks, fn0 in ops:
            for variant, use_kernel in (("kernel", True), ("xla", False)):
                base = {"tool": "decodebench", "variant": "kv-dtype",
                        "op": op_name, "kv_dtype": name,
                        "kernel": use_kernel, "chunk": C, "pages": npl,
                        "page": page, "heads": H, "dh": dh, **prov}
                if use_kernel and not is_tpu_backend():
                    print(json.dumps({
                        **base,
                        "skipped": "Pallas fused-dequant kernel needs a "
                                   "TPU backend (XLA row is the CPU "
                                   "path)"}), flush=True)
                    continue
                fn = jax.jit(lambda uk=use_kernel, f=fn0: f(uk))
                dt_s = _bench(fn, args.repeats)
                print(json.dumps({
                    **base,
                    "tokens_per_sec": round(toks / dt_s, 2),
                    "us_per_call": round(1e6 * dt_s, 2),
                }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
