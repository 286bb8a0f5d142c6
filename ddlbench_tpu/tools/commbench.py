"""Collective bandwidth microbenchmark.

Parity target: the reference's communication tests measure allreduce
bandwidth over tensor sizes 10..1e8 as two localhost gloo ranks
(pipedream-fork/runtime/tests/communication/all_to_all.py:42-59). Here the
same sweep runs over a real device mesh with XLA collectives — psum
(allreduce), all_gather, ppermute (the pipeline edge transfer), and
all_to_all (the EP dispatch) — so the numbers are the actual ICI/DCN rates
the strategies see.

Each timing chains the collective output into the next iteration's input
(out -> in dependency), which defeats dispatch caching/overlap and measures
real sequential executions.

Output: one JSON line per (collective, size) with seconds/op and the
algorithmic bandwidth GB/s = payload_bytes / time (payload = the per-device
shard). Usage:

    python -m ddlbench_tpu.tools.commbench -g 8 [--platform cpu] \
        [--sizes 1e4,1e6,1e8] \
        [--collectives psum,all_gather,reduce_scatter,ppermute,all_to_all] \
        [--buckets 1,4,8]

``--buckets`` sweeps the BUCKETED variant (one collective per contiguous
chunk of the same payload) — the wire-level cost model for the dp engine's
``--comm-buckets`` comm/compute overlap, measured without a train step.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _mesh_and_shardings(n, axis="x", devices=None):
    # topology-aware ordering (ICI neighbor rings) via the shared constructor,
    # so the reported bandwidth matches what the strategies' meshes see
    from ddlbench_tpu.distributed import make_mesh

    return make_mesh([(axis, n)], devices=devices)


def _make_collective(name: str, mesh, n: int, buckets: int = 1):
    """Return (fn(local_array) -> local_array, payload_scale) shard_map'd over
    the mesh. payload_scale converts the per-device shard bytes into the
    bytes each device actually moves for the algorithmic-bandwidth figure.

    ``buckets`` splits the local buffer into that many contiguous chunks and
    issues one collective PER CHUNK inside the same program — the wire-level
    shape of the dp engine's ``--comm-buckets`` bucketed reduce-scatter /
    all-gather, measurable here independently of any train step (total
    payload unchanged; what moves is dispatch overhead vs pipelining)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ddlbench_tpu.parallel.gpipe import _shard_map as shard_map

    axis = mesh.axis_names[0]

    if name == "psum":
        def one(x):
            return lax.psum(x, axis)
        # ring allreduce moves 2*(n-1)/n of the buffer per device
        scale = 2.0 * (n - 1) / n
        in_spec, out_spec = P(axis), P(axis)
    elif name == "all_gather":
        def one(x):
            return lax.all_gather(x, axis, tiled=True)
        # each device receives the other n-1 shards
        scale = float(n - 1)
        # out kept "varying" (concatenated globally) so the VMA checker is
        # happy on every shard_map version; the timing is unaffected
        in_spec, out_spec = P(axis), P(axis)
    elif name == "reduce_scatter":
        def one(x):
            return lax.psum_scatter(x, axis, tiled=True)
        # ring RS: each device ships (n-1)/n of the buffer once
        scale = (n - 1) / n
        in_spec, out_spec = P(axis), P(axis)
    elif name == "ppermute":
        def one(x):
            return lax.ppermute(x, axis, [(i, (i + 1) % n) for i in range(n)])
        scale = 1.0
        in_spec, out_spec = P(axis), P(axis)
    elif name == "all_to_all":
        def one(x):
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        scale = (n - 1) / n
        in_spec, out_spec = P(axis), P(axis)
    else:
        raise ValueError(f"unknown collective {name!r}")

    if buckets <= 1:
        op = one
    else:
        def op(x):
            # contiguous equal chunks, one collective each — each chunk's
            # collective is independent dataflow, exactly like the engine's
            # per-bucket psum_scatter
            chunk = x.shape[0] // buckets
            outs = [one(x[b * chunk:(b + 1) * chunk])
                    for b in range(buckets)]
            return jnp.concatenate(outs)

    fn = shard_map(op, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec)
    return fn, scale, in_spec


def bench_collective(name: str, mesh, n: int, size_floats: int,
                     iters: int = 10, buckets: int = 1):
    """Time one collective at the given GLOBAL element count; returns a dict.

    ``buckets`` > 1 measures the bucketed variant: same payload, one
    collective per contiguous chunk (see _make_collective)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if buckets < 1:
        raise ValueError(f"buckets must be >= 1 (got {buckets})")
    fn, scale, in_spec = _make_collective(name, mesh, n, buckets)
    # round the per-device shard up to a multiple of n*buckets, so each
    # bucket chunk still splits n ways (all_to_all / reduce_scatter)
    per_dev = max(1, (size_floats + n - 1) // n)
    align = n * buckets
    per_dev = ((per_dev + align - 1) // align) * align
    global_n = per_dev * n
    x = jax.device_put(
        jax.numpy.ones((global_n,), jax.numpy.float32),
        NamedSharding(mesh, in_spec),
    )

    def chained(x0):
        def step(c, _):
            # fold the output into the carry — the dependency defeats
            # dispatch caching. all_gather's output is the concatenation of
            # every shard (n x larger; slice back), reduce_scatter's is a
            # 1/n slice (tile back up) — jnp.resize covers both while
            # keeping the data dependency.
            out = fn(c)
            if out.shape != c.shape:
                out = jnp.resize(out, c.shape)
            return c + 0.0 * out, None
        return lax.scan(step, x0, None, length=iters)[0]

    run = jax.jit(chained)
    jax.block_until_ready(run(x))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(run(x))
    dt = (time.perf_counter() - t0) / iters

    shard_bytes = per_dev * 4
    moved = shard_bytes * scale
    return {
        "collective": name,
        "global_floats": global_n,
        "shard_bytes": shard_bytes,
        "buckets": buckets,
        "sec_per_op": dt,
        "algbw_gbps": moved / dt / 1e9,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="commbench", description=__doc__)
    p.add_argument("-g", "--devices", type=int, default=None)
    p.add_argument("--collectives",
                   default="psum,all_gather,ppermute,all_to_all",
                   help="also available: reduce_scatter (the dp ZeRO-1 "
                        "gradient collective)")
    p.add_argument("--sizes", default="1e4,1e5,1e6,1e7,1e8",
                   help="global float32 counts (reference sweep: 10..1e8)")
    p.add_argument("--buckets", default="1",
                   help="comma sweep of bucket counts: each point issues "
                        "one collective per contiguous chunk (the dp "
                        "--comm-buckets wire pattern) — e.g. 1,4,8")
    p.add_argument("--iters", type=int, default=10)
    from ddlbench_tpu.distributed import add_platform_arg

    add_platform_arg(p)
    args = p.parse_args(argv)

    import jax

    from ddlbench_tpu.distributed import apply_platform, force_host_mesh_platform

    if args.platform:
        apply_platform(args.platform)
    else:
        force_host_mesh_platform()

    n = args.devices or len(jax.devices())
    mesh = _mesh_and_shardings(n)
    bucket_counts = [int(b) for b in args.buckets.split(",")]
    for name in args.collectives.split(","):
        for size in args.sizes.split(","):
            for buckets in bucket_counts:
                r = bench_collective(name.strip(), mesh, n,
                                     int(float(size)), args.iters,
                                     buckets=buckets)
                print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
