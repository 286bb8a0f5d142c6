"""Multi-host initialization and topology-aware mesh construction.

The reference reaches multi-node through SLURM + ssh + per-rank process spawns
with hand-computed global ranks (run_template.sh:539-558,
pipedream_run.sh:83-101) over NCCL/Gloo/MPI. The TPU equivalent is one process
per host in a single `jax.distributed` world: every process sees the global
device list, and all cross-chip traffic is XLA collectives over ICI (within a
slice) or DCN (across slices/hosts).

`initialize()` is a no-op on single-process runs, so every entry point can
call it unconditionally; on multi-host it reads either explicit env
(DDLB_COORDINATOR, DDLB_NUM_PROCESSES, DDLB_PROCESS_ID) or defers to JAX's
TPU auto-detection.

`make_mesh()` builds meshes with DCN-friendly axis ordering: axes that carry
heavy, latency-tolerant traffic (data parallel) span hosts, while
bandwidth-hungry axes (pipeline stage transfers, sequence rings) stay inside a
slice — the layout the partitioner's cost model assumes.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_initialized = False


def is_tpu_backend() -> bool:
    """True when the default jax backend is TPU hardware. The single home
    for this check: kernel dispatch (flash attention, fused xent, paged
    decode) and tools key off it."""
    return jax.default_backend() == "tpu"


def add_platform_arg(parser) -> None:
    """Attach the shared --platform flag (one help string for every entry
    point; see apply_platform)."""
    parser.add_argument(
        "--platform", default=None,
        help="force a jax platform (e.g. 'cpu'; combine with "
             "XLA_FLAGS=--xla_force_host_platform_device_count=N for a "
             "virtual mesh)")


# <repo>/.jax_cache: a fixed path derived from this file, because the
# directory is part of the cache key — a temp name, pid or timestamp in it
# would never hit.
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Persistent XLA compilation cache, called once by every entry point
    before its first compile: repeat invocations reuse compiled executables
    keyed by HLO hash. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax
    already reads it and no directory is set in code; otherwise the cache
    lives at ``<repo>/.jax_cache``. Also installs the process's tally of
    jax's compile events (``telemetry/compiles.py``), once. Returns the
    directory in use."""
    from ddlbench_tpu.telemetry import compiles

    compiles.install()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


# Latency-hiding-scheduler knobs for the comm/compute-overlap engine
# (--comm-buckets > 1): convert the bucketed reduce-scatters/all-gathers
# into async collectives that the scheduler interleaves with the
# backward/forward compute instead of running them back-to-back at the
# step boundary. They are libtpu's flags, so they travel in
# LIBTPU_INIT_ARGS: jaxlib's own XLA_FLAGS parser does not know them and
# aborts the process on an unknown flag. libtpu 0.0.34 knows these four
# (--xla_tpu_spmd_threshold_for_windowed_einsum_mib, carried until PR 21,
# is not one of its flags and was fatal at init).
_COMM_OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def comm_flags() -> str:
    """The LIBTPU_INIT_ARGS string enabling async-collective overlap on
    TPU — one authoritative home; the train CLI / bench drivers apply it
    via :func:`apply_comm_flags` before the first backend touch."""
    return " ".join(_COMM_OVERLAP_FLAGS)


def apply_comm_flags(platform: Optional[str] = None) -> bool:
    """Append the overlap flags to LIBTPU_INIT_ARGS unless a non-TPU
    platform is pinned (--platform / JAX_PLATFORMS).

    Returns True when the flags are in the environment. libtpu reads the
    variable once, when the TPU backend initializes, so a call that would
    have to add a flag after that point raises instead of returning with
    the flags silently not in effect. Idempotent across retried entry
    points.
    """
    pinned = (platform or os.environ.get("JAX_PLATFORMS", "")).lower()
    if pinned and "tpu" not in pinned:
        return False
    current = os.environ.get("LIBTPU_INIT_ARGS", "")
    # exact flag-NAME comparison on tokenized flags — a substring test
    # would see the base ..._async_collective_fusion as already present
    # whenever only a longer variant (..._fuse_all_gather) is set
    present = {tok.split("=")[0] for tok in current.split()}
    missing = [f for f in _COMM_OVERLAP_FLAGS
               if f.split("=")[0] not in present]
    if not missing:
        return True
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "apply_comm_flags: the jax backend is already initialized, so "
            "libtpu will not see " + " ".join(missing) + "; apply the "
            "comm-overlap flags before the first backend touch")
    os.environ["LIBTPU_INIT_ARGS"] = (current + " " + " ".join(missing)).strip()
    return True


def backend_provenance(platform_arg: Optional[str] = None,
                       what: str = "measurement") -> dict:
    """What jax ACTUALLY selected, vs what was asked for — embedded in
    every measurement artifact. One authoritative home for the refusal to
    measure on a CPU nobody asked for: when jax found no accelerator and
    fell back to the host, this raises instead of returning a row, so no
    timing tool can record a host number under a device metric's name.
    Touches the backend; call only after platform pinning
    (apply_platform / jax.config) is done.
    """
    backend = jax.default_backend()
    # jax.config.jax_platforms is where every way of asking lands: the
    # JAX_PLATFORMS environment variable, apply_platform(--platform), and
    # a test harness pinning the platform in code
    cpu_requested = "cpu" in ((platform_arg or "").lower(),
                              (jax.config.jax_platforms or "").lower())
    if backend == "cpu" and not cpu_requested:
        raise SystemExit(
            f"{what}: jax found no accelerator and selected the CPU "
            f"backend, and cpu was not asked for (--platform cpu / "
            f"JAX_PLATFORMS=cpu) — refusing to measure")
    return {
        "jax_backend": backend,
        "jax_device_count": jax.device_count(),
        "cpu_requested": cpu_requested,
        "cpu_fallback": False,
    }


# Version stamp for every JSON record the tools emit (bench/scalebench/
# servebench/chaosbench/planbench rows and audit manifests). Bump when a
# record's field set changes incompatibly so downstream diff tooling
# (tools/auditbench.py, perf_runs consumers) can refuse mixed ledgers.
RECORD_SCHEMA_VERSION = 1


def record_provenance(platform_arg: Optional[str] = None,
                      what: str = "measurement") -> dict:
    """The one shared record header: ``schema_version`` + the
    :func:`backend_provenance` fields (which refuses an unrequested CPU).
    Merge into every emitted JSON row."""
    return {"schema_version": RECORD_SCHEMA_VERSION,
            **backend_provenance(platform_arg, what)}


def apply_platform(platform) -> None:
    """Apply an explicit --platform override before the first backend
    touch (the JAX_PLATFORMS environment variable does the same from
    outside)."""
    if platform:
        jax.config.update("jax_platforms", platform)


def force_host_mesh_platform() -> None:
    """Pin the CPU platform when XLA_FLAGS asks for a virtual host mesh.

    ``--xla_force_host_platform_device_count=N`` only shapes the CPU
    backend; on a machine with an accelerator the default platform would
    win and the requested N-device mesh would never appear. Call this
    before the first backend touch from any entry point that should
    respect the virtual mesh.
    """
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_platforms", "cpu")


def _initialize_with_retry(connect, what: str) -> None:
    """Bounded retry with exponential backoff around one connect attempt.

    A slow-starting peer (host still booting, coordinator not yet bound)
    must not fail the whole multihost run on the first connect error — the
    reference's SLURM launcher simply dies there. Tunables:
    ``DDLB_INIT_ATTEMPTS`` (default 3) total attempts and
    ``DDLB_INIT_BACKOFF_S`` (default 1.0) base delay, doubling per retry.
    The final attempt's exception propagates to the caller.
    """
    import time

    attempts = max(1, int(os.environ.get("DDLB_INIT_ATTEMPTS", "3")))
    base = float(os.environ.get("DDLB_INIT_BACKOFF_S", "1.0"))
    for attempt in range(1, attempts + 1):
        try:
            connect()
            return
        except Exception as e:
            if attempt == attempts:
                raise
            delay = base * 2 ** (attempt - 1)
            print(f"{what} attempt {attempt}/{attempts} failed ({e}); "
                  f"retrying in {delay:.1f}s", flush=True)
            time.sleep(delay)


def initialize() -> bool:
    """Join the jax.distributed world if configured; returns True if multi-host."""
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    # fault hook: `slow-host` injects a delay here, modeling a peer that is
    # slow to reach the coordinator (ddlbench_tpu/faults/)
    from ddlbench_tpu import faults

    faults.multihost_init()
    coord = os.environ.get("DDLB_COORDINATOR")
    nproc = os.environ.get("DDLB_NUM_PROCESSES")
    pid = os.environ.get("DDLB_PROCESS_ID")
    if coord and nproc and pid:
        _initialize_with_retry(
            lambda: jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nproc),
                process_id=int(pid),
            ),
            f"jax.distributed.initialize({coord})",
        )
        _initialized = True
    elif os.environ.get("DDLB_AUTO_DISTRIBUTED") == "1":
        # TPU metadata auto-detection
        _initialize_with_retry(lambda: jax.distributed.initialize(),
                               "jax.distributed.initialize(auto)")
        _initialized = True
    return jax.process_count() > 1


def make_mesh(axis_sizes: Sequence[Tuple[str, int]],
              devices: Optional[Sequence[jax.Device]] = None,
              dcn_axis: Optional[str] = None) -> Mesh:
    """Build a mesh with the named axes.

    axis_sizes: ordered (name, size) pairs, fastest-varying last. If dcn_axis
    is given and the run spans multiple processes/slices, that axis is mapped
    across hosts via mesh_utils.create_hybrid_device_mesh so its collectives
    ride DCN and everything else stays on ICI.
    """
    names = [n for n, _ in axis_sizes]
    sizes = [s for _, s in axis_sizes]
    total = int(np.prod(sizes))
    devs = list(devices or jax.devices())
    if len(devs) < total:
        raise ValueError(f"need {total} devices, have {len(devs)}")
    devs = devs[:total]

    if dcn_axis is not None and jax.process_count() > 1 and devices is None:
        from jax.experimental import mesh_utils

        dcn_idx = names.index(dcn_axis)
        per_slice = list(sizes)
        dcn = [1] * len(sizes)
        dcn[dcn_idx] = jax.process_count()
        if per_slice[dcn_idx] % jax.process_count():
            raise ValueError(
                f"axis {dcn_axis} ({per_slice[dcn_idx]}) must divide across "
                f"{jax.process_count()} processes"
            )
        per_slice[dcn_idx] //= jax.process_count()
        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn_mesh_shape=dcn
        )
        return Mesh(arr, axis_names=tuple(names))

    if devices is None and total > 1:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(sizes, devices=devs)
        return Mesh(arr, axis_names=tuple(names))
    return Mesh(np.array(devs).reshape(sizes), axis_names=tuple(names))


def put_global_batch(x, sharding):
    """Place a host-materialized GLOBAL array onto a (possibly multi-host)
    sharding.

    Single-process: plain device_put. Multi-process: every host materializes
    the same global array (synthetic data is deterministic in (epoch, step) —
    data/synthetic.py), and each host hands jax.make_array_from_callback the
    slices for its addressable shards. Works for any PartitionSpec — batch
    rows for dp/fsdp/ep, sequence columns for sp, replicated for params —
    which is what the reference needs DistributedSampler + broadcast for
    (mnist_horovod.py:207-231).
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def put_global_tree(tree, sharding):
    """Multi-host-safe device_put over a pytree. ``sharding`` is one Sharding
    applied to every leaf, or a prefix pytree of Shardings (jax.device_put's
    prefix convention — each Sharding leaf covers its whole subtree)."""
    from jax.sharding import Sharding

    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)
    if isinstance(sharding, Sharding):
        return jax.tree.map(lambda leaf: put_global_batch(leaf, sharding), tree)
    # prefix pytree: tree.map flattens by the sharding tree's structure and
    # hands each Sharding leaf its corresponding subtree
    return jax.tree.map(
        lambda sh, sub: jax.tree.map(lambda l: put_global_batch(l, sh), sub),
        sharding, tree,
        is_leaf=lambda x: isinstance(x, Sharding),
    )


def local_batch_slice(global_batch: int) -> slice:
    """This process's slice of a host-generated global batch (data staging for
    multi-host: each host materializes only its shard)."""
    n = jax.process_count()
    i = jax.process_index()
    per = global_batch // n
    return slice(i * per, (i + 1) * per)
