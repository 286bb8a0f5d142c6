"""Shared Pallas plumbing for the ops kernels."""

from __future__ import annotations

import jax


class gspmd_jit:
    """Trace-time marker: the enclosed trace is a plain multi-device jit
    whose operands GSPMD shards (the dp, tp and fsdp step bodies enter it;
    ``single`` and every shard_map body never do). It carries no value:
    pallas_partitions_safely below, its one reader, asks only whether the
    trace is inside one. Kernel dispatch happens at trace time, so the
    answer is captured into the traced program. Same idiom as
    models/layers.axis_context and paged_decode.live_pages: a class-level
    stack, re-entrant, popped on exit."""

    _stack: list = []

    def __enter__(self):
        gspmd_jit._stack.append(self)
        return self

    def __exit__(self, *exc):
        gspmd_jit._stack.pop()
        return False

    @staticmethod
    def active() -> bool:
        return bool(gspmd_jit._stack)


def pallas_partitions_safely(*operands) -> bool:
    """Whether a Pallas kernel over ``operands`` runs where it was placed
    instead of being gathered: pallas_call has no GSPMD partitioning rule, so
    under a plain multi-device jit with sharded operands XLA replicates them
    onto every device (ADVICE r1). Inside shard_map the operands are already
    per-shard (nonempty varying-manual-axes type), and outside a gspmd_jit
    trace (single-device programs, whatever the host's chip count) there is
    nothing to partition — both are safe."""
    from ddlbench_tpu.compat import vma_of

    if any(vma_of(o) for o in operands):
        return True
    return not gspmd_jit.active()


def takes_pallas(backend: str, forced: str, *operands) -> bool:
    """The rule every kernel's ``backend`` argument shares: ``"xla"`` never
    takes the Pallas kernel, ``forced`` (the kernel's own name for it:
    ``"flash"``, ``"pallas"``) always does, and ``"auto"`` takes it on a TPU
    where it partitions safely. A kernel adds what only it knows on top
    (flash_attention.flash_dispatch: alignment and the crossover)."""
    if backend == "xla":
        return False
    if backend == forced:
        return True
    if backend != "auto":
        raise ValueError(f"unknown backend {backend!r}; known: auto, "
                         f"{forced}, xla")
    from ddlbench_tpu.distributed import is_tpu_backend

    return is_tpu_backend() and pallas_partitions_safely(*operands)


# What a kernel may plan to hold in a v5e TensorCore's 128 MiB of VMEM, as its
# own accounting sums it (flash_attention._resident_vmem_bytes,
# fused_xent._held_vmem_bytes): half. The other half takes the kernel's margin
# (vmem_limit_bytes: a limit of up to 80 MiB) and what XLA keeps in VMEM across
# the call. Set by room, not by a measured loss: no flash shape that compiles
# ran slower resident (PERF.md, PR 28: 61.2 MiB at T 32768 dh 64 and 58.1 MiB
# at T 16384 192/128 beat streaming 1.9x forward and 2.0-2.4x backward).
RESIDENT_VMEM_BUDGET = (128 << 20) // 2


def tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a [rows, cols] array in VMEM: the lanes padded to 128, the
    sublanes to a whole tile (8 rows of 32 bits: 16 of bf16)."""
    up = lambda n, m: -(-n // m) * m
    return up(rows, 8 * max(1, 4 // itemsize)) * up(cols, 128) * itemsize


def vmem_limit_bytes(held: int) -> int:
    """The ``vmem_limit_bytes`` of a kernel that holds ``held`` by its own
    accounting: a quarter more, for Mosaic's own scratch and for the shapes
    at which the accounting reads under Mosaic's report."""
    return held + held // 4


def grid_params(interpret: bool, *semantics: str, vmem_limit_bytes=None):
    """Mosaic grid hints: "parallel" grid axes are independent, an
    "arbitrary" one is sequential — it carries an accumulator across its
    steps (a streamed inner dimension; the one-pass backwards' outer axis,
    across which dQ / dh accumulate). No-op under interpret (CPU tests)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes)}


def pick_block(t: int, preferred: int, unit: int = 1):
    """Largest divisor of ``t`` that is <= preferred and a multiple of
    ``unit`` (block shapes must tile the dimension). Returns None when t is
    not a multiple of unit — on real TPU, Mosaic rejects blocks that are not
    tile-aligned (8 sublanes / 128 lanes), so compiled kernels pass the
    hardware unit and fall back (or error clearly) on a None instead of
    handing Mosaic an arbitrary divisor (ADVICE r1)."""
    if t % unit or preferred < unit:
        # no divisor <= preferred can be a multiple of unit (ADVICE r2:
        # returning unit here would silently exceed the caller's block/VMEM
        # budget)
        return None
    b = max(unit, min(preferred - preferred % unit, t))
    while t % b:
        b -= unit
    return b


def pallas_out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct for a pallas_call output, carrying the union of the
    operands' varying-axes (VMA) types — required when a kernel runs inside a
    shard_map (e.g. per-block calls from ring attention, or any strategy
    whose model apply is shard_mapped)."""
    from ddlbench_tpu.compat import vma_of

    vma = set()
    for a in operands:
        vma |= set(vma_of(a))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)
