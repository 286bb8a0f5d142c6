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
