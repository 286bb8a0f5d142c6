"""Shared benchmark timing ritual for bench.py / lmbench / scalebench.

One home for the measurement discipline so the copies cannot drift:
* warmup at least once (compilation stays out of the timed loop),
* time a loop whose train state chains step-to-step (so nothing overlaps
  past the measured region),
* close the clock on jax.block_until_ready of the last step's metrics
  (dispatch is asynchronous: without it the loop times the enqueue).
"""

from __future__ import annotations

import time
from typing import Callable, Tuple

import jax


def timed_steps(run_step: Callable[[object, object], dict],
                get_batch: Callable[[int, int], Tuple[object, object]],
                steps: int, warmup: int) -> float:
    """Return the wall-clock seconds for ``steps`` chained train steps.

    ``run_step(x, y) -> metrics`` must thread its own train state (the chain
    is what makes the last step's metrics a full barrier);
    ``get_batch(epoch, step)`` supplies batches (epoch 0 = warmup, 1 =
    timed)."""
    m = None
    x, y = get_batch(0, 0)
    for _ in range(max(1, warmup)):
        m = run_step(x, y)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for step in range(steps):
        x, y = get_batch(1, step)
        m = run_step(x, y)
    jax.block_until_ready(m)
    return time.perf_counter() - t0


def timed_steps_prefetched(run_step: Callable[..., dict], prefetcher,
                           warmup: int) -> Tuple[float, float, int, list]:
    """``timed_steps`` driven by the async input pipeline.

    ``prefetcher`` is a data.prefetch.Prefetcher; the timed region consumes
    one full epoch-1 stream (so batch production + device placement overlap
    the steps, exactly as in the training loop) and returns
    ``(seconds, input_stall_seconds, steps, step_seconds)`` — the stall
    term is how much of the measured wall clock was spent blocked waiting
    on input, ``steps`` is the number of steps actually driven (the
    stream's epoch length; callers must derive throughput from it, not
    from their own step count), and ``step_seconds`` is the per-step
    dispatch wall time (ring wait excluded — it is the stall), feeding the
    p50/p95 step-latency fields of bench.py's JSON. Same discipline as
    timed_steps: warmup outside the clock, chained state,
    block_until_ready as the closing barrier."""
    m = None
    batch = prefetcher.shard_fn(*prefetcher.data.batch(0, 0))
    for _ in range(max(1, warmup)):
        m = run_step(*batch)
    jax.block_until_ready(m)
    # clock starts BEFORE the stream spawns its producer (training-loop
    # parity: loop.py takes its epoch tick before prefetch.stream) — a
    # pre-clock head start of depth batches would bias both dt and the
    # stall figure optimistic
    t0 = time.perf_counter()
    stream = prefetcher.stream(1, train=True)
    steps = 0
    step_s = []
    try:
        for fetched in stream:
            ts0 = time.perf_counter()
            m = run_step(*fetched.batch)
            step_s.append(time.perf_counter() - ts0)
            steps += 1
        jax.block_until_ready(m)
        dt = time.perf_counter() - t0
    finally:
        stream.close()
    return dt, stream.stall_s, steps, step_s
