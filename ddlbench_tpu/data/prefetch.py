"""Asynchronous input pipeline: background device prefetch + stall accounting.

The reference hides input cost behind torch DataLoader worker processes and
prices residual input time into pipeline stage 0 (profiler main.py:388-407);
our loop was fully synchronous — every step paid ``data.batch()`` plus the
strategy's ``shard_batch`` (a blocking ``device_put``) on the critical path
before the device could start. :class:`Prefetcher` restores the overlap
TPU-natively: a producer thread runs BOTH the host-side batch production and
the H2D placement ``prefetch_depth`` steps ahead of consumption through a
bounded ring (a ``queue.Queue``), so step N's transfer overlaps step N-1's
compute. ``depth=0`` degrades to the old synchronous behavior through the
same interface (that is what ``--no-prefetch`` selects).

Determinism: the producer asks the data source for ``batch(epoch, step)`` in
strictly increasing step order — sources address batches by (epoch, step),
so thread timing can never reorder or resample anything, and a prefetched
run is bitwise-identical to a synchronous one (pinned by
tests/test_prefetch.py). Sequential streams (OnDiskData) are likewise safe:
one producer thread per epoch consumes the stream in order.

Epoch boundaries: each :meth:`Prefetcher.stream` owns one epoch and one
producer thread; the stream's iterator joins the thread when the epoch's
batches are exhausted (and ``close()`` tears it down early on exceptions),
so no batch of epoch E+1 can be produced — let alone consumed — during
epoch E.

Input-stall accounting: the consumer clocks every blocking wait on the ring
(``stall_s``/``stall_ms``). In synchronous mode the whole inline fetch
counts — the semantic is uniform: *time the training loop spent blocked
waiting for input*. The per-epoch figure is reported by
``MetricLogger.epoch_done`` and lands in bench.py's JSON next to
samples/sec, so throughput curves can distinguish input-bound from
compute-bound regimes.

Watchdog heartbeat: on streams with ``heartbeat`` enabled (the default for
eval streams), every produced and consumed batch kicks the (optional)
``HangWatchdog``, covering phases where slow input production is the
bottleneck. Heartbeat kicks prove HOST progress only — the armed
watchdog's device-hang deadline is still enforced by per-step ``float()``
syncs in both the train and eval loops (train/loop.py). Train streams
default to ``heartbeat=False`` so input-side kicks can never postpone that
per-step deadline by depth x batch-production-time.

Thread-safety contract: ``shard_batch`` runs on the producer thread (see
parallel/api.py). JAX dispatch and ``device_put`` are thread-safe; the
strategies keep no per-call mutable host state in ``shard_batch``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

from ddlbench_tpu import faults
from ddlbench_tpu.telemetry import get_tracer
from ddlbench_tpu.train.watchdog import TrainingFailure

# Sentinel step index marking an exception delivery from the producer.
_ERROR = -1


class Fetched(NamedTuple):
    """One prepared step: the sharded batch-args tuple plus (optionally) the
    raw host-side (x, y) pair — kept only when a consumer (the activation
    logger) asked for it, so the ring does not pin extra buffers."""

    batch: Tuple[Any, ...]
    raw: Optional[Tuple[Any, Any]]


class EpochStream:
    """Iterator over one epoch's prepared batches (one producer thread).

    Iterate it (``for fetched in stream``) and call :meth:`close` in a
    ``finally`` — closing is idempotent and also happens automatically when
    the epoch is exhausted. ``stall_ms`` is valid at any point and final
    after exhaustion.
    """

    def __init__(self, data, shard_fn: Callable, epoch: int, steps: int,
                 train: bool, depth: int, watchdog=None,
                 keep_raw: bool = False, heartbeat: bool = True,
                 start_step: int = 0):
        if not heartbeat:
            watchdog = None
        self._data = data
        self._shard_fn = shard_fn
        self._epoch = epoch
        self._steps = steps
        self._train = train
        self._watchdog = watchdog
        self._keep_raw = keep_raw
        # mid-epoch resume (train/checkpoint.py step-granular checkpoints):
        # the stream serves steps [start_step, steps). Random-access sources
        # jump straight to start_step; sequential streams (OnDiskData) are
        # fast-forwarded — earlier batches are fetched and DISCARDED so the
        # underlying reader/shuffle state matches an uninterrupted epoch.
        self._start = start_step
        self._ff_pending = (start_step if getattr(data, "stateful_stream",
                                                  False) else 0)
        self._served = 0
        self.stall_s = 0.0
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        if depth > 0:
            self._queue = queue.Queue(maxsize=depth)
            self._thread = threading.Thread(
                target=self._produce, daemon=True,
                name=f"ddlbench-prefetch-e{epoch}-{'train' if train else 'eval'}",
            )
            self._thread.start()

    # ---- producer (background thread) ----

    def _fetch(self, step: int) -> Fetched:
        # Telemetry (telemetry/tracer.py): the producer's two phases —
        # host-side batch production and shard/device_put — are separate
        # spans on the producer thread's track (ddl/batch_produce and
        # ddl/shard_device_put in a profiler trace), so an input-bound
        # epoch shows WHERE the producer spends its time.
        tr = get_tracer()
        args = {"epoch": self._epoch, "step": step, "train": self._train}
        with tr.span("batch_produce", **args):
            bx, by = self._data.batch(self._epoch, step, train=self._train)
        with tr.span("shard_device_put", **args):
            batch = self._shard_fn(bx, by)
        return Fetched(batch, (bx, by) if self._keep_raw else None)

    def _put(self, item) -> bool:
        """Bounded put that polls the stop flag — backpressure without ever
        deadlocking against a consumer that already gave up."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            if self._ff_pending:
                self._fast_forward()
            for step in range(self._start, self._steps):
                if self._stop.is_set():
                    return
                # fault hook: `prefetch-die` kills this producer thread here
                faults.prefetch_producer(self._epoch, step)
                item = self._fetch(step)
                if not self._put((step, item)):
                    return
                if self._watchdog is not None:
                    self._watchdog.kick()
        except BaseException as e:  # delivered to the consumer, then re-raised there
            self._put((_ERROR, e))

    def _fast_forward(self) -> None:
        """Advance a sequential source past the resumed-over steps."""
        tr = get_tracer()
        t0 = time.perf_counter_ns()
        for step in range(self._ff_pending):
            if self._stop.is_set():
                return
            self._data.batch(self._epoch, step, train=self._train)
        self._ff_pending = 0
        if tr.enabled:
            tr.complete("resume_fastforward", t0, time.perf_counter_ns(),
                        {"epoch": self._epoch, "steps": self._start})

    # ---- consumer ----

    def __iter__(self) -> "EpochStream":
        return self

    def __next__(self) -> Fetched:
        if self._start + self._served >= self._steps:
            self.close()
            raise StopIteration
        # the consumer-side blocking wait on the ring (or the inline fetch
        # in synchronous mode) — the stall scalar, visible as a span on the
        # consuming thread's timeline (ddl/ring_wait in a profiler trace)
        with get_tracer().span("ring_wait", epoch=self._epoch,
                               step=self._start + self._served,
                               train=self._train):
            t0 = time.perf_counter_ns()
            if self._queue is None:  # synchronous (depth 0): inline fetch
                if self._ff_pending:
                    self._fast_forward()
                step, item = None, self._fetch(self._start + self._served)
            else:
                step, item = self._get_or_fail()
            self.stall_s += (time.perf_counter_ns() - t0) / 1e9
        if step == _ERROR:
            self.close()
            # TrainingFailure with the producer's exception CHAINED, so the
            # consumer-side abort carries the original traceback (a dead
            # producer must not surface only as a watchdog timeout or an
            # anonymous hang)
            raise TrainingFailure(
                f"prefetch producer failed in epoch {self._epoch}: "
                f"{item}") from item
        self._served += 1
        if self._watchdog is not None:
            self._watchdog.kick()
        return item

    def _get_or_fail(self):
        """Ring get that notices a dead producer instead of blocking forever.

        The producer delivers its own exceptions through the ring; this
        covers the remaining gap — a producer that died WITHOUT managing a
        delivery (e.g. killed hard, or the interpreter tore the thread
        down) — by polling thread liveness while waiting."""
        while True:
            try:
                return self._queue.get(timeout=0.2)
            except queue.Empty:
                t = self._thread
                if t is not None and not t.is_alive():
                    try:  # a final drain beats the race where the producer
                        return self._queue.get_nowait()  # put then exited
                    except queue.Empty:
                        self.close()
                        raise TrainingFailure(
                            f"prefetch producer for epoch {self._epoch} "
                            f"died without delivering a batch") from None

    @property
    def stall_ms(self) -> float:
        return self.stall_s * 1e3

    def close(self, grace_s: float = 2.0) -> None:
        """Stop the producer and join its thread. Idempotent; safe mid-epoch
        (e.g. from a ``finally`` after a training exception) — the producer's
        polling put means it can never stay blocked on a full ring. If the
        producer is wedged INSIDE a fetch (e.g. a hung device_put), the
        join is abandoned after ``grace_s`` so a
        propagating training exception surfaces instead of hanging the
        teardown — the thread is a daemon and cannot outlive the process."""
        self._stop.set()
        if self._thread is not None:
            deadline = time.monotonic() + grace_s
            while self._thread.is_alive():
                try:  # drain so a blocked put wakes immediately
                    self._queue.get_nowait()
                except queue.Empty:
                    pass
                self._thread.join(timeout=0.05)
                if time.monotonic() > deadline and self._thread.is_alive():
                    import sys

                    print(f"prefetch: producer thread {self._thread.name} "
                          f"did not exit within {grace_s:.0f}s (stuck in a "
                          f"fetch?); abandoning join", file=sys.stderr,
                          flush=True)
                    break
            self._thread = None

    def __enter__(self) -> "EpochStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class Prefetcher:
    """Factory for per-epoch :class:`EpochStream`s over one (data, shard_fn).

    ``depth`` is the ring capacity (``RunConfig.prefetch_depth``); 0 selects
    the synchronous fallback. One Prefetcher serves both train and eval
    epochs; the loop reads each stream's ``stall_ms`` after the epoch.
    """

    def __init__(self, data, shard_fn: Callable, depth: int = 2,
                 watchdog=None):
        if depth < 0:
            raise ValueError("prefetch depth must be >= 0")
        self.data = data
        self.shard_fn = shard_fn
        self.depth = depth
        self.watchdog = watchdog

    def stream(self, epoch: int, train: bool = True, keep_raw: bool = False,
               heartbeat: Optional[bool] = None,
               start_step: int = 0) -> EpochStream:
        """``heartbeat`` defaults to eval-only (``not train``): an armed
        watchdog's train-path deadline stays per-step (driven by the loop's
        own float() syncs), while eval — which never syncs mid-epoch —
        takes its liveness from the pipeline. ``start_step`` serves only
        steps [start_step, steps) — the mid-epoch resume entry point."""
        if heartbeat is None:
            heartbeat = not train
        steps = self.data.steps_per_epoch(train=train)
        if not 0 <= start_step <= steps:
            raise ValueError(
                f"start_step {start_step} outside epoch of {steps} steps")
        return EpochStream(self.data, self.shard_fn, epoch, steps, train,
                          self.depth, watchdog=self.watchdog,
                          keep_raw=keep_raw, heartbeat=heartbeat,
                          start_step=start_step)
