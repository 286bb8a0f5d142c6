"""Thread-safe, ring-buffered span/counter tracer on monotonic clocks.

Every span is ALSO a ``jax.profiler.TraceAnnotation("ddl/<name>")``, ring
on or off: under a profiler session (``--trace-dir`` /
``--xla-trace-steps``, the benchmark's ``--trace 1`` run) the program's
host spans land in the same ``.xplane.pb`` as the device ops, on the
profiler's clock, instead of in a second file that can only be lined up by
step number. Without a session an annotation is an inactive TraceMe.

Overhead contract (pinned by tests/test_telemetry.py):

* **Ring off** (the default), no profiler session: ``tracer.span(...)``
  builds and enters one inactive ``TraceAnnotation`` — no lock, no clock
  read, nothing recorded; under 2 µs a span (measured ~0.3 µs).
  :meth:`Tracer.complete` records regions the caller already timed for
  other reasons; such a region cannot be put on the profiler's clock after
  the fact, so ``complete`` stays ring-only and sites that belong in the
  profiler's trace use ``span``.
* **Ring on**: the annotation plus two ``time.perf_counter_ns`` reads per
  span and one lock-guarded append into a bounded ``deque``. The ring drops
  the OLDEST events when full (``dropped_events`` counts them), so a long
  run can always be traced — you get the most recent window.

Timestamps are ``time.perf_counter_ns()`` — monotonic, never wall clock —
so spans from different threads order correctly on one timeline and a
host NTP step can never fold the trace. All threads (main loop, prefetch
producer, watchdog) share one tracer; each event records its thread id
and name so the exporter can lay out one track per thread.

Determinism: recording never reorders or perturbs the traced computation
— the tracer only reads clocks — which is what makes the tracing-on/off
bitwise-loss pin possible.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# Event tuples: (phase, name, t0_ns, dur_ns, thread_id, thread_name, args).
# phase follows the Chrome trace-event phases the exporter emits:
# "X" = complete span, "C" = counter sample, "i" = instant.
Event = Tuple[str, str, int, int, int, str, Optional[Dict[str, Any]]]


ANNOTATION_PREFIX = "ddl/"  # the program's host spans in a profiler trace


class _Span:
    """Live span: clocks its own enter/exit and records on exit, inside
    the profiler annotation of the same name."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + name)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._tracer.complete(self._name, self._t0, t1, self._args)
        return False


class Tracer:
    """Bounded, thread-safe event recorder. One instance serves all threads.

    ``capacity`` bounds host memory: at ~120 bytes/event the default
    200k-event ring tops out around 25 MB regardless of run length.
    """

    def __init__(self, capacity: int = 200_000):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.enabled = False
        self._capacity = capacity
        self._events: deque[Event] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0

    # ---- recording ----

    def span(self, name: str, **args: Any):
        """Context manager timing a region: always a profiler annotation
        ``ddl/<name>``, and a ring event too when the ring is on."""
        if not self.enabled:
            return TraceAnnotation(ANNOTATION_PREFIX + name)
        return _Span(self, name, args or None)

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """Record an already-timed region (both stamps from
        ``time.perf_counter_ns``) in the ring only: it is over, so it
        cannot be annotated on the profiler's clock. Callers on hot paths
        guard with ``tracer.enabled`` so the disabled path never reaches
        here."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._append(("X", name, t0_ns, t1_ns - t0_ns, th.ident or 0,
                      th.name, args))

    def counter(self, name: str, value: float) -> None:
        """Record one sample of a named counter track (e.g. ring depth)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._append(("C", name, time.perf_counter_ns(), 0, th.ident or 0,
                      th.name, {"value": value}))

    def instant(self, name: str, **args: Any) -> None:
        """Record a zero-duration marker (e.g. watchdog kick, epoch edge)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._append(("i", name, time.perf_counter_ns(), 0, th.ident or 0,
                      th.name, args or None))

    def emit(self, phase: str, name: str, t0_ns: int, dur_ns: int = 0,
             track: str = "virtual",
             args: Optional[Dict[str, Any]] = None) -> None:
        """Record an event on a named SYNTHETIC track with caller-supplied
        timestamps — the virtual-time entry point. The serving engine
        stamps request-lifecycle events in model-pass units scaled by
        1000, so one virtual unit renders as 1 µs in the exported trace
        and every timestamp stays an exact integer (serveview's TTFT
        decomposition tiles without float drift). Synthetic tracks use
        thread id 0, which no started thread carries, so they can never
        alias a real thread's track in the exporter."""
        if not self.enabled:
            return
        self._append((phase, name, int(t0_ns), int(dur_ns), 0, track, args))

    def _append(self, evt: Event) -> None:
        with self._lock:
            if len(self._events) == self._capacity:
                self._dropped += 1
            self._events.append(evt)

    # ---- lifecycle / readout ----

    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    @property
    def capacity(self) -> int:
        """Ring size — exported in the trace metadata so reducers can say
        how big a --trace-capacity would have kept everything."""
        return self._capacity

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def events(self) -> List[Event]:
        """Snapshot of the recorded events in record order."""
        with self._lock:
            return list(self._events)

    @property
    def dropped_events(self) -> int:
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


# Process-global tracer: instrumentation sites (train/loop.py,
# data/prefetch.py, bench.py) grab it once; the CLI enables it when
# --trace is passed. A plain module global, not a context var — producer
# threads must see the same instance as the loop that spawned them.
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer (tests install bounded fresh ones)."""
    global _TRACER
    _TRACER = tracer
    return tracer
