"""jax's own compile path, tallied: every trace, lowering, compile and
persistent-cache load the process makes, with the seconds jax measured for
it and the moment it ended.

jax reports each part from inside its compile path through
``jax.monitoring`` (jax 0.9: ``_src/dispatch.py``, ``_src/compiler.py``,
``_src/compilation_cache.py``). The kinds kept here:

    kind        jax event
    trace       /jax/core/compile/jaxpr_trace_duration
    lower       /jax/core/compile/jaxpr_to_mlir_module_duration
    backend     /jax/core/compile/backend_compile_duration — around
                ``compile_or_get_cached``: a compile, or a load from the
                persistent cache, the cache key's hash included
    cache_load  /jax/compilation_cache/cache_retrieval_time_sec
    cache_hit   /jax/compilation_cache/cache_hits    (a count)
    cache_miss  /jax/compilation_cache/cache_misses  (a count: a program
                compiled and written to the persistent cache)

:func:`install` registers the two listeners once per process;
``distributed.enable_compilation_cache`` calls it, and every entry point
calls that before its first compile. Each event is one tuple
``(kind, fun_name, seconds, end_ns)``, ``end_ns`` read from
``time.perf_counter_ns()`` when jax calls the listener: the
:class:`~ddlbench_tpu.telemetry.tracer.Tracer`'s clock, so a stamp splits
the events at any moment the program marks (the benchmark's window
opening, the train loop's warm-up block).

While the global tracer's ring is on, each duration event also goes into
it as ``compile/<kind>`` with ``{"fun": fun_name}`` (through
``Tracer.complete``: the event is over when jax reports it), so the CLI's
Chrome trace shows the compile path on the host track of the thread that
compiled. With the ring off an event costs one tuple appended; nothing
here runs on a step's path.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple, Union

import jax

from ddlbench_tpu.telemetry.tracer import get_tracer

DURATION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
COUNT_KINDS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}

# (kind, fun_name, seconds, end_ns); a count's seconds are 0
CompileEvent = Tuple[str, str, float, int]

# A set-up makes hundreds of events; the bound, like the ring's, keeps a
# process that compiles without end from growing without end.
_CAPACITY = 100_000


class CompileTally:
    """Bounded, thread-safe record of the compile path's events, the newest
    ``_CAPACITY`` of them (``dropped`` counts the oldest let go)."""

    def __init__(self):
        self._events: deque[CompileEvent] = deque(maxlen=_CAPACITY)
        self._lock = threading.Lock()
        self.dropped = 0

    # ---- the listeners ----

    def on_duration(self, event: str, duration_secs: float, **kw) -> None:
        kind = DURATION_KINDS.get(event)
        if kind is None:
            return
        end = time.perf_counter_ns()
        fun = str(kw.get("fun_name", ""))
        self._append((kind, fun, float(duration_secs), end))
        tracer = get_tracer()
        if tracer.enabled:
            tracer.complete("compile/" + kind,
                            end - int(duration_secs * 1e9), end,
                            {"fun": fun} if fun else None)

    def on_event(self, event: str, **_) -> None:
        kind = COUNT_KINDS.get(event)
        if kind is not None:
            self._append((kind, "", 0.0, time.perf_counter_ns()))

    def _append(self, evt: CompileEvent) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(evt)

    # ---- readout ----

    def events(self) -> List[CompileEvent]:
        with self._lock:
            return list(self._events)

    def count(self, kind: str, until_ns: Optional[int] = None) -> int:
        """Events of ``kind`` that ended at or before ``until_ns`` (all of
        them without it)."""
        return sum(1 for k, _, _, end in self.events()
                   if k == kind and (until_ns is None or end <= until_ns))

    def seconds(self, kinds: Union[str, Iterable[str]],
                until_ns: Optional[int] = None) -> float:
        """Seconds during which an event of ``kinds`` that ended at or
        before ``until_ns`` was running: the union of the events'
        intervals, so a trace nested in another (a jit called inside a
        traced function) or two threads compiling at once count once."""
        kinds = {kinds} if isinstance(kinds, str) else set(kinds)
        spans = sorted((end - int(s * 1e9), end)
                       for k, _, s, end in self.events()
                       if k in kinds and (until_ns is None or end <= until_ns))
        total, reach = 0, None
        for t0, t1 in spans:
            if reach is None or t0 > reach:
                total += t1 - t0
                reach = t1
            elif t1 > reach:
                total += t1 - reach
                reach = t1
        return total / 1e9


_TALLY: Optional[CompileTally] = None
_INSTALL_LOCK = threading.Lock()


def install() -> CompileTally:
    """Register the tally's listeners with jax, once per process; a later
    call returns the same tally, registers nothing and clears nothing."""
    global _TALLY
    with _INSTALL_LOCK:
        if _TALLY is None:
            tally = CompileTally()
            jax.monitoring.register_event_duration_secs_listener(
                tally.on_duration)
            jax.monitoring.register_event_listener(tally.on_event)
            _TALLY = tally
        return _TALLY


def installed() -> Optional[CompileTally]:
    """The process's tally, or None before :func:`install`."""
    return _TALLY


def between(t0_ns: int, t1_ns: int) -> Dict[str, float]:
    """What the installed tally gained from ``t0_ns`` to ``t1_ns``: seconds
    of trace + lower, seconds of compile or cache load, the programs
    compiled because the persistent cache lacked them and those loaded from
    it. Empty without a tally."""
    t = _TALLY
    if t is None:
        return {}
    tl = ("trace", "lower")
    return {
        "trace_lower_s": t.seconds(tl, t1_ns) - t.seconds(tl, t0_ns),
        "backend_s": t.seconds("backend", t1_ns) - t.seconds("backend", t0_ns),
        "cache_misses": (t.count("cache_miss", t1_ns)
                         - t.count("cache_miss", t0_ns)),
        "cache_hits": t.count("cache_hit", t1_ns) - t.count("cache_hit", t0_ns),
    }
