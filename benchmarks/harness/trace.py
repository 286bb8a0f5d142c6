"""From a profiler trace to numbers: device busy/idle, time per kernel name,
collective time exposed, top device ops and the idle gaps by what the host
was doing. Two stages, so that the arithmetic is testable on a recorded
event list without the profiler:

    load_xplane(path)  -> events   (reads the .xplane.pb with jax only)
    reduce(events)     -> Summary  (pure interval arithmetic)

``events`` is plain data::

    {"devices": {"/device:TPU:0": [[name, start_s, end_s], ...], ...},
     "host":    [[name, start_s, end_s], ...]}    # the benchmark's own spans

Device events are the leaf operations of the "XLA Ops" line of each device
plane. Host events are the ``bench/...`` ``jax.profiler.TraceAnnotation``
spans the window drivers write; ``bench/window`` brackets the traced window.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from benchmarks.harness import intervals as iv

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start..done of async copies and collectives
HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
ASYNC_PREFIX = "async:"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


def load_xplane(path: str) -> Dict:
    """Read a ``.xplane.pb`` into the event lists above (seconds)."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for e in line.events:
                    name = short_name(e.name)
                    if line.name == ASYNC_LINE:
                        # an async op's span is start..done, most of it
                        # overlapped: only collectives are read from it
                        if not COLLECTIVE.search(name):
                            continue
                        name = ASYNC_PREFIX + name
                    s = e.start_ns * 1e-9
                    evs.append([name, s, s + e.duration_ns * 1e-9])
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = e.start_ns * 1e-9
                        host.append([e.name, s, s + e.duration_ns * 1e-9])
    return {"devices": devices, "host": host}


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a Pallas
    kernel keeps its name as the instruction's name."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def describe_xplane(path: str) -> Dict:
    """Planes, lines and event counts — for a human's first look."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "first": [[e.name, e.start_ns, e.duration_ns]
                          for e in evs[:5]]}
        out[plane.name] = lines
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices of the union of device-op intervals
    n_devices: int
    op_seconds: Dict[str, float]  # by event name, mean over devices
    collective_exposed_s: float  # of the device that shows most of it
    collective_s: float  # mean over devices
    idle_gaps: List[Tuple[str, float]]  # by host span, device 0, sorted

    def kernel_seconds(self, names) -> float:
        """Summed device time of the events whose name holds one of
        ``names`` (a kernel's name is a substring of its trace event)."""
        return sum(t for op, t in self.op_seconds.items()
                   if any(n in op for n in names))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds.items(), key=lambda x: -x[1])[:k]


def reduce(events: Dict) -> Summary:
    host = [(n, s, e) for n, s, e in events["host"]]
    wins = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = wins[0]
    devices = events["devices"]
    if not devices:
        raise ValueError("trace holds no device plane")
    busy = exposed = coll_total = 0.0
    op_seconds: Dict[str, float] = defaultdict(float)
    gaps0: List[Tuple[float, float]] = []
    for di, name in enumerate(sorted(devices)):
        spans, coll, comp = [], [], []
        for op, s, e in devices[name]:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if op.startswith(ASYNC_PREFIX):
                coll.append((s, e))
                continue
            spans.append((s, e))
            op_seconds[op] += e - s
            (coll if COLLECTIVE.search(op) else comp).append((s, e))
        busy += iv.total(spans)
        coll_total += iv.total(coll)
        exposed = max(exposed, iv.total(iv.subtract(coll, comp)))
        if di == 0:
            gaps0 = iv.gaps(spans, lo, hi)
    n = len(devices)
    # each idle gap goes to the benchmark span that covers most of it; the
    # drivers' spans follow one another on one thread, so a bisect finds them
    by_host: Dict[str, float] = defaultdict(float)
    spans = sorted((s, e, hn) for hn, s, e in host if hn != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    for gs, ge in gaps0:
        best, best_cov = "(no benchmark span)", 0.0
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(spans) and spans[i][0] < ge:
            cov = min(spans[i][1], ge) - max(spans[i][0], gs)
            if cov > best_cov:
                best, best_cov = spans[i][2], cov
            i += 1
        by_host[best] += ge - gs
    return Summary(
        window_s=hi - lo, busy_s=busy / n, n_devices=n,
        op_seconds={k: v / n for k, v in op_seconds.items()},
        collective_exposed_s=exposed, collective_s=coll_total / n,
        idle_gaps=sorted(by_host.items(), key=lambda x: -x[1]))
