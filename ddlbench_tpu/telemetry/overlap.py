"""Comm/compute overlap-fraction reducer over Chrome-trace files.

The point of the bucketed dp engine (``--comm-buckets``, parallel/dp.py) is
that collective wire time hides under compute. This module turns a trace
into the single number that says whether it actually did: the fraction of
total COMMUNICATION span time that ran concurrently with at least one
COMPUTE span::

    overlap_fraction = |union(comm) ∩ union(compute)| / |union(comm)|

Works on a trace in the Chrome trace-event JSON format, and means
something on a DEVICE trace only: an XLA trace exported from ``--trace-dir``
via Perfetto/TensorBoard, where comm spans are the collective ops
(``all-reduce``, ``reduce-scatter``, ``all-gather``, ...; the dp engine's
carry ``grad_sync/bucket<b>`` in their op_name) and compute spans the
fusions. A ``--trace`` host span trace holds no communication span: the
host dispatches one program and the collectives run on the device.

Spans are classified by name prefix (case-insensitive), and intervals are
unioned ACROSS tracks before intersecting — an async collective on a
separate stream track overlapping a fusion on the compute track is
precisely the signal. Container spans that would blanket the timeline
(``dp_explicit_update``, ``train_step``, epochs) are excluded from the
default compute set by prefix denylist.

CLI::

    python -m ddlbench_tpu.telemetry.overlap trace.json \
        [--comm all-reduce,reduce-scatter] [--compute fusion,dot,conv]
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# Default comm-span prefixes: the op names XLA device traces use for
# collectives.
COMM_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "psum", "ppermute", "send", "recv",
)

# Host-trace container/bookkeeping spans that span the whole step and must
# not count as "compute running under the collective".
CONTAINER_PREFIXES = (
    "dp_explicit_update", "train_step", "epoch", "run", "warmup",
    "checkpoint", "eval", "prefetch_wait", "sync",
)


def _matches(name: str, prefixes: Sequence[str]) -> bool:
    low = name.lower()
    return any(low.startswith(p.lower()) for p in prefixes)


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _total(merged: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merged)


def _intersection(a: List[Tuple[float, float]],
                  b: List[Tuple[float, float]]) -> float:
    """Total length of the intersection of two DISJOINT sorted lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def _iter_complete_events(doc: Any) -> Iterable[Dict[str, Any]]:
    """'X' (complete) events from a trace dict, event list, or Tracer."""
    if hasattr(doc, "events"):  # a live telemetry.Tracer
        from ddlbench_tpu.telemetry.export import chrome_trace_dict

        doc = chrome_trace_dict(doc)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    for e in events:
        if isinstance(e, dict) and e.get("ph") == "X" \
                and "ts" in e and "dur" in e:
            yield e


def overlap_fraction(trace: Any,
                     comm_prefixes: Sequence[str] = COMM_PREFIXES,
                     compute_prefixes: Optional[Sequence[str]] = None,
                     ) -> Dict[str, Any]:
    """Reduce a trace to its comm/compute overlap figures.

    ``trace``: a Chrome trace dict (``{"traceEvents": [...]}``), a bare
    event list, or a live Tracer. ``compute_prefixes`` None means "every
    complete span that is neither comm nor a container". Returns a dict
    with total/overlapped comm seconds, the overlap fraction (0 when no
    comm spans exist), span counts, and summed ``wire_bytes`` args per
    comm span name (where a trace's events carry that argument).
    """
    comm_iv: List[Tuple[float, float]] = []
    compute_iv: List[Tuple[float, float]] = []
    comm_spans = compute_spans = 0
    wire_bytes: Dict[str, float] = {}
    for e in _iter_complete_events(trace):
        name = str(e.get("name", ""))
        t0 = float(e["ts"])
        t1 = t0 + float(e["dur"])
        if _matches(name, comm_prefixes):
            comm_iv.append((t0, t1))
            comm_spans += 1
            args = e.get("args") or {}
            if "wire_bytes" in args:
                wire_bytes[name] = (wire_bytes.get(name, 0.0)
                                    + float(args["wire_bytes"]))
        elif compute_prefixes is not None:
            if _matches(name, compute_prefixes):
                compute_iv.append((t0, t1))
                compute_spans += 1
        elif not _matches(name, CONTAINER_PREFIXES):
            compute_iv.append((t0, t1))
            compute_spans += 1
    comm = _merge(comm_iv)
    compute = _merge(compute_iv)
    comm_us = _total(comm)
    overlapped_us = _intersection(comm, compute)
    from ddlbench_tpu.telemetry.export import trace_truncation

    return {
        "comm_s": comm_us / 1e6,  # trace ts/dur are microseconds
        "overlapped_s": overlapped_us / 1e6,
        "overlap_fraction": (overlapped_us / comm_us) if comm_us else 0.0,
        "comm_spans": comm_spans,
        "compute_spans": compute_spans,
        "wire_bytes": wire_bytes,
        # > 0 = the ring dropped events: the fractions under-count
        "dropped_events": trace_truncation(trace),
    }


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="overlap", description=__doc__)
    p.add_argument("trace", help="Chrome trace-event JSON file "
                                 "(--trace output or an exported XLA trace)")
    p.add_argument("--comm", default=None,
                   help="comma list of comm span-name prefixes "
                        f"(default: {','.join(COMM_PREFIXES[:4])},...)")
    p.add_argument("--compute", default=None,
                   help="comma list of compute span-name prefixes "
                        "(default: every non-comm, non-container span)")
    args = p.parse_args(argv)
    with open(args.trace) as f:
        doc = json.load(f)
    from ddlbench_tpu.telemetry.export import warn_if_truncated

    warn_if_truncated(doc, "overlap")
    comm = (tuple(s for s in args.comm.split(",") if s) if args.comm
            else COMM_PREFIXES)
    compute = (tuple(s for s in args.compute.split(",") if s)
               if args.compute else None)
    print(json.dumps(overlap_fraction(doc, comm, compute)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
