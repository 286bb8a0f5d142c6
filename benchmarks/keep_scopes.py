#!/usr/bin/env python3
"""A traced run of a train cell that also keeps, cut down to two steps, the
pair the scope readers join: the event list ``harness/trace.py`` reduces and
the scope table of the SAME program (``harness/scopes.py``). For the
recorded pair under ``benchmarks/data/``, which the tests put through the
readers and which no later compile can change. Not part of a benchmark run:
the driver never calls it.

    python3 benchmarks/keep_scopes.py <dir> --workload <name> --seed <n> \\
        --seconds <s>

writes ``<dir>/<name>.2steps.scoped.events.json.gz`` and
``<dir>/<name>.2steps.scope_table.json.gz`` (only the instructions the two
steps ran), says on stderr how many ``bench/*`` and ``ddl/*`` host spans the
profile held, and prints the run's ``--trace 1`` line.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import scopes, trace  # noqa: E402

STEPS = 2


def cut(events, table, steps_traced: int, steps: int = STEPS):
    """The first ``steps`` whole steps of device 0's timeline, rebased to 0,
    under a ``bench/window`` span that brackets exactly them. A step starts
    at the first instruction of the step's own program (``table``) that
    runs once a step; the batch generator's few events stay where they
    fell."""
    (name, evs), = sorted(events["devices"].items())[:1]
    lo, hi = next((s, e) for n, s, e in events["host"]
                  if n == trace.WINDOW_SPAN)
    evs = sorted((e for e in evs if lo <= e[1] and e[2] <= hi),
                 key=lambda e: e[1])
    count = {}
    for n, _, _ in evs:
        count[n] = count.get(n, 0) + 1
    first = next(n for n, _, _ in evs if count[n] == steps_traced
                 and table.get(n, "").startswith("jit(train_step)"))
    starts = [s for n, s, _ in evs if n == first]
    t0, t_end = starts[0], starts[steps]
    evs = [e for e in evs if t0 <= e[1] < t_end]
    t1 = max(e for _, _, e in evs)
    host = [[trace.WINDOW_SPAN, 0.0, t1 - t0]] + [
        [n, max(s, t0) - t0, min(e, t1) - t0] for n, s, e in events["host"]
        if n != trace.WINDOW_SPAN and s < t1 and e > t0]
    return {"devices": {name: [[n, s - t0, e - t0] for n, s, e in evs]},
            "host": host}


def host_span_counts(path):
    """How many ``bench/*`` (the benchmark's) and ``ddl/*`` (the program's
    own, ``telemetry/tracer.py``) host spans the ``.xplane.pb`` holds."""
    import jax.profiler

    counts = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench/", "ddl/")):
                        counts[e.name] = counts.get(e.name, 0) + 1
    return counts


def main(argv) -> int:
    out, rest = argv[0], argv[1:]
    stem = os.path.join(out, rest[rest.index("--workload") + 1])
    kept = {}
    load, reduce_trace = trace.load_xplane, run_cell.RunContext.reduce_trace

    def keeping(path):
        kept["events"] = load(path)
        kept["host_spans"] = host_span_counts(path)
        return kept["events"]

    def remembering(rc):
        kept["rc"] = rc
        return reduce_trace(rc)

    trace.load_xplane = keeping
    run_cell.RunContext.reduce_trace = remembering
    code = run_cell.main(rest + ["--trace", "1"])
    if code or "events" not in kept:
        return code or 1
    rc = kept["rc"]
    table = scopes.scope_table(scopes.step_hlo(rc))
    events = cut(kept["events"], table, rc.counters["steps"])
    ran = {n for n, _, _ in next(iter(events["devices"].values()))}
    table = {n: op for n, op in table.items() if n in ran}
    os.makedirs(out, exist_ok=True)
    with gzip.open(f"{stem}.{STEPS}steps.scoped.events.json.gz", "wt") as f:
        json.dump(events, f)
    with gzip.open(f"{stem}.{STEPS}steps.scope_table.json.gz", "wt") as f:
        json.dump(table, f, indent=0, sort_keys=True)
    print(f"keep_scopes: host spans {json.dumps(kept['host_spans'])}",
          file=sys.stderr)
    print(f"keep_scopes: {len(ran)} instruction names in {STEPS} steps, "
          f"{len(table)} of them in the step's table", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
