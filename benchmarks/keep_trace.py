#!/usr/bin/env python3
"""A traced run of a cell that also keeps what the reduction read, for a
human's look and for the recorded trace under ``benchmarks/data/``. Not part
of a benchmark run: the driver never calls it.

    python3 benchmarks/keep_trace.py <dir> --workload <name> --seed <n> \
        --seconds <s>

writes ``<dir>/<name>.events.json.gz`` (the event list ``harness/trace.py``
reduces) and ``<dir>/<name>.planes.json`` (the planes and lines of the
``.xplane.pb``), then prints the run's ``--trace 1`` line.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import trace  # noqa: E402


def main(argv) -> int:
    out, rest = argv[0], argv[1:]
    stem = os.path.join(out, rest[rest.index("--workload") + 1])
    load = trace.load_xplane

    def keeping(path):
        events = load(path)
        os.makedirs(out, exist_ok=True)
        with gzip.open(stem + ".events.json.gz", "wt") as f:
            json.dump(events, f)
        with open(stem + ".planes.json", "w") as f:
            json.dump(trace.describe_xplane(path), f, indent=1)
        return events

    trace.load_xplane = keeping
    return run_cell.main(rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
