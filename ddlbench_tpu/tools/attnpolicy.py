"""Check the auto-dispatch decision table against measured attnbench sweeps.

Reads every ``perf_runs/attnsweep_*.json`` (and legacy attn_crossover.json)
produced by tools/attnbench.py median-of-N sweeps, computes the measured
winner per (T, B, prefix) cell, and reports where
``ops.flash_attention.flash_pays_off`` disagrees: policy from medians,
re-checkable whenever a sweep is re-run on the chip.

One JSON document on stdout:
    {"cells": [...], "disagreements": [...], "agreement_pct": N}

Cells inside the +-noise margin (default 7%) count as ties and never
disagree. Exit code 1 if any out-of-margin disagreement exists.

Usage:
    python -m ddlbench_tpu.tools.attnpolicy [--dir perf_runs]
        [--noise-margin 0.07]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_cells(run_dir: str):
    cells = []
    for path in sorted(glob.glob(os.path.join(run_dir, "attnsweep_*.json"))) \
            + [os.path.join(run_dir, "attn_crossover.json")]:
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if "flash_speedup" in row:
                    cells.append({
                        "T": row["T"], "B": row["B"],
                        "prefix": row.get("prefix", 0),
                        "flash_speedup": row["flash_speedup"],
                        # rows without a repeats stamp predate median
                        # support (the round-3 single-shot sweep)
                        "repeats": row.get("repeats", 1),
                        "source": os.path.basename(path),
                    })
    return cells


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dir", default="perf_runs")
    p.add_argument("--noise-margin", type=float, default=0.07,
                   help="speedups within 1 +- margin count as ties")
    args = p.parse_args(argv)

    from ddlbench_tpu.ops.flash_attention import flash_pays_off

    raw = load_cells(args.dir)
    # aggregate repeated measurements of the same (T, B, prefix) cell to the
    # MEDIAN — legacy single-shot rows (attn_crossover.json) and fresh
    # median-of-5 sweeps judge each cell once, not once per artifact line
    import statistics

    by_cell: dict = {}
    for c in raw:
        by_cell.setdefault((c["T"], c["B"], c["prefix"]), []).append(c)
    cells = []
    for (T, B, prefix), rows in sorted(by_cell.items()):
        cells.append({
            "T": T, "B": B, "prefix": prefix,
            "flash_speedup": round(statistics.median(
                r["flash_speedup"] for r in rows), 3),
            "num_measurements": len(rows),
            # a cell is trustworthy once ANY of its rows was itself a
            # median over >= 3 timed loops (attnbench --repeats); the
            # round-3 single-shot rows only ever count as provisional
            "measured_with_medians": any(r["repeats"] >= 3 for r in rows),
            "sources": sorted({r["source"] for r in rows}),
        })
    disagreements = []
    decided = 0
    for c in cells:
        s = c["flash_speedup"]
        lo, hi = 1.0 - args.noise_margin, 1.0 + args.noise_margin
        if lo <= s <= hi:
            c["winner"] = "tie"
            continue
        c["winner"] = "flash" if s > 1.0 else "xla"
        c["policy"] = ("flash" if flash_pays_off(c["T"], c["B"], c["prefix"])
                       else "xla")
        decided += 1
        if c["policy"] != c["winner"]:
            disagreements.append(c)
    # only median-backed cells gate (exit code); single-shot legacy rows are
    # reported as provisional — the exact noise the policy exists to discount
    hard = [c for c in disagreements if c["measured_with_medians"]]
    doc = {
        "num_cells": len(cells),
        "num_decided": decided,
        "agreement_pct": round(
            100.0 * (decided - len(disagreements)) / max(1, decided), 1),
        "disagreements": hard,
        "provisional_disagreements": [
            c for c in disagreements if not c["measured_with_medians"]],
        "cells": cells,
    }
    print(json.dumps(doc))
    return 1 if hard else 0


if __name__ == "__main__":
    sys.exit(main())
