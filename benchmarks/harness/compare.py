"""The comparison that decides ``correct``: numbers of the timed path beside
numbers of the plain reference, each with a limit of its own (the limits
live in the configuration file's ``limits`` group, set from chip readings —
PERF.md says from which)."""

from __future__ import annotations

import dataclasses
import statistics
import sys
from typing import Dict, List, Optional


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        """A number with no limit is printed and not judged (PERF.md names
        each such number with its readings)."""
        if self.limit is None:
            return True
        return self.value == self.value and self.value <= self.limit


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], leaves=None):
    """Per leaf, the gap between the program's norm and the reference's
    norm, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero). Returns
    (widest gap, its leaf, median gap)."""
    leaves = sorted(leaves if leaves is not None else ref)
    med = statistics.median(ref[k] for k in leaves)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    worst = max(leaves, key=lambda k: (gaps[k] != gaps[k], gaps[k]))  # nan
    return gaps[worst], worst, statistics.median(gaps.values())


def moving_leaves(ref_grad_norm: Dict[str, float]) -> List[str]:
    """Leaves whose parameter change is compared: those whose reference
    gradient is not nought to rounding (under a thousandth of the median
    leaf's, Adam moves a leaf by round-off alone)."""
    med = statistics.median(ref_grad_norm.values())
    return [k for k, g in ref_grad_norm.items() if g >= 1e-3 * med]


def train_numbers(prog: Dict, ref: Dict, limits: Dict) -> List[Compared]:
    """``prog`` / ``ref``: {"losses": [..], "grad_norm": {leaf: norm},
    "delta_norm": {leaf: norm}}, ``ref["matrices"]``: the leaves of two
    dimensions or more (convolution kernels, dense and embedding matrices),
    and ``prog["grad_diff"]``: {leaf: norm of the program's first gradient
    less the reference's}. Every number is computed and printed; one whose
    limit the configuration leaves null is not judged."""
    out = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out.append(Compared(f"loss_step{i}", abs(a - b) / abs(b),
                            limits.get(f"loss_step{i}")))
    gap, leaf, med = leaf_gaps(prog["grad_norm"], ref["grad_norm"])
    moving = moving_leaves(ref["grad_norm"])
    dgap, dleaf, dmed = leaf_gaps(prog["delta_norm"], ref["delta_norm"],
                                  moving)
    # the worst MATRIX leaf: where the worst leaf of all is an ill-conditioned
    # norm scale or bias and only medians are judged, this is the number that
    # sees a fault in a minority of leaves (one layer, one group)
    mats = set(ref["matrices"])
    mgap = leaf_gaps(prog["grad_norm"], ref["grad_norm"], mats)[0]
    mdgap = leaf_gaps(prog["delta_norm"], ref["delta_norm"],
                      mats & set(moving))[0]
    # first-order in rounding noise, where a gap of norms is second-order:
    # the median leaf's ||g_prog - g_ref|| against the reference's norm
    rmed = statistics.median(ref["grad_norm"].values())
    diff = statistics.median(
        d / max(ref["grad_norm"][k], rmed)
        for k, d in prog["grad_diff"].items())
    for name, value in (("grad1_norm_gap", gap), ("delta3_norm_gap", dgap),
                        ("grad1_norm_gap_matrix", mgap),
                        ("delta3_norm_gap_matrix", mdgap),
                        ("grad1_norm_gap_median", med),
                        ("delta3_norm_gap_median", dmed),
                        ("grad1_diff_median", diff)):
        out.append(Compared(name, value, limits.get(name)))
    if ref.get("norm_var"):
        # quantization noise adds its power to a batch variance: the median
        # layer's gap of the running variance's batch part (it starts at 1
        # and keeps nine tenths of that after one step)
        import numpy as np

        vgap = statistics.median(
            float(np.linalg.norm(prog["norm_var"][k] - v)
                  / np.linalg.norm(v - 0.9))
            for k, v in ref["norm_var"].items())
        out.append(Compared("norm_var_gap_median", vgap,
                            limits.get("norm_var_gap_median")))
    print(f"compare: worst gradient leaf {leaf}, worst change leaf {dleaf} "
          f"({len(moving)} of {len(ref['grad_norm'])} leaves move)",
          file=sys.stderr)
    return out


def report(numbers: List[Compared]) -> bool:
    """Print each number beside its limit as the last lines on stderr;
    True when every one holds."""
    for c in numbers:
        limit = "none" if c.limit is None else f"{c.limit:.6g}"
        print(f"compared {c.name} {c.value:.6g} limit {limit} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return all(c.ok for c in numbers)
