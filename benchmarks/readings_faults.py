#!/usr/bin/env python3
"""Readings that ``readings.py`` does not take or cannot hold, for a
configuration whose reference module plants faults of its own (``FAULTS``,
chosen by ``config["fault"]``) — several seeds in ONE process, on the chip, at
the cell's own size. Not part of a benchmark run: the driver never calls it.

    python3 benchmarks/readings_faults.py --workload <name> --seeds 3 \
        [--first-seed N] [--sides control,half_batch,<fault>,rounding:<r>]
        [--steps 1] [--flips bfloat16] [--out FILE]

Per seed the sound reference (float32) is computed once; then, each put in
the program's place and judged against it by the harness's own comparison
with the configuration's limits: the reference at the configuration's
control precision, the reference over half the batch, and the reference
with each of its own planted faults. One JSON line a reading, with each
number, the verdict and the numbers that failed it (every one of these has
to fail a limit). No program is built here, so the chip holds one side's
reference flow at a time and nothing else: ``flat`` and the side's ``p``,
``m``, ``v`` while ``loss_and_grads`` computes, the gradient with them at
the optimizer call (``readings.py``'s docstring), every side's numbers and
first gradient on the host. On a TPU the last line is the process's peak on
the chip (``what: process_peak``: in use + reserved scratch as ``run_cell``
reads them, and bytes a parameter), which is what a one-chip training cut is
sized by; ``--sides none`` takes the sound reference alone, for the peak of
one reference flow. A process's peak never falls, so it is the peak of the
largest side taken. Off a TPU there is no such counter and no such line.
``--sides`` names which of them to take (default: all of the above);
``rounding:<r>`` is a side reading and no fault: the reference itself at
another compute precision, e.g. ``rounding:bfloat16`` for how far the
program's own precision moves each number; ``choices:<r>`` (with
``--steps 1``, where the reference has ``choices``) the same with the
float32 reference's top-k choices put in place of its own, so that what is
left is the rounding without its flipped choices. ``--steps 1`` follows the first
step only, a third of the time: the numbers of the first step (``loss_step1``,
``grad1_*``) are what the harness computes, those of later steps are left out
of the line and the verdict is of the first step's limits alone.
``--flips <rounding>`` also reports, where the reference has ``choices``,
the share of a router's top-k choices that differ between float32 and that
rounding of the hidden states, per expert layer, over the first sequence of
the first batch.
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import readings, run_cell  # noqa: E402
from benchmarks.harness import manifest  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--sides", default=None,
                    help="comma list of control, half_batch, the reference's "
                         "FAULTS, rounding:<name>, choices:<name>, or none; "
                         "default the control, half_batch and the FAULTS")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--flips", default=None, metavar="ROUNDING")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    readings.OUT = args.out

    jax = run_cell.cached_jax()
    import jax.numpy as jnp

    from benchmarks.harness import train_driver as td
    from benchmarks.harness import weights
    from benchmarks.harness.traffic import SeededBatches
    from benchmarks.reference import common

    man = manifest.Manifest()
    ns = types.SimpleNamespace(workload=args.workload, seed=args.first_seed,
                               seconds=1.0, trace=0)
    rc = run_cell.RunContext(man, ns, jax.devices())
    config, traffic = rc.config, rc.traffic
    hp = td.hyperparameters(traffic["run_config"])
    cfg, strategy = td.build(config, traffic)
    names = [l.name for l in strategy.model.layers]
    specs = weights.flat_specs(
        jax.eval_shape(strategy.init, jax.random.key(0)).params, names)
    del strategy
    ds = config["dataset"]
    cell = rc.cell["name"]
    steps = args.steps or td.CHECK_STEPS
    later = () if steps >= td.CHECK_STEPS else ("delta3_",)  # not step 1's
    control = (config["precision"].get("train")
               or config["precision"])["control"]
    faults = tuple(getattr(rc.reference, "FAULTS", ()))
    wanted = ([] if args.sides == "none" else args.sides.split(",")
              if args.sides else ["control", "half_batch", *faults])
    half = slice(0, cfg.global_batch() // 2)
    sides = []
    for side in wanted:
        if side == "control":
            sides.append((f"control:{control}", config, control, None))
        elif side == "half_batch":
            sides.append(("fault:half_batch", config, "float32", half))
        elif side.startswith("rounding:") or (
                side.startswith("choices:") and steps == 1
                and hasattr(rc.reference, "choices")):
            sides.append((side, config, side.split(":", 1)[1], None))
        elif side in faults:
            sides.append((f"fault:{side}", dict(config, fault=side),
                          "float32", None))
        else:
            ap.error(f"--sides: {side!r} is none of control, half_batch, "
                     f"rounding:<name>, choices:<name> (--steps 1), {faults}")
    for i in range(args.seeds):
        seed = args.first_seed + i
        data = SeededBatches(seed, ds["kind"], tuple(ds["sample_shape"]),
                             config.get("vocab_size", ds["num_classes"]),
                             cfg.global_batch())
        flat = weights.make_weights(seed, specs, config["weights"])
        batches = [data.batch(0, k) for k in range(steps)]
        ref = td.reference_numbers(rc.reference, config, hp, flat, batches,
                                   "float32")
        for what, conf, rounding, rows in sides:
            if what.startswith("choices:"):
                own = jax.jit(lambda P, t: rc.reference.choices(P, t, config))
                conf = dict(config, choices=jnp.stack(
                    [own(flat, t) for t in batches[0][0]]))
            side = td.reference_numbers(rc.reference, conf, hp, flat,
                                        batches, rounding, rows)
            by = readings.judged(side, ref, config["limits"])
            by = {k: v for k, v in by.items() if not k.startswith(later)}
            by["failed"] = [n for n in by["failed"]
                            if not n.startswith(later)]
            by["correct"] = not by["failed"]
            readings.emit(cell=cell, seed=seed, what=what, steps=steps, **by)
            del side
        if args.flips and hasattr(rc.reference, "choices"):
            tokens = batches[0][0][0]
            pick = lambda r: jax.jit(lambda P, t: rc.reference.choices(
                P, t, config, common.ROUNDINGS[r]))(flat, tokens)
            a, b = pick("float32"), pick(args.flips)
            # a choice flipped: an expert chosen at float32 and not at the
            # rounding (sets of k a token; order does not count)
            kept = jnp.any(a[..., :, None] == b[..., None, :], axis=-1)
            readings.emit(cell=cell, seed=seed,
                          what=f"flips:float32-vs-{args.flips}",
                          flipped_share_by_layer=[
                              float(x) for x in 1.0 - jnp.mean(kept, (1, 2))])
        del flat, batches, ref
    if jax.default_backend() == "tpu":
        peak, n = rc.read_memory_peak(), sum(
            math.prod(shape) for shape in specs.values())
        readings.emit(cell=cell, what="process_peak",
                      device_kind=rc.device_kind, parameters=n,
                      peak_bytes=peak, bytes_per_parameter=peak / n,
                      sides=[side[0] for side in sides])
    return 0


if __name__ == "__main__":
    sys.exit(main())
