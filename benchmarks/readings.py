#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, many seeds in ONE
process (set-up is most of a run). Not part of a benchmark run: the driver
never calls it. On the chip, at the cell's own size:

    python3 benchmarks/readings.py --workload <name> --seeds 12 \
        --control-seeds 3 --fault-seeds 3 [--first-seed N] [--seconds S]

train cells: per seed the program's first steps against the plain reference
(the lower readings); for the first ``--control-seeds`` the reference at the
configuration's control precision in the program's place (the upper
readings); for the first ``--fault-seeds`` the planted faults (half of the
batch left out; on several chips each chip's own rows only, the exchange
left out) in the reference put in the program's place.
serve cells: per seed a short window at the cell's own load, then the
served tokens' widest logit gap and the control's.
Every reading goes through the harness's own comparison with the
configuration's limits: one JSON line per reading with each number, the
verdict (``correct``) and the numbers that ``failed`` it. ``--out FILE`` also
appends each line there with every leaf's own gaps (``leaves``: leaf ->
[gradient gap, change gap, is a matrix]), for choosing a number that
separates the readings.

What is on the chip when (P: one float32 copy of the parameters; the train
driver's docstring has the rule). A seed's program: its state (16 bytes a
parameter under Adam) and its activations, nothing of the check beside them:
the check steps leave the first moment after step 1 and the parameters after
step 3 on the host. Then the state is dropped, ``flat`` is made from the
seed, the program's gradient and change are formed from it and the two host
copies (3 P), and each side follows in turn with 4 P while ``loss_and_grads``
computes (``flat``, which all sides of a seed share, and the side's own
``p``, ``m``, ``v``) plus the reference's working set, and 5 P at the
optimizer call. A side leaves nothing on the chip: its numbers and its first
gradient (``["grad"]``) are on the host, so the seeds and sides that fit one
process are bounded by time and by host memory alone (4 bytes a parameter
for the program's gradient, the sound reference's and the side's being
judged: 6.8 GB at 560 M parameters), not by the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import compare, manifest  # noqa: E402


OUT = None


def emit(leaves=None, **kw):
    print(json.dumps(kw), flush=True)
    if OUT:
        with open(OUT, "a") as f:
            f.write(json.dumps(dict(kw, leaves=leaves)) + "\n")


def judged(prog, ref, limits):
    """The harness's numbers of ``prog`` against ``ref`` with its verdict,
    and every leaf's own gaps."""
    import statistics

    from benchmarks.harness.train_driver import gradient_differences

    prog = dict(prog, grad_diff=gradient_differences(prog["grad"],
                                                     ref["grad"]))
    numbers = compare.train_numbers(prog, ref, limits)
    by = {c.name: c.value for c in numbers}
    by["correct"] = compare.report(numbers)
    by["failed"] = [c.name for c in numbers if not c.ok]
    med = {k: statistics.median(ref[k].values())
           for k in ("grad_norm", "delta_norm")}
    by["leaves"] = {
        leaf: [abs(prog[k][leaf] - ref[k][leaf]) / max(ref[k][leaf], med[k])
               for k in ("grad_norm", "delta_norm")]
        + [leaf in ref["matrices"]] for leaf in ref["grad_norm"]}
    return by


def train(rc, args):
    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.data.prefetch import Prefetcher

    from benchmarks.harness import train_driver as td
    from benchmarks.harness import weights
    from benchmarks.harness.traffic import SeededBatches

    config, traffic = rc.config, rc.traffic
    hp = td.hyperparameters(traffic["run_config"])
    cfg, strategy = td.build(config, traffic)
    ds = config["dataset"]
    B = cfg.global_batch()
    control = (config["precision"].get("train") or config["precision"])[
        "control"]
    limits = config["limits"]
    for i in range(args.seeds):
        seed = args.first_seed + i
        data = SeededBatches(seed, ds["kind"], tuple(ds["sample_shape"]),
                             config.get("vocab_size", ds["num_classes"]), B)
        ts, specs, names = td.seeded_state(strategy, seed, config["weights"])
        stream = Prefetcher(data, strategy.shard_batch,
                            depth=cfg.prefetch_depth).stream(epoch=0)
        held, ts = td.first_steps(strategy.train_step, stream, ts, hp, names,
                                  jnp.float32(hp["lr"]))
        stream.close()
        del ts
        flat = weights.make_weights(seed, specs, config["weights"])
        batches = [data.batch(0, k) for k in range(td.CHECK_STEPS)]
        if rc.chips > 1:
            batches, flat = rc.spread(batches, flat)
        prog = td.check_numbers(held, flat, hp)
        del held

        def reference(rounding, rows=None):
            return td.reference_numbers(rc.reference, config, hp, flat,
                                        batches, rounding, rows)

        ref = reference("float32")
        emit(cell=rc.cell["name"], seed=seed, what="program",
             **judged(prog, ref, limits), losses=prog["losses"],
             ref_losses=ref["losses"])
        if i < args.control_seeds:
            emit(cell=rc.cell["name"], seed=seed, what=f"control:{control}",
                 **judged(reference(control), ref, limits))
        if i < args.probe_seeds:
            emit(cell=rc.cell["name"], seed=seed, what="probe:bits16",
                 **judged(reference("bits16"), ref, limits))
        if i < args.fault_seeds:
            emit(cell=rc.cell["name"], seed=seed, what="fault:half_batch",
                 **judged(reference("float32", slice(0, B // 2)), ref,
                          limits))
            if rc.chips > 1:
                emit(cell=rc.cell["name"], seed=seed,
                     what="fault:no_exchange",
                     **judged(reference("float32", slice(0, B // rc.chips)),
                              ref, limits))
        del flat, batches


def serve(rc, args):
    from benchmarks.harness import serve_driver as sd

    control = rc.config["precision"]["serve"]["control"]
    fns = None
    for i in range(args.seeds):
        rc.seed = args.first_seed + i
        out = sd.run(rc, shared_fns=fns, control=control)
        fns = out["jit_fns"]
        by = {c.name: c.value for c in out["numbers"]}
        limit = rc.config["limits"]["served_logit_gap"]
        emit(cell=rc.cell["name"], seed=rc.seed, what="program",
             served_logit_gap=by["served_logit_gap"],
             correct=compare.report(out["numbers"]),
             control_gap=out["control_gap"], control=control,
             control_correct=out["control_gap"] <= limit,
             requests=out["attempted"], never=out["failed"],
             tokens_per_s=out["end_to_end"]["serve_out_tokens_per_s"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--probe-seeds", type=int, default=0,
                    help="also put the reference at 16 mantissa bits in the "
                         "program's place: how ill-conditioned is a number")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    global OUT
    OUT = args.out

    jax = run_cell.cached_jax()
    man = manifest.Manifest()
    ns = types.SimpleNamespace(workload=args.workload, seed=args.first_seed,
                               seconds=args.seconds, trace=0)
    rc = run_cell.RunContext(man, ns, jax.devices())
    rc.mark = lambda phase: None
    (train if rc.traffic["kind"] == "train" else serve)(rc, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
