#!/usr/bin/env python3
"""A stand-in reference, for sizing the NEXT one-chip training cut before its
reference is written: the reference flow (``train_driver.reference_numbers``,
its three optimizer steps) ALONE in one process on a TPU, and that process's
peak in bytes a parameter. A throwaway of PR 31, no part of a benchmark run;
a cell that exists is read by ``benchmarks/readings_faults.py --sides none``.

    python3 tests/benchmark/data/room_stub.py 4,8,2048,65568 2

The first argument is ``layers,experts,hidden,vocabulary_rows``: leaves with
the shapes of a mixture-of-experts share — a tied embedding [rows, hidden],
and a layer: three stacked expert matrices [experts, hidden, hidden], a narrow
attention pair, an MLP router — under Adam, on ``TOKENS`` sequences a step.
The second is how the gradient is summed over the sequences: ``2`` as
``reference/kanana2.py`` does (a scan whose carry is the sum, each sequence's
own gradient added to it; XLA adds it leaf by leaf, so no second copy of the
parameters appears), ``1`` differentiates the scanned, rematerialized sum (the
backward scan's one accumulator; its residuals make the larger scratch).
One JSON line: the device, parameters, peak bytes (in use + reserved, as
``run_cell`` reads them), bytes a parameter, seconds, the three losses; where
the chip runs out of memory ``"out_of_memory": true`` and exit code 1. Without
a TPU it refuses (exit code 2) and prints no line: a CPU has no such peak.
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

HP = {"optimizer": "adam", "lr": 3e-4, "weight_decay": 0.0,
      "beta1": 0.9, "beta2": 0.95, "eps": 1e-8}
RULES = {"matrix": 0.02}
ATTN, ROUTER = 1360, 256  # 5.57 M and 0.59 M a layer at hidden 2048
TOKENS = (4, 512)  # sequences a step, their length
SEED = 1000


def specs(layers, experts, hidden, rows, attn=ATTN, router=ROUTER):
    out = {"embed/W": (rows, hidden)}
    for i in range(layers):
        b = f"block{i}"
        out.update({
            f"{b}/attn/w_in": (hidden, attn),
            f"{b}/attn/w_out": (attn, hidden),
            f"{b}/router/w1": (hidden, router),
            f"{b}/router/w2": (router, router),
            f"{b}/router/w3": (router, 2 * experts),
            f"{b}/experts/w_gate": (experts, hidden, hidden),
            f"{b}/experts/w_up": (experts, hidden, hidden),
            f"{b}/experts/w_down": (experts, hidden, hidden)})
    return out


def reference(layers, experts):
    """A module-like object with the plain references' ``loss_and_grads``;
    ``config["accumulators"]`` chooses how the gradient is summed."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.reference.common import HIGHEST, cross_entropy_sum

    mm = lambda a, b: jnp.matmul(a, b, precision=HIGHEST)

    def seq_loss(P, tokens, labels):
        x = P["embed/W"][tokens]
        for i in range(layers):
            b = f"block{i}"
            x = x + mm(jnp.tanh(mm(x, P[f"{b}/attn/w_in"])),
                       P[f"{b}/attn/w_out"])
            r = jax.nn.gelu(mm(x, P[f"{b}/router/w1"]))
            r = mm(jax.nn.gelu(mm(r, P[f"{b}/router/w2"])),
                   P[f"{b}/router/w3"])
            gate = jnp.max(jax.nn.softmax(r, axis=-1), axis=-1, keepdims=True)
            # the held experts' tokens by position: an even, dropless share
            xe = x.reshape(experts, -1, x.shape[-1])
            h = jax.nn.silu(mm(xe, P[f"{b}/experts/w_gate"])) \
                * mm(xe, P[f"{b}/experts/w_up"])
            x = x + gate * mm(h, P[f"{b}/experts/w_down"]).reshape(x.shape)
        return cross_entropy_sum(mm(x, P["embed/W"].T), labels)

    def loss_and_grads(P, tokens, labels, config, rnd=None):
        n_tok = labels.size
        if config["accumulators"] == 1:
            def total(P):
                step = lambda loss, xy: (
                    loss + jax.checkpoint(seq_loss)(P, *xy), None)
                return lax.scan(step, jnp.float32(0.0),
                                (tokens, labels))[0] / n_tok
            loss, grads = jax.value_and_grad(total)(P)
            return loss, grads, {}

        def step(carry, xy):
            loss, grads = carry
            l, g = jax.value_and_grad(
                lambda P: seq_loss(P, *xy) / n_tok)(P)
            return (loss + l, jax.tree.map(jnp.add, grads, g)), None

        init = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, P))
        (loss, grads), _ = lax.scan(step, init, (tokens, labels))
        return loss, grads, {}

    return types.SimpleNamespace(loss_and_grads=loss_and_grads)


def main(argv) -> int:
    if len(argv) != 2 or argv[1] not in ("1", "2"):
        print(__doc__, file=sys.stderr)
        return 2
    from benchmarks import run_cell

    jax = run_cell.cached_jax()
    device = jax.devices()[0]
    if jax.default_backend() != "tpu" or device.memory_stats() is None:
        print(f"room_stub: needs a TPU and its memory counters; jax found "
              f"{jax.default_backend()!r} ({device.device_kind})",
              file=sys.stderr)
        return 2
    from benchmarks.harness import train_driver as td
    from benchmarks.harness import weights
    from benchmarks.harness.traffic import SeededBatches

    layers, experts, hidden, rows = map(int, argv[0].split(","))
    data = SeededBatches(SEED, "tokens", TOKENS[1:], rows, TOKENS[0])
    flat = weights.make_weights(
        SEED, specs(layers, experts, hidden, rows), RULES)
    batches = [data.batch(0, k) for k in range(td.CHECK_STEPS)]
    n = sum(a.size for a in flat.values())
    out, t0 = {}, time.perf_counter()
    try:
        out["losses"] = td.reference_numbers(
            reference(layers, experts), {"accumulators": int(argv[1])}, HP,
            flat, batches, "float32")["losses"]
    except Exception as e:  # the chip ran out: that is the reading
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        out.update(out_of_memory=True, error=str(e)[:400])
    stats = device.memory_stats()
    peak = stats["peak_bytes_in_use"] + stats["peak_bytes_reserved"]
    print(json.dumps(dict(
        out, what=f"stub:{argv[0]}:accumulators{argv[1]}",
        device_kind=device.device_kind, parameters=n, peak_bytes=peak,
        peak_bytes_in_use=stats["peak_bytes_in_use"],
        peak_bytes_reserved=stats["peak_bytes_reserved"],
        bytes_limit=stats["bytes_limit"], bytes_per_parameter=peak / n,
        seconds=time.perf_counter() - t0)), flush=True)
    return 1 if out.get("out_of_memory") else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
