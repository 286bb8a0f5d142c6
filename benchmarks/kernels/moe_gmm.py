"""Work of an expert layer's grouped matrix products, forward + backward,
from the call's shapes (token-slots that reach a held expert, D, the expert
width F, G experts held, element bytes) — not from the row buffer the
program gathers them into. The shapes are the configuration's: slots at
BALANCED routing (the harness reads no counter of the step), while the
kernels' time in the trace is that of the slots the seed's router really
sent, so a run's reading is the kernels' share of their roofline over that
run's held share (PERF.md §7 row 14).

FLOPs: three products a SwiGLU expert (gate, up, down), each 2*slots*D*F
forward and twice that backward (the slots' gradient and the weights');
the rematerialized forward is the implementation's cost and is not counted.
Bytes: forward reads the slots' rows and the 3*G*D*F weights and writes the
slots' outputs; backward reads rows, output gradients and weights, writes
row gradients and weight gradients (4 row tensors of slots*D, 3 passes over
the weights; the [slots, F] intermediates are the implementation's).

The product the program runs on the chip is the Pallas grouped product of
``jax.experimental.pallas.ops.tpu.megablox`` (``models/kanana2.grouped_dot``):
its kernels' trace events are ``gmm.<n>`` (the forward products and the
slots' gradients) and ``tgmm.<n>`` (the weights' gradients); both hold
"gmm"."""

EVENTS = ("gmm",)


def work(slots: float, D: int, F: int, G: int, elem_bytes: int = 2):
    flops = 3 * 3 * 2.0 * slots * D * F
    nbytes = (4.0 * slots * D + 3 * 3.0 * G * D * F) * elem_bytes
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window, at the call shapes the
    configuration's reference module gives for this cell's traffic."""
    f = b = 0.0
    for per_step, shape in ctx.reference.kernel_calls(
            "moe_gmm", ctx.config, ctx.traffic):
        df, db = work(**shape)
        f, b = f + per_step * df, b + per_step * db
    return f * ctx.counters["steps"], b * ctx.counters["steps"]
