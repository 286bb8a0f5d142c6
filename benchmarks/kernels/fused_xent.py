"""Work of the fused output projection + cross-entropy, forward + backward,
from the call's shapes (N rows, D, V, element bytes).

FLOPs: logits = h W (2NDV), dh = dlogits W^T (2NDV), dW = h^T dlogits
(2NDV); the backward's recomputation of the logits is not counted.
Bytes: read h and W, write dh and dW (W-shaped, float32 accumulate is the
implementation's choice: counted at the element size), labels left out."""

EVENTS = ("fused_xent_fwd", "fused_xent_dh", "fused_xent_dw")


def work(N: int, D: int, V: int, elem_bytes: int = 2):
    flops = 3 * 2.0 * N * D * V
    nbytes = 2.0 * (N * D + D * V) * elem_bytes
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window, at the call shapes the
    configuration's reference module gives for this cell's traffic."""
    f = b = 0.0
    for per_step, shape in ctx.reference.kernel_calls(
            "fused_xent", ctx.config, ctx.traffic):
        df, db = work(**shape)
        f, b = f + per_step * df, b + per_step * db
    return f * ctx.counters["steps"], b * ctx.counters["steps"]
