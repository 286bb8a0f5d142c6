"""Work of causal flash attention, forward + backward, from the call's
shapes (B, H, T, dh, element bytes) — not from the kernel's blocking.

FLOPs: forward QK^T and PV, backward dV, dP, dQ, dK: six matmuls of
2*B*H*T*T*dh each, halved for the causal mask. The backward's recomputation
of the scores is the implementation's cost and is not counted.
Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
writes dQ, dK, dV (12 tensors of B*H*T*dh elements; the logsumexp rows are
1/dh of one and left out)."""

EVENTS = ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv")


def work(B: int, H: int, T: int, dh: int, elem_bytes: int = 2):
    flops = 6 * 2.0 * B * H * T * T * dh / 2
    nbytes = 12.0 * B * H * T * dh * elem_bytes
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window. The call shapes are the
    configuration's: its reference module gives ``[(calls a step, keyword
    arguments of work)]`` for this cell's traffic."""
    f = b = 0.0
    for per_step, shape in ctx.reference.kernel_calls(
            "flash_attn", ctx.config, ctx.traffic):
        df, db = work(**shape)
        f, b = f + per_step * df, b + per_step * db
    return f * ctx.counters["steps"], b * ctx.counters["steps"]
