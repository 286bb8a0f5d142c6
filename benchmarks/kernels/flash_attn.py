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
    """(flops, bytes) of the traced window: one fwd+bwd per layer per step."""
    c, t = ctx.config, ctx.traffic
    B = t["run_config"]["batch_size"]
    f, b = work(B, c["n_head"], c["n_positions"], c["n_embd"] // c["n_head"])
    n = ctx.counters["steps"] * c["n_layer"]
    return f * n, b * n
