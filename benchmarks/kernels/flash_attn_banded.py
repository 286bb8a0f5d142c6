"""Work of causal flash attention under a sliding window, forward +
backward, from the call's shapes (B, H, T, dh, window, element bytes) — not
from the kernel's blocking. A query at position i sees the keys j with
``i - window < j <= i`` (``window`` keys, its own among them); ``window`` 0
is plain causal attention, every ``j <= i``.

FLOPs: forward QK^T and PV, backward dV, dP, dQ, dK: six matmuls of
2*B*H*dh a (query, key) pair, over the pairs INSIDE the mask (``pairs``):
T (T + 1) / 2 without a window, W T - W (W - 1) / 2 with one of W <= T.
Tiles the kernels visit and mask in part are their blocking's cost, the
backward's recomputation of the scores the implementation's; neither is
counted. (``flash_attn.work`` counts T * T / 2 pairs and knows no window: on
a window layer it would credit the kernels with work they are not asked
for.)
Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
writes dQ, dK, dV: 12 tensors of B*H*T*dh elements. With fewer key/value
heads than query heads six of them are smaller, so this is too many; at the
shapes that run FLOPs bound the call several times over (T 16,384, dh 128,
window 4,096: 61 us of bytes a head against 458 us of FLOPs), and the
least time is the FLOPs'."""

EVENTS = ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv")


def pairs(T: int, window: int = 0) -> float:
    """(query, key) pairs inside the mask of one head's T x T scores."""
    if not window or window >= T:
        return T * (T + 1) / 2
    return window * T - window * (window - 1) / 2


def work(B: int, H: int, T: int, dh: int, window: int = 0,
         elem_bytes: int = 2):
    flops = 6 * 2.0 * B * H * dh * pairs(T, window)
    nbytes = 12.0 * B * H * T * dh * elem_bytes
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window. The call shapes are the
    configuration's: its reference module gives ``[(calls a step, keyword
    arguments of work)]`` for this cell's traffic, one entry a kind of
    layer."""
    f = b = 0.0
    for per_step, shape in ctx.reference.kernel_calls(
            "flash_attn_banded", ctx.config, ctx.traffic):
        df, db = work(**shape)
        f, b = f + per_step * df, b + per_step * db
    return f * ctx.counters["steps"], b * ctx.counters["steps"]
