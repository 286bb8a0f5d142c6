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
    c, t = ctx.config, ctx.traffic
    N = t["run_config"]["batch_size"] * c["n_positions"]
    f, b = work(N, c["n_embd"], c["padded_vocab_size"])
    return f * ctx.counters["steps"], b * ctx.counters["steps"]
