"""Work of paged decode attention: one query token per live row against the
row's cached keys and values, from the rows' depths — not from the layout.

Per row with L live positions (its depth + 1), per layer: FLOPs 2*2*H*dh*L
(q.K^T and p.V); bytes = K and V of the pages actually read,
ceil(L / page) * page * H * dh * kv_bytes * 2. The pool layout's padding of
the minor dims is the kernel's own cost and is not payload."""

EVENTS = ("paged_decode_attn",)


def work(live_positions, H: int, dh: int, page: int, kv_bytes: int = 4):
    flops = nbytes = 0.0
    for L in live_positions:
        flops += 4.0 * H * dh * L
        nbytes += 2.0 * (-(-L // page) * page) * H * dh * kv_bytes
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window: the decode passes the driver
    counted (``decode_calls``: the live rows' depths), each at the static
    shapes the configuration's reference module gives, ``[(calls a pass,
    keyword arguments of work)]``."""
    f = b = 0.0
    for per_pass, shape in ctx.reference.kernel_calls(
            "paged_decode_attn", ctx.config, ctx.traffic):
        for depths in ctx.counters["decode_calls"]:
            df, db = work([d + 1 for d in depths], **shape)
            f, b = f + per_pass * df, b + per_pass * db
    return f, b
