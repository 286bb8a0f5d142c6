"""Work of paged chunked-prefill attention: a chunk of C real query tokens
at stream positions [start, start + C) against keys [0, start + C).

Per chunk, per layer: FLOPs 2*2*H*dh * sum over queries of their visible
keys; bytes = K and V of the pages up to the chunk's end plus the chunk's
own Q and O. Padding of a short last chunk is the implementation's cost."""

EVENTS = ("paged_chunk_attn",)


def work(start: int, n_real: int, H: int, dh: int, page: int,
         kv_bytes: int = 4, q_bytes: int = 4):
    end = start + n_real
    visible = sum(range(start + 1, end + 1))
    flops = 4.0 * H * dh * visible
    nbytes = (2.0 * (-(-end // page) * page) * H * dh * kv_bytes
              + 2.0 * n_real * H * dh * q_bytes)
    return flops, nbytes


def calls(ctx):
    """(flops, bytes) of the traced window: the chunks the driver counted
    (``prefill_calls``: start, real tokens), each at the static shapes the
    configuration's reference module gives, ``[(calls a chunk, keyword
    arguments of work)]``."""
    f = b = 0.0
    for per_chunk, shape in ctx.reference.kernel_calls(
            "paged_chunk_attn", ctx.config, ctx.traffic):
        for start, n_real in ctx.counters["prefill_calls"]:
            df, db = work(start, n_real, **shape)
            f, b = f + per_chunk * df, b + per_chunk * db
    return f, b
