"""Decoder-only transformer LM as a flat layer chain.

This is the framework's sequence workload — the modern analog of the
reference's GNMT translation workload (pipedream-fork/{runtime,profiler}/
translation, SURVEY.md §2 C13), re-designed rather than translated: a causal
transformer whose blocks are pipeline-atomic layers, so the SAME model runs
under single/dp/gpipe/pipedream, and whose attention has a sequence-parallel
ring implementation (parallel/sp.py) for long-context training — the
capability the reference approximates spatially with its "highres" dataset
(SURVEY.md §5.7).

Arch variants: transformer_s (8 x d512), transformer_m (12 x d768).
Pre-LN blocks, learned positions, GELU MLP (4x), untied LM head.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models.layers import Layer, LayerModel, axis_context
from ddlbench_tpu.telemetry import scopes

LN_EPS = 1e-5

_VARIANTS = {
    # _t is the test/smoke size: big enough to exercise every code path
    # (attention, MLP, fused head), small enough for 1-core CPU compiles.
    "transformer_t": dict(d_model=32, n_layers=2, n_heads=4),
    "transformer_s": dict(d_model=512, n_layers=8, n_heads=8),
    "transformer_m": dict(d_model=768, n_layers=12, n_heads=12),
}

class sequence_parallel(axis_context):
    """Context manager: trace model applies in sequence-parallel mode. When
    active (parallel/sp.py enters it inside its shard_map), embed offsets
    positions by the shard index and attention runs the ring algorithm over
    the named mesh axis. One model definition serves both modes."""

    _stack: list = []


def _seq_axis():
    return sequence_parallel.current()


class tensor_parallel(axis_context):
    """Context manager: trace model applies in Megatron-style tensor-parallel
    mode over ``num_shards`` shards of a named mesh axis (parallel/tpp.py
    enters it inside its shard_map). When active, each shard holds the
    slices produced by :func:`tp_split_layer_params` — attention runs its
    local contiguous head group (wqkv column-slice, wo row-slice) and the
    MLP its local hidden columns (w1/b1 column-slice, w2 row-slice) — and
    the two row-parallel projections psum over the axis. Activations stay
    replicated across shards, so LN/bias/embedding leaves are shared
    (their gradients all-reduce via the strategy's replicated param path).
    """

    _stack: list = []

    def __init__(self, axis: str, num_shards: int):
        self.axis = (axis, int(num_shards))  # pushed by axis_context


def _tp_ctx():
    return tensor_parallel.current()


# Transformer-block leaves sliced per TP shard; everything else (LN scales,
# the output bias b2, embeddings, heads) is replicated across shards.
TP_SLICED_KEYS = ("wqkv", "wo", "w1", "b1", "w2")


def tp_split_layer_params(p, n: int):
    """Split one layer's params for n-way tensor parallelism.

    Returns ``(shards, repl)``: ``shards[s]`` is shard s's dict of sliced
    leaves and ``repl`` the shared remainder; a layer that is not a dense
    transformer block (no wqkv/wo/w1/w2 — embeddings, heads, MoE blocks
    whose FFN is expert-routed) is fully replicated (``shards[s] == {}``).
    Head alignment: the contiguous d/n column group of wqkv covers whole
    heads iff n divides n_heads — asserted at trace time in
    attention_sublayer, where the head count is known.
    """
    if not (isinstance(p, dict) and {"wqkv", "wo", "w1", "w2"} <= set(p)):
        return [{} for _ in range(n)], p
    d = p["wo"].shape[1]
    f = p["w1"].shape[1]
    if d % n or f % n:
        raise ValueError(
            f"tensor parallelism: d_model={d} / mlp width={f} not divisible "
            f"by tp_size={n}")
    dl, fl = d // n, f // n
    shards = [{
        # wqkv columns are q|k|v blocks of d each; slice the SAME head
        # group out of each block and re-concatenate so the apply-side
        # jnp.split(qkv, 3) still lands on q/k/v
        "wqkv": p["wqkv"].reshape(d, 3, d)[:, :, s * dl:(s + 1) * dl]
                .reshape(d, 3 * dl),
        "wo": p["wo"][s * dl:(s + 1) * dl, :],
        "w1": p["w1"][:, s * fl:(s + 1) * fl],
        "b1": p["b1"][s * fl:(s + 1) * fl],
        "w2": p["w2"][s * fl:(s + 1) * fl, :],
    } for s in range(n)]
    repl = {k: v for k, v in p.items() if k not in TP_SLICED_KEYS}
    return shards, repl


@scopes.scope(scopes.LN)
def layer_norm(p, x):
    """f32-accumulated LayerNorm over the feature axis, compute-dtype out."""
    mean = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    mean2 = jnp.mean(lax.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = lax.rsqrt(jnp.maximum(mean2 - lax.square(mean), 0.0) + LN_EPS)
    y = (x.astype(jnp.float32) - mean) * inv
    return (y.astype(x.dtype) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _ln_init(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def _dense_init(key, din, dout, std=0.02):
    return jax.random.normal(key, (din, dout), jnp.float32) * std


def shard_positions(pos_table: jax.Array, T: int):
    """(position embeddings [T, d], absolute positions [T]) for the local
    sequence shard: rows [0, T) outside sequence parallelism, this shard's
    contiguous slice (axis_index * T offset) inside it. Single home for the
    shard layout, shared by every embedding (transformer + seq2seq)."""
    axis = _seq_axis()
    if axis is None:
        return pos_table[:T], jnp.arange(T)
    offset = lax.axis_index(axis) * T
    return (lax.dynamic_slice_in_dim(pos_table, offset, T, axis=0),
            offset + jnp.arange(T))


def embed(name: str, vocab: int, d_model: int, max_len: int) -> Layer:
    def init(key, in_shape):
        (T,) = in_shape
        k1, k2 = jax.random.split(key)
        p = {
            "tok": _dense_init(k1, vocab, d_model),
            "pos": _dense_init(k2, max_len, d_model),
        }
        return p, {}, (T, d_model)

    @scopes.scope(scopes.EMBED)
    def apply(p, s, x, train):
        # x: [B, T] int32 (T = local shard length under sequence parallelism)
        pos, _ = shard_positions(p["pos"], x.shape[1])
        y = jnp.take(p["tok"], x, axis=0) + pos
        return y, s

    def decode(p, s, cache, x, pos):
        # x: [B, 1] int32 at dynamic absolute position `pos`
        pe = lax.dynamic_slice_in_dim(p["pos"], pos, 1, axis=0)
        return jnp.take(p["tok"], x, axis=0) + pe, cache

    def serve_prefill(p, s, pool, table, x, start, npl, page):
        # x: [R, C] chunk at positions [start, start + C); padded positions
        # past the position table are clipped (their outputs are discarded)
        C = x.shape[1]
        pe = jnp.take(p["pos"], start + jnp.arange(C), axis=0)
        return jnp.take(p["tok"], x, axis=0) + pe, pool

    def serve_decode(p, s, pool, table, x, pos, npl, page):
        # x: [B, 1] at PER-ROW positions pos [B] (each row its own request)
        pe = jnp.take(p["pos"], pos, axis=0)[:, None]
        return jnp.take(p["tok"], x, axis=0) + pe, pool

    def serve_verify(p, s, pool, table, x, pos0, npl, page):
        # x: [B, W] draft spans at per-row positions [pos0, pos0 + W);
        # pad positions past the table clip (their outputs are discarded)
        W = x.shape[1]
        pe = jnp.take(p["pos"], pos0[:, None] + jnp.arange(W), axis=0)
        return jnp.take(p["tok"], x, axis=0) + pe, pool

    from ddlbench_tpu.models.layers import ServeOps

    return Layer(name, init, apply, decode=decode,
                 serve=ServeOps(None, serve_prefill, serve_decode,
                                serve_verify))


def causal_attention(q, k, v, q_offset: int = 0, k_offset: int = 0,
                     prefix_len: int = 0, backend: str = "auto",
                     window: int = 0):
    """Masked attention for blocks of a causal (or prefix-LM) sequence.

    q: [B, H, Tq, Dh]; k: [B, K, Tk, Dh]; v: [B, K, Tk, Dv] (Dv = Dh but
    for latent attention, models/kanana2.py: 192 and 128; K = H but for
    grouped queries, models/zaya.py: query head j attends to key/value head
    j // (H / K)); the scale is
    1/sqrt(Dh) and the output is Dv wide on both paths. Offsets give each block's absolute
    position so the same primitive serves full attention (offsets 0) and ring
    attention over sequence shards (parallel/sp.py). ``prefix_len`` > 0 adds
    the prefix-LM rule: key positions < prefix_len are visible to every query
    (the seq2seq source segment, models/seq2seq.py). ``window`` > 0 adds a
    sliding window: the query at position i sees the keys j with
    i - window < j <= i (models/smallthinker.py; 0: none). ``backend``
    ("auto" | "flash" | "xla", config.ATTENTION_BACKENDS) goes to
    ops/flash_attention.flash_dispatch, which says whether this call takes
    the fused Pallas kernel — same prefix rule, with block-level skipping —
    or the einsum below.
    """
    from ddlbench_tpu.ops.flash_attention import (flash_attention,
                                                  flash_dispatch)

    if window and prefix_len:
        raise ValueError("causal_attention: a window with a prefix is not "
                         "written")
    use_flash, interpret = flash_dispatch(backend, q, k, v, prefix_len)
    if use_flash:
        return flash_attention(q, k, v, q_offset, k_offset, prefix_len,
                               interpret=interpret, window=window)
    dh = q.shape[-1]
    if k.shape[1] != q.shape[1]:  # grouped queries: a group's heads share
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1)
                for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    q_pos = q_offset + jnp.arange(q.shape[2])[:, None]
    k_pos = k_offset + jnp.arange(k.shape[2])[None, :]
    ok = q_pos >= k_pos
    if prefix_len:
        ok = ok | (k_pos < prefix_len)
    if window:
        ok = ok & (k_pos > q_pos - window)
    scores = jnp.where(ok, scores, -jnp.inf)
    # numerically safe softmax that tolerates fully-masked rows
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(scores - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", e / jnp.maximum(z, 1e-20), v)


def ring_attention(q, k, v, axis: str, prefix_len: int = 0,
                   backend: str = "auto"):
    """Causal (or prefix-LM) attention over a sequence sharded on mesh axis
    `axis`.

    Each device holds the Q/K/V block for its sequence shard; K/V blocks rotate
    around the ring with `lax.ppermute` while a streaming (online-softmax)
    accumulator — running max m, normalizer l, weighted sum acc — combines the
    partial attention of the local queries against each visiting block. This is
    blockwise/ring attention: peak memory is O(T_local^2) instead of O(T^2),
    and the ring transfers ride ICI neighbor links. ``prefix_len`` > 0 adds
    the prefix-LM rule on ABSOLUTE key positions (the seq2seq source segment
    is globally visible), so sequence-parallel translation works even when
    the source spans multiple shards.

    On TPU the causal (prefix_len == 0) path runs each visiting block through
    the fused Pallas kernel (_ring_attention_flash) instead of the einsum
    below; the prefix-LM path keeps the einsum (its visible-key count per
    block is data-dependent on the shard index, which the kernel's static
    offsets can't express).
    """
    from ddlbench_tpu.ops.flash_attention import flash_dispatch

    use_flash, interpret = flash_dispatch(backend, q, k, v, prefix_len)
    if use_flash and prefix_len == 0:
        return _ring_attention_flash(q, k, v, axis, interpret)
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    B, H, Tl, dh = q.shape
    qf = q.astype(jnp.float32)
    q_pos = idx * Tl + jnp.arange(Tl)[:, None]  # absolute query positions

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - i) % n  # which shard's K/V we hold this round
        k_pos = src * Tl + jnp.arange(Tl)[None, :]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        s = s / math.sqrt(dh)
        ok = q_pos >= k_pos
        if prefix_len:
            ok = ok | (k_pos < prefix_len)
        s = jnp.where(ok, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, m_new, l, acc), None

    from ddlbench_tpu.parallel.common import vary

    m0 = vary(jnp.full((B, H, Tl, 1), -jnp.inf, jnp.float32), (axis,))
    l0 = vary(jnp.zeros((B, H, Tl, 1), jnp.float32), (axis,))
    # the output is as wide as v, which need not be q's width
    acc0 = vary(jnp.zeros((B, H, Tl, v.shape[-1]), jnp.float32), (axis,))
    (k, v, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-20)).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis: str, interpret: bool):
    """Ring attention with the fused kernel per visiting block.

    Each ring step classifies the visiting K/V block against the local shard
    index — fully visible (src < idx), causal-diagonal (src == idx), or
    invisible (src > idx) — so the kernel's STATIC offsets suffice: the
    "full" case fakes q_offset=Tl to open the whole block. Partial results
    combine exactly through their logsumexps:
        lse' = logaddexp(lse, lse_i);  o' = e^{lse-lse'} o + e^{lse_i-lse'} o_i
    (the associative flash combination), and the kernel's custom VJP carries
    gradients through both o_i and lse_i, so jax.grad of the scan yields the
    reverse ring schedule.
    """
    from ddlbench_tpu.ops.flash_attention import NEG_INF, flash_attention_lse
    from ddlbench_tpu.parallel.common import vary

    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    B, H, Tl, _ = q.shape
    dv = v.shape[-1]  # the output is as wide as v, not as q

    def full_blk(q, kb, vb):
        return flash_attention_lse(q, kb, vb, Tl, 0, 0, interpret=interpret)

    def diag_blk(q, kb, vb):
        return flash_attention_lse(q, kb, vb, 0, 0, 0, interpret=interpret)

    def skip_blk(q, kb, vb):
        return (vary(jnp.zeros((B, H, Tl, dv), q.dtype), (axis,)),
                vary(jnp.full((B, H, Tl), NEG_INF, jnp.float32), (axis,)))

    def step(carry, i):
        k_blk, v_blk, o, lse = carry
        src = (idx - i) % n  # which shard's K/V we hold this round
        case = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
        o_i, lse_i = lax.switch(case, [full_blk, diag_blk, skip_blk],
                                q, k_blk, v_blk)
        new_lse = jnp.logaddexp(lse, lse_i)
        safe = jnp.maximum(new_lse, NEG_INF)
        o = (o * jnp.exp(lse - safe)[..., None]
             + o_i.astype(jnp.float32) * jnp.exp(lse_i - safe)[..., None])
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, o, new_lse), None

    o0 = vary(jnp.zeros((B, H, Tl, dv), jnp.float32), (axis,))
    lse0 = vary(jnp.full((B, H, Tl), NEG_INF, jnp.float32), (axis,))
    (k, v, o, lse), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(n))
    return o.astype(q.dtype)


def attention_sublayer(p, x, n_heads: int, prefix_len: int = 0,
                       backend: str = "auto"):
    """Pre-LN self-attention sublayer with residual: reads p["ln1"],
    p["wqkv"], p["wo"]. Dispatches to ring attention over the active
    sequence_parallel axis, so every block (dense and MoE) gets the
    sequence-parallel path from one implementation; under an active
    tensor_parallel context the shard computes its local head group and the
    output projection psums over the TP axis. ``prefix_len`` selects the
    prefix-LM mask (seq2seq) on both paths, ``backend`` the attention
    backend its builder was given (causal_attention)."""
    B, T, d = x.shape
    dh = d // n_heads
    # Sliced-vs-replicated is decided by the PARAMS the shard actually
    # holds, not by the context alone: under tp a layer the splitter left
    # replicated (e.g. an MoE block — tp_split_layer_params) carries the
    # full-width wqkv, computes the full attention identically on every
    # shard, and must NOT psum (that would multiply by tp).
    tp = _tp_ctx()
    sliced = tp is not None and p["wqkv"].shape[1] < 3 * d
    n_local = n_heads
    if sliced:
        assert n_heads % tp[1] == 0, (
            f"tensor parallelism: n_heads={n_heads} not divisible by "
            f"tp_size={tp[1]}")
        n_local = n_heads // tp[1]
    h = layer_norm(p["ln1"], x)
    # qkv projection, attention core (the flash kernels on TPU), output
    # projection and the residual add: one kind; its LayerNorm is under ln
    with scopes.scope(scopes.ATTN):
        qkv = h @ p["wqkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, n_local, dh).transpose(0, 2, 1, 3)

        axis = _seq_axis()
        if axis is None:
            o = causal_attention(heads(q), heads(k), heads(v),
                                 prefix_len=prefix_len, backend=backend)
        else:
            assert tp is None, (
                "ring (sequence-parallel) attention composed with tensor "
                "parallelism is not supported")
            o = ring_attention(heads(q), heads(k), heads(v), axis,
                               prefix_len=prefix_len, backend=backend)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, n_local * dh)
        proj = o @ p["wo"].astype(x.dtype)
        if sliced:
            proj = lax.psum(proj, tp[0])
        return x + proj


def transformer_block(name: str, d_model: int, n_heads: int, mlp_ratio: int = 4,
                      prefix_len: int = 0, *,
                      attention_backend: str) -> Layer:
    """Pre-LN block; ``prefix_len`` > 0 switches the attention to the
    prefix-LM mask (the seq2seq workload, models/seq2seq.py);
    ``attention_backend`` is what its full-sequence attention calls pass to
    causal_attention / ring_attention."""
    dh = d_model // n_heads

    def init(key, in_shape):
        T, d = in_shape
        assert d == d_model
        ks = jax.random.split(key, 6)
        p = {
            "ln1": _ln_init(d),
            "wqkv": _dense_init(ks[0], d, 3 * d),
            "wo": _dense_init(ks[1], d, d),
            "ln2": _ln_init(d),
            "w1": _dense_init(ks[2], d, mlp_ratio * d),
            "b1": jnp.zeros((mlp_ratio * d,), jnp.float32),
            "w2": _dense_init(ks[3], mlp_ratio * d, d),
            "b2": jnp.zeros((d,), jnp.float32),
        }
        return p, {}, (T, d)

    def apply(p, s, x, train):
        x = attention_sublayer(p, x, n_heads, prefix_len, attention_backend)
        return mlp(p, x), s

    def mlp(p, x):
        h = layer_norm(p["ln2"], x)
        with scopes.scope(scopes.MLP):
            h = jax.nn.gelu(h @ p["w1"].astype(x.dtype)
                            + p["b1"].astype(x.dtype))
            proj = h @ p["w2"].astype(x.dtype)
            tp = _tp_ctx()
            # row-parallel psum ONLY when this shard holds a column slice
            # (see attention_sublayer — replicated layers compute the full
            # MLP)
            if tp is not None and p["w1"].shape[1] < mlp_ratio * d_model:
                proj = lax.psum(proj, tp[0])
            return x + proj + p["b2"].astype(x.dtype)

    def prefill(p, s, cache, x, start):
        x, cache = attn_prefill_op(p, x, cache, n_heads, prefix_len, start,
                                   attention_backend)
        return mlp(p, x), cache

    def decode(p, s, cache, x, pos):
        x, cache = attn_decode_op(p, x, cache, n_heads, pos)
        return mlp(p, x), cache

    def paged_prefill(p, s, cache, x, start):
        x, cache = attn_paged_prefill_op(p, x, cache, n_heads, prefix_len,
                                         start, attention_backend)
        return mlp(p, x), cache

    def paged_decode(p, s, cache, x, pos):
        x, cache = attn_paged_decode_op(p, x, cache, n_heads, pos)
        return mlp(p, x), cache

    def serve_prefill(p, s, pool, table, x, start, npl, page):
        x, pool = attn_serve_prefill_op(p, x, pool, table, n_heads, start,
                                        npl, page)
        return mlp(p, x), pool

    def serve_decode(p, s, pool, table, x, pos, npl, page):
        x, pool = attn_serve_decode_op(p, x, pool, table, n_heads, pos,
                                       npl, page)
        return mlp(p, x), pool

    def serve_verify(p, s, pool, table, x, pos0, npl, page):
        x, pool = attn_serve_verify_op(p, x, pool, table, n_heads, pos0,
                                       npl, page)
        return mlp(p, x), pool

    from ddlbench_tpu.models.layers import PagedOps, ServeOps

    # serving is causal-LM only: the prefix-LM mask (seq2seq) would need the
    # per-request source length threaded through every chunk's mask
    serve = (None if prefix_len else
             ServeOps(attn_serve_pool_init(n_heads, dh),
                      serve_prefill, serve_decode, serve_verify))
    return Layer(name, init, apply, init_cache=attn_cache_init(n_heads, dh),
                 prefill=prefill, decode=decode,
                 paged=PagedOps(attn_paged_cache_init(n_heads, dh),
                                paged_prefill, paged_decode,
                                attn_paged_reorder),
                 serve=serve)


# ---------------------------------------------------------------------------
# Shared attention-sublayer cache ops (models/decode.py protocol), used by the
# dense transformer block above and the MoE block (models/moe.py).
# ---------------------------------------------------------------------------


def attn_cache_init(n_heads: int, dh: int):
    def init_cache(p, batch, max_len, dtype):
        shape = (batch, n_heads, max_len, dh)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    return init_cache


def _qkv_heads(p, x, n_heads: int):
    B, T, d = x.shape
    dh = d // n_heads
    h = layer_norm(p["ln1"], x)
    qkv = h @ p["wqkv"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    # the LOCAL head count comes from the params actually held: a TP
    # shard's wqkv is the [d, 3 * (d/tp)] column slice
    # (tp_split_layer_params), so its q/k/v carry n_heads/tp heads.
    # Unsliced params give n_local == n_heads — bitwise the old path.
    n_local = q.shape[-1] // dh
    return [t.reshape(B, T, n_local, dh).transpose(0, 2, 1, 3)
            for t in (q, k, v)]


def attn_prefill_op(p, x, cache, n_heads: int, prefix_len: int, start: int,
                    backend: str = "auto"):
    """Attention sublayer (incl. residual) over a whole prompt, recording K/V.

    Attention runs only within the segment, so the prompt must start the
    stream (chunked prefill against existing cache entries is future work).
    """
    assert start == 0, "chunked prefill (start > 0) is not implemented"
    B, T, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)
    cache = {
        "k": lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), start, axis=2),
        "v": lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), start, axis=2),
    }
    o = causal_attention(q, k, v, start, start, prefix_len=prefix_len,
                         backend=backend)
    x = x + o.transpose(0, 2, 1, 3).reshape(B, T, d) @ p["wo"].astype(x.dtype)
    return x, cache


def attn_paged_cache_init(n_heads: int, dh: int):
    def init_cache(p, batch, max_len, dtype):
        from ddlbench_tpu.ops.paged_decode import paged_cache_init

        return paged_cache_init(batch, max_len, n_heads, dh, dtype)

    return init_cache


def attn_paged_prefill_op(p, x, cache, n_heads: int, prefix_len: int,
                          start: int, backend: str = "auto"):
    """attn_prefill_op with the K/V recorded into pages ([rows, T, H, dh]
    page layout; ops/paged_decode.py)."""
    from ddlbench_tpu.ops.paged_decode import paged_prefill_write

    assert start == 0, "chunked prefill (start > 0) is not implemented"
    B, T, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)
    cache = paged_prefill_write(cache, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3))
    o = causal_attention(q, k, v, start, start, prefix_len=prefix_len,
                         backend=backend)
    x = x + o.transpose(0, 2, 1, 3).reshape(B, T, d) @ p["wo"].astype(x.dtype)
    return x, cache


def attn_paged_decode_op(p, x, cache, n_heads: int, pos):
    """attn_decode_op against the paged cache: write one position into the
    row's own page slot, then single-query attention over only the LIVE
    pages (flash-decode kernel on TPU). Must be traced inside a
    ``live_pages`` segment (models/decode.py paged loops)."""
    from ddlbench_tpu.ops.paged_decode import (live_pages, paged_attention,
                                               paged_decode_write)

    B, _, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)  # [B, H, 1, dh]
    cache = paged_decode_write(cache, k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), pos)
    o = paged_attention(q[:, :, 0].astype(x.dtype), cache, pos,
                        live_pages.current())  # [B, H, dh]
    x = x + o.reshape(B, 1, d) @ p["wo"].astype(x.dtype)
    return x, cache


def attn_paged_reorder(cache, parent, pos):
    from ddlbench_tpu.ops.paged_decode import paged_reorder

    return paged_reorder(cache, parent, pos)


def attn_serve_pool_init(n_heads: int, dh: int):
    def pool_init(p, n_pages, page, dtype):
        from ddlbench_tpu.ops.paged_decode import serve_pool_init

        # pool shape follows the params it serves: a TP shard's wqkv
        # column slice produces n_heads/tp heads of K/V per position, so
        # its pool slice holds exactly those. Full params keep the full
        # head count — the single-chip layout, bitwise.
        n_local = p["wqkv"].shape[1] // (3 * dh)
        return serve_pool_init(n_pages, page, n_local, dh, dtype)

    return pool_init


def _serve_proj(p, o2, x):
    """Output projection + residual shared by the serve attention ops:
    ``o2`` is the [B, T, n_local * dh] attention output. Row-parallel
    under an active tensor_parallel context when this shard holds a wo
    row slice (the attention_sublayer discipline — a replicated layer
    computes the full projection on every shard and must NOT psum)."""
    d = x.shape[-1]
    proj = o2 @ p["wo"].astype(x.dtype)
    tp = _tp_ctx()
    if tp is not None and p["wqkv"].shape[1] < 3 * d:
        proj = lax.psum(proj, tp[0])
    return x + proj


def _serve_pool_out(cache):
    """The pool dict back out of a write's cache (everything but the
    table — quantized pools carry scale sidecars + the layer's kv_seed
    alongside pool_k/pool_v, and all of it must round-trip through the
    engine's donated pool pytree)."""
    return {k: v for k, v in cache.items() if k != "table"}


def attn_serve_prefill_op(p, x, pool, table, n_heads: int, start, npl: int,
                          page: int):
    """Chunked-prefill attention sublayer for the serving engine: write the
    page-aligned chunk's K/V through the shared table, then attend the
    chunk queries against the live pages (which the table already exposes
    for positions < start). ``start`` is dynamic — the same compiled chunk
    serves every request at the same page depth."""
    from ddlbench_tpu.ops.paged_decode import (paged_chunk_attention,
                                               paged_table_chunk_write)

    B, C, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)  # [B, H, C, dh]
    cache = {**pool, "table": table}
    cache = paged_table_chunk_write(cache, k.transpose(0, 2, 1, 3),
                                    v.transpose(0, 2, 1, 3), start, page)
    o = paged_chunk_attention(q, cache, start, npl, page)  # [B, H, C, dh]
    x = _serve_proj(p, o.transpose(0, 2, 1, 3).reshape(B, C, -1), x)
    return x, _serve_pool_out(cache)


def attn_serve_decode_op(p, x, pool, table, n_heads: int, pos, npl: int,
                         page: int):
    """attn_paged_decode_op for the serving engine: per-ROW positions and
    table-indirected writes into the shared pool (rows borrow free-list
    slots instead of owning a stripe). Inactive rows are routed to the
    scratch slot by the table the engine passes in."""
    from ddlbench_tpu.ops.paged_decode import (paged_attention,
                                               paged_table_write)

    B, _, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)  # [B, H, 1, dh]
    cache = {**pool, "table": table}
    cache = paged_table_write(cache, k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), pos, page)
    o = paged_attention(q[:, :, 0].astype(x.dtype), cache, pos, npl,
                        page)  # [B, H, dh]
    x = _serve_proj(p, o.reshape(B, 1, -1), x)
    return x, _serve_pool_out(cache)


def attn_serve_verify_op(p, x, pool, table, n_heads: int, pos0, npl: int,
                         page: int):
    """Speculative-decoding verify pass: write a W-token span's K/V at
    page-UNALIGNED per-row positions [pos0, pos0 + W) through the table
    (ops/paged_decode.paged_table_span_write), then attend all W queries
    causally at their absolute positions — the multi-query chunk
    attention with per-row starts, which the chunk-prefill path already
    compiles. One call scores the pending token plus every draft; the
    engine accepts the longest prefix whose drafts match greedy argmax."""
    from ddlbench_tpu.ops.paged_decode import (paged_chunk_attention,
                                               paged_table_span_write)

    B, W, d = x.shape
    q, k, v = _qkv_heads(p, x, n_heads)  # [B, H, W, dh]
    cache = {**pool, "table": table}
    cache = paged_table_span_write(cache, k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), pos0, page)
    o = paged_chunk_attention(q, cache, pos0, npl, page)  # [B, H, W, dh]
    x = _serve_proj(p, o.transpose(0, 2, 1, 3).reshape(B, W, -1), x)
    return x, _serve_pool_out(cache)


def attn_decode_op(p, x, cache, n_heads: int, pos):
    """Attention sublayer for ONE token at dynamic position pos against the
    populated cache. Every cached position <= pos, so the prefix rule needs
    no extra term: the mask is just k_pos <= pos."""
    B, _, d = x.shape
    dh = d // n_heads
    q, k, v = _qkv_heads(p, x, n_heads)  # [B, H, 1, dh]
    cache = {
        "k": lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), pos, axis=2),
        "v": lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), pos, axis=2),
    }
    kc, vc = cache["k"].astype(x.dtype), cache["v"].astype(x.dtype)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kc) / math.sqrt(dh)
    k_pos = jnp.arange(kc.shape[2])[None, None, None, :]
    scores = jnp.where(k_pos <= pos, scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd",
                   jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype),
                   vc)
    x = x + o.transpose(0, 2, 1, 3).reshape(B, 1, d) @ p["wo"].astype(x.dtype)
    return x, cache


def lm_head(name: str, vocab: int) -> Layer:
    def init(key, in_shape):
        T, d = in_shape
        p = {"ln_f": _ln_init(d), "head": _dense_init(key, d, vocab)}
        return p, {}, (T, vocab)

    def apply(p, s, x, train):
        h = layer_norm(p["ln_f"], x)
        with scopes.scope(scopes.HEAD):
            return h @ p["head"].astype(x.dtype), s

    def fused_loss(p, x, labels, smoothing):
        # Projection + CE fused per row chunk: the [B*T, vocab] logits never
        # hit HBM (ops/fused_xent.py) — at vocab 32k this is the largest
        # tensor a token workload would otherwise materialize.
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent

        d = x.shape[-1]
        h = layer_norm(p["ln_f"], x).reshape(-1, d)
        return fused_linear_xent(h, p["head"].astype(x.dtype),
                                 labels.reshape(-1), smoothing)

    def fused_eval(p, x, labels):
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent_eval

        d = x.shape[-1]
        h = layer_norm(p["ln_f"], x).reshape(-1, d)
        return fused_linear_xent_eval(h, p["head"].astype(x.dtype),
                                      labels.reshape(-1))

    return Layer(name, init, apply, pointwise=True, fused_loss=fused_loss,
                 fused_eval=fused_eval)


def build_transformer(arch: str, in_shape, vocab: int,
                      attention_backend: str = "auto") -> LayerModel:
    cfgv = _VARIANTS[arch]
    T = in_shape[0]
    layers: List[Layer] = [embed("embed", vocab, cfgv["d_model"], T)]
    for i in range(cfgv["n_layers"]):
        layers.append(
            transformer_block(f"block{i + 1}", cfgv["d_model"], cfgv["n_heads"],
                              attention_backend=attention_backend)
        )
    layers.append(lm_head("lm_head", vocab))
    return LayerModel(arch, layers, tuple(in_shape), vocab, input_kind="tokens")
