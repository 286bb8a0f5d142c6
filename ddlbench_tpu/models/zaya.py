"""ZAYA1 (HF ``zaya``, ``Zyphra/ZAYA1-8B`` config.json): a decoder of
``hybrid`` layers, each an attention sublayer in a compressed latent with
causal convolutions over q and k (arXiv:2510.04476) and a top-1, dropless
mixture of SwiGLU experts chosen by an MLP router that carries a state from
layer to layer (the ZAYA1 report, arXiv:2511.17127), under a tied embedding.
``benchmarks/reference/zaya.py`` holds the equations to the letter, every
assumption and the one departure (the router-chosen skip path of the sibling
models is left out); this file computes the same function.

Between two blocks travels a PAIR: the residual stream ``x`` [B, T, D] in the
compute dtype and the router's carried state ``r`` [B, T, R] in float32.
``Layer.apply`` takes and returns that pair as its ``x`` (``apply_slice`` and
``jax.checkpoint`` pass any pytree through); the first block takes the stream
alone and starts ``r`` at nought, the last hands the stream alone to the
head. ``Layer.init`` reports such a boundary as a pair of shapes.

Per block, with ``h = RMSNorm(x)``:

* **Attention** (``H`` query heads over ``K`` key/value heads of ``d``):
  ``q0 = h W_q``, ``k0 = h W_k``; values ``[h W_v1 || (h W_v2)[t-1]]``: kv
  head 0 is this token's value, kv head 1 the previous token's; ``m_q = (q0
  + k0 of the head's group) / 2`` and ``m_k`` its mean over a group; over q0
  and k0 a depthwise causal convolution of ``cca_time0`` taps, then per head
  one of ``cca_time1`` taps of d x d; ``q1 = conv + m_q``, ``k1 = conv +
  m_k``; both L2-normalised to sqrt(d) per token and head (k times ``e^temp``
  of its kv head); RoPE on the first half of every head (halves rotated);
  causal attention of query head j on kv head ``j // (H / K)``
  (``ops/flash_attention.py`` takes the two head counts apart); ``W_o``.
* **Experts**: ``r = h W_d + b_d + gamma * r_prev`` (no ``gamma`` in the
  first block); ``p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r))))`` in
  float32; ``e = argmax(p + beta)``, weight ``p[e]``; the held experts' part
  of ``p[e] E_e(h)`` by ``models/dropless.py``. ``beta`` moves the choice,
  never the weight, and is no parameter: it is layer STATE, nought at the
  start, that every training step moves by the layer's own loads as a
  running statistic is moved (``balance``, below).
* Each sublayer ends in ``x <- (a_x x + b_x) + (a_f f + b_f)``.

**The tied head.** The head owns a norm only; ``LayerModel.ties`` says that
it reads the embedding's ``tok`` as its own ``tok``, and the strategy's loss
function puts the leaf there inside what it differentiates
(``layers.resolve_ties``): one leaf, one gradient (the sum of both uses), one
optimizer slot.

**The share a chip holds** is ``models/kanana2.py``'s (and
``models/smallthinker.py``'s: the three families route their own way and
share ``models/dropless.py`` after the choice): a block is told which
experts it holds, routes over all ``n_experts`` and computes its own experts'
part; a token whose expert is absent gets nought. The arch string carries it:
``zaya1_8b`` is the whole model, ``zaya1_8b-l5-e8`` its first 5 layers with
experts 0..7 held, ``-e8r1`` experts 8..15.

Leaf names follow the rules of the benchmark's weight maker: every
multiplicative vector is a ``.../scale``, every other 1-D leaf is seeded as a
bias, convolution kernels are 2-D or more.

Serving (a paged cache of the compressed k and v plus the one-token
convolution and value-shift state) is not written.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models import dropless
from ddlbench_tpu.models.kanana2 import (_rms_norm, _scale_init, _swiglu_init,
                                         embed_tokens, rms_norm)
from ddlbench_tpu.models.layers import Layer, LayerModel, Tie
from ddlbench_tpu.models.transformer import _dense_init, causal_attention
from ddlbench_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    conv_taps: Tuple[int, int]  # cca_time0 (depthwise), cca_time1 (per head)
    rotary: int                 # channels of a head that RoPE turns
    router_dim: int
    expert_ff: int
    n_experts: int
    n_layers: int
    rope_theta: float = 5e6
    rms_eps: float = 1e-5


FAMILY = {
    # Zyphra/ZAYA1-8B config.json, as published
    "zaya1_8b": Dims(d_model=2048, n_heads=8, n_kv_heads=2, head_dim=128,
                     conv_taps=(2, 2), rotary=64, router_dim=256,
                     expert_ff=2048, n_experts=16, n_layers=40),
}

# (rows, contraction, columns) tile of the Pallas grouped product for this
# family's [., 2048] x [2048, 2048] experts
GMM_TILING = (512, 1024, 1024)

# the size and the number of the sign updates that one training step makes to
# a layer's selection biases, in turn, each from the loads counted anew
# (``balance``)
BIAS_UPDATE_RATE = 1e-3
BIAS_UPDATES_PER_STEP = 16


def is_family(arch: str) -> bool:
    return dropless.arch_base(arch) in FAMILY


def parse_arch(arch: str) -> Optional[Tuple[Dims, int, Tuple[int, int]]]:
    """``(dims, layers kept, (first held expert, experts held))`` of an arch
    string of this family (``dropless.parse_share`` reads the syntax), None
    for any other."""
    return dropless.parse_share(arch, FAMILY)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _previous(x, n: int = 1):
    """x[..., t - n, :] along the time axis (second to last), zeros before
    the start of the packed sequence."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (n, 0)
    return jnp.pad(lax.slice_in_dim(x, 0, x.shape[-2] - n, axis=x.ndim - 2),
                   pad)


def causal_convs(u, w_dw, b_dw, w_head, b_head):
    """The two causal convolutions over u [B, heads, T, d], zero left
    padding: depthwise with ``w_dw`` [n0, heads, d] and ``b_dw`` [heads, d],
    then per head ``w_head`` [heads, n1, d, d] and ``b_head`` [heads, d]. The
    taps are shifts and (head-batched) matmuls that XLA fuses: no window op."""
    n0, n1 = w_dw.shape[0], w_head.shape[1]
    c1 = b_dw[:, None, :] + sum(
        w_dw[i][:, None, :] * _previous(u, n0 - 1 - i) for i in range(n0))
    return b_head[:, None, :] + sum(
        jnp.einsum("bgtc,gce->bgte", _previous(c1, n1 - 1 - i), w_head[:, i])
        for i in range(n1))


def unit_rows(x, gain=None):
    """sqrt(d) x / |x| over the last axis, float32 statistics, times
    ``gain`` (broadcast) where given; x's dtype out."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.sum(lax.square(xf), axis=-1, keepdims=True) + 1e-12)
    inv = inv * math.sqrt(x.shape[-1])
    return (xf * (inv if gain is None else inv * gain)).astype(x.dtype)


def rope_halves(x, positions, theta: float, rotary: int):
    """Rotary positions on the first ``rotary`` channels of x [..., T, d]:
    the halves (i, i + rotary/2) are turned by ``pos * theta^(-2i/rotary)``
    (``rope_type: default``; models/kanana2.rope_interleaved turns pairs).
    Written on whole heads — the partner channel comes by a roll along the
    lanes and channels past ``rotary`` meet cos 1, sin 0 — so that no head
    is cut and put together again around the kernels. Float32 angles."""
    d, half = x.shape[-1], rotary // 2
    inv_freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32)
                         / rotary)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ones = jnp.ones((ang.shape[0], d - rotary), jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang), ones], axis=-1)
    sin = jnp.concatenate([-jnp.sin(ang), jnp.sin(ang), 0 * ones], axis=-1)
    xf = x.astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, xf.shape, xf.ndim - 1)
    partner = jnp.where(lane < half, jnp.roll(xf, -half, axis=-1),
                        jnp.roll(xf, half, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def merge(p, x, f):
    """(a_x x + b_x) + (a_f f + b_f), per channel."""
    c = lambda v: v.astype(x.dtype)
    return (c(p["x"]["scale"]) * x + c(p["x"]["bias"])
            + c(p["f"]["scale"]) * f + c(p["f"]["bias"]))


def _merge_init(d):
    vec = lambda: {"scale": jnp.ones((d,), jnp.float32),
                   "bias": jnp.zeros((d,), jnp.float32)}
    return {"x": vec(), "f": vec()}


def cca_sublayer(p, x, dims: Dims, backend: str = "auto"):
    """merge(x, CCA(RMSNorm(x))), causal, positions 0..T-1. Projections are
    einsums straight into and out of the kernels' [B, heads, T, d] layout;
    the convolutions' weights are cut into their q and k parts as WEIGHTS,
    so q0 and k0 are never put side by side (models/kanana2.py's lesson: a
    cut activation costs a relayout copy a piece)."""
    B, T, D = x.shape
    H, K, d = dims.n_heads, dims.n_kv_heads, dims.head_dim
    G = H // K
    c = lambda v: v.astype(x.dtype)
    h = rms_norm(p["ln1"], x, dims.rms_eps)
    with scopes.scope(scopes.ATTN):
        q0 = jnp.einsum("btd,dhe->bhte", h, c(p["wq"]).reshape(D, H, d))
        k0 = jnp.einsum("btd,dhe->bhte", h, c(p["wk"]).reshape(D, K, d))
        v_now = h @ c(p["wv1"])   # [B, T, K d / 2]: this token's value
        v_then = h @ c(p["wv2"])  # the previous token's, once shifted
        with scopes.scope(scopes.CCA_MIX):
            # a linear map commutes with the shift: h[t-1] W = (h W)[t-1]
            v = jnp.concatenate([v_now, _previous(v_then)], axis=-1)
            v = v.reshape(B, T, K, d).transpose(0, 2, 1, 3)
            m_q = (q0.reshape(B, K, G, T, d) + k0[:, :, None]) * 0.5
            m_k = jnp.mean(m_q, axis=2, dtype=jnp.float32).astype(x.dtype)
            w_dw = c(p["conv_dw"]).reshape(-1, H + K, d)
            b_dw = c(p["conv_dw_bias"]).reshape(H + K, d)
            w_head = c(p["conv_head"])
            b_head = c(p["conv_head_bias"]).reshape(H + K, d)
            q1 = causal_convs(q0, w_dw[:, :H], b_dw[:H], w_head[:H],
                              b_head[:H]) + m_q.reshape(B, H, T, d)
            k1 = causal_convs(k0, w_dw[:, H:], b_dw[H:], w_head[H:],
                              b_head[H:]) + m_k
            q2 = unit_rows(q1)
            k2 = unit_rows(k1, jnp.exp(p["temp"].astype(jnp.float32))[
                None, :, None, None])
        pos = jnp.arange(T)
        q2 = rope_halves(q2, pos, dims.rope_theta, dims.rotary)
        k2 = rope_halves(k2, pos, dims.rope_theta, dims.rotary)
        o = causal_attention(q2, k2, v, backend=backend)  # [B, H, T, d]
        f = jnp.einsum("bhtv,hvd->btd", o, c(p["wo"]).reshape(H, d, D))
    return merge(p["merge_attn"], x, f)


def _cca_init(key, dims: Dims):
    D, H, K, d = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    n0, n1 = dims.conv_taps
    ks = jax.random.split(key, 7)
    return {
        "ln1": _scale_init(D),
        "wq": _dense_init(ks[0], D, H * d),
        "wk": _dense_init(ks[1], D, K * d),
        "wv1": _dense_init(ks[2], D, K * d // 2),
        "wv2": _dense_init(ks[3], D, K * d // 2),
        "conv_dw": jax.random.normal(ks[4], (n0, (H + K) * d),
                                     jnp.float32) * 0.02,
        "conv_dw_bias": jnp.zeros(((H + K) * d,), jnp.float32),
        "conv_head": jax.random.normal(ks[5], (H + K, n1, d, d),
                                       jnp.float32) * 0.02,
        "conv_head_bias": jnp.zeros(((H + K) * d,), jnp.float32),
        "temp": jnp.zeros((K,), jnp.float32),
        "wo": _dense_init(ks[6], H * d, D),
        "merge_attn": _merge_init(D),
    }


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(p, h, r_prev, select_bias, eps: float):
    """``(idx [S, 1] int32, w [S, 1] float32, r [S, R] float32, prob [S, E]
    float32)`` of h [S, D]: the MLP router of ``p`` (the block's ``router`` group) in float32
    at HIGHEST on the hidden states as they are, ``r_prev`` the state the
    block before passed on (None in the first). The top 1 of ``softmax +
    select_bias`` is chosen; the weight is the softmax's alone."""
    f32 = lambda v: v.astype(jnp.float32)
    dot = lambda a, b: jnp.dot(a, f32(b), precision=lax.Precision.HIGHEST)
    r = dot(f32(h), p["w_d"]) + f32(p["b_d"])
    if r_prev is not None:
        r = r + f32(p["carry"]["scale"]) * r_prev
    z = _rms_norm(p["norm"], r, eps)
    gelu = lambda a: jax.nn.gelu(a, approximate=False)
    a1 = gelu(dot(z, p["w_1"]) + f32(p["b_1"]))
    a2 = gelu(dot(a1, p["w_2"]) + f32(p["b_2"]))
    prob = jax.nn.softmax(dot(a2, p["w_3"]), axis=-1)
    idx = jnp.argmax(prob + lax.stop_gradient(f32(select_bias)), axis=-1)
    idx = idx.astype(jnp.int32)[:, None]
    return idx, jnp.take_along_axis(prob, idx, axis=-1), r, prob


def balance(select_bias, prob):
    """The selection bias after a training step whose router gave ``prob``
    [S, E]: ``BIAS_UPDATES_PER_STEP`` times in turn, ``beta_i +=
    BIAS_UPDATE_RATE * sign(mean load - load_i)`` with the loads of
    ``argmax(prob + beta)`` counted anew over ALL experts (the absent ones
    too: the pair's other chip makes the same update from the same
    choices). Bias-based balancing without an auxiliary loss, as the ZAYA1
    report trains its router; the report's own controller and gains are not
    in config.json, so the update is the sign rule of arXiv:2408.15664 at
    the rate DeepSeek-V3 states (arXiv:2412.19437, section 4.2), repeated on
    the step's own probabilities because Adam at 3e-4 without a warm-up
    moves an untrained router's probabilities further in a step than one
    such update follows (PERF.md section 6, PR 32): ``assumed`` in the
    configuration's file."""
    n = prob.shape[-1]
    for _ in range(BIAS_UPDATES_PER_STEP):
        chosen = jnp.argmax(prob + select_bias, axis=-1)
        load = jnp.sum(jax.nn.one_hot(chosen, n, dtype=jnp.float32), axis=0)
        select_bias = select_bias + BIAS_UPDATE_RATE * jnp.sign(
            jnp.mean(load) - load)
    return select_bias


def _router_init(key, dims: Dims, carried: bool):
    D, R, E = dims.d_model, dims.router_dim, dims.n_experts
    ks = jax.random.split(key, 4)
    zeros = lambda: jnp.zeros((R,), jnp.float32)
    p = {"w_d": _dense_init(ks[0], D, R), "b_d": zeros(),
         "norm": _scale_init(R),
         "w_1": _dense_init(ks[1], R, R), "b_1": zeros(),
         "w_2": _dense_init(ks[2], R, R), "b_2": zeros(),
         "w_3": _dense_init(ks[3], R, E)}
    if carried:
        p["carry"] = _scale_init(R)
    return p


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def hybrid_block(name: str, dims: Dims, held: Tuple[int, int],
                 attention_backend: str, first: bool, last: bool) -> Layer:
    """One ``hybrid`` layer. ``first``: takes the stream alone and carries no
    router state in; ``last``: hands the stream alone on. Its state holds
    the selection bias (``select_bias``: nought at the start, moved by
    ``balance`` in every training step, left alone in evaluation) and the
    step's routing counters (``moe/held_slots``,
    ``moe/load_max_over_mean``, ``moe/buffer_fill``,
    ``moe/top1_weight_mean``): outputs of the
    apply, so they leave a rematerialized layer like BatchNorm's statistics
    do."""
    count = held[1]

    def init(key, in_shape):
        T, D = in_shape if first else in_shape[0]
        assert D == dims.d_model
        ks = jax.random.split(key, 3)
        stack = lambda k: jax.vmap(
            lambda kk: _swiglu_init(kk, D, dims.expert_ff))(
                jax.random.split(k, count))
        p = dict(_cca_init(ks[0], dims), ln2=_scale_init(D),
                 router=_router_init(ks[1], dims, carried=not first),
                 experts=stack(ks[2]), merge_moe=_merge_init(D))
        state = {"select_bias": jnp.zeros((dims.n_experts,), jnp.float32),
                 "moe": dropless.initial_counters("top1_weight_mean")}
        out = (T, D) if last else ((T, D), (T, dims.router_dim))
        return p, state, out

    def apply(p, s, x, train):
        x, r_prev = (x, None) if first else x
        B, T, D = x.shape
        x = cca_sublayer(p, x, dims, attention_backend)
        h = rms_norm(p["ln2"], x, dims.rms_eps).reshape(B * T, D)
        with scopes.scope(scopes.ROUTE):
            with scopes.scope(scopes.ROUTER):
                idx, w, r, prob = route(
                    p["router"], h,
                    None if first else r_prev.reshape(B * T, -1),
                    s["select_bias"], dims.rms_eps)
                bias = s["select_bias"]
                if train:
                    bias = balance(bias, lax.stop_gradient(prob))
            y, counters = dropless.routed_experts(
                p["experts"], h, idx, w, held, dims.n_experts, GMM_TILING)
        counters["top1_weight_mean"] = jnp.mean(w)
        x = merge(p["merge_moe"], x, y.reshape(B, T, D))
        state = {"select_bias": bias, "moe": counters}
        return (x if last else (x, r.reshape(B, T, -1))), state

    return Layer(name, init, apply, f32_params=("router",))


def tied_head(name: str, vocab: int, dims: Dims) -> Layer:
    """Final RMSNorm and the projection by the EMBEDDING's matrix: the layer
    owns the norm alone and reads ``tok`` [V, D] where ``LayerModel.ties``
    puts it (layers.resolve_ties). ``ops/fused_xent.py`` takes [D, V]: one
    transpose a step of the compute-dtype matrix."""

    def init(key, in_shape):
        T, d = in_shape
        return {"norm": _scale_init(d)}, {}, (T, vocab)

    def apply(p, s, x, train):
        h = rms_norm(p["norm"], x, dims.rms_eps)
        with scopes.scope(scopes.HEAD):
            return h @ p["tok"].astype(x.dtype).T, s

    def fused_loss(p, x, labels, smoothing):
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent

        h = rms_norm(p["norm"], x, dims.rms_eps).reshape(-1, x.shape[-1])
        return fused_linear_xent(h, p["tok"].astype(x.dtype).T,
                                 labels.reshape(-1), smoothing)

    def fused_eval(p, x, labels):
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent_eval

        h = rms_norm(p["norm"], x, dims.rms_eps).reshape(-1, x.shape[-1])
        return fused_linear_xent_eval(h, p["tok"].astype(x.dtype).T,
                                      labels.reshape(-1))

    return Layer(name, init, apply, pointwise=True, fused_loss=fused_loss,
                 fused_eval=fused_eval)


def build(arch: str, in_shape, vocab: int,
          attention_backend: str = "auto") -> LayerModel:
    dims, n_layers, held = parse_arch(arch)
    layers: List[Layer] = [embed_tokens("embed", vocab, dims.d_model)]
    for i in range(n_layers):
        layers.append(hybrid_block(f"block{i + 1}", dims, held,
                                   attention_backend, first=i == 0,
                                   last=i == n_layers - 1))
    layers.append(tied_head("lm_head", vocab, dims))
    # one chip's share of an expert-parallel pair, without its exchange, a
    # pair-valued boundary and a tied leaf: no strategy across chips and no
    # pipeline is brought up
    return LayerModel(arch, layers, tuple(in_shape), vocab,
                      input_kind="tokens", strategies=("single",),
                      ties=(Tie(layer=-1, key="tok", owner=0,
                                owner_key="tok"),))
