"""kanana-2-30b-a3b (HF ``deepseek_v3``): a decoder of latent-attention
blocks whose feed-forward is dense in the leading layer and a sigmoid-routed,
dropless mixture of SwiGLU experts (plus shared experts) after it.

Per block, with ``h = RMSNorm(x)``:

* **MLA.** ``q = h W_q`` -> [T, H, nope + rope]; ``h W_kva`` -> a latent
  ``c`` and ONE rotary key ``k_pe`` for all heads; ``RMSNorm(c) W_kvb`` ->
  [T, H, nope + v]: each head's ``k_nope`` and ``v``. RoPE turns the
  interleaved pairs (2i, 2i+1) of ``q_pe`` and ``k_pe``. Keys are
  ``k_nope || k_pe``, so q and k are ``nope + rope`` wide and v is ``v`` wide:
  ``ops/flash_attention.py`` takes the two widths apart.
* **Dense layer** (the first ``first_dense``): SwiGLU of width ``dense_ff``.
* **Expert layer**: float32 router logits, ``s = sigmoid(logits)``; the top
  ``top_k`` of ``s + b`` are chosen (``b`` moves the choice and never the
  weight, and gets no gradient); ``w = s[idx] / sum * route_scale``;
  ``y = sum_k w_k E_k(h) + Shared(h)``.

**The share a chip holds.** An expert layer is told which experts it holds
(``held = (first, count)``): it routes over all ``n_experts``, normalises
``w`` over all ``top_k`` chosen, and computes the part of the result its own
experts give; what the absent experts would add is left out (on one chip
there is no exchange, and no code stands in for one). The routed part is
dropless, and everything after the router's choice — sort by expert, gather
the held slots into a row buffer, the grouped products, the weighted
scatter, the counters — is ``models/dropless.py``, which ``models/zaya.py``
and ``models/smallthinker.py`` share: this file keeps ``route`` and the
tiling of its 768-wide experts.

The arch string carries the share: ``kanana2_30b_a3b`` is the whole model,
``kanana2_30b_a3b-l5-e8`` its first 5 layers with experts 0..7 of each
expert layer held, ``-e8r3`` the eighth-wide share of rank 3 (experts
24..31). ``dropless.parse_share`` is the one reader of that syntax and
``FAMILY`` the one home of the published sizes.

Serving (a latent paged cache) is not written: the blocks leave
``decode``/``paged``/``serve`` unset and ``serve/engine.py`` refuses them.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models import dropless
from ddlbench_tpu.models.layers import Layer, LayerModel
from ddlbench_tpu.models.transformer import _dense_init, causal_attention
from ddlbench_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_latent: int
    dense_ff: int
    expert_ff: int
    n_experts: int
    n_shared: int
    top_k: int
    route_scale: float
    n_layers: int
    first_dense: int = 1
    rope_theta: float = 1e6
    rms_eps: float = 1e-6


FAMILY = {
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, as published
    "kanana2_30b_a3b": Dims(
        d_model=2048, n_heads=32, qk_nope=128, qk_rope=64, v_head=128,
        kv_latent=512, dense_ff=6144, expert_ff=768, n_experts=128,
        n_shared=2, top_k=6, route_scale=2.448, n_layers=48),
}

# (rows, contraction, columns) tile of the Pallas grouped product for this
# family's 768-wide experts
GMM_TILING = (512, 768, 768)

def is_family(arch: str) -> bool:
    return dropless.arch_base(arch) in FAMILY


def parse_arch(arch: str) -> Optional[Tuple[Dims, int, Tuple[int, int]]]:
    """``(dims, layers kept, (first held expert, experts held))`` of an arch
    string of this family (``dropless.parse_share`` reads the syntax), None
    for any other."""
    if not is_family(arch):
        return None
    return dropless.parse_share(
        arch, FAMILY, FAMILY[dropless.arch_base(arch)].first_dense)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _scale_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def _rms_norm(p, x, eps: float):
    """x * rsqrt(mean x^2 + eps) * scale, the statistics in float32."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(lax.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * p["scale"].astype(x.dtype)


rms_norm = scopes.scope(scopes.LN)(_rms_norm)


def rope_interleaved(x, positions, theta: float):
    """Rotary positions on the last axis of x [..., T, r]: the pair
    (2i, 2i+1) is turned by ``pos * theta^(-2i/r)`` (``rope_interleave``).
    ``positions``: [T]. Float32 angles, x's dtype out."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)  # [T, r/2]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def swiglu(p, h):
    """W_down(silu(W_gate h) * W_up h)."""
    g = h @ p["w_gate"].astype(h.dtype)
    u = h @ p["w_up"].astype(h.dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(h.dtype)


def _swiglu_init(key, d, f):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense_init(k1, d, f), "w_up": _dense_init(k2, d, f),
            "w_down": _dense_init(k3, f, d)}


def mla_sublayer(p, x, dims: Dims, backend: str = "auto"):
    """x + MLA(RMSNorm(x)), causal, positions 0..T-1; ``backend`` is the
    attention backend (transformer.causal_attention). The projections are
    einsums straight into and out of the kernels' [B, H, T, width] layout
    (W_kvb's columns split into their k_nope and v halves as weights, not
    as activations: a [.., H, 256] activation cut in two cost a relayout
    copy a piece on the chip)."""
    B, T, d = x.shape
    H, nope, rope, dv = dims.n_heads, dims.qk_nope, dims.qk_rope, dims.v_head
    h = rms_norm(p["ln1"], x, dims.rms_eps)
    pos = jnp.arange(T)
    with scopes.scope(scopes.LATENT):
        # the latent and the shared rotary key, then each head's k_nope, v
        ckv = h @ p["wkv_a"].astype(x.dtype)
        c, k_pe = ckv[..., :dims.kv_latent], ckv[..., dims.kv_latent:]
        c = _rms_norm(p["kv_norm"], c, dims.rms_eps)
        wkv_b = p["wkv_b"].astype(x.dtype).reshape(dims.kv_latent, H,
                                                   nope + dv)
        k_nope = jnp.einsum("btc,chd->bhtd", c, wkv_b[..., :nope])
        v = jnp.einsum("btc,chd->bhtd", c, wkv_b[..., nope:])
        k_pe = rope_interleaved(k_pe, pos, dims.rope_theta)  # [B, T, rope]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, None], (B, H, T, rope))],
            axis=-1)
    with scopes.scope(scopes.ATTN):
        q = jnp.einsum("btd,dhe->bhte", h, p["wq"].astype(x.dtype).reshape(
            d, H, nope + rope))
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], pos,
                                             dims.rope_theta)], axis=-1)
        o = causal_attention(q, k, v, backend=backend)  # [B, H, T, dv]
        return x + jnp.einsum("bhtv,hvd->btd", o, p["wo"].astype(
            x.dtype).reshape(H, dv, d))


def _mla_init(key, dims: Dims):
    d, H = dims.d_model, dims.n_heads
    ks = jax.random.split(key, 4)
    return {
        "ln1": _scale_init(d),
        "wq": _dense_init(ks[0], d, H * (dims.qk_nope + dims.qk_rope)),
        "wkv_a": _dense_init(ks[1], d, dims.kv_latent + dims.qk_rope),
        "kv_norm": _scale_init(dims.kv_latent),
        "wkv_b": _dense_init(ks[2], dims.kv_latent,
                             H * (dims.qk_nope + dims.v_head)),
        "wo": _dense_init(ks[3], H * dims.v_head, d),
        "ln2": _scale_init(d),
    }


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(p, h, dims: Dims):
    """``(idx [S, k] int32, w [S, k] float32)`` of h [S, d]: float32 logits,
    sigmoid scores, the top k of score + bias, weights from the scores
    alone, normalised over all k chosen and scaled."""
    logits = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + lax.stop_gradient(
        p["router_bias"].astype(jnp.float32)), dims.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * dims.route_scale
    return idx, w


def routed_experts(p, h, dims: Dims, held: Tuple[int, int]):
    """The held experts' part of ``sum_k w_k E_idx_k(h)`` for h [S, d] by
    this family's router, and the layer's counters (models/dropless.py)."""
    idx, w = route(p, h, dims)
    return dropless.routed_experts(p["experts"], h, idx, w, held,
                                   dims.n_experts, GMM_TILING)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def embed_tokens(name: str, vocab: int, d_model: int) -> Layer:
    def init(key, in_shape):
        (T,) = in_shape
        return {"tok": _dense_init(key, vocab, d_model)}, {}, (T, d_model)

    @scopes.scope(scopes.EMBED)
    def apply(p, s, x, train):
        return jnp.take(p["tok"], x, axis=0), s

    return Layer(name, init, apply)


def dense_block(name: str, dims: Dims, attention_backend: str) -> Layer:
    def init(key, in_shape):
        T, d = in_shape
        assert d == dims.d_model
        k1, k2 = jax.random.split(key)
        p = dict(_mla_init(k1, dims), **_swiglu_init(k2, d, dims.dense_ff))
        return p, {}, (T, d)

    def apply(p, s, x, train):
        x = mla_sublayer(p, x, dims, attention_backend)
        h = rms_norm(p["ln2"], x, dims.rms_eps)
        with scopes.scope(scopes.MLP):
            return x + swiglu(p, h), s

    return Layer(name, init, apply)


def expert_block(name: str, dims: Dims, held: Tuple[int, int],
                 attention_backend: str) -> Layer:
    """Its state holds the step's routing counters (``moe/held_slots``,
    ``moe/load_max_over_mean``, ``moe/buffer_fill``): outputs of the apply,
    so they leave a rematerialized layer like BatchNorm's statistics do."""
    count = held[1]

    def init(key, in_shape):
        T, d = in_shape
        assert d == dims.d_model
        ks = jax.random.split(key, 4)
        f = dims.expert_ff
        stack = lambda k, a, b: jax.vmap(lambda kk: _dense_init(kk, a, b))(
            jax.random.split(k, count))
        kg, ku, kd = jax.random.split(ks[3], 3)
        p = dict(
            _mla_init(ks[0], dims),
            router=_dense_init(ks[1], d, dims.n_experts),
            router_bias=jnp.zeros((dims.n_experts,), jnp.float32),
            shared=_swiglu_init(ks[2], d, dims.n_shared * f),
            experts={"w_gate": stack(kg, d, f), "w_up": stack(ku, d, f),
                     "w_down": stack(kd, f, d)})
        state = {"moe": dropless.initial_counters()}
        return p, state, (T, d)

    def apply(p, s, x, train):
        B, T, d = x.shape
        x = mla_sublayer(p, x, dims, attention_backend)
        h = rms_norm(p["ln2"], x, dims.rms_eps)
        with scopes.scope(scopes.ROUTE):
            y, counters = routed_experts(p, h.reshape(B * T, d), dims, held)
        with scopes.scope(scopes.MLP):
            x = x + swiglu(p["shared"], h)
        return x + y.reshape(B, T, d), {"moe": counters}

    return Layer(name, init, apply, f32_params=("router", "router_bias"))


def lm_head(name: str, vocab: int, dims: Dims) -> Layer:
    """Final RMSNorm and the untied projection; the fused projection + loss
    of ops/fused_xent.py as the GPT-2 head offers it."""

    def init(key, in_shape):
        T, d = in_shape
        return ({"norm": _scale_init(d), "head": _dense_init(key, d, vocab)},
                {}, (T, vocab))

    def apply(p, s, x, train):
        h = rms_norm(p["norm"], x, dims.rms_eps)
        with scopes.scope(scopes.HEAD):
            return h @ p["head"].astype(x.dtype), s

    def fused_loss(p, x, labels, smoothing):
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent

        h = rms_norm(p["norm"], x, dims.rms_eps).reshape(-1, x.shape[-1])
        return fused_linear_xent(h, p["head"].astype(x.dtype),
                                 labels.reshape(-1), smoothing)

    def fused_eval(p, x, labels):
        from ddlbench_tpu.ops.fused_xent import fused_linear_xent_eval

        h = rms_norm(p["norm"], x, dims.rms_eps).reshape(-1, x.shape[-1])
        return fused_linear_xent_eval(h, p["head"].astype(x.dtype),
                                      labels.reshape(-1))

    return Layer(name, init, apply, pointwise=True, fused_loss=fused_loss,
                 fused_eval=fused_eval)


def build(arch: str, in_shape, vocab: int,
          attention_backend: str = "auto") -> LayerModel:
    dims, n_layers, held = parse_arch(arch)
    layers: List[Layer] = [embed_tokens("embed", vocab, dims.d_model)]
    for i in range(n_layers):
        name = f"block{i + 1}"
        layers.append(dense_block(name, dims, attention_backend)
                      if i < dims.first_dense
                      else expert_block(name, dims, held, attention_backend))
    layers.append(lm_head("lm_head", vocab, dims))
    # one chip's share of an expert-parallel group, without its exchange:
    # no strategy across chips is brought up
    return LayerModel(arch, layers, tuple(in_shape), vocab,
                      input_kind="tokens", strategies=("single",))
