"""SmallThinker (``PowerInfer/SmallThinker-21BA3B-Instruct`` config.json,
report arXiv:2507.20984): a decoder whose layers are of TWO kinds, told
apart by one layout list (``sliding_window_layout`` = ``rope_layout`` =
``[0, 1, 1, 1] x 13``): a GLOBAL layer attends to every earlier key and
carries NO positions at all; a WINDOW layer attends to the 4,096 nearest
keys and turns q and k by rotary positions. Every layer's feed-forward is a
dropless mixture of ReLU-gated experts, six a token, chosen by a softmax
router that reads the layer's INPUT — before the input norm and before
attention. ``benchmarks/reference/smallthinker.py`` holds the equations to
the letter, every assumption and the departures; this file computes the same
function.

Per block, with x the layer's input:

* **Router**, first: ``g = x W_r`` in float32 (HIGHEST, on the hidden
  states as they are); the top 6 of the logits; ``w = softmax`` over the six
  chosen. The choice waits for the experts below.
* **Attention** on ``h = RMSNorm(x)``: ``H`` query heads over ``K``
  key/value heads of ``d`` (query head n on key/value head ``n // (H / K)``;
  ``ops/flash_attention.py`` takes the two head counts apart). A window
  layer turns the halves of every whole head by ``pos * theta^(-2i/d)`` and
  hands its window to the kernels, which skip the tiles outside the band; a
  global layer does neither. ``x1 = x + o W_o``.
* **Experts** on ``h2 = RMSNorm(x1)``: the held experts' part of ``sum_k w_k
  W_down[idx_k] (relu(h2 W_gate[idx_k]) * (h2 W_up[idx_k]))`` by
  ``models/dropless.py``; ``x1 + y``.

**The share a chip holds** is ``models/kanana2.py``'s: a block is told which
experts it holds, routes over all ``n_experts`` and computes its own experts'
part; a slot whose expert is absent adds nought. The arch string carries it:
``smallthinker_21b_a3b`` is the whole model, ``smallthinker_21b_a3b-l4-e16``
the FIRST 4 layers of the layout (one whole period: global, window, window,
window) with experts 0..15 held, ``-e16r1`` experts 16..31.

Serving (a page table per kind of layer: a window layer's cache is 4,096
keys long, a global layer's the whole context) is not written.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models import dropless
from ddlbench_tpu.models.kanana2 import (_scale_init, embed_tokens, lm_head,
                                         rms_norm)
from ddlbench_tpu.models.layers import Layer, LayerModel
from ddlbench_tpu.models.transformer import _dense_init, causal_attention
from ddlbench_tpu.models.zaya import rope_halves
from ddlbench_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    expert_ff: int
    n_experts: int
    top_k: int
    window: int
    layout: Tuple[int, ...]  # per layer: 1 window + rotary, 0 global, none
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6

    @property
    def n_layers(self) -> int:
        return len(self.layout)


FAMILY = {
    # PowerInfer/SmallThinker-21BA3B-Instruct config.json, as published
    # (sliding_window_layout and rope_layout are the same list)
    "smallthinker_21b_a3b": Dims(
        d_model=2560, n_heads=28, n_kv_heads=4, head_dim=128, expert_ff=768,
        n_experts=64, top_k=6, window=4096, layout=(0, 1, 1, 1) * 13),
}

# (rows, contraction, columns) tile of the Pallas grouped product for this
# family's [., 2560] x [2560, 768] experts: the fastest of the eight of ten
# tried that fit VMEM (8.75 ms forward + backward of the grouped ReGLU at
# 24,576 live rows of a 49,152-row buffer against 9.19-10.60; PERF.md, PR 35)
GMM_TILING = (512, 768, 768)


def is_family(arch: str) -> bool:
    return dropless.arch_base(arch) in FAMILY


def parse_arch(arch: str) -> Optional[Tuple[Dims, int, Tuple[int, int]]]:
    """``(dims, layers kept, (first held expert, experts held))`` of an arch
    string of this family (``dropless.parse_share`` reads the syntax), None
    for any other. ``-l<n>`` keeps the FIRST n layers of the layout."""
    return dropless.parse_share(arch, FAMILY)


def route(p, x, dims: Dims):
    """``(idx [S, k] int32, w [S, k] float32)`` of the layer's input x
    [S, D]: float32 logits at HIGHEST on the hidden states as they are, the
    top k of the logits, the softmax over the chosen."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    g, idx = lax.top_k(logits, dims.top_k)
    return idx, jax.nn.softmax(g, axis=-1)


def attention_sublayer(p, x, dims: Dims, windowed: bool,
                       backend: str = "auto"):
    """x + Attention(RMSNorm(x)), causal, positions 0..T-1. Projections are
    einsums straight into and out of the kernels' [B, heads, T, d] layout."""
    B, T, D = x.shape
    H, K, d = dims.n_heads, dims.n_kv_heads, dims.head_dim
    c = lambda v: v.astype(x.dtype)
    h = rms_norm(p["ln1"], x, dims.rms_eps)
    with scopes.scope(scopes.ATTN):
        q = jnp.einsum("btd,dhe->bhte", h, c(p["wq"]).reshape(D, H, d))
        k = jnp.einsum("btd,dhe->bhte", h, c(p["wk"]).reshape(D, K, d))
        v = jnp.einsum("btd,dhe->bhte", h, c(p["wv"]).reshape(D, K, d))
        if windowed:
            with scopes.scope(scopes.WINDOW):
                pos = jnp.arange(T)
                q = rope_halves(q, pos, dims.rope_theta, d)
                k = rope_halves(k, pos, dims.rope_theta, d)
                o = causal_attention(q, k, v, backend=backend,
                                     window=dims.window)
        else:
            o = causal_attention(q, k, v, backend=backend)
        return x + jnp.einsum("bhtv,hvd->btd", o,
                              c(p["wo"]).reshape(H, d, D))


def block(name: str, dims: Dims, held: Tuple[int, int], windowed: bool,
          attention_backend: str) -> Layer:
    """One layer of the kind ``windowed`` says. Its state holds the step's
    routing counters (``moe/held_slots``, ``moe/load_max_over_mean``,
    ``moe/buffer_fill``, ``moe/top1_weight_mean``): outputs of the apply,
    so they leave a rematerialized layer like BatchNorm's statistics do."""
    count = held[1]

    def init(key, in_shape):
        T, D = in_shape
        assert D == dims.d_model
        H, K, d, f = dims.n_heads, dims.n_kv_heads, dims.head_dim, \
            dims.expert_ff
        ks = jax.random.split(key, 8)
        stack = lambda k, a, b: jax.vmap(lambda kk: _dense_init(kk, a, b))(
            jax.random.split(k, count))
        p = {"ln1": _scale_init(D),
             "wq": _dense_init(ks[0], D, H * d),
             "wk": _dense_init(ks[1], D, K * d),
             "wv": _dense_init(ks[2], D, K * d),
             "wo": _dense_init(ks[3], H * d, D),
             "ln2": _scale_init(D),
             "router": _dense_init(ks[4], D, dims.n_experts),
             "experts": {"w_gate": stack(ks[5], D, f),
                         "w_up": stack(ks[6], D, f),
                         "w_down": stack(ks[7], f, D)}}
        state = {"moe": dropless.initial_counters("top1_weight_mean")}
        return p, state, (T, D)

    def apply(p, s, x, train):
        B, T, D = x.shape
        with scopes.scope(scopes.ROUTE), scopes.scope(scopes.ROUTER):
            idx, w = route(p, x.reshape(B * T, D), dims)
        x = attention_sublayer(p, x, dims, windowed, attention_backend)
        h = rms_norm(p["ln2"], x, dims.rms_eps)
        with scopes.scope(scopes.ROUTE):
            y, counters = dropless.routed_experts(
                p["experts"], h.reshape(B * T, D), idx, w, held,
                dims.n_experts, GMM_TILING, act=jax.nn.relu)
        counters["top1_weight_mean"] = jnp.mean(w[:, 0])
        return x + y.reshape(B, T, D), {"moe": counters}

    return Layer(name, init, apply, f32_params=("router",))


def build(arch: str, in_shape, vocab: int,
          attention_backend: str = "auto") -> LayerModel:
    dims, n_layers, held = parse_arch(arch)
    layers: List[Layer] = [embed_tokens("embed", vocab, dims.d_model)]
    for i, windowed in enumerate(dims.layout[:n_layers]):
        layers.append(block(f"block{i + 1}", dims, held, bool(windowed),
                            attention_backend))
    layers.append(lm_head("lm_head", vocab, dims))
    # one chip's share of an expert-parallel group, without its exchange:
    # no strategy across chips is brought up. The layers are rematerialized
    # whatever the run's remat_layers says: at the published context the
    # interior activations of four layers are 11 GB beside 6.7 GB of train
    # state (local v5e compile), 5.1 GB rematerialized
    return LayerModel(arch, layers, tuple(in_shape), vocab,
                      input_kind="tokens", strategies=("single",),
                      remat_layers=True)
