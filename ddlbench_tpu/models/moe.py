"""Mixture-of-experts transformer LM — the expert-parallel (EP) workload.

The reference has no MoE models anywhere (SURVEY.md §2E marks EP absent), so
this is a new first-class capability, designed TPU-first rather than ported:

* Switch-style top-1 routing with a static capacity per expert, expressed as
  dense one-hot dispatch/combine einsums — fixed shapes, no gather/scatter, so
  XLA tiles the whole layer onto the MXU.
* Expert FFNs are a single batched einsum over a stacked ``[E, ...]`` weight
  axis; under expert parallelism that axis is sharded over an ``expert`` mesh
  axis and token blocks move with two ``lax.all_to_all`` collectives
  (dispatch there, combine back) riding ICI.
* The router's load-balance auxiliary loss (Switch eq. 4) is published through
  a trace-time collector so strategies can add it to the objective without
  threading it through every Layer signature.

One model definition serves dense (single/dp/sp/tp/fsdp) and expert-parallel
(ep) execution: parallel/ep.py enters :class:`expert_parallel` inside its
shard_map, exactly the pattern models/transformer.py uses for sequence
parallelism. EVERY strategy adds the collected aux loss to its training
objective (weight cfg.moe_aux_weight): single/dp/tp/fsdp through
loss_with_moe_aux, sp/ep with a psum over their shard axis, gpipe by
accumulating per-stage aux through its scan, and pipedream by adding each
stage's aux term to the per-microbatch objective in its recompute-based
backward.
"""

from __future__ import annotations

import contextlib
import math
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models.layers import Layer, LayerModel, axis_context
from ddlbench_tpu.models.transformer import (
    _dense_init,
    _ln_init,
    attention_sublayer,
    attn_cache_init,
    attn_decode_op,
    attn_prefill_op,
    embed,
    layer_norm,
    lm_head,
)
from ddlbench_tpu.telemetry import scopes

_VARIANTS = {
    # every other block is MoE (Switch/GShard convention)
    "transformer_moe_s": dict(d_model=512, n_layers=8, n_heads=8, n_experts=8),
}

class expert_parallel(axis_context):
    """Context manager: trace MoE applies in expert-parallel mode. When active
    (parallel/ep.py enters it inside its shard_map), the stacked expert
    weights seen by apply are the LOCAL shard and token blocks are exchanged
    with all_to_all over the named axis."""

    _stack: list = []


def _expert_axis():
    return expert_parallel.current()


# Trace-time sink for router auxiliary losses (one scalar per MoE layer).
_AUX_SINK: list = []


@contextlib.contextmanager
def collect_aux_losses(out: list):
    """Collect each MoE layer's load-balance loss traced inside the block."""
    _AUX_SINK.append(out)
    try:
        yield out
    finally:
        _AUX_SINK.pop()


def _record_aux(v):
    if _AUX_SINK:
        _AUX_SINK[-1].append(v)


def _top1_gate(gate_logits: jax.Array):
    """Shared top-1 routing core: (probs f32, one-hot choice, chosen-expert
    probability). Used by training routing (switch_route) AND the cached
    decode path so the two can never diverge."""
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
    gate = jnp.sum(probs * onehot, axis=-1)
    return probs, onehot, gate


def switch_route(gate_logits: jax.Array, capacity: int):
    """Top-1 switch routing over [S, E] router logits.

    Returns (dispatch [S, E, C] 0/1, combine [S, E, C] gate-weighted, aux).
    Tokens beyond an expert's capacity C are dropped (their dispatch row is
    all-zero, so they pass through the residual unchanged) — the standard
    Switch semantics, static shapes throughout.
    """
    S, E = gate_logits.shape
    probs, onehot, gate = _top1_gate(gate_logits)
    # load-balance aux (Switch eq. 4): E * sum_e fraction_e * mean_prob_e
    aux = E * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    _record_aux(aux)
    # 1-based position of each token within its expert's queue
    pos1 = jnp.cumsum(onehot, axis=0) * onehot
    within = (pos1 <= capacity).astype(jnp.float32)
    # one_hot of -1 (token not routed to e) is all-zero
    dispatch = jax.nn.one_hot(
        (pos1 - 1.0).astype(jnp.int32), capacity, dtype=jnp.float32
    ) * within[..., None]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, aux


def _expert_ffn(pe, x):
    """Batched expert MLP: x [E_local, C', d] -> [E_local, C', d]."""
    h = jnp.einsum("ecd,edf->ecf", x, pe["w1"].astype(x.dtype))
    h = jax.nn.gelu(h + pe["b1"][:, None, :].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", h, pe["w2"].astype(x.dtype))
    return y + pe["b2"][:, None, :].astype(x.dtype)


@scopes.scope(scopes.MLP)
def moe_mlp(p, x, capacity_factor: float):
    """Switch MoE feed-forward over x [B, T, d]; returns [B, T, d].

    Dense mode: all E experts are local. Expert-parallel mode (inside
    :class:`expert_parallel`): ``p["experts"]`` holds this device's E/n
    experts; dispatched token blocks are exchanged with ``lax.all_to_all``
    (split the expert axis, concatenate the capacity axis), the local experts
    run one batched einsum over tokens from every device, and a second
    all_to_all brings results home for the combine.
    """
    B, T, d = x.shape
    S = B * T
    xf = x.reshape(S, d)
    E = p["gate"].shape[1]
    E_local = p["experts"]["w1"].shape[0]
    capacity = max(1, math.ceil(capacity_factor * S / E))

    gate_logits = xf.astype(jnp.float32) @ p["gate"]
    dispatch, combine, _ = switch_route(gate_logits, capacity)
    dispatch = dispatch.astype(x.dtype)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, xf)  # [E, C, d]

    axis = _expert_axis()
    if axis is None:
        if E_local != E:
            raise ValueError(
                f"{E_local}/{E} experts present outside expert_parallel context"
            )
        expert_out = _expert_ffn(p["experts"], expert_in)
    else:
        # [E, C, d] -> [E/n, n*C, d]: each device keeps its experts' blocks
        # from every peer.
        expert_in = lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=1, tiled=True
        )
        expert_out = _expert_ffn(p["experts"], expert_in)
        # [E/n, n*C, d] -> [E, C, d]: blocks return to their source device.
        expert_out = lax.all_to_all(
            expert_out, axis, split_axis=1, concat_axis=0, tiled=True
        )
    y = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), expert_out)
    return y.reshape(B, T, d)


def moe_block(name: str, d_model: int, n_heads: int, n_experts: int,
              mlp_ratio: int = 4, capacity_factor: float = 1.25, *,
              attention_backend: str) -> Layer:
    """Pre-LN transformer block whose MLP is a switch-routed expert bank."""
    d_ff = mlp_ratio * d_model

    def init(key, in_shape):
        T, dm = in_shape
        assert dm == d_model
        ks = jax.random.split(key, 5)
        p = {
            "ln1": _ln_init(dm),
            "wqkv": _dense_init(ks[0], dm, 3 * dm),
            "wo": _dense_init(ks[1], dm, dm),
            "ln2": _ln_init(dm),
            "gate": _dense_init(ks[2], dm, n_experts),
            "experts": {
                "w1": jax.vmap(lambda k: _dense_init(k, dm, d_ff))(
                    jax.random.split(ks[3], n_experts)
                ),
                "b1": jnp.zeros((n_experts, d_ff), jnp.float32),
                "w2": jax.vmap(lambda k: _dense_init(k, d_ff, dm))(
                    jax.random.split(ks[4], n_experts)
                ),
                "b2": jnp.zeros((n_experts, dm), jnp.float32),
            },
        }
        return p, {}, (T, dm)

    def apply(p, s, x, train):
        x = attention_sublayer(p, x, n_heads, backend=attention_backend)
        h = layer_norm(p["ln2"], x)
        x = x + moe_mlp(
            {"gate": p["gate"], "experts": p["experts"]}, h, capacity_factor
        )
        return x, s

    # ---- KV-cached incremental decoding (models/decode.py protocol) ----

    def _moe_ffn(p, x):
        h = layer_norm(p["ln2"], x)
        return x + moe_mlp(
            {"gate": p["gate"], "experts": p["experts"]}, h, capacity_factor
        )

    def _reject_ep():
        if _expert_axis() is not None:
            raise NotImplementedError(
                "cached decoding under expert_parallel is not supported; "
                "decode outside the ep shard_map")

    def prefill(p, s, cache, x, start):
        _reject_ep()
        x, cache = attn_prefill_op(p, x, cache, n_heads, 0, start,
                                   attention_backend)
        return _moe_ffn(p, x), cache

    def _moe_ffn_token(p, x):
        """Per-token top-1 expert FFN for one decoded position [B, 1, d].
        Decode routing has no capacity limit (each token simply runs its
        chosen expert — standard MoE inference); this matches the training
        semantics exactly whenever apply's capacity didn't drop the
        token."""
        h = layer_norm(p["ln2"], x)  # [B, 1, d]
        hf = h[:, 0]
        _, onehot, gate = _top1_gate(hf.astype(jnp.float32) @ p["gate"])
        pe = p["experts"]
        # all-expert compute for the single position (E small, B small at
        # decode time), then gate-weighted top-1 combine
        eh = jnp.einsum("bd,edf->bef", hf, pe["w1"].astype(hf.dtype))
        eh = jax.nn.gelu(eh + pe["b1"][None].astype(hf.dtype))
        ey = jnp.einsum("bef,efd->bed", eh, pe["w2"].astype(hf.dtype))
        ey = ey + pe["b2"][None].astype(hf.dtype)
        w = (onehot * gate[:, None]).astype(hf.dtype)
        y = jnp.einsum("be,bed->bd", w, ey)
        return x + y[:, None, :]

    def decode(p, s, cache, x, pos):
        _reject_ep()
        x, cache = attn_decode_op(p, x, cache, n_heads, pos)
        return _moe_ffn_token(p, x), cache

    dh = d_model // n_heads

    # paged-cache protocol: same attention sublayer ops as the dense
    # transformer block (models/transformer.py), same MoE FFN as decode
    from ddlbench_tpu.models.layers import PagedOps
    from ddlbench_tpu.models.transformer import (attn_paged_cache_init,
                                                 attn_paged_decode_op,
                                                 attn_paged_prefill_op,
                                                 attn_paged_reorder)

    def paged_prefill(p, s, cache, x, start):
        _reject_ep()
        x, cache = attn_paged_prefill_op(p, x, cache, n_heads, 0, start,
                                         attention_backend)
        return _moe_ffn(p, x), cache

    def paged_decode(p, s, cache, x, pos):
        _reject_ep()
        x, cache = attn_paged_decode_op(p, x, cache, n_heads, pos)
        return _moe_ffn_token(p, x), cache

    return Layer(name, init, apply, init_cache=attn_cache_init(n_heads, dh),
                 prefill=prefill, decode=decode,
                 paged=PagedOps(attn_paged_cache_init(n_heads, dh),
                                paged_prefill, paged_decode,
                                attn_paged_reorder))


def build_transformer_moe(arch: str, in_shape, vocab: int,
                          capacity_factor: float = 1.25,
                          attention_backend: str = "auto") -> LayerModel:
    """MoE variant of the transformer LM: dense and MoE blocks alternate."""
    from ddlbench_tpu.models.transformer import transformer_block

    cfgv = _VARIANTS[arch]
    T = in_shape[0]
    layers: List[Layer] = [embed("embed", vocab, cfgv["d_model"], T)]
    for i in range(cfgv["n_layers"]):
        if i % 2 == 1:
            layers.append(moe_block(
                f"moe_block{i + 1}", cfgv["d_model"], cfgv["n_heads"],
                cfgv["n_experts"], capacity_factor=capacity_factor,
                attention_backend=attention_backend,
            ))
        else:
            layers.append(
                transformer_block(f"block{i + 1}", cfgv["d_model"],
                                  cfgv["n_heads"],
                                  attention_backend=attention_backend)
            )
    layers.append(lm_head("lm_head", vocab))
    return LayerModel(arch, layers, tuple(in_shape), vocab, input_kind="tokens")
