"""Seq2seq (translation) workload — the reference's GNMT, re-designed TPU-first.

The reference's translation workload (SURVEY.md §2 C13;
pipedream-fork/{runtime,profiler}/translation) is a GNMT LSTM encoder-decoder
with Bahdanau attention, varlen packing CUDA kernels (D2), label smoothing, and
beam-search inference. None of that machinery survives a TPU-first redesign:

* LSTM recurrence serializes over time — the one thing the MXU cannot hide.
  The TPU-native seq2seq is a transformer with a **prefix-LM attention
  pattern**: source and target ride ONE [B, S+T] token stream; source
  positions attend bidirectionally within the source (the "encoder"), target
  positions attend causally to targets and fully to the source (the
  "decoder" + cross-attention), all in the same block. One activation stream
  means the model is a flat layer chain like every other model here, so it
  runs unchanged under single/dp/tp/fsdp/gpipe/pipedream AND sequence
  parallelism (ring attention applies the prefix rule on absolute key
  positions, so the source may span shards; ep stays causal-LM-only since
  MoE archs are LMs) — where the reference needed a separate model family
  and runtime driver (runtime/translation/main_with_runtime.py) for GNMT.
* The blocks ARE models/transformer.py's blocks: transformer_block takes a
  ``prefix_len`` that generalizes the causal mask, so seq2seq adds only the
  segment-aware embedding and the decode entry points below.
* Varlen packing (pack_utils CUDA, D2) disappears: batches are fixed-shape
  [B, S+T] streams with loss masking (label -1) on source positions — XLA
  gets static shapes, the masked positions cost FLOPs but keep the MXU busy,
  and the data pipeline needs no scatter kernels.
* Label smoothing (GNMT trains with 0.1) is in the shared loss
  (parallel/common.py cross_entropy_loss), applied via
  RunConfig.resolved_label_smoothing().
* Inference parity: greedy_decode and beam_search_decode below, both fully
  jitted with static shapes (lax.fori_loop over positions), replacing GNMT's
  Python beam-search generator.

The prefix split point (src_len) is static per dataset spec ("synthmt":
128 source + 128 target), so the attention mask is a compile-time constant.

Variants: seq2seq_s (8 x d512, ~GNMT-scale), seq2seq_m (12 x d768).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.models.layers import Layer, LayerModel
from ddlbench_tpu.models.transformer import (
    _dense_init,
    lm_head,
    shard_positions,
    transformer_block,
)

_VARIANTS = {
    "seq2seq_s": dict(d_model=512, n_layers=8, n_heads=8),
    "seq2seq_m": dict(d_model=768, n_layers=12, n_heads=12),
}


def seq2seq_embed(name: str, vocab: int, d_model: int, max_len: int,
                  src_len: int) -> Layer:
    """Token + learned position + segment (source=0 / target=1) embedding."""

    def init(key, in_shape):
        (T,) = in_shape
        k1, k2, k3 = jax.random.split(key, 3)
        p = {
            "tok": _dense_init(k1, vocab, d_model),
            "pos": _dense_init(k2, max_len, d_model),
            "seg": _dense_init(k3, 2, d_model),
        }
        return p, {}, (T, d_model)

    def apply(p, s, x, train):
        # x: [B, T] int32 (T = local shard length under sequence parallelism;
        # position/segment embeddings use absolute positions either way)
        pos_emb, abs_pos = shard_positions(p["pos"], x.shape[1])
        seg_ids = (abs_pos >= src_len).astype(jnp.int32)
        y = (jnp.take(p["tok"], x, axis=0)
             + pos_emb
             + jnp.take(p["seg"], seg_ids, axis=0))
        return y, s

    def decode(p, s, cache, x, pos):
        # x: [B, 1] at dynamic absolute position pos
        pe = lax.dynamic_slice_in_dim(p["pos"], pos, 1, axis=0)
        seg_id = (jnp.asarray(pos, jnp.int32) >= src_len).astype(jnp.int32)
        seg = jnp.take(p["seg"], seg_id[None], axis=0)
        return jnp.take(p["tok"], x, axis=0) + pe + seg, cache

    return Layer(name, init, apply, decode=decode)


def build_seq2seq(arch: str, in_shape, vocab: int, src_len: int,
                  attention_backend: str = "auto") -> LayerModel:
    cfgv = _VARIANTS[arch]
    T = in_shape[0]
    if not 0 < src_len < T:
        raise ValueError(f"src_len {src_len} must be inside the stream (T={T})")
    layers: List[Layer] = [
        seq2seq_embed("embed", vocab, cfgv["d_model"], T, src_len)
    ]
    for i in range(cfgv["n_layers"]):
        layers.append(
            transformer_block(f"block{i + 1}", cfgv["d_model"],
                              cfgv["n_heads"], prefix_len=src_len,
                              attention_backend=attention_backend)
        )
    layers.append(lm_head("lm_head", vocab))
    return LayerModel(arch, layers, tuple(in_shape), vocab,
                      input_kind="tokens", src_len=src_len)


# ---------------------------------------------------------------------------
# Inference (GNMT beam-search parity, reference
# runtime/translation seq2seq inference modules). Both decoders re-run the
# full forward per emitted token — O(T^2) per sequence but fully static-shaped
# and jittable. By default both delegate to the KV-cached incremental
# implementation (models/decode.py, O(T) per token); the full-forward loops
# below are the reference semantics the cached path is tested against.
# ---------------------------------------------------------------------------


def _check_src(model: LayerModel, src, total_len: int) -> None:
    if model.src_len is None:
        raise ValueError(f"{model.name} is not a seq2seq model")
    if src.ndim != 2 or src.shape[1] != model.src_len:
        raise ValueError(
            f"src must be [B, {model.src_len}] (the src_len baked into "
            f"{model.name}'s attention masks), got {tuple(src.shape)}"
        )
    T = model.in_shape[0]
    if not model.src_len < total_len <= T:
        raise ValueError(
            f"total_len must be in ({model.src_len}, {T}] (past the source, "
            f"within {model.name}'s trained context), got {total_len}"
        )


def _forward_logits(model: LayerModel, params, state, tokens):
    from ddlbench_tpu.models.layers import apply_model

    logits, _ = apply_model(model, params, state, tokens, False)
    return logits


def greedy_decode(model: LayerModel, params, state, src, total_len: int,
                  use_cache: bool = True):
    """Greedy continuation of `src` [B, src_len] to length `total_len`.

    Returns [B, total_len] where positions >= src_len are argmax
    continuations. ``use_cache=True`` (default) takes the KV-cached
    incremental path (models/decode.py, O(T) per token); ``use_cache=False``
    is the full-forward reference implementation the cached path is tested
    against.
    """
    _check_src(model, src, total_len)
    if use_cache:
        from ddlbench_tpu.models.decode import greedy_decode as cached

        return cached(model, params, state, src, total_len)
    B, S = src.shape
    x0 = jnp.zeros((B, total_len), jnp.int32).at[:, :S].set(src)

    def body(t, x):
        logits = _forward_logits(model, params, state, x)
        nxt = jnp.argmax(logits[:, t - 1], axis=-1).astype(jnp.int32)
        return x.at[:, t].set(nxt)

    return lax.fori_loop(S, total_len, body, x0)


def beam_search_decode(model: LayerModel, params, state, src, total_len: int,
                       beam: int = 4, length_penalty: float = 0.6,
                       use_cache: bool = True):
    """Beam-search continuation of `src` [B, src_len] to length `total_len`.

    Standard length-normalized beam search (GNMT inference semantics:
    score = logprob_sum / ((5+len)/6)^alpha) over a static position loop.
    ``use_cache=True`` (default) keeps per-hypothesis KV caches and regathers
    them along the parent beam (models/decode.py); ``use_cache=False``
    re-runs the full forward per step (the reference implementation).
    Hypotheses all have the same (full) length so no finished-hypothesis
    bookkeeping is needed. Returns (tokens [B, total_len], score [B]) for
    the best beam.
    """
    _check_src(model, src, total_len)
    if use_cache:
        from ddlbench_tpu.models.decode import beam_search_decode as cached

        return cached(model, params, state, src, total_len, beam,
                      length_penalty)
    B, S = src.shape
    V = model.num_classes
    # [B*beam, total_len] hypothesis buffer; beams identical at start.
    x0 = jnp.zeros((B, total_len), jnp.int32).at[:, :S].set(src)
    x0 = jnp.repeat(x0, beam, axis=0)
    # First expansion must come from ONE beam per batch item (all beams are
    # identical); mask others with -inf.
    score0 = jnp.where(
        jnp.arange(B * beam) % beam == 0, 0.0, -jnp.inf
    ).astype(jnp.float32)

    def body(t, carry):
        x, score = carry
        logits = _forward_logits(model, params, state, x)  # [B*beam, T, V]
        logp = jax.nn.log_softmax(logits[:, t - 1].astype(jnp.float32), -1)
        # candidate scores: [B, beam*V]
        cand = (score[:, None] + logp).reshape(B, beam * V)
        top_score, top_idx = lax.top_k(cand, beam)  # [B, beam]
        beam_src = top_idx // V  # which parent beam
        token = (top_idx % V).astype(jnp.int32)
        flat_src = (jnp.arange(B)[:, None] * beam + beam_src).reshape(-1)
        x = x[flat_src].at[:, t].set(token.reshape(-1))
        return x, top_score.reshape(-1)

    x, score = lax.fori_loop(S, total_len, body, (x0, score0))
    # length-normalized best beam per batch item
    norm = ((5.0 + (total_len - S)) / 6.0) ** length_penalty
    score = (score / norm).reshape(B, beam)
    best = jnp.argmax(score, axis=-1)
    x = x.reshape(B, beam, total_len)[jnp.arange(B), best]
    return x, score[jnp.arange(B), best]
