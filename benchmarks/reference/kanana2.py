"""Plain reference for the ``kanana-2-30b-a3b`` configuration (HF
``deepseek_v3`` as ``kakaocorp/kanana-2-30b-a3b-instruct-2601`` config.json
sets it: ``q_lora_rank`` null, ``rope_scaling`` null, no MTP layer) in
float32 jax.numpy at HIGHEST matmul precision: no kernels, no sorting, no
cache. Nothing here imports the program.

With ``h = RMSNorm(x) = x * rsqrt(mean x^2 + eps) * w``, per block:

* MLA. ``q = h W_q`` -> [T, H, nope + rope]; ``h W_kva`` -> ``c`` [latent]
  and ``k_pe`` [rope], one for all heads; ``RMSNorm(c) W_kvb`` -> [T, H,
  nope + v] = ``k_nope``, ``v``. RoPE on ``q_pe`` and ``k_pe``: the pair
  (2i, 2i+1) turned by ``pos * theta^(-2i/rope)`` (``rope_interleave``).
  ``k = k_nope || k_pe``, scores ``q k^T / sqrt(nope + rope)``, causal
  softmax, ``o = P v``, ``x += o W_o``.
* the first ``first_k_dense_replace`` layers: ``x += W_down(silu(W_gate h)
  * W_up h)``.
* the others: logits ``h W_r`` in float32, ``s = sigmoid(logits)``, the top
  ``num_experts_per_tok`` of ``s + b`` (``n_group = topk_group = 1``: the
  group step is the identity), ``w = s[idx]`` (without ``b``),
  ``w /= sum w + 1e-20``, ``w *= routed_scaling_factor``;
  ``x += sum_k w_k E_idx_k(h) + Shared(h)``.
* final RMSNorm, untied head, no bias anywhere, mean cross-entropy over the
  vocabulary held.

**The share.** The configuration holds ``n_routed_experts_held`` experts
from ``first_expert_held`` on, of ``n_routed_experts``: the router keeps all
its outputs and its k per token, ``w`` is normalised over all k chosen, and
only the held experts' terms are summed — each held expert over every token,
weighted by what the router gave it (0 for most). What the absent experts
would add is left out, here as in the program.

Departures from the published description (``assumed`` in the
configuration's file): the vocabulary is a slice (padded to a multiple of
128); ``b`` is a fixed leaf (``stop_gradient``), seeded like a bias;
positions are 0..T-1 of each packed sequence.

Parameters are a flat dict: ``embed/tok``; per block ``ln1/scale``, ``wq``,
``wkv_a``, ``kv_norm/scale``, ``wkv_b``, ``wo``, ``ln2/scale`` and either
``w_gate``, ``w_up``, ``w_down`` or ``router``, ``router_bias``,
``shared/w_*``, ``experts/w_*`` (stacked over the experts held);
``lm_head/norm/scale``, ``lm_head/head``.

FLOPs from shapes, by the repo's convention (matmuls only, one multiply-add
= 2, the causal half of the attention scores, nothing recomputed, training =
3 x forward, the padded vocabulary) **with the held experts counted at
balanced routing**: ``tokens x num_experts_per_tok x held / n_routed``
token-slots a layer, whatever the seed's router really sent (the step's
``moe_held_slots`` counter says what it sent).

``config["fault"]`` plants one of FAULTS for the readings that set the
limits, and ``config["choices"]`` ([sequences, expert layers, T, k]) puts
given experts in place of the router's own top-k, for the side reading that
tells a rounding's flipped choices from the rest of what it moves
(``readings_faults.py``); a benchmark run sets neither.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import HIGHEST, cross_entropy_sum, exact

HEAD_BLOCK = 4  # heads whose [T, T] float32 scores exist at once
FAULTS = ("no_renorm",   # weights not renormalised over the chosen
          "capacity",    # tokens over 1.25 x the mean load dropped
          "no_rope")     # the rotary slice left unrotated


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _rms(w, x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def _rope(x, theta):
    """x [T, ..., r]: pairs (2i, 2i+1) turned by pos * theta^(-2i/r)."""
    T, r = x.shape[0], x.shape[-1]
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(P, name, h, rnd, e=None):
    w = (lambda k: P[f"{name}/{k}"]) if e is None else \
        (lambda k: P[f"{name}/{k}"][e])
    return _mm(jax.nn.silu(_mm(h, w("w_gate"), rnd)) * _mm(h, w("w_up"), rnd),
               w("w_down"), rnd)


def _attention(q, k, v, rnd):
    """q, k [H, T, dqk], v [H, T, dv] -> [H, T, dv]; HEAD_BLOCK heads at a
    time, each block rematerialized in the backward pass."""
    H, T, dqk = q.shape
    hb = math.gcd(H, HEAD_BLOCK)
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv
        s = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(kb),
                       precision=HIGHEST) / math.sqrt(dqk)
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)),
                          rnd(vb), precision=HIGHEST)

    split = lambda t: t.reshape(H // hb, hb, T, t.shape[-1])
    return lax.map(block, (split(q), split(k), split(v))).reshape(H, T, -1)


def _mla(P, name, x, cfg, rnd):
    T = x.shape[0]
    H, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    latent, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    h = _rms(P[f"{name}/ln1/scale"], x, eps)
    q = _mm(h, P[f"{name}/wq"], rnd).reshape(T, H, nope + rope)
    ckv = _mm(h, P[f"{name}/wkv_a"], rnd)
    c, k_pe = ckv[:, :latent], ckv[:, latent:]
    kv = _mm(_rms(P[f"{name}/kv_norm/scale"], c, eps), P[f"{name}/wkv_b"],
             rnd).reshape(T, H, nope + dv)
    q_pe = q[..., nope:]
    if cfg.get("fault") != "no_rope":
        q_pe, k_pe = _rope(q_pe, cfg["rope_theta"]), \
            _rope(k_pe, cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (T, H, rope))], axis=-1)
    o = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                   kv[..., nope:].transpose(1, 0, 2), rnd)
    return x + _mm(o.transpose(1, 0, 2).reshape(T, H * dv), P[f"{name}/wo"],
                   rnd)


def route(P, name, h, cfg):
    """(idx [T, k], w [T, k]): the router in float32, whatever ``rnd``."""
    s = jax.nn.sigmoid(jnp.matmul(h, P[f"{name}/router"], precision=HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(P[f"{name}/router_bias"]),
                       cfg["num_experts_per_tok"])
    if cfg.get("choices") is not None:  # this sequence's: [expert layers, T, k]
        layer = int(name[len("block"):]) - cfg["first_k_dense_replace"] - 1
        idx = cfg["choices"][layer]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("fault") != "no_renorm":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def _moe(P, name, h, cfg, rnd):
    """Shared(h) + the held experts' part of sum_k w_k E_idx_k(h)."""
    idx, w = route(P, name, h, cfg)
    y = _swiglu(P, f"{name}/shared", h, rnd)
    first = cfg.get("first_expert_held", 0)
    for e in range(cfg["n_routed_experts_held"]):
        sent = idx == first + e  # [T, k]: at most one True a token
        we = jnp.sum(jnp.where(sent, w, 0.0), axis=-1)
        if cfg.get("fault") == "capacity":
            cap = math.ceil(1.25 * h.shape[0] * idx.shape[1]
                            / cfg["n_routed_experts"])
            took = jnp.any(sent, axis=-1)
            we = jnp.where(jnp.cumsum(took) <= cap, we, 0.0)
        y = y + we[:, None] * _swiglu(P, f"{name}/experts", h, rnd, e)
    return y


def _block(P, i, x, cfg, rnd):
    name = f"block{i}"
    x = _mla(P, name, x, cfg, rnd)
    h = _rms(P[f"{name}/ln2/scale"], x, cfg["rms_norm_eps"])
    if i <= cfg["first_k_dense_replace"]:
        return x + _swiglu(P, name, h, rnd)
    return x + _moe(P, name, h, cfg, rnd)


def logits(P, tokens, config, rnd=exact, store=exact):
    """[T, padded vocabulary] of ONE sequence ``tokens`` [T]. ``store``
    rounds what a lower-precision run would keep between blocks."""
    x = store(jnp.take(P["embed/tok"], tokens, axis=0))
    for i in range(1, config["n_layer"] + 1):
        sub = {k: v for k, v in P.items() if k.startswith(f"block{i}/")}
        x = store(jax.checkpoint(
            lambda s, x, i=i: _block(s, i, x, config, rnd))(sub, x))
    h = _rms(P["lm_head/norm/scale"], x, config["rms_norm_eps"])
    return _mm(store(h), P["lm_head/head"], rnd)


def choices(P, tokens, config, rnd=exact):
    """[expert layers, T, k]: the experts the router chooses for ONE
    sequence, with every matmul operand and every kept activation rounded
    by ``rnd`` — how far a lower compute precision moves the top-k."""
    x = rnd(jnp.take(P["embed/tok"], tokens, axis=0))
    out = []
    for i in range(1, config["n_layer"] + 1):
        if i > config["first_k_dense_replace"]:
            name = f"block{i}"
            h = _rms(P[f"{name}/ln2/scale"], _mla(P, name, x, config, rnd),
                     config["rms_norm_eps"])
            out.append(route(P, name, h, config)[0])
        x = rnd(_block(P, i, x, config, rnd))
    return jnp.stack(out)


def loss_and_grads(P, tokens, labels, config, rnd=exact):
    """Mean next-token loss over all positions and its gradients, summed
    over the sequences one at a time (rows are independent; a sequence's
    [H, T, T] scores exist for HEAD_BLOCK heads at a time). A control rounds
    what is kept between blocks as well as the matmuls' operands; the router
    stays float32, as the configuration states."""
    n_tok = labels.size

    def seq_loss(P, x, y, given):
        cfg = config if given is None else dict(config, choices=given)
        return cross_entropy_sum(logits(P, x, cfg, rnd, rnd), y) / n_tok

    def step(carry, xy):
        loss, grads = carry
        l, g = jax.value_and_grad(seq_loss)(P, *xy)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    init = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, P))
    (loss, grads), _ = lax.scan(step, init,
                                (tokens, labels, config.get("choices")))
    return loss, grads, {}  # no normalization statistics are kept


def held_slots_balanced(config, tokens: int) -> float:
    """Token-slots that reach a held expert in one layer when the router
    spreads its choices evenly."""
    return (tokens * config["num_experts_per_tok"]
            * config["n_routed_experts_held"] / config["n_routed_experts"])


def kernel_calls(kernel: str, config, traffic):
    """``[(calls a train step, keyword arguments of
    benchmarks/kernels/<kernel>.work)]`` for this configuration under a mix.

    ``flash_attn``: one forward + backward a block at q/k 192 wide and v/o
    128 wide. ``flash_attn.work`` counts six matmuls and twelve tensors of
    ONE width; three of each six are q/k-wide and three v-wide (QK^T, dQ, dK
    against PV, dV, dP; Q, K read twice + dQ, dK against V, O read twice +
    dO, dV), so the mean width ``(3 * 192 + 3 * 128) / 6 = 160`` gives the
    split shapes' FLOPs and bytes exactly.
    ``moe_gmm``: the expert layers' grouped products, slots at balanced
    routing."""
    B, T = traffic["run_config"]["batch_size"], config["n_positions"]
    L, H = config["n_layer"], config["num_attention_heads"]
    if kernel == "flash_attn":
        dh = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"]) / 2
        return [(L, dict(B=B, H=H, T=T, dh=dh))]
    if kernel == "fused_xent":
        return [(1, dict(N=B * T, D=config["hidden_size"],
                         V=config["padded_vocab_size"]))]
    if kernel == "moe_gmm":
        return [(L - config["first_k_dense_replace"],
                 dict(slots=held_slots_balanced(config, B * T),
                      D=config["hidden_size"],
                      F=config["moe_intermediate_size"],
                      G=config["n_routed_experts_held"]))]
    raise KeyError(f"the kanana2 reference has no call shapes of {kernel!r}")


def matmul_params_per_token(config) -> float:
    """Parameters a token meets in a matmul, forward: MLA (W_q, W_kva, W_kvb,
    W_o), the dense SwiGLU or router + shared + routed experts (the held
    ones at balanced routing), and the head over the padded vocabulary."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, latent = config["v_head_dim"], config["kv_lora_rank"]
    L, dense = config["n_layer"], config["first_k_dense_replace"]
    f = config["moe_intermediate_size"]
    mla = (d * H * (nope + rope) + d * (latent + rope)
           + latent * H * (nope + dv) + H * dv * d)
    routed = held_slots_balanced(config, 1) * 3 * d * f
    expert_layer = (d * config["n_routed_experts"]
                    + 3 * d * config["n_shared_experts"] * f + routed)
    return (L * mla + dense * 3 * d * config["intermediate_size"]
            + (L - dense) * expert_layer + d * config["padded_vocab_size"])


def train_flops_per_sample(config, sample_shape) -> float:
    """One sequence of ``sample_shape[0]`` tokens, forward and backward."""
    T, L, H = sample_shape[0], config["n_layer"], config["num_attention_heads"]
    widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
              + config["v_head_dim"])  # QK^T over q/k, PV over v
    attn = L * H * 2.0 * widths * T * (T + 1) / 2
    return 3.0 * (2.0 * matmul_params_per_token(config) * T + attn)
