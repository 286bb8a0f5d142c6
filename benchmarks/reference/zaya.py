"""Plain reference for the ``zaya1-8b`` configuration (HF ``zaya`` as
``Zyphra/ZAYA1-8B`` config.json sets it: 40 ``hybrid`` layers, hidden 2048,
8 query heads over 2 key/value heads of 128, ``cca_time0 = cca_time1 = 2``,
16 experts of 2048, one per token, ``router_hidden_size`` 256, tied
embedding) in float32 jax.numpy at HIGHEST matmul precision: no kernels, no
sorting, no cache. Nothing here imports the program. The layers are written
from that config and the two descriptions its catalog row names: compressed
convolutional attention (arXiv:2510.04476) and the ZAYA1 report
(arXiv:2511.17127).

``D`` hidden, ``H`` query heads, ``K`` key/value heads, ``d`` head width,
``G = H / K``, ``R`` the router's width, ``E`` experts. Between layers
travels the pair ``(x [T, D], r [T, R])``, ``r = 0`` into the first layer.
``RMSNorm(x) = x * rsqrt(mean x^2 + eps) * w``. One ``hybrid`` layer is an
attention sublayer, then an expert sublayer; each ends in the merge

    x <- (a_x * x + b_x) + (a_f * f + b_f)

with ``f`` the sublayer's output and four vectors of ``D`` of its own.

**Attention in the compressed latent**, on ``h = RMSNorm(x)``:

1. ``q0 = h W_q`` [T, H, d], ``k0 = h W_k`` [T, K, d], no bias.
2. value shift: ``v = [h W_v1 || h_prev W_v2]`` [T, K, d] with ``h_prev[t] =
   h[t-1]``, ``h_prev[0] = 0``, each matrix ``D x (K d / 2)``: kv head 0 is
   this token's value, kv head 1 the previous token's.
3. q-k mean: ``m_q = (q0 + rep(k0)) / 2`` (query head j reads kv head
   ``j // G``), ``m_k[:, c]`` the mean of ``m_q`` over the G query heads of c.
4. two causal convolutions over ``u = [q0 || k0]`` (``(H + K) d`` channels,
   zero left padding): ``c1[t] = beta1 + sum_tau w1[tau] * u[t - (n0 - 1) +
   tau]`` (depthwise, ``n0 = cca_time0``), then per head g of the H + K:
   ``c2_g[t] = beta2_g + sum_tau c1_g[t - (n1 - 1) + tau] W2[g, tau]`` (d x d
   each, ``n1 = cca_time1``); the second pads with zeros too, not beta1.
5. ``q1 = c2[:, :H d] + m_q``, ``k1 = c2[:, H d:] + m_k``.
6. ``q2 = sqrt(d) q1 / |q1|``, ``k2 = sqrt(d) e^tau_c k1 / |k1|`` per token
   and head (``|x| = sqrt(sum x^2 + 1e-12)``), one temperature a kv head.
7. RoPE on the first ``d * partial_rotary_factor`` channels of every q and
   k head, ``rope_type: default``: the HALVES (i, i + r/2) are turned by
   ``pos * theta^(-2i/r)``, positions 0..T-1.
8. ``o = softmax_causal(q2 k2^T / sqrt(d)) v`` with query head j on kv head
   ``j // G``; ``f = o W_o``.

**Experts**, on ``h = RMSNorm(x)``:

1. ``r = h W_d + b_d``, and from the second layer on ``r += gamma * r_prev``
   (``r_prev``: what the layer before passed on); ``r`` is passed on.
2. ``z = RMSNorm_R(r)``, ``a1 = gelu(z W_1 + b_1)``, ``a2 = gelu(a1 W_2 +
   b_2)`` (erf), ``logits = a2 W_3`` [E], ``p = softmax(logits)``: float32
   whatever the compute precision.
3. ``e = argmax(p + beta)``; ``beta`` moves the choice and never the weight
   and gets no gradient. Weight ``g = p[e]``, not renormalised (top-1).
   ``beta`` is no parameter but the layer's state, 0 at the start; AFTER a
   training step it is moved by that step's own probabilities over all
   the step's tokens and all E experts: ``select_bias_updates_per_step``
   times in turn ``beta_i += u sign(mean load - load_i)``, the loads of
   ``argmax(p + beta)`` counted anew each time (``balanced_bias``; u =
   ``select_bias_update_rate``).
4. ``f = g * W_down,e (silu(W_gate,e h) * W_up,e h)``; no shared expert.

**Head.** RMSNorm, ``logits = h E^T`` with E the embedding (tied), mean
cross-entropy over the rows held.

**The share.** The configuration holds ``num_experts_held`` experts from
``first_expert_held`` on, of ``num_experts``: the router keeps all its
outputs; a token whose expert is not held gets ``f = 0``; nothing stands in
for the other chip. The vocabulary is a slice of rows.

**Assumed** (the row's ``config`` has no key for it; ``assumed`` in the
configuration's file): the merge (``scale_residual_merge`` in the family's
other catalog rows; the order of scale and bias is a parametrisation); the
place of the temperature in step 6 and the 1e-12 under the root; the
carried router state (``zaya_use_eda`` in the sibling rows); erf GELU in the
router; ``beta``'s update: the report balances its router by the
selection biases alone, without an auxiliary loss, but its controller and
gains are not in config.json, so the rule is the sign rule of
arXiv:2408.15664 at the rate DeepSeek-V3 states (arXiv:2412.19437, section
4.2: 0.001), repeated within the step because Adam at 3e-4 without a
warm-up moves an untrained router further in a step than one such update
follows. The parameters hold no ``beta``: ``loss_and_grads`` reads
``block<i>/router/select_bias`` from ``P`` where its caller put the state
(``next_select_bias`` gives the state after a step) and 0 where not — the
benchmark's harness hands the parameters alone from step to step, so its
flow follows steps 2 and 3 with ``beta = 0`` (PERF.md section 7); packed sequences,
positions 0..T-1 in each, no document mask; the vocabulary slice padded to
a multiple of 128.

**Departure.** The sibling rows' ``zaya_use_mod`` (a skip path chosen by the
router) has no key in this row's ``config`` and its equation cannot be taken
from the row: it is left out, and the router has ``num_experts`` outputs.

Parameters are a flat dict: ``embed/tok``; per block ``ln1/scale``, ``wq``,
``wk``, ``wv1``, ``wv2``, ``conv_dw`` [n0, (H+K) d], ``conv_dw_bias``,
``conv_head`` [H+K, n1, d, d], ``conv_head_bias``, ``temp`` [K], ``wo``,
``merge_attn/{x,f}/{scale,bias}``, ``ln2/scale``, ``router/{w_d, b_d,
carry/scale (not in block1), norm/scale, w_1, b_1, w_2, b_2, w_3}``,
``experts/w_{gate,up,down}`` (stacked over the experts held),
``merge_moe/{x,f}/{scale,bias}``; ``lm_head/norm/scale``.

FLOPs from shapes, by the repo's convention (matmuls and convolutions, one
multiply-add = 2, the causal half of the attention scores, nothing
recomputed, training = 3 x forward, the padded vocabulary) **with the held
experts counted at balanced routing**: ``tokens x held / num_experts``
token-slots a layer, whatever the seed's router really sent.

``config["fault"]`` plants one of FAULTS for the readings that set the
limits, and ``config["choices"]`` ([sequences, layers, T, 1]) puts given
experts in place of the router's own; a benchmark run sets neither.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import HIGHEST, cross_entropy_sum, exact

HEAD_BLOCK = 1  # query heads whose [T, T] float32 scores exist at once
CE_BLOCK = 1024  # tokens whose float32 logits exist at once in the loss
FAULTS = ("conv_ahead",   # the depthwise convolution looks one token ahead
          "no_shift",     # the value shift left out: both kv heads read h
          "kv_map",       # query head j on kv head j % K
          "no_carry",     # the router's carried state dropped
          "untied_grad",  # the tied gradient counted once: the lookup's lost
          "capacity")     # tokens over 1.25 x the mean load dropped


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _rms(w, x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def _shift(x, n):
    """x[t - n] along the leading (time) axis, zeros where there is none."""
    if n == 0:
        return x
    pad = jnp.zeros_like(x[:abs(n)])
    return (jnp.concatenate([pad, x[:-n]], axis=0) if n > 0
            else jnp.concatenate([x[-n:], pad], axis=0))


def _taps(x, n, ahead=0):
    """[x[t - (n-1)], ..., x[t]]: the n taps of a causal convolution
    (``ahead`` = 1 is the fault: the window slid one token into the
    future)."""
    return [_shift(x, n - 1 - i - ahead) for i in range(n)]


def _rope_halves(x, theta, r):
    """x [T, heads, d]: on the first r channels the halves (i, i + r/2) are
    turned by pos * theta^(-2i/r); the rest pass."""
    T = x.shape[0]
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def _unit(x, d):
    return math.sqrt(d) * x * lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-12)


def _attention(q, k, v, rnd):
    """q, k, v [H, T, d] -> [H, T, d]; HEAD_BLOCK heads at a time, each
    block rematerialized in the backward pass."""
    H, T, d = q.shape
    hb = math.gcd(H, HEAD_BLOCK)
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv
        s = jnp.einsum("hqd,hkd->hqk", rnd(qb), rnd(kb),
                       precision=HIGHEST) / math.sqrt(d)
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)),
                          rnd(vb), precision=HIGHEST)

    split = lambda t: t.reshape(H // hb, hb, T, d)
    return lax.map(block, (split(q), split(k), split(v))).reshape(H, T, d)


def _merge(P, name, x, f):
    return (P[f"{name}/x/scale"] * x + P[f"{name}/x/bias"]
            + P[f"{name}/f/scale"] * f + P[f"{name}/f/bias"])


def _cca(P, name, x, cfg, rnd):
    T = x.shape[0]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, G = cfg["head_dim"], H // K
    fault = cfg.get("fault")
    h = _rms(P[f"{name}/ln1/scale"], x, cfg["rms_norm_eps"])
    q0 = _mm(h, P[f"{name}/wq"], rnd).reshape(T, H, d)
    k0 = _mm(h, P[f"{name}/wk"], rnd).reshape(T, K, d)
    h_prev = h if fault == "no_shift" else _shift(h, 1)
    v = jnp.concatenate([_mm(h, P[f"{name}/wv1"], rnd),
                         _mm(h_prev, P[f"{name}/wv2"], rnd)],
                        axis=-1).reshape(T, K, d)
    m_q = (q0 + jnp.repeat(k0, G, axis=1)) / 2
    m_k = jnp.mean(m_q.reshape(T, K, G, d), axis=2)
    u = jnp.concatenate([q0, k0], axis=1)  # [T, H + K, d]
    w1 = P[f"{name}/conv_dw"].reshape(-1, H + K, d)
    c1 = P[f"{name}/conv_dw_bias"].reshape(H + K, d) + sum(
        w1[i] * tap for i, tap in enumerate(
            _taps(u, cfg["cca_time0"], ahead=int(fault == "conv_ahead"))))
    w2 = P[f"{name}/conv_head"]  # [H + K, n1, d, d]
    c2 = P[f"{name}/conv_head_bias"].reshape(H + K, d) + sum(
        jnp.einsum("tgc,gce->tge", rnd(tap), rnd(w2[:, i]),
                   precision=HIGHEST)
        for i, tap in enumerate(_taps(c1, cfg["cca_time1"])))
    q1, k1 = c2[:, :H] + m_q, c2[:, H:] + m_k
    q2 = _unit(q1, d)
    k2 = _unit(k1, d) * jnp.exp(P[f"{name}/temp"])[None, :, None]
    rope = cfg["rope_parameters"]["hybrid"]
    r = int(d * rope["partial_rotary_factor"])
    q2 = _rope_halves(q2, float(rope["rope_theta"]), r)
    k2 = _rope_halves(k2, float(rope["rope_theta"]), r)
    of_query = (jnp.arange(H) % K if fault == "kv_map"
                else jnp.arange(H) // G)
    heads = lambda t: jnp.take(t, of_query, axis=1).transpose(1, 0, 2)
    o = _attention(q2.transpose(1, 0, 2), heads(k2), heads(v), rnd)
    f = _mm(o.transpose(1, 0, 2).reshape(T, H * d), P[f"{name}/wo"], rnd)
    return _merge(P, f"{name}/merge_attn", x, f)


def route(P, name, h, r_prev, cfg):
    """(e [T], g [T], r [T, R], p [T, E]): the router in float32, whatever
    ``rnd``."""
    R = f"{name}/router"
    mm = lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    r = mm(h, P[f"{R}/w_d"]) + P[f"{R}/b_d"]
    if f"{R}/carry/scale" in P and cfg.get("fault") != "no_carry":
        r = r + P[f"{R}/carry/scale"] * r_prev
    z = _rms(P[f"{R}/norm/scale"], r, cfg["rms_norm_eps"])
    gelu = lambda a: jax.nn.gelu(a, approximate=False)
    a1 = gelu(mm(z, P[f"{R}/w_1"]) + P[f"{R}/b_1"])
    a2 = gelu(mm(a1, P[f"{R}/w_2"]) + P[f"{R}/b_2"])
    p = jax.nn.softmax(mm(a2, P[f"{R}/w_3"]), axis=-1)
    beta = P.get(f"{R}/select_bias", 0.0)
    e = jnp.argmax(p + lax.stop_gradient(beta), axis=-1)
    if cfg.get("choices") is not None:  # this sequence's: [layers, T, 1]
        e = cfg["choices"][int(name[len("block"):]) - 1][:, 0]
    g = jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]
    return e, g, r, p


def _expert(w, h, rnd):
    """One expert's SwiGLU, ``w = (W_gate, W_up, W_down)``."""
    return _mm(jax.nn.silu(_mm(h, w[0], rnd)) * _mm(h, w[1], rnd), w[2], rnd)


def _moe(P, name, x, r_prev, cfg, rnd):
    h = _rms(P[f"{name}/ln2/scale"], x, cfg["rms_norm_eps"])
    e, g, r, _ = route(P, name, h, r_prev, cfg)
    first = cfg.get("first_expert_held", 0)
    f = jnp.zeros_like(x)
    for i in range(cfg["num_experts_held"]):
        sent = e == first + i
        if cfg.get("fault") == "capacity":
            cap = math.ceil(1.25 * h.shape[0] / cfg["num_experts"])
            sent = sent & (jnp.cumsum(sent) <= cap)
        w = tuple(P[f"{name}/experts/{k}"][i]
                  for k in ("w_gate", "w_up", "w_down"))
        f = f + jnp.where(sent, g, 0.0)[:, None] * _expert(w, h, rnd)
    return _merge(P, f"{name}/merge_moe", x, f), r


def _block(P, i, x, r, cfg, rnd):
    name = f"block{i}"
    return _moe(P, name, _cca(P, name, x, cfg, rnd), r, cfg, rnd)


def _embedding(P, config, use: str):
    """The tied matrix as one of its two uses reads it (the planted fault
    loses the lookup's gradient)."""
    E = P["embed/tok"]
    if config.get("fault") == "untied_grad" and use == "lookup":
        return lax.stop_gradient(E)
    return E


def _hidden(P, tokens, config, rnd, store):
    """The head's input [T, D] of ONE sequence: every block, then the final
    RMSNorm. ``store`` rounds what a lower-precision run would keep between
    blocks (the residual stream; the router's state stays float32, as the
    router)."""
    x = store(jnp.take(_embedding(P, config, "lookup"), tokens, axis=0))
    r = jnp.zeros((tokens.shape[0], config["router_hidden_size"]),
                  jnp.float32)
    for i in range(1, config["n_layer"] + 1):
        sub = {k: v for k, v in P.items() if k.startswith(f"block{i}/")}
        x, r = jax.checkpoint(
            lambda s, x, r, i=i: _block(s, i, x, r, config, rnd))(sub, x, r)
        x = store(x)
    return store(_rms(P["lm_head/norm/scale"], x, config["rms_norm_eps"]))


def logits(P, tokens, config, rnd=exact, store=exact):
    """[T, padded vocabulary] of ONE sequence ``tokens`` [T]."""
    return _mm(_hidden(P, tokens, config, rnd, store),
               _embedding(P, config, "head").T, rnd)


def _loss_sum(P, tokens, labels, config, rnd):
    """Summed next-token cross-entropy of ONE sequence: ``logits`` against
    ``labels``, CE_BLOCK tokens at a time (a sequence's float32 logits are
    1 GB at 8,192 x 32,896), each block rematerialized in the backward
    pass."""
    h = _hidden(P, tokens, config, rnd, rnd)
    E = _embedding(P, config, "head")
    n = max(1, h.shape[0] // CE_BLOCK)

    @jax.checkpoint
    def block(hy):
        return cross_entropy_sum(_mm(hy[0], E.T, rnd), hy[1])

    return jnp.sum(lax.map(block, (h.reshape(n, -1, h.shape[-1]),
                                   labels.reshape(n, -1))))


def balanced_bias(beta, p, config):
    """``beta`` [E] after a training step whose router gave the
    probabilities ``p`` [N, E] (every token of the step):
    ``select_bias_updates_per_step`` times in turn a sign update of
    ``select_bias_update_rate`` from the loads of ``argmax(p + beta)``,
    counted anew each time."""
    experts = jnp.arange(config["num_experts"])
    for _ in range(config["select_bias_updates_per_step"]):
        e = jnp.argmax(p + beta, axis=-1)
        load = jnp.sum(e[:, None] == experts, axis=0).astype(jnp.float32)
        beta = beta + config["select_bias_update_rate"] * jnp.sign(
            jnp.mean(load) - load)
    return beta


def next_select_bias(P, tokens, config, rnd=exact):
    """``{block<i>/router/select_bias: [E]}`` after a training step on
    ``tokens`` [sequences, T] from the state that ``P`` holds (0 where it
    holds none)."""
    p = jnp.concatenate([_routing(P, t, config, rnd)[1] for t in tokens],
                        axis=1)  # [layers, every token, E]
    out = {}
    for i in range(config["n_layer"]):
        key = f"block{i + 1}/router/select_bias"
        beta = P.get(key, jnp.zeros((config["num_experts"],), jnp.float32))
        out[key] = balanced_bias(beta, p[i], config)
    return out


def _routing(P, tokens, config, rnd):
    """``(e [layers, T, 1], p [layers, T, E])`` of ONE sequence: the experts
    the router chooses and its probabilities, with every matmul operand and
    every kept activation rounded by ``rnd``."""
    x = rnd(jnp.take(P["embed/tok"], tokens, axis=0))
    r = jnp.zeros((tokens.shape[0], config["router_hidden_size"]),
                  jnp.float32)
    es, ps = [], []
    for i in range(1, config["n_layer"] + 1):
        name = f"block{i}"
        x = _cca(P, name, x, config, rnd)
        h = _rms(P[f"{name}/ln2/scale"], x, config["rms_norm_eps"])
        e, _, _, p = route(P, name, h, r, config)
        es.append(e[:, None])
        ps.append(p)
        x, r = _moe(P, name, x, r, config, rnd)
        x = rnd(x)
    return jnp.stack(es), jnp.stack(ps)


def choices(P, tokens, config, rnd=exact):
    """[layers, T, 1]: the expert the router chooses for ONE sequence — how
    far a lower compute precision (``rnd``) moves the choice."""
    return _routing(P, tokens, config, rnd)[0]


def loss_and_grads(P, tokens, labels, config, rnd=exact):
    """Mean next-token loss over all positions and its gradients, summed
    over the sequences one at a time in a scan that carries the sum (rows
    are independent; a sequence's [T, T] scores exist for HEAD_BLOCK heads
    at a time, its logits for CE_BLOCK tokens). A control rounds what is kept between blocks as well as the
    matmuls' operands; the router stays float32, as the configuration
    states."""
    n_tok = labels.size

    def seq_loss(P, x, y, given):
        cfg = config if given is None else dict(config, choices=given)
        return _loss_sum(P, x, y, cfg, rnd) / n_tok

    def step(carry, xy):
        loss, grads = carry
        l, g = jax.value_and_grad(seq_loss)(P, *xy)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    init = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, P))
    (loss, grads), _ = lax.scan(step, init,
                                (tokens, labels, config.get("choices")))
    return loss, grads, {}  # no normalization statistics are kept


def held_slots_balanced(config, tokens: int) -> float:
    """Tokens that reach a held expert in one layer when the router spreads
    its choices evenly."""
    return (tokens * config["num_experts_per_tok"]
            * config["num_experts_held"] / config["num_experts"])


def kernel_calls(kernel: str, config, traffic):
    """``[(calls a train step, keyword arguments of
    benchmarks/kernels/<kernel>.work)]`` for this configuration under a mix.

    ``flash_attn``: one forward + backward a layer over H query heads.
    ``flash_attn.work`` counts twelve tensors of H heads where six (K, V
    read twice, dK, dV) are K-headed, so its bytes are too many; but FLOPs
    bound the call at these shapes, not bytes (per sequence and layer
    4.1e11 FLOP = 2.1 ms at the peak against 0.2 GB = 0.25 ms), and the
    FLOPs go by the query heads: the least time, and so the reading, is
    exact.
    ``moe_gmm``: the layers' grouped products, slots at balanced routing."""
    B, T = traffic["run_config"]["batch_size"], config["n_positions"]
    L = config["n_layer"]
    if kernel == "flash_attn":
        return [(L, dict(B=B, H=config["num_attention_heads"], T=T,
                         dh=config["head_dim"]))]
    if kernel == "fused_xent":
        return [(1, dict(N=B * T, D=config["hidden_size"],
                         V=config["padded_vocab_size"]))]
    if kernel == "moe_gmm":
        return [(L, dict(slots=held_slots_balanced(config, B * T),
                         D=config["hidden_size"],
                         F=config["moe_intermediate_size"],
                         G=config["num_experts_held"]))]
    raise KeyError(f"the zaya reference has no call shapes of {kernel!r}")


def matmul_params_per_token(config) -> float:
    """Parameters a token meets in a matmul or a convolution, forward: the
    projections (W_q, W_k, both value matrices, W_o), both convolutions'
    taps, the router (W_d, W_1, W_2, W_3), the held experts at balanced
    routing, and the head over the padded vocabulary."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    K, d = config["num_key_value_heads"], config["head_dim"]
    R, E = config["router_hidden_size"], config["num_experts"]
    attn = (D * H * d + 2 * D * K * d + H * d * D
            + config["cca_time0"] * (H + K) * d
            + config["cca_time1"] * (H + K) * d * d)
    router = D * R + 2 * R * R + R * E
    routed = held_slots_balanced(config, 1) * 3 * D * \
        config["moe_intermediate_size"]
    return (config["n_layer"] * (attn + router + routed)
            + D * config["padded_vocab_size"])


def train_flops_per_sample(config, sample_shape) -> float:
    """One sequence of ``sample_shape[0]`` tokens, forward and backward."""
    T, L = sample_shape[0], config["n_layer"]
    H, d = config["num_attention_heads"], config["head_dim"]
    attn = L * H * 2.0 * (d + d) * T * (T + 1) / 2  # QK^T and PV
    return 3.0 * (2.0 * matmul_params_per_token(config) * T + attn)
