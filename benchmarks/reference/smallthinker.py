"""Plain reference for the ``smallthinker-21b-a3b`` configuration
(``PowerInfer/SmallThinker-21BA3B-Instruct`` config.json: 52 layers, hidden
2560, 28 query heads over 4 key/value heads of 128, 64 experts of width 768,
6 a token, no shared expert, no dense layer, an untied head; report:
arXiv:2507.20984) in float32 jax.numpy at HIGHEST matmul precision: no
kernels, no sorting, no cache. Nothing here imports the program.

``sliding_window_layout`` = ``rope_layout`` = ``[0, 1, 1, 1] x 13``: layer l
is a WINDOW layer where the layout holds 1 (``sliding_window_size`` keys,
rotary positions) and a GLOBAL layer where it holds 0 (every earlier key, NO
positions at all). With ``RMSNorm(x) = x * rsqrt(mean x^2 + eps) * w`` and x
[T, D] the layer's input, one layer is

    g  = x W_r                      E logits, float32: the router reads the
                                    layer's INPUT, before the input norm
                                    and before attention
    h  = RMSNorm(x)
    q  = h W_q [T, H, d];  k = h W_k, v = h W_v [T, K, d]
    window layer: RoPE on q and k over the WHOLE head — the halves
        (i, i + d/2) turned by pos * theta^(-2i/d) — and query i sees the
        keys j with i - W < j <= i
    global layer: no rotation, j <= i
    query head n reads key/value head n // (H / K)
    x1 = x + softmax(q k^T / sqrt(d)) v W_o
    h2 = RMSNorm(x1)
    idx = top6(g);  w = softmax(g[idx]) over the six chosen, float32
    y  = sum_k w_k W_down[idx_k] (relu(h2 W_gate[idx_k]) * (h2 W_up[idx_k]))
    x_out = x1 + y

Head: RMSNorm, then the untied ``lm_head``; mean next-token cross-entropy
over the vocabulary held. No bias anywhere.

**The share.** The configuration holds ``moe_num_primary_experts_held``
experts from ``first_expert_held`` on, of ``moe_num_primary_experts``: the
router keeps all its outputs and its six a token, the softmax is over all
six chosen, and only the held experts' terms are summed — each held expert
over every token, weighted by what the router gave it (0 for most). A slot
whose expert is absent adds nought, here as in the program; nothing stands
in for the other chips. The vocabulary is a slice of rows.

**Assumed** (the row's ``config`` has no key for it; ``assumed`` in the
configuration's file): that the router reads the raw input and not its norm
(``described_as``: "router placed before attention"); that its six are
chosen from the logits and the softmax taken over the chosen
(``moe_primary_router_apply_softmax`` true; ``norm_topk_prob`` then changes
nothing); rotated halves and not interleaved pairs; the window counted as
HF's sliding mask counts it (W keys, the query's own among them); no
attention bias; ReLU-gated experts (ReGLU); one packed sequence, positions
0..T-1, no document mask; the vocabulary slice padded to a multiple of 128.

**Departures.** The report's load-balance loss has no coefficient in
config.json and is left out. ``described_as`` speaks of "secondary experts";
the row's ``config`` defines primary experts alone, and where the two
disagree ``config`` is trusted: the experts are those 64.

Parameters are a flat dict: ``embed/tok``; per block ``ln1/scale``, ``wq``
[D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``ln2/scale``,
``router`` [D, E], ``experts/w_{gate,up,down}`` (stacked over the experts
held); ``lm_head/norm/scale``, ``lm_head/head``.

**Blocks, so that it fits beside the check's copies at T 16,384:** a layer
at a time rematerialized; the scores of ONE head and Q_BLOCK queries at a
time ([2048, 16384] float32 = 134 MB), each block rematerialized, a
key/value head's group of query heads after the other; one
expert's products at a time, rematerialized; the logits CE_BLOCK tokens at
a time.

FLOPs from shapes, by the repo's convention (matmuls only, one multiply-add
= 2, nothing recomputed, training = 3 x forward, the padded vocabulary): the
scores over the (query, key) pairs INSIDE each layer's mask — T (T + 1) / 2
on a global layer, W T - W (W - 1) / 2 on a window layer — and the held
experts at balanced routing: ``tokens x 6 x held / 64`` token-slots a
layer, whatever the seed's router really sent.

``config["fault"]`` plants one of FAULTS for the readings that set the
limits, and ``config["choices"]`` ([sequences, layers, T, k]) puts given
experts in place of the router's own top-k; a benchmark run sets neither.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# (query, key) pairs inside one head's mask: the kernel's own count
from benchmarks.kernels.flash_attn_banded import pairs as mask_pairs
from benchmarks.reference.common import (HIGHEST, bf16, cross_entropy_sum,
                                         exact)

Q_BLOCK = 2048   # queries of one head whose float32 scores exist at once
CE_BLOCK = 1024  # tokens whose float32 logits exist at once in the loss
FAULTS = ("no_window",     # the window layers attend to every earlier key
          "rope_global",   # the global layers rotate q and k too
          "router_late",   # the router reads h2, after attention, not x
          "silu",          # the experts gate with SiLU where ReLU stands
          "router_bf16")   # the router's operands and logits in bfloat16


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _rms(w, x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def _rope_halves(x, theta):
    """x [T, heads, d]: the halves (i, i + d/2) of every head turned by
    pos * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def is_window_layer(config, i: int) -> bool:
    """Layer i (from 1) attends within the window and carries rotary
    positions; the two layouts of config.json are one list."""
    if config["sliding_window_layout"] != config["rope_layout"]:
        raise ValueError("the reference is written for one layout list: a "
                         "window layer carries the rotary positions")
    return bool(config["sliding_window_layout"][i - 1])


def _attention(q, k, v, window: int, rnd):
    """q [H, T, d], k and v [K, T, d] -> [H, T, d]: query head n on
    key/value head n // (H / K), keys j <= i, and j > i - window where
    ``window`` is not 0. One key/value head at a time, and under it one of
    its query heads and Q_BLOCK queries at a time, each block
    rematerialized in the backward pass; no key or value is repeated."""
    H, T, d = q.shape
    K = k.shape[0]
    bq = math.gcd(T, Q_BLOCK)
    k_pos = jnp.arange(T)[None, :]

    def group(qkv):
        qg, kh, vh = qkv  # [H / K, T, d], [T, d], [T, d]

        @jax.checkpoint
        def block(q_lo):
            qb, lo = q_lo
            q_pos = lo + jnp.arange(bq)[:, None]
            s = jnp.matmul(rnd(qb), rnd(kh).T,
                           precision=HIGHEST) / math.sqrt(d)
            seen = k_pos <= q_pos
            if window:
                seen = seen & (k_pos > q_pos - window)
            s = jnp.where(seen, s, -jnp.inf)
            return jnp.matmul(rnd(jax.nn.softmax(s, axis=-1)), rnd(vh),
                              precision=HIGHEST)

        # a map within a map: XLA lifts the masks out of the loop they are
        # made in, all T / bq of them at once ([T, T] bits), and would lift
        # H / K times as many out of one loop over heads and blocks
        head = lambda qh: lax.map(block, (qh.reshape(-1, bq, d),
                                          jnp.arange(0, T, bq)))
        return lax.map(head, qg).reshape(H // K, T, d)

    return lax.map(group, (q.reshape(K, H // K, T, d), k, v)).reshape(H, T, d)


def _attend(P, name, x, cfg, window_layer: bool, rnd):
    """x + Attention(RMSNorm(x)) of one layer."""
    T = x.shape[0]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, fault = cfg["head_dim"], cfg.get("fault")
    h = _rms(P[f"{name}/ln1/scale"], x, cfg["rms_norm_eps"])
    q = _mm(h, P[f"{name}/wq"], rnd).reshape(T, H, d)
    k = _mm(h, P[f"{name}/wk"], rnd).reshape(T, K, d)
    v = _mm(h, P[f"{name}/wv"], rnd).reshape(T, K, d)
    if window_layer or fault == "rope_global":
        q = _rope_halves(q, float(cfg["rope_theta"]))
        k = _rope_halves(k, float(cfg["rope_theta"]))
    window = cfg["sliding_window_size"] \
        if window_layer and fault != "no_window" else 0
    o = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                   v.transpose(1, 0, 2), window, rnd)
    return x + _mm(o.transpose(1, 0, 2).reshape(T, H * d), P[f"{name}/wo"],
                   rnd)


def route(P, name, x, cfg):
    """(idx [T, k], w [T, k]) of the router's input x [T, D]: float32
    logits whatever ``rnd``, the top k of them, the softmax over the
    chosen."""
    if cfg.get("fault") == "router_bf16":
        g = bf16(jnp.matmul(bf16(x), bf16(P[f"{name}/router"]),
                            precision=HIGHEST))
    else:
        g = jnp.matmul(x, P[f"{name}/router"], precision=HIGHEST)
    _, idx = lax.top_k(g, cfg["moe_num_active_primary_experts"])
    if cfg.get("choices") is not None:  # this sequence's: [layers, T, k]
        idx = cfg["choices"][int(name[len("block"):]) - 1]
    return idx, jax.nn.softmax(jnp.take_along_axis(g, idx, axis=-1), axis=-1)


def held_experts(P, name, h2, idx, w, cfg, rnd):
    """The held experts' part of sum_k w_k E_idx_k(h2): one expert at a time
    over every token (a scan over the stacked weights that carries the
    sum), each expert's products rematerialized in the backward pass."""
    act = jax.nn.silu if cfg.get("fault") == "silu" else jax.nn.relu
    ids = cfg.get("first_expert_held", 0) + jnp.arange(
        cfg["moe_num_primary_experts_held"])
    # [held, T]: at most one of a token's k slots names a given expert
    we = jnp.sum(jnp.where(idx[None] == ids[:, None, None], w[None], 0.0),
                 axis=-1)

    @jax.checkpoint
    def term(gate, up, down, h2, we):
        return we[:, None] * _mm(act(_mm(h2, gate, rnd)) * _mm(h2, up, rnd),
                                 down, rnd)

    def add(y, expert):
        return y + term(*expert[:3], h2, expert[3]), None

    y, _ = lax.scan(add, jnp.zeros_like(h2), (
        *(P[f"{name}/experts/{key}"] for key in ("w_gate", "w_up", "w_down")),
        we))
    return y


def _block(P, i, x, cfg, rnd):
    name = f"block{i}"
    x1 = _attend(P, name, x, cfg, is_window_layer(cfg, i), rnd)
    h2 = _rms(P[f"{name}/ln2/scale"], x1, cfg["rms_norm_eps"])
    idx, w = route(P, name, h2 if cfg.get("fault") == "router_late" else x,
                   cfg)
    return x1 + held_experts(P, name, h2, idx, w, cfg, rnd)


def _hidden(P, tokens, config, rnd, store):
    """The head's input [T, D] of ONE sequence: every block, a layer at a
    time rematerialized, then the final RMSNorm. ``store`` rounds what a
    lower-precision run would keep between blocks."""
    x = store(jnp.take(P["embed/tok"], tokens, axis=0))
    for i in range(1, config["n_layer"] + 1):
        sub = {k: v for k, v in P.items() if k.startswith(f"block{i}/")}
        x = store(jax.checkpoint(
            lambda s, x, i=i: _block(s, i, x, config, rnd))(sub, x))
    return store(_rms(P["lm_head/norm/scale"], x, config["rms_norm_eps"]))


def logits(P, tokens, config, rnd=exact, store=exact):
    """[T, padded vocabulary] of ONE sequence ``tokens`` [T]."""
    return _mm(_hidden(P, tokens, config, rnd, store), P["lm_head/head"],
               rnd)


def _loss_sum(P, tokens, labels, config, rnd):
    """Summed next-token cross-entropy of ONE sequence, CE_BLOCK tokens at a
    time (a sequence's float32 logits are 1.25 GB at 16,384 x 19,072), each
    block rematerialized in the backward pass."""
    h = _hidden(P, tokens, config, rnd, rnd)
    n = max(1, h.shape[0] // CE_BLOCK)

    @jax.checkpoint
    def block(hy):
        return cross_entropy_sum(_mm(hy[0], P["lm_head/head"], rnd), hy[1])

    return jnp.sum(lax.map(block, (h.reshape(n, -1, h.shape[-1]),
                                   labels.reshape(n, -1))))


def choices(P, tokens, config, rnd=exact):
    """[layers, T, k]: the experts the router chooses for ONE sequence, with
    every matmul operand and every kept activation rounded by ``rnd`` — how
    far a lower compute precision moves the top-k."""
    x = rnd(jnp.take(P["embed/tok"], tokens, axis=0))
    out = []
    for i in range(1, config["n_layer"] + 1):
        out.append(route(P, f"block{i}", x, config)[0])
        x = rnd(_block(P, i, x, config, rnd))
    return jnp.stack(out)


def loss_and_grads(P, tokens, labels, config, rnd=exact):
    """Mean next-token loss over all positions and its gradients, summed
    over the sequences one at a time in a scan that carries the sum (rows
    are independent); one sequence alone, as the cell's step has, goes
    without the scan. A control rounds what is kept between blocks as well
    as the matmuls' operands; the router stays float32, as the
    configuration states."""
    n_tok = labels.size

    def seq_loss(P, x, y, given):
        cfg = config if given is None else dict(config, choices=given)
        return _loss_sum(P, x, y, cfg, rnd) / n_tok

    def step(carry, xy):
        loss, grads = carry
        l, g = jax.value_and_grad(seq_loss)(P, *xy)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    given = config.get("choices")
    if tokens.shape[0] == 1:
        # nothing to sum: the scan's carried sum is a second and third copy
        # of the gradients (4.5 GB at this configuration's cut)
        loss, grads = jax.value_and_grad(seq_loss)(
            P, tokens[0], labels[0], None if given is None else given[0])
    else:
        init = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, P))
        (loss, grads), _ = lax.scan(step, init, (tokens, labels, given))
    return loss, grads, {}  # no normalization statistics are kept


def held_slots_balanced(config, tokens: int) -> float:
    """Token-slots that reach a held expert in one layer when the router
    spreads its choices evenly."""
    return (tokens * config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts_held"]
            / config["moe_num_primary_experts"])


def kernel_calls(kernel: str, config, traffic):
    """``[(calls a train step, keyword arguments of
    benchmarks/kernels/<kernel>.work)]`` for this configuration under a mix.

    ``flash_attn_banded``: one forward + backward a layer over H query
    heads, the global layers' shape (``window`` 0) and the window layers'.
    ``moe_gmm``: the layers' grouped products, slots at balanced routing
    (``moe_gmm.work`` prices three products an expert whatever its gate)."""
    B, T = traffic["run_config"]["batch_size"], config["n_positions"]
    L = config["n_layer"]
    if kernel == "flash_attn_banded":
        shape = dict(B=B, H=config["num_attention_heads"], T=T,
                     dh=config["head_dim"])
        windowed = sum(is_window_layer(config, i) for i in range(1, L + 1))
        both = [(L - windowed, dict(shape, window=0)),
                (windowed, dict(shape,
                                window=config["sliding_window_size"]))]
        return [c for c in both if c[0]]
    if kernel == "fused_xent":
        return [(1, dict(N=B * T, D=config["hidden_size"],
                         V=config["padded_vocab_size"]))]
    if kernel == "moe_gmm":
        return [(L, dict(slots=held_slots_balanced(config, B * T),
                         D=config["hidden_size"],
                         F=config["moe_ffn_hidden_size"],
                         G=config["moe_num_primary_experts_held"]))]
    raise KeyError(
        f"the smallthinker reference has no call shapes of {kernel!r}")


def matmul_params_per_token(config) -> float:
    """Parameters a token meets in a matmul, forward: the projections (W_q,
    W_k, W_v, W_o), the router, the held experts at balanced routing, and
    the head over the padded vocabulary."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    K, d = config["num_key_value_heads"], config["head_dim"]
    attn = 2 * D * H * d + 2 * D * K * d
    router = D * config["moe_num_primary_experts"]
    routed = held_slots_balanced(config, 1) * 3 * D * \
        config["moe_ffn_hidden_size"]
    return (config["n_layer"] * (attn + router + routed)
            + D * config["padded_vocab_size"])


def train_flops_per_sample(config, sample_shape) -> float:
    """One sequence of ``sample_shape[0]`` tokens, forward and backward."""
    T, L = sample_shape[0], config["n_layer"]
    H, d = config["num_attention_heads"], config["head_dim"]
    pairs = sum(mask_pairs(T, config["sliding_window_size"]
                           if is_window_layer(config, i) else 0)
                for i in range(1, L + 1))
    attn = H * 2.0 * (d + d) * pairs  # QK^T and PV
    return 3.0 * (2.0 * matmul_params_per_token(config) * T + attn)
