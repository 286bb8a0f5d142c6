"""Plain reference for the ``gpt2-small`` configuration: the GPT-2 decoder
(Radford et al. 2019; ``openai-community/gpt2`` config.json) in float32
jax.numpy at HIGHEST matmul precision — pre-LN blocks, learned positions,
tanh-GELU MLP 4x, LayerNorm eps 1e-5, causal softmax attention; no kernels,
no cache, no batching tricks.

Departures that the program makes and this file follows (listed under
``assumed`` in the configuration file): the output head is its own matrix
(not tied to the token embedding), the vocabulary is padded to a multiple
of 128, and the attention and MLP-input projections carry no bias.

Parameters are a flat dict: ``embed/tok``, ``embed/pos``,
``block3/ln1/scale``, ``block3/wqkv``, ``block3/wo``, ``block3/w1``,
``block3/b1``, ``block3/w2``, ``block3/b2``, ``lm_head/ln_f/bias``,
``lm_head/head``.

FLOPs from shapes, for the whole step's share of the chip's peak: matmuls
only (LayerNorm, GELU, softmax, the optimizer and the embedding lookup count
as 0), one multiply-add = 2 FLOPs, recomputed operations never counted,
training = 3 x forward. Causal attention counts the unmasked half (T(T+1)/2
key positions per head), the work an ideal kernel does; serving counts the
positions a token really attends (its depth + 1). The vocabulary is the
padded one the configuration runs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import HIGHEST, cross_entropy_sum, exact

LN_EPS = 1e-5


def _ln(P, name, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + LN_EPS) * P[f"{name}/scale"]
            + P[f"{name}/bias"])


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(P, name, x, n_heads, rnd):
    B, T, d = x.shape
    dh = d // n_heads
    q, k, v = jnp.split(_mm(_ln(P, f"{name}/ln1", x), P[f"{name}/wqkv"], rnd),
                        3, axis=-1)
    heads = lambda t: t.reshape(B, T, n_heads, dh).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", rnd(heads(q)), rnd(heads(k)),
                   precision=HIGHEST) / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", rnd(jax.nn.softmax(s, axis=-1)),
                   rnd(heads(v)), precision=HIGHEST)
    x = x + _mm(o.transpose(0, 2, 1, 3).reshape(B, T, d), P[f"{name}/wo"], rnd)
    h = _gelu(_mm(_ln(P, f"{name}/ln2", x), P[f"{name}/w1"], rnd)
              + P[f"{name}/b1"])
    return x + _mm(h, P[f"{name}/w2"], rnd) + P[f"{name}/b2"]


def hidden(P, tokens, config, rnd=exact, store=exact):
    """Final hidden states [B, T, d] (before ln_f). ``store`` rounds what a
    lower-precision run would keep between blocks."""
    n_layers, n_heads = config["n_layer"], config["n_head"]
    T = tokens.shape[1]
    x = store(jnp.take(P["embed/tok"], tokens, axis=0) + P["embed/pos"][:T])
    for i in range(1, n_layers + 1):
        name = f"block{i}"
        sub = {k: v for k, v in P.items() if k.startswith(name + "/")}
        x = store(jax.checkpoint(
            lambda s, x, name=name: _block(s, name, x, n_heads, rnd))(sub, x))
    return x


def logits(P, tokens, config, rnd=exact, store=exact):
    h = _ln(P, "lm_head/ln_f", hidden(P, tokens, config, rnd, store))
    return _mm(store(h), P["lm_head/head"], rnd)


def loss_and_grads(P, tokens, labels, config, rnd=exact):
    """Mean next-token loss over all positions and its gradients, summed
    over blocks of up to 4 sequences (rows are independent, so the
    [rows, T, vocab] logits exist for one block at a time). A control
    rounds what is kept between blocks as well as the operands: it computes
    where the configuration computes, in a lower type."""
    B = tokens.shape[0]
    n_tok = labels.size
    row_block = next(r for r in (4, 2, 1) if B % r == 0)

    def block_loss(P, xb, yb):
        return cross_entropy_sum(logits(P, xb, config, rnd, rnd), yb) / n_tok

    def step(carry, xy):
        loss, grads = carry
        l, g = jax.value_and_grad(block_loss)(P, *xy)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    xs = tokens.reshape(B // row_block, row_block, -1)
    ys = labels.reshape(B // row_block, row_block, -1)
    init = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, P))
    (loss, grads), _ = jax.lax.scan(step, init, (xs, ys))
    return loss, grads, {}  # no normalization statistics are kept


KV_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def kernel_calls(kernel: str, config, traffic):
    """The shapes at which this configuration calls a kernel under a mix:
    ``[(calls, keyword arguments of benchmarks/kernels/<kernel>.work)]``.
    ``calls`` is per train step for the training kernels (one flash
    forward + backward a block, one fused head + loss a step) and per
    prefill chunk or decode pass for the paged ones (one a block), whose
    ``work`` takes the rest of its arguments from the driver's counters."""
    d, H, L = config["n_embd"], config["n_head"], config["n_layer"]
    if kernel == "flash_attn":
        return [(L, dict(B=traffic["run_config"]["batch_size"], H=H,
                         T=config["n_positions"], dh=d // H))]
    if kernel == "fused_xent":
        N = traffic["run_config"]["batch_size"] * config["n_positions"]
        return [(1, dict(N=N, D=d, V=config["padded_vocab_size"]))]
    if kernel in ("paged_decode_attn", "paged_chunk_attn"):
        sc = traffic["serve_config"]
        return [(L, dict(H=H, dh=d // H, page=sc["page"],
                         kv_bytes=KV_BYTES[sc.get("kv_dtype", "float32")]))]
    raise KeyError(f"the gpt2 reference has no call shapes of {kernel!r}")


def matmul_params(config, with_head: bool = True) -> float:
    """Parameters that sit in a matmul: per block wqkv 3d^2, wo d^2, MLP
    2 * ratio * d^2; plus the output head d x padded vocab."""
    d, L = config["n_embd"], config["n_layer"]
    n = L * (4 + 2 * config["mlp_ratio"]) * d * d
    return n + (d * config["padded_vocab_size"] if with_head else 0)


def train_flops_per_sample(config, sample_shape) -> float:
    """One sequence of ``sample_shape[0]`` tokens, forward and backward."""
    d, L, T = config["n_embd"], config["n_layer"], sample_shape[0]
    attn = L * 2 * 2.0 * d * T * (T + 1) / 2  # QK^T and PV, causal half
    return 3.0 * (2.0 * matmul_params(config) * T + attn)


def served_token_flops(config, depth: int, with_head: bool) -> float:
    """Forward FLOPs of ONE served token at stream position ``depth`` (it
    attends depth + 1 keys)."""
    d, L = config["n_embd"], config["n_layer"]
    return (2.0 * matmul_params(config, with_head)
            + L * 2 * 2.0 * d * (depth + 1))
