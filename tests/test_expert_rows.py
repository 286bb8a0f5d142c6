"""The dropless layer's common buffer moved by gathers alone
(``dropless._dispatch`` and ``_combine``, each the other's transpose)
against one buffer of every slot moved by a gather and a scatter-add
(``dropless._through``): the layer's output and its gradients in the tokens,
the router's weights and the experts' weights.

The gathered path runs the grouped products over the buffer unmasked: on a
TPU megablox's kernels leave the rows past the groups undefined, in the
product and in its gradient in the rows. Here those kernels run interpreted
with exactly those rows made NaN, and no NaN may reach a live value, a
gradient or a weight.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ddlbench_tpu.models import dropless

TILING = (128, 128, 128)
D, F = 128, 128


def _choices(rng, S, k, n_experts, held, held_per_token):
    """idx [S, k] of distinct experts a token, ``held_per_token[t]`` of
    them among the held ``(first, count)``."""
    first, count = held
    mine = np.arange(first, first + count)
    rest = np.setdiff1d(np.arange(n_experts), mine)
    idx = np.empty((S, k), np.int32)
    for t, h in enumerate(held_per_token):
        idx[t] = np.concatenate([rng.choice(mine, h, replace=False),
                                 rng.choice(rest, k - h, replace=False)])
        rng.shuffle(idx[t])
    return idx


def _held_per_token(rng, S, k, n_experts, count, case):
    if case == "random":  # as a router might; the first token all held
        h = rng.hypergeometric(count, n_experts - count, k, size=S)
        h[0] = k
        return h
    if case == "none":
        return np.zeros(S, int)
    small = dropless.buffer_rows(S * k, n_experts, count)
    total = {"all": S * k, "fills": small, "overflow": small + 88}[case]
    h = np.zeros(S, int)
    h[:total // k] = k
    if total % k:
        h[total // k] = total % k
    return h


# (k, experts, held, tokens, how many slots the router sends to the held)
CASES = {
    "k1-8of16-random": (1, 16, (0, 8), 256, "random"),
    "k1-8of16-all-held": (1, 16, (0, 8), 256, "all"),
    "k6-16of64-random": (6, 64, (16, 16), 128, "random"),
    "k6-16of64-none-held": (6, 64, (16, 16), 128, "none"),
    "k6-16of64-fills-the-buffer": (6, 64, (16, 16), 128, "fills"),
    "k6-16of64-overflows": (6, 64, (16, 16), 128, "overflow"),
}


def _nan_past_the_groups(x, sizes):
    return jnp.where((jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None],
                     jnp.nan, x)


def _undefined_past_the_groups(a, w, sizes, tiling, interpret=False):
    """megablox's grouped product, interpreted, with the rows it leaves
    undefined NaN: past ``sum(sizes)`` in its output and in its gradient in
    ``a`` (its gradient in ``w``, ``tgmm``, is the kernel's own)."""
    product = lambda a, w, sizes: gmm(a, w, sizes, a.dtype, tiling,
                                      interpret=True)

    @jax.custom_vjp
    def dot(a, w, sizes):
        return _nan_past_the_groups(product(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        out, vjp = jax.vjp(lambda a, w: product(a, w, sizes), a, w)
        return _nan_past_the_groups(out, sizes), (vjp, sizes)

    def bwd(res, ct):
        vjp, sizes = res
        da, dw = vjp(ct)
        return _nan_past_the_groups(da, sizes), dw, None

    dot.defvjp(fwd, bwd)
    return dot(a, w, sizes)


def _layer(case):
    k, n_experts, held, S, how = CASES[case]
    seed = sorted(CASES).index(case)
    rng = np.random.default_rng(seed)
    per_token = _held_per_token(rng, S, k, n_experts, held[1], how)
    idx = jnp.asarray(_choices(rng, S, k, n_experts, held, per_token))
    ks = jax.random.split(jax.random.key(seed), 6)
    h = jax.random.normal(ks[0], (S, D), jnp.float32)
    w = jax.random.uniform(ks[1], (S, k), jnp.float32, 0.1, 1.0)
    pe = {n: 0.1 * jax.random.normal(kk, (held[1],) + s, jnp.float32)
          for n, kk, s in (("w_gate", ks[2], (D, F)), ("w_up", ks[3], (D, F)),
                           ("w_down", ks[4], (F, D)))}
    probe = jax.random.normal(ks[5], (S, D), jnp.float32)

    def layer(h, w, pe):
        y, counters = dropless.routed_experts(pe, h, idx, w, held, n_experts,
                                              TILING)
        return jnp.sum(y * probe), (y, counters)

    def reference(h, w, pe):
        order, sizes, ends, w_flat = dropless._route(idx, w, held)
        y = dropless._through(order, sizes, ends, 0, S * k, TILING,
                              jax.nn.silu)(
            jnp.zeros((S, D), jnp.float32), h, w_flat, pe).astype(h.dtype)
        return jnp.sum(y * probe), (y, None)

    return layer, reference, (h, w, pe), int(per_token.sum()), \
        dropless.buffer_rows(S * k, n_experts, held[1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gathers_move_what_the_gather_and_scatter_move(case,
                                                           monkeypatch):
    layer, reference, args, n_held, small = _layer(case)
    run = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2),
                                       has_aux=True)(*args)
    (_, (y_ref, _)), grads_ref = run(reference)
    monkeypatch.setattr(dropless, "grouped_dot", _undefined_past_the_groups)
    (_, (y, counters)), grads = run(layer)
    assert float(counters["held_slots"]) == n_held
    assert float(counters["buffer_fill"]) == n_held / small
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _scatters(jaxpr, in_branch=False, found=None):
    """(scatter-adds outside every cond branch, inside one)."""
    found = found if found is not None else [0, 0]
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found[in_branch] += 1
        branch = in_branch or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _scatters(sub, branch, found)
    return found


@pytest.mark.parametrize("case", ["k1-8of16-random", "k6-16of64-random"])
def test_the_gathered_buffer_scatters_nothing(case):
    """Forward and backward of the common buffer hold no scatter-add (a
    buffer moved by a gather and a scatter-add holds three: the sum, and
    the gather's transpose twice over); the slots past the buffer, where a
    step has any, keep theirs inside the cond's branch."""
    layer, reference, args, _, _ = _layer(case)
    grad = lambda f: jax.make_jaxpr(jax.grad(
        lambda *a: f(*a)[0], argnums=(0, 1, 2)))(*args).jaxpr
    outside, inside = _scatters(grad(layer))
    assert outside == 0
    cond = case.startswith("k6")
    assert (inside > 0) == cond
    assert _scatters(grad(reference))[0] > 0
