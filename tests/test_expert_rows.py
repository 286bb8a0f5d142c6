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



def _positions(case):
    """``pos`` [S, k] as ``dropless._gathered`` builds it for the case, and
    the common buffer's rows."""
    k, n_experts, held, S, how = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    per_token = _held_per_token(rng, S, k, n_experts, held[1], how)
    idx = jnp.asarray(_choices(rng, S, k, n_experts, held, per_token))
    w = jnp.ones((S, k), jnp.float32)
    order, _, ends, _ = dropless._route(idx, w, held)
    rows = dropless.buffer_rows(S * k, n_experts, held[1])
    at = jnp.argsort(order)
    pos = jnp.where(at < jnp.minimum(ends[-1], rows), at, -1).reshape(S, k)
    return np.asarray(pos), rows


@pytest.mark.parametrize("case", sorted(CASES))
def test_an_empty_slot_reads_a_row_of_its_own(case):
    """Each gather of the token sum reads a slot's own row where it holds
    one, and where it does not a row of the buffer that no more than
    ceil(S / rows) tokens of that gather read: never one row for all."""
    pos, rows = _positions(case)
    read = np.asarray(dropless._read_rows(jnp.asarray(pos), rows))
    S = pos.shape[0]
    held = pos >= 0
    np.testing.assert_array_equal(read[held], pos[held])
    assert ((0 <= read) & (read < rows)).all()
    for j in range(pos.shape[1]):
        empty = read[~held[:, j], j]
        if empty.size:
            assert np.bincount(empty).max() <= -(-S // rows)


# how many of each token's k slots hold a row: 0, 1 and all by turns, and
# between; none; one token alone
HELD = {
    "0-1-all": [0, 1, 6, 2, 0, 6, 1, 3, 6, 0, 1, 4, 5, 6, 0, 1] * 8,
    "none-held": [0] * 128,
    "one-token-holds": [0] * 60 + [6] + [0] * 67,
    "all-held": [6] * 128,
}


@pytest.mark.parametrize("k", [1, 6])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(HELD))
def test_the_token_sum_is_the_sum_of_the_held_rows(case, dtype, weighted, k):
    """``_token_sum`` against numpy: each token's weighted float32 sum of
    the rows its held slots name, with NaN in every buffer row past the
    held ones, which the empty slots may read."""
    per_token = np.minimum(np.asarray(HELD[case]), k)
    S = len(per_token)
    rng = np.random.default_rng(sorted(HELD).index(case) + 10 * k)
    n_held = int(per_token.sum())
    R = max(n_held, 1) + 96
    rows_of = rng.permutation(n_held)
    pos = np.full((S, k), -1, np.int32)
    at = 0
    for t, n in enumerate(per_token):
        pos[t, np.sort(rng.choice(k, n, replace=False))] = \
            rows_of[at:at + n]
        at += n
    rows = rng.normal(size=(R, D)).astype(np.float32)
    rows[n_held:] = np.nan
    rows = np.asarray(jnp.asarray(rows, dtype).astype(jnp.float32))
    w = rng.uniform(0.05, 1.0, (S, k)).astype(np.float32)
    want = np.zeros((S, D), np.float64)
    for t in range(S):
        for j in range(k):
            if pos[t, j] >= 0:
                want[t] += (w[t, j] if weighted else 1.0) * \
                    rows[pos[t, j]].astype(np.float64)
    got = dropless._token_sum(jnp.asarray(rows, dtype),
                              jnp.asarray(w) if weighted else None,
                              jnp.asarray(pos), jnp.float32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[per_token == 0].any()

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
