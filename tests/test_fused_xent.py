"""fused_linear_xent == (linear -> cross_entropy_loss) in values AND grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy (see conftest --runslow)

from ddlbench_tpu.ops.fused_xent import fused_linear_xent
from ddlbench_tpu.parallel.common import cross_entropy_loss


def _ref(h, w, labels, smoothing):
    logits = h @ w
    mask = labels >= 0
    valid = jnp.maximum(1, jnp.sum(mask.astype(jnp.int32)))
    obj = cross_entropy_loss(logits, labels, smoothing) * valid
    ce = cross_entropy_loss(logits, labels) * valid
    correct = jnp.sum(((jnp.argmax(logits, -1) == labels) & mask).astype(jnp.int32))
    return obj, ce, correct


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
@pytest.mark.parametrize("n,chunk", [(24, 8), (25, 8), (7, 64)])
def test_matches_reference(smoothing, n, chunk):
    k = jax.random.key(0)
    kh, kw, kl = jax.random.split(k, 3)
    D, V = 16, 40
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V)
    labels = labels.at[::5].set(-1)  # masked rows

    obj, ce, corr = fused_linear_xent(h, w, labels, smoothing, chunk)
    obj_r, ce_r, corr_r = _ref(h, w, labels, smoothing)
    np.testing.assert_allclose(obj, obj_r, rtol=1e-5)
    np.testing.assert_allclose(ce, ce_r, rtol=1e-5)
    assert int(corr) == int(corr_r)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_grads_match_reference(smoothing):
    k = jax.random.key(1)
    kh, kw, kl = jax.random.split(k, 3)
    n, D, V = 20, 12, 33
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V).at[3].set(-1)

    # objective-sum gradient
    gf = jax.grad(lambda h, w: fused_linear_xent(h, w, labels, smoothing, 8)[0],
                  argnums=(0, 1))(h, w)
    gr = jax.grad(lambda h, w: _ref(h, w, labels, smoothing)[0],
                  argnums=(0, 1))(h, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    # ce-sum gradient (the second differentiable output)
    gf = jax.grad(lambda h, w: fused_linear_xent(h, w, labels, smoothing, 8)[1],
                  argnums=(0, 1))(h, w)
    gr = jax.grad(lambda h, w: _ref(h, w, labels, smoothing)[1],
                  argnums=(0, 1))(h, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_combined_cotangents():
    """Both outputs used in one objective — cotangents combine linearly."""
    k = jax.random.key(2)
    kh, kw, kl = jax.random.split(k, 3)
    n, D, V = 16, 8, 21
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V)

    def f_fused(h):
        o, c, _ = fused_linear_xent(h, w, labels, 0.1, 8)
        return 0.7 * o + 0.3 * c

    def f_ref(h):
        o, c, _ = _ref(h, w, labels, 0.1)
        return 0.7 * o + 0.3 * c

    np.testing.assert_allclose(jax.grad(f_fused)(h), jax.grad(f_ref)(h),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_pallas_kernels_match_reference(smoothing):
    """Pallas fwd/bwd (interpret mode on CPU) == the XLA chunked path."""
    k = jax.random.key(3)
    kh, kw, kl = jax.random.split(k, 3)
    n, D, V = 70, 16, 96  # n not a block multiple: exercises row padding
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V).at[::7].set(-1)

    def f_pl(h, w):
        return fused_linear_xent(h, w, labels, smoothing, 512, "pallas", True)

    obj, ce, corr = f_pl(h, w)
    obj_r, ce_r, corr_r = _ref(h, w, labels, smoothing)
    np.testing.assert_allclose(obj, obj_r, rtol=1e-5)
    np.testing.assert_allclose(ce, ce_r, rtol=1e-5)
    assert int(corr) == int(corr_r)

    for out_idx in (0, 1):
        gp = jax.grad(lambda h, w: f_pl(h, w)[out_idx], argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h, w: _ref(h, w, labels, smoothing)[out_idx],
                      argnums=(0, 1))(h, w)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _caps(monkeypatch, rows, cols, sub):
    """The kernels' block caps, small enough that a test's shape spans
    several row blocks, vocabulary blocks and z tiles."""
    from ddlbench_tpu.ops import fused_xent as fx

    monkeypatch.setattr(fx, "ROW_BLOCK", rows)
    monkeypatch.setattr(fx, "V_BLOCK", cols)
    monkeypatch.setattr(fx, "SUB_ROWS", sub)
    return fx


def _pallas_parity(h, w, labels, smoothing, tol=1e-5):
    """Interpret-mode kernels against the dense reference (values, top-1)
    and against the chunked-XLA scan (both cotangents, alone and mixed)."""
    f32 = jnp.float32

    def f_pl(h, w):
        return fused_linear_xent(h, w, labels, smoothing, 512, "pallas", True)

    def f_xla(h, w):
        return fused_linear_xent(h, w, labels, smoothing, 8, "xla")

    obj, ce, corr = f_pl(h, w)
    obj_r, ce_r, corr_r = _ref(h.astype(f32), w.astype(f32), labels, smoothing)
    np.testing.assert_allclose(obj, obj_r, rtol=tol)
    np.testing.assert_allclose(ce, ce_r, rtol=tol)
    assert int(corr) == int(corr_r)
    for go, gce in ((1.0, 0.0), (0.0, 1.0), (0.7, 0.3)):
        def mixed(f):
            return lambda h, w: go * f(h, w)[0] + gce * f(h, w)[1]

        gp, gx = (jax.grad(mixed(f), argnums=(0, 1))(h, w)
                  for f in (f_pl, f_xla))
        for a, b in zip(gp, gx):
            a, b = np.asarray(a, f32), np.asarray(b, f32)
            assert not np.isnan(a).any()
            np.testing.assert_allclose(a, b, rtol=20 * tol, atol=10 * tol)


def _head(n, D, V, dtype=jnp.float32, seed=4):
    kh, kw, kl = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(kh, (n, D), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (D, V), jnp.float32) * 0.5).astype(dtype)
    labels = jax.random.randint(kl, (n,), 0, V).at[5::7].set(-1)
    return h, w, labels


def test_pallas_multiblock_v(monkeypatch):
    """V spanning several v-blocks, rows several row blocks (padded): the
    per-lane running statistics across the sweep, dh summed across the
    vocabulary blocks, dW across the row blocks."""
    fx = _caps(monkeypatch, 16, 32, 256)
    n, D, V = 33, 8, 160  # 5 v-blocks, 3 row blocks (padded)
    assert fx._blocks("fwd", n, D, V, 4, True) == (16, 32)
    assert fx._blocks("bwd", n, D, V, 4, True) == (16, 32)
    _pallas_parity(*_head(n, D, V), 0.1)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
@pytest.mark.parametrize("V,cols,blocks,dtype", [
    (7 * 128, 256, (4, 256), jnp.float32),   # the last block holds 128 of 256
    (7 * 128, 256, (4, 256), jnp.bfloat16),
    (5 * 128, 384, (2, 384), jnp.float32),   # ... 256 of 384
    (3 * 131, 16, (25, 16), jnp.float32),    # 50304 = 3 * 131 * 128 in small
    (3 * 131, 48, (9, 44), jnp.float32),     # evened out: 9 x 44, the last 41
])
def test_pallas_last_vocabulary_block_is_cut(monkeypatch, smoothing, V, cols,
                                             blocks, dtype):
    """The vocabulary block need not divide V. What Pallas pads the cut
    block with is NaN in interpret mode: a statistic, a dz or a W column
    past V that leaked would show in every number compared here."""
    fx = _caps(monkeypatch, 16, cols, 8)  # two z tiles a row block
    n, D = 40, 16  # 3 row blocks, the last one padded
    bv = fx._blocks("bwd", n, D, V, 4, True)[1]
    assert (-(-V // bv), bv) == blocks and V % bv
    _pallas_parity(*_head(n, D, V, dtype), smoothing,
                   tol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("row_blocks", [1, 2, 3])
@pytest.mark.parametrize("sub", [8, 256])
def test_pallas_backward_row_block_extents(monkeypatch, row_blocks, sub):
    """dh goes through HBM once per vocabulary block, by the kernel's own
    DMAs: inner extents 1, 2 and 3 (two steps apart is where a pipelined,
    aliased accumulator would race on the chip; on the chip the comparison
    is PERF.md's, PR 30), one z tile a block and two."""
    _caps(monkeypatch, 16, 128, sub)
    _pallas_parity(*_head(16 * row_blocks - 3, 8, 3 * 128), 0.1)


@pytest.mark.parametrize("cols", [128, 256, 1024])
def test_pallas_argmax_ties_go_to_the_lowest_column(monkeypatch, cols):
    """Equal maxima in two lanes of one lane column, in two lane columns of
    one block (the lower column in the HIGHER lane), and in two vocabulary
    blocks: ``correct`` counts the label at the lowest column alone, as
    jnp.argmax does."""
    _caps(monkeypatch, 16, cols, 8)
    n, D, V = 16, 8, 1024
    h = jnp.abs(jax.random.normal(jax.random.key(7), (n, D))) + 0.1
    tied = [(3, 9), (200, 300), (70, 70 + 512), (130, 131)]
    for rows, (lo, hi) in zip(range(0, n, 4), tied):
        w = jnp.zeros((D, V), jnp.float32).at[:, jnp.array([lo, hi])].set(1.)
        labels = jnp.full((n,), hi, jnp.int32)
        labels = labels.at[rows:rows + 4].set(lo).at[rows].set(-1)
        z = h @ w
        assert bool(jnp.all(z[:, lo] == z[:, hi]))
        corr = fused_linear_xent(h, w, labels, 0.0, 512, "pallas", True)[2]
        assert int(corr) == int(_ref(h, w, labels, 0.0)[2]) == 3


def test_eval_fusion_matches_reference():
    from ddlbench_tpu.ops.fused_xent import fused_linear_xent_eval
    from ddlbench_tpu.parallel.common import correct_topk

    k = jax.random.key(5)
    kh, kw, kl = jax.random.split(k, 3)
    n, D, V = 37, 12, 50
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V).at[::6].set(-1)

    ce_s, corr, corr5, cnt = fused_linear_xent_eval(h, w, labels, 5, 8)
    obj_r, ce_r, corr_r = _ref(h, w, labels, 0.0)
    logits = h @ w
    np.testing.assert_allclose(ce_s, ce_r, rtol=1e-5)
    assert int(corr) == int(corr_r)
    assert int(corr5) == int(correct_topk(logits, labels, 5))
    assert int(cnt) == int(jnp.sum(labels >= 0))

    # degenerate constant logits: tie order must match correct_topk
    wz = jnp.zeros((D, V), jnp.float32)
    _, _, corr5z, _ = fused_linear_xent_eval(h, wz, labels, 5, 8)
    assert int(corr5z) == int(correct_topk(h @ wz, labels, 5))


def test_all_masked_rows():
    h = jnp.ones((8, 4), jnp.float32)
    w = jnp.ones((4, 10), jnp.float32)
    labels = jnp.full((8,), -1, jnp.int32)
    obj, ce, corr = fused_linear_xent(h, w, labels)
    assert float(obj) == 0.0 and float(ce) == 0.0 and int(corr) == 0
    g = jax.grad(lambda h: fused_linear_xent(h, w, labels)[0])(h)
    np.testing.assert_array_equal(g, jnp.zeros_like(h))


def test_pallas_under_shard_map(devices):
    """The Pallas kernels inside a shard_map (the TPU pipeline/sp setting):
    row-sharded h/labels, replicated w — sums psum to the global values and
    dw aggregates across shards. check_vma=False because interpret-mode
    pallas discharge trips the VMA checker (compiled TPU runs use the
    default checked path via the kernels' vma-annotated out_shapes)."""
    import numpy as onp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from ddlbench_tpu.parallel.gpipe import _shard_map

    k = jax.random.key(9)
    kh, kw, kl = jax.random.split(k, 3)
    n, D, V = 32, 8, 48
    h = jax.random.normal(kh, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D, V), jnp.float32) * 0.3
    labels = jax.random.randint(kl, (n,), 0, V).at[::5].set(-1)
    mesh = Mesh(onp.array(jax.devices()[:4]), ("data",))

    def global_sums(h, w, labels):
        def local(hl, w, ll):
            o, c, corr = fused_linear_xent(hl, w, ll, 0.1, 8, "pallas", True)
            return (lax.psum(o, "data"), lax.psum(c, "data"),
                    lax.psum(corr, "data"))

        return _shard_map(
            local, mesh=mesh, in_specs=(P("data"), P(), P("data")),
            out_specs=(P(), P(), P()), check_vma=False,
        )(h, w, labels)

    obj, ce, corr = global_sums(h, w, labels)
    obj_r, ce_r, corr_r = _ref(h, w, labels, 0.1)
    np.testing.assert_allclose(obj, obj_r, rtol=1e-5)
    np.testing.assert_allclose(ce, ce_r, rtol=1e-5)
    assert int(corr) == int(corr_r)

    gw = jax.grad(lambda w: global_sums(h, w, labels)[0])(w)
    gw_r = jax.grad(lambda w: _ref(h, w, labels, 0.1)[0])(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-5)
