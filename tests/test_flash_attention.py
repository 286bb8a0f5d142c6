"""Pallas flash-attention kernel vs the jnp reference (interpret mode on CPU).

The XLA CPU backend runs f32 matmuls in reduced precision by default, so
comparisons force highest matmul precision; tolerances then reflect only the
kernel's own (f32-accumulated) arithmetic.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.models import transformer
from ddlbench_tpu.ops import flash_attention as fa
from ddlbench_tpu.ops.flash_attention import (_pick_block, flash_attention,
                                              flash_attention_lse)


# the reference of every comparison below: the einsum path, wherever this runs
causal_attention = functools.partial(transformer.causal_attention,
                                     backend="xla")


def _rand(shape, key):
    return jax.random.normal(key, shape, jnp.float32)


def test_pick_block():
    import pytest

    assert _pick_block(1024, 512) == 512
    assert _pick_block(96, 128) == 96
    # interpret mode: any divisor tiles
    assert _pick_block(96, 64, interpret=True) == 48
    assert _pick_block(7, 4, interpret=True) == 1
    # compiled: blocks must be 8-aligned (Mosaic sublane tile)
    assert _pick_block(96, 64) == 48  # 48 = 6*8, largest 8-multiple divisor
    assert _pick_block(1024, 500) == 256
    with pytest.raises(ValueError, match="multiple of 8"):
        _pick_block(7, 4)


def test_forward_matches_reference():
    B, H, T, dh = 2, 3, 128, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        ref = causal_attention(q, k, v)
        got = flash_attention(q, k, v, 0, 0, 0, 32, 32, True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


# (Tq, Tk, q_offset, k_offset, prefix_len, block_q, block_k): the backward
# (one-pass resident kernel, stream=None at these sizes) against the
# gradients of the jnp reference
GRAD_CASES = {
    "causal": (64, 64, 0, 0, 0, 32, 32),
    "causal_fine_tiles": (64, 64, 0, 0, 0, 8, 8),
    "prefix": (64, 64, 0, 0, 24, 16, 16),
    "prefix_cuts_a_tile": (96, 96, 0, 0, 40, 32, 32),
    "prefix_covers_k_tiles": (64, 64, 0, 0, 48, 16, 16),
    # queries 0..63 vs keys at absolute 10..73: rows 0-9 fully masked
    # (lse ~ -1e30) must give zero — not NaN — gradients
    "k_offset_masks_rows": (64, 64, 0, 10, 0, 32, 32),
    "q_offset_ring_block": (64, 128, 500, 0, 0, 32, 32),
    "q_offset_mid_tile": (48, 48, 8, 0, 0, 16, 16),
    "both_offsets": (64, 64, 40, 24, 0, 16, 16),
    "fully_masked_block": (32, 32, 0, 1000, 0, 16, 16),
    "offsets_and_prefix": (64, 64, 8, 0, 20, 16, 16),
    "bq_lt_bk": (64, 64, 0, 0, 0, 16, 32),
    "bq_gt_bk": (64, 64, 0, 0, 0, 32, 16),
    "bq_gt_bk_prefix": (64, 64, 0, 0, 24, 32, 8),
    "uneven_tiling": (96, 96, 0, 0, 0, 64, 64),   # tiles shrink to 48
    "uneven_tq_ne_tk": (48, 96, 48, 0, 0, 32, 64),
    "one_tile": (32, 32, 0, 0, 0, 64, 64),
}


def _grads(fn, q, k, v, g):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * g), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", GRAD_CASES)
def test_grads_match_reference(case):
    Tq, Tk, qo, ko, pfx, bq, bk = GRAD_CASES[case]
    B, H, dh = 1, 2, 16
    ks = jax.random.split(jax.random.key(1), 4)
    q, g = _rand((B, H, Tq, dh), ks[0]), _rand((B, H, Tq, dh), ks[3])
    k, v = _rand((B, H, Tk, dh), ks[1]), _rand((B, H, Tk, dh), ks[2])
    with jax.default_matmul_precision("highest"):
        ref_g = _grads(lambda *a: causal_attention(
            *a, q_offset=qo, k_offset=ko, prefix_len=pfx), q, k, v, g)
        fa_g = _grads(lambda *a: flash_attention(
            *a, qo, ko, pfx, bq, bk, True), q, k, v, g)
    for a, b in zip(ref_g, fa_g):
        assert np.all(np.isfinite(np.asarray(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_offsets_match_reference():
    """Ring-style blocks: queries at absolute position 500 over K/V block 0."""
    B, H, dh = 1, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q = _rand((B, H, 64, dh), ks[0])
    k = _rand((B, H, 128, dh), ks[1])
    v = _rand((B, H, 128, dh), ks[2])
    with jax.default_matmul_precision("highest"):
        ref = causal_attention(q, k, v, q_offset=500, k_offset=0)
        got = flash_attention(q, k, v, 500, 0, 0, 32, 32, True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


def test_fully_masked_is_zero():
    B, H, T, dh = 1, 1, 32, 8
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    out = flash_attention(q, k, v, 0, 1000, 0, 16, 16, True)
    assert np.all(np.asarray(out) == 0.0)


def test_uneven_blocks():
    """T not divisible by the preferred block: blocks shrink to a divisor."""
    B, H, T, dh = 1, 2, 96, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        ref = causal_attention(q, k, v)
        got = flash_attention(q, k, v, 0, 0, 0, 64, 64, True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


def test_backend_dispatch_forced_flash():
    """backend="flash" routes causal_attention through the kernel
    (interpret mode off-TPU) with identical results."""
    B, H, T, dh = 1, 2, 32, 8
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        ref = causal_attention(q, k, v)
        got = transformer.causal_attention(q, k, v, backend="flash")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


def test_backend_validation():
    q = jnp.zeros((1, 1, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="backend"):
        fa.flash_dispatch("cuda", q, q, q)
    with pytest.raises(ValueError, match="backend"):
        transformer.causal_attention(q, q, q, backend="cuda")
    from ddlbench_tpu.config import RunConfig

    with pytest.raises(ValueError, match="attention_backend"):
        RunConfig(attention_backend="cuda").validate()


def test_prefix_forward_matches_reference():
    B, H, T, dh = 2, 2, 96, 16
    S = 40  # not block-aligned (blocks of 32): exercises the partial block
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        ref = causal_attention(q, k, v, prefix_len=S)
        got = flash_attention(q, k, v, 0, 0, S, 32, 32, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # sanity: the prefix result differs from pure-causal
    causal = flash_attention(q, k, v, 0, 0, 0, 32, 32, True)
    assert not np.allclose(np.asarray(got), np.asarray(causal))


def _ref_with_lse(q, k, v, q_offset=0, k_offset=0):
    """(o, lse) from the plain jnp path, matching flash_attention_lse."""
    import math as _math

    dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / _math.sqrt(dh)
    q_pos = q_offset + jnp.arange(q.shape[2])[:, None]
    k_pos = k_offset + jnp.arange(k.shape[2])[None, :]
    s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    msafe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - msafe)
    z = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", e / jnp.maximum(z, 1e-20), v)
    lse = (msafe + jnp.log(jnp.maximum(z, 1e-20)))[..., 0]
    return o, lse


def test_lse_output_matches_reference():
    B, H, T, dh = 2, 2, 64, 16
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        o, lse = flash_attention_lse(q, k, v, 0, 0, 0, 16, 16, True)
        o_r, lse_r = _ref_with_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=1e-5, atol=1e-5)


# (q_offset, block_q, block_k, stream): ring attention's diagonal block
# (offset 0) and its "whole block visible" case (q_offset = Tl)
@pytest.mark.parametrize("qoff,bq,bk,stream", [
    (0, 8, 8, None), (32, 8, 8, None), (0, 16, 8, None), (32, 8, 16, None),
    (0, 8, 8, True)])
def test_lse_cotangent_flows(qoff, bq, bk, stream):
    """Gradients through BOTH outputs (the ring-combination use case): the
    lse cotangent is a delta shift, so the one-pass kernel (and the
    streaming pair) carry it unchanged."""
    B, H, T, dh = 1, 2, 32, 8
    ks = jax.random.split(jax.random.key(8), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)

    def f_flash(q, k, v):
        o, lse = flash_attention_lse(q, k, v, qoff, 0, 0, bq, bk, True,
                                     stream)
        return jnp.sum(o * 0.3) + jnp.sum(jnp.sin(lse))

    def f_ref(q, k, v):
        o, lse = _ref_with_lse(q, k, v, q_offset=qoff)
        return jnp.sum(o * 0.3) + jnp.sum(jnp.sin(lse))

    with jax.default_matmul_precision("highest"):
        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def pallas_calls(jaxpr, found=None):
    """{kernel name: calls} over a jaxpr and every jaxpr inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) + 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    pallas_calls(sub, found)
    return found


def _kernel_names(fn, *xs):
    return set(pallas_calls(jax.make_jaxpr(fn)(*xs).jaxpr))


@pytest.mark.parametrize("pfx,qoff,bq,bk", [
    (0, 0, 16, 16), (16, 0, 16, 16), (0, 8, 16, 16), (20, 0, 8, 16)])
def test_streaming_design_matches_resident(pfx, qoff, bq, bk):
    """The two grid designs share their block math and must agree closely:
    resident forward + ONE-PASS backward (dq, dk, dv from one kernel)
    against the streaming forward + two-kernel backward, on one input. The
    rule picks per shape on TPU (_use_streaming), so both need coverage
    off-chip."""
    B, H, T, dh = 1, 2, 48, 8
    ks = jax.random.split(jax.random.key(11), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)

    def f(q, k, v, stream):
        o = flash_attention(q, k, v, qoff, 0, pfx, bq, bk, True, stream)
        return jnp.sum(o ** 2)

    res = jax.value_and_grad(lambda *xs: f(*xs, False), argnums=(0, 1, 2))
    stream = jax.value_and_grad(lambda *xs: f(*xs, True), argnums=(0, 1, 2))
    assert _kernel_names(res, q, k, v) == {"flash_attn_fwd",
                                           "flash_attn_dq_dkv"}
    assert _kernel_names(stream, q, k, v) == {
        "flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"}
    with jax.default_matmul_precision("highest"):
        vr, gr = res(q, k, v)
        vs, gs = stream(q, k, v)
    np.testing.assert_allclose(float(vs), float(vr), rtol=1e-6)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stream", [False, True])
def test_lse_is_kept_as_rows(stream):
    """Both designs hand the backward (and flash_attention_lse) the lse as
    [B*H, 1, Tq]: dense in HBM, where [B*H, Tq, 1] f32 is tiled (8, 128) on
    its last two dimensions — 128 times the bytes, 100 MB a layer at the
    benchmark's shape."""
    B, H, T, dh = 2, 2, 64, 16
    ks = jax.random.split(jax.random.key(13), 3)
    q, k, v = (_rand((B, H, T, dh), kk) for kk in ks)
    with jax.default_matmul_precision("highest"):
        o, lse = fa._flash_fwd_impl(q, k, v, 0, 0, 0, 16, 32, True, stream)
        o_r, lse_r = _ref_with_lse(q, k, v)
    assert lse.shape == (B * H, 1, T) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse).reshape(B, H, T),
                               np.asarray(lse_r), rtol=1e-5, atol=1e-5)


def _visible(q_lo, bq, k_lo, bk, pfx):
    qp = q_lo + np.arange(bq)[:, None]
    kp = k_lo + np.arange(bk)[None, :]
    return (qp >= kp) | (kp < pfx)


@pytest.mark.parametrize("T,bq,bk,qoff,koff,pfx", [
    (1024, 256, 256, 0, 0, 0), (1024, 512, 512, 0, 0, 0),
    (1024, 128, 256, 0, 0, 0), (1024, 256, 128, 0, 0, 0),
    (96, 32, 32, 0, 0, 40), (64, 16, 16, 0, 10, 0), (64, 16, 16, 40, 24, 0),
    (64, 32, 8, 8, 0, 20), (64, 8, 32, 0, 0, 33), (128, 32, 32, 500, 0, 0),
    (32, 16, 16, 0, 1000, 0), (128, 128, 128, 128, 0, 0)])
def test_sweep_bounds_cover_every_live_tile(T, bq, bk, qoff, koff, pfx):
    """The forward sweeps K blocks [0, bound) of a Q block, the one-pass
    backward Q blocks [start, num_q) of a K block: no tile with a visible
    element may be left out, and under pure causal masking none without one
    is computed — against the elementwise mask, tile by tile."""
    nq, nk = T // bq, T // bk
    live = {(i, j): _visible(qoff + i * bq, bq, koff + j * bk, bk, pfx).any()
            for i in range(nq) for j in range(nk)}
    for i in range(nq):
        bound = int(fa._causal_kv_bound(qoff + (i + 1) * bq - 1, koff, bk,
                                        nk, pfx))
        want = [j for j in range(nk) if live[i, j]]
        assert 0 <= bound <= nk and set(range(bound)) >= set(want)
        if not pfx:
            assert list(range(bound)) == want
    for j in range(nk):
        start = int(fa._first_q_block(koff + j * bk, qoff, bq, nq, pfx))
        want = [i for i in range(nq) if live[i, j]]
        assert 0 <= start <= nq and set(range(start, nq)) >= set(want)
        if not pfx:
            assert list(range(start, nq)) == want
    if (T, bq, bk, qoff, koff, pfx) == (1024, 512, 512, 0, 0, 0):
        assert sum(live.values()) == 3  # the benchmark cell: 3 of 4 tiles


MiB = 1 << 20


def test_use_streaming_rule():
    """The rule reads one accounting of what a resident kernel holds in VMEM
    (_resident_vmem_bytes) against one budget. Each case the old byte
    constants streamed and the budget admits names its chip run (PERF.md §6
    PR 28, device ms per call resident / streaming)."""
    from ddlbench_tpu.ops.flash_attention import (RESIDENT_VMEM_BUDGET,
                                                  _use_streaming)

    assert RESIDENT_VMEM_BUDGET == 64 * MiB  # half of a v5e core's VMEM
    # benchmarked shapes stay resident, as before
    assert not _use_streaming(8192, 64, 2, 512, 512, None)
    assert not _use_streaming(1024, 64, 2, 512, 512, None)
    # K + V of T=16384, dh=64 (19.1 MiB held): forward 9.92 / 18.35 ms
    assert not _use_streaming(16384, 64, 2, 512, 512, None)
    # wide heads at 8k (11.3 MiB): 5.89 / 9.61; f32 at 8k (20.1): 3.67 / 8.33
    assert not _use_streaming(8192, 128, 2, 512, 512, None)
    assert not _use_streaming(8192, 64, 4, 512, 512, None)
    # the tile temporaries are in the sum, so oversized blocks need no
    # clause of their own: (256, 1024) at T=8192 holds 10.8 MiB, 5.18 / 5.94
    assert not _use_streaming(8192, 64, 2, 256, 1024, None)
    assert not _use_streaming(1024, 64, 2, 1024, 1024, None)
    # what passes the budget streams: 67.1 and 68.1 MiB of K + V
    assert _use_streaming(65536, 64, 2, 512, 512, None)
    assert _use_streaming(32768, 64, 4, 512, 512, None)
    # explicit override wins both ways
    assert _use_streaming(64, 8, 2, 8, 8, True)
    assert not _use_streaming(1 << 20, 64, 2, 512, 512, False)
    # the resident kernels put the queries on the lanes: a compiled q block
    # that is no multiple of 128 (T=648 -> 216) streams; the interpreter
    # takes any
    assert _use_streaming(648, 64, 2, 216, 216, None)
    assert not _use_streaming(648, 64, 2, 216, 216, None, interpret=True)
    assert not _use_streaming(768, 64, 2, 384, 384, None)


@pytest.mark.parametrize("T,dh,itemsize,fused", [
    (1024, 64, 2, True),     # the benchmark cell: 6.5 MiB held
    (8192, 64, 2, True),     # longctx: 18.8 MiB
    (16384, 64, 2, True),    # 32.9 MiB: 15.87 / 38.70 ms (chip, PR 28)
    (32768, 64, 2, True),    # 61.2 MiB, the largest run: 31.07 / 75.73
    (8192, 128, 2, True),    # wide heads, 21.0 MiB: 9.12 / 20.11
    (4096, 128, 2, True),
    (8192, 64, 4, True),     # f32 operands, 32.9 MiB: 7.18 / 16.30
    (65536, 64, 2, False),   # 117.7 MiB
    (32768, 128, 2, False),  # 69.4 MiB
    (32768, 64, 4, False),   # 111.3 MiB
])
def test_one_pass_backward_where_it_fits_the_budget(T, dh, itemsize, fused):
    """The one-pass kernel keeps Q, dO, the dQ block and its f32 scratch of
    a whole head resident, so it serves a shape only while their sum fits
    the budget; the streaming pair takes the rest. Tiles: 512x512 at every
    length (the chip sweeps' winner at T=1024..8192, dh 64 and 192/128)."""
    from ddlbench_tpu.ops.flash_attention import _use_streaming

    bq = bk = _pick_block(T, 512)
    assert bq == 512
    assert _use_streaming(T, dh, itemsize, bq, bk, None, backward=True) \
        == (not fused)
    assert _use_streaming(T, dh, itemsize, bq, bk, True, backward=True)
    assert not _use_streaming(T, dh, itemsize, bq, bk, False, backward=True)


# (T, dqk, dv, backward) at bf16, 512x512: the two benchmark shapes, summed
# by hand from the kernels' BlockSpecs. Lanes pad to 128 (64 -> 128, 192 ->
# 256); every input and output block is double-buffered.
HAND_SUMS = {
    # Q, O blocks 2 x 2 x 128 KiB + lse 2 x 2 KiB; K, V 2 x 2 x 256 KiB;
    # s, p f32 + p bf16 = 10 B x 512^2; O^T accumulator [64, 512] f32
    (1024, 64, 64, False): (524288 + 4096) + 1048576 + (2621440 + 131072),
    # K, V, dK, dV blocks 4 x 2 x 128 KiB; Q, dO, dQ 3 x 2 x 256 KiB; lse,
    # delta rows 2 x 2 x 4 KiB; dQ^T scratch [64, 1024] f32; tiles 12 B x
    # 512^2, dK + dV carry 2 x 256 KiB, K^T 64 KiB, a dQ^T tile 128 KiB
    (1024, 64, 64, True): (1048576 + 1572864 + 16384 + 262144
                           + (3145728 + 524288 + 65536 + 131072)),
    # kanana2-ep16-train: Q 256 KiB + O 128 KiB blocks, K 2 MiB + V 1 MiB
    (4096, 192, 128, False): ((786432 + 4096) + 6291456
                              + (2621440 + 262144)),
    # K, dK 256 KiB + V, dV 128 KiB blocks; Q, dQ 2 MiB + dO 1 MiB, twice;
    # rows 64 KiB; scratch [192, 4096] f32 = 3 MiB; tiles 3 MiB, carry
    # 512 + 256 KiB, K^T [192, 512] bf16, a dQ^T tile [192, 512] f32
    (4096, 192, 128, True): (1572864 + 10485760 + 65536 + 3145728
                             + (3145728 + 786432 + 196608 + 393216)),
}


@pytest.mark.parametrize("T,dqk,dv,backward", HAND_SUMS)
def test_resident_vmem_bytes_against_hand_sums(T, dqk, dv, backward):
    got = fa._resident_vmem_bytes(T, dqk, dv, 2, 512, 512, backward)
    assert got == HAND_SUMS[T, dqk, dv, backward]
    # and against Mosaic's own number (local v5e compile, PR 28: the least
    # vmem_limit_bytes it accepts at B*H = 128): never under, within a third
    mosaic = {(1024, 64, 64, False): 2.939, (1024, 64, 64, True): 4.928,
              (4096, 192, 128, False): 9.021, (4096, 192, 128, True): 18.727}
    assert 1.0 <= got / MiB / mosaic[T, dqk, dv, backward] < 1.45


@pytest.mark.parametrize("T,dqk,dv,itemsize,bq,bk", [
    (1024, 64, 64, 2, 512, 512), (4096, 192, 128, 2, 512, 512),
    (8192, 64, 64, 2, 512, 512), (8192, 64, 64, 2, 256, 1024),
    (8192, 64, 64, 4, 512, 512), (16384, 192, 128, 2, 512, 512),
    (32768, 64, 64, 2, 512, 512), (768, 64, 64, 2, 384, 384)])
def test_vmem_limit_covers_what_the_kernel_holds(T, dqk, dv, itemsize, bq,
                                                 bk):
    """Every admitted case asks Mosaic for its accounted sum and a quarter
    more, in both kernels, and stays inside the chip's 128 MiB."""
    for backward in (False, True):
        assert not fa._use_streaming(T, dqk, itemsize, bq, bk, None,
                                     backward=backward, dv=dv)
        held = fa._resident_vmem_bytes(T, dqk, dv, itemsize, bq, bk,
                                       backward)
        assert held <= fa.RESIDENT_VMEM_BUDGET
        assert held * 1.25 - 1 <= fa._vmem_limit_bytes(held) <= 80 * MiB


@pytest.mark.parametrize("shape,dv", [
    ((4, 32, 4096, 192), 128),   # kanana2-ep16-train
    ((16, 12, 1024, 64), 64),    # gpt2s-train
])
def test_benchmark_shapes_take_the_one_pass_backward(shape, dv):
    """By tracing alone (nothing is lowered): at both cells' call shapes the
    compiled path's rule picks the resident forward and ONE backward kernel,
    whose name holds the substrings benchmarks/kernels/flash_attn.py finds
    its events by."""
    qk = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv,), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(qk, qk, v)
    assert pallas_calls(jaxpr.jaxpr) == {"flash_attn_dq_dkv": 1,
                                         "flash_attn_fwd": 1}
    # the forward is the resident one: grid (B*H, T / 512), no inner axis
    assert f"grid=({shape[0] * shape[1]}, {shape[2] // 512})" in str(jaxpr)


@pytest.mark.parametrize("extra", [
    [], ["--head-dim", "24", "--v-dim", "16", "--design", "resident"]],
    ids=["equal_widths", "split_widths"])
def test_attnbench_tile_sweep_needs_the_compiled_kernels(extra, capsys):
    """`attnbench --tiles` reads device time per kernel from a trace: off
    the TPU there is nothing to time, and it says so instead of timing the
    interpreter. The XLA cell of the same shape runs anywhere, split widths
    (``--v-dim``) included."""
    import json

    from ddlbench_tpu.tools.attnbench import main

    shape = ["--seq-lens", "64", "--batch", "1", "--heads", "2", "--steps",
             "1", "--platform", "cpu"] + extra
    with pytest.raises(SystemExit):
        main(shape + ["--tiles", "32x32"])
    capsys.readouterr()
    assert main(shape) == 0
    row = json.loads(capsys.readouterr().out.splitlines()[-1])
    want = (24, 16) if extra else (64, 64)
    assert (row["dh"], row["dv"]) == want and row["xla_ms"] > 0


# ---------------------------------------------------------------------------
# q/k width apart from the v/o width (latent attention: models/kanana2.py)
# ---------------------------------------------------------------------------

SPLIT = dict(B=2, H=2, T=128, dqk=24, dv=16)


def _split_qkv(seed=0):
    s = SPLIT
    ks = jax.random.split(jax.random.key(seed), 4)
    q = _rand((s["B"], s["H"], s["T"], s["dqk"]), ks[0])
    k = _rand((s["B"], s["H"], s["T"], s["dqk"]), ks[1])
    v = _rand((s["B"], s["H"], s["T"], s["dv"]), ks[2])
    g = _rand((s["B"], s["H"], s["T"], s["dv"]), ks[3])
    return q, k, v, g


def _einsum_attention(q, k, v):
    """The plain thing: scale from the q/k width, output as wide as v."""
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("stream", [False, True],
                         ids=["resident", "streaming"])
def test_split_widths_forward_and_gradients(stream):
    """q/k 24 wide, v/o 16 wide: forward and all three gradients of both
    grid designs against the einsum (the scale is 1/sqrt(24))."""
    q, k, v, g = _split_qkv()
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(_einsum_attention, q, k, v)
        got, got_vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, 0, 0, 0, 32, 32, True,
                                            stream), q, k, v)
        assert got.shape == v.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        for a, b, name in zip(got_vjp(g), ref_vjp(g), "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, err_msg=f"d{name}")


def test_split_widths_through_the_dispatch():
    """causal_attention's XLA path and the forced kernel agree on split
    widths too (the einsum path scales by the q/k width as well)."""
    q, k, v, _ = _split_qkv(1)
    with jax.default_matmul_precision("highest"):
        xla = causal_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(xla),
                                   np.asarray(_einsum_attention(q, k, v)),
                                   atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(transformer.causal_attention(q, k, v,
                                                    backend="flash")),
            np.asarray(xla), atol=2e-5)


def test_split_widths_lse_and_its_cotangent():
    q, k, v, g = _split_qkv(2)
    with jax.default_matmul_precision("highest"):
        (o, lse), vjp = jax.vjp(
            lambda q, k, v: flash_attention_lse(q, k, v, 0, 0, 0, 32, 32,
                                                True), q, k, v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(s, -1)), atol=2e-5)
        ref = jax.grad(lambda q: jnp.sum(jax.nn.logsumexp(
            jnp.where(jnp.tril(jnp.ones((128, 128), bool)),
                      jnp.einsum("bhqd,bhkd->bhqk", q, k)
                      / np.sqrt(q.shape[-1]), -jnp.inf), -1)))(q)
        dq = vjp((jnp.zeros_like(o), jnp.ones_like(lse)))[0]
        np.testing.assert_allclose(np.asarray(dq), np.asarray(ref),
                                   atol=5e-5)


@pytest.mark.parametrize("T,dqk,dv,fwd_streams,bwd_streams", [
    # kanana2-ep16-train: forward 9.5 MiB held; the one-pass backward 18.9
    # (chip, PR 28: 14.95 ms a call at B*H 128 against the pair's 30.34)
    (4096, 192, 128, False, False),
    (2048, 192, 128, False, False),
    # 15.5 / 31.9 MiB: forward 6.62 / 11.80 ms, backward 13.56 / 27.46
    (8192, 192, 128, False, False),
    # 27.5 / 58.1 MiB: forward 12.68 / 22.68, backward 25.92 / 52.64
    (16384, 192, 128, False, False),
    (32768, 192, 128, False, True),  # 51.5 MiB of K + V; 110.3 MiB
    (65536, 192, 128, True, True),
    (4096, 64, 64, False, False),    # equal widths: the same rule
    (8192, 64, 64, False, False),
])
def test_streaming_rule_on_split_widths(T, dqk, dv, fwd_streams,
                                        bwd_streams):
    """The resident side holds one operand of each width (K and V forward,
    Q and dO backward); the dQ block and its f32 scratch are q/k wide."""
    from ddlbench_tpu.ops.flash_attention import _use_streaming

    assert _use_streaming(T, dqk, 2, 512, 512, None, dv=dv) == fwd_streams
    assert _use_streaming(T, dqk, 2, 512, 512, None, backward=True, dv=dv) \
        == bwd_streams
    if dqk == dv:  # dv left out means dv = dh: what every old caller gets
        assert _use_streaming(T, dqk, 2, 512, 512, None) == fwd_streams


@pytest.mark.parametrize("T,bwd_streams", [(64, False), (128, True)])
def test_auto_rule_gradients_on_each_side_of_the_budget(T, bwd_streams,
                                                        monkeypatch):
    """stream=None at split widths (q/k 24, v 16, 32x32 tiles): with the
    budget put between what the one-pass backward holds at T=64 and at
    T=128, the rule's own pick is the one-pass kernel on one side and the
    pair on the other (the forward stays resident), and either way the
    gradients are the einsum's."""
    held = {t: fa._resident_vmem_bytes(t, 24, 16, 4, 32, 32, True)
            for t in (64, 128)}
    fwd_held = fa._resident_vmem_bytes(128, 24, 16, 4, 32, 32, False)
    assert fwd_held <= held[64] < held[128]
    monkeypatch.setattr(fa, "RESIDENT_VMEM_BUDGET", held[64])
    ks = jax.random.split(jax.random.key(3), 4)
    q, k = _rand((1, 2, T, 24), ks[0]), _rand((1, 2, T, 24), ks[1])
    v, g = _rand((1, 2, T, 16), ks[2]), _rand((1, 2, T, 16), ks[3])
    auto = lambda q, k, v: flash_attention(q, k, v, 0, 0, 0, 32, 32, True)
    names = _kernel_names(jax.grad(lambda *a: jnp.sum(auto(*a) * g),
                                   argnums=(0, 1, 2)), q, k, v)
    assert names == ({"flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"}
                     if bwd_streams else
                     {"flash_attn_fwd", "flash_attn_dq_dkv"})
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(_einsum_attention, q, k, v)
        got, got_vjp = jax.vjp(auto, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        for a, b, name in zip(got_vjp(g), ref_vjp(g), "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# fewer key/value heads than query heads (grouped queries: models/zaya.py)
# ---------------------------------------------------------------------------

GROUPED = dict(B=2, H=8, K=2, T=128, d=16)


def _grouped_qkv(seed=0, H=GROUPED["H"], K=GROUPED["K"]):
    s = GROUPED
    ks = jax.random.split(jax.random.key(seed), 4)
    q = _rand((s["B"], H, s["T"], s["d"]), ks[0])
    k = _rand((s["B"], K, s["T"], s["d"]), ks[1])
    v = _rand((s["B"], K, s["T"], s["d"]), ks[2])
    g = _rand((s["B"], H, s["T"], s["d"]), ks[3])
    return q, k, v, g


def _grouped_einsum(q, k, v):
    """Query head j on key/value head j // (H / K), the groups written out."""
    G = q.shape[1] // k.shape[1]
    return _einsum_attention(q, jnp.repeat(k, G, axis=1),
                             jnp.repeat(v, G, axis=1))


@pytest.mark.parametrize("stream", [False, True],
                         ids=["resident", "streaming"])
@pytest.mark.parametrize("H,K", [(8, 2), (4, 1), (6, 3)])
def test_grouped_queries_forward_and_gradients(H, K, stream):
    """8 query heads over 2 key/value heads (and 4 over 1, 6 over 3): the
    forward and all three gradients of both grid designs against the
    einsum with the groups written out; dK and dV come back K-headed, each
    the sum over its group's query heads."""
    q, k, v, g = _grouped_qkv(H + K, H, K)
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(_grouped_einsum, q, k, v)
        got, got_vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, 0, 0, 0, 32, 32, True,
                                            stream), q, k, v)
        assert got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        for a, b, name in zip(got_vjp(g), ref_vjp(g), "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, err_msg=f"d{name}")


def test_grouped_queries_through_the_dispatch():
    """causal_attention's XLA path takes the two head counts too, and the
    forced kernel agrees with it, forward and gradients."""
    q, k, v, g = _grouped_qkv(1)
    with jax.default_matmul_precision("highest"):
        xla, xla_vjp = jax.vjp(causal_attention, q, k, v)
        np.testing.assert_allclose(np.asarray(xla),
                                   np.asarray(_grouped_einsum(q, k, v)),
                                   atol=2e-5)
        got, got_vjp = jax.vjp(functools.partial(
            transformer.causal_attention, backend="flash"), q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                                   atol=2e-5)
        for a, b, name in zip(got_vjp(g), xla_vjp(g), "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, err_msg=f"d{name}")


def test_grouped_queries_in_bfloat16_sum_each_group_in_float32():
    """The per-query-head dK, dV leave the kernel in bfloat16; the group's
    sum is taken in float32 and rounded once."""
    q, k, v, g = (t.astype(jnp.bfloat16) for t in _grouped_qkv(2))
    f = lambda q, k, v: flash_attention(q, k, v, 0, 0, 0, 32, 32, True)
    dk = jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * g),
                  argnums=1)(q, k, v)
    wide = jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * g),
                    argnums=1)(q, jnp.repeat(k, 4, axis=1),
                               jnp.repeat(v, 4, axis=1))
    want = jnp.sum(wide.reshape(2, 2, 4, 128, 16).astype(jnp.float32),
                   axis=2).astype(jnp.bfloat16)
    assert dk.dtype == jnp.bfloat16 and dk.shape == k.shape
    np.testing.assert_array_equal(np.asarray(dk.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("k_heads,v_heads", [(3, 3), (2, 4), (16, 16)])
def test_head_counts_that_do_not_group_are_refused(k_heads, v_heads):
    s = GROUPED
    q = jnp.zeros((1, 8, s["T"], s["d"]))
    k = jnp.zeros((1, k_heads, s["T"], s["d"]))
    v = jnp.zeros((1, v_heads, s["T"], s["d"]))
    with pytest.raises(ValueError, match="head"):
        flash_attention(q, k, v, 0, 0, 0, 32, 32, True)


# sha256 of the lowered text (no locations) of flash attention's forward and
# backward in interpret mode with as many key/value heads as query heads, AT
# THE PARENT of the PR that gave the kernels a second head count (e009dd1):
# K == H lowers to the kernels it lowered to before. The text is hashed with
# the NUMBERS of jax's private helper functions taken out (``@clip_55`` ->
# ``@clip``): jax numbers them by how many functions the trace has made, and
# naming the forward's outputs for a rematerialized layer to keep (``_kept``)
# makes one more and no operation — the four texts differ from that parent's
# in 8 / 11 / 8 / 11 lines, each one such a number (96a420c against the tree
# that named them: the hashes of the whole text moved 4835ecfa -> 283f57c4,
# 68d26650 -> bc547810, 670b6e43 -> 6aef66da, 4abffb0d -> 173d0331, these
# did not).
EQUAL_HEADS_AT_PARENT = {
    (64, 64, False):
        "f0922f3545d5be4a8c3e4cb1f1ad6804b217bcdd48b866206a3718eeec1cd35f",
    (64, 64, True):
        "cdffb2bf8029ab859ad6087b5666ce8af4bc25cd142f4f5d606f217479522b28",
    (48, 32, False):
        "ecec28c52895e9ddafb2c06a48a17fc75c4b4d68814c2ab2de77f29b70937aa3",
    (48, 32, True):
        "ead3d8271538d6f8de9881f4876b9d67045b3b569825424397cfb912b949bd2c",
}


def _unnumbered(text):
    """``text`` with the numbers of private functions' names taken out."""
    import re

    return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)


@pytest.mark.parametrize("case", sorted(EQUAL_HEADS_AT_PARENT),
                         ids=lambda c: f"qk{c[0]}-v{c[1]}-"
                                       f"{'streaming' if c[2] else 'resident'}")
def test_equal_head_counts_lower_to_the_kernels_they_did(case):
    import hashlib

    dh, dv, stream = case
    q = jax.ShapeDtypeStruct((2, 4, 256, dh), jnp.float32)
    v = jax.ShapeDtypeStruct((2, 4, 256, dv), jnp.float32)
    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, 0, 0, 0, 128, 128,
                                                True, stream))
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, v).as_text()
    assert hashlib.sha256(_unnumbered(text).encode()).hexdigest() == \
        EQUAL_HEADS_AT_PARENT[case]


# ---------------------------------------------------------------------------
# a sliding window on top of the causal rule (models/smallthinker.py)
# ---------------------------------------------------------------------------


def _banded_einsum(q, k, v, window, q_offset=0, k_offset=0):
    """The plain thing: query i on the keys j with i - window < j <= i
    (absolute positions), the groups of a grouped call written out."""
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    qp = q_offset + jnp.arange(q.shape[2])[:, None]
    kp = k_offset + jnp.arange(k.shape[2])[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where((kp <= qp) & (kp > qp - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# (window, Tq, Tk, q_offset, k_offset) at 32 x 32 tiles, 14 query heads over
# 2 key/value heads
WINDOW_CASES = {
    "inside_a_block": (8, 128, 128, 0, 0),
    "two_blocks": (64, 128, 128, 0, 0),
    "cuts_a_block": (40, 128, 128, 0, 0),
    "one_key": (1, 128, 128, 0, 0),
    "ring_block": (40, 64, 128, 64, 0),
    "reaches_every_key": (128, 128, 128, 0, 0),
    "past_the_sequence": (1000, 128, 128, 0, 0),
}


@pytest.mark.parametrize("design", ["resident", "streaming"])
@pytest.mark.parametrize("case", WINDOW_CASES)
def test_window_forward_and_gradients(case, design, monkeypatch):
    """The windowed kernels of both grid designs — picked by the rule's own
    accounting, the budget put out of reach on either side — against the
    banded einsum: the forward and all three gradients, 14 query heads over
    2 key/value heads. causal_attention's einsum path is that banded einsum
    too. A window that reaches every key is the plain call: bit-equal, and
    the same lowered text."""
    window, Tq, Tk, qoff, koff = WINDOW_CASES[case]
    monkeypatch.setattr(fa, "RESIDENT_VMEM_BUDGET",
                        0 if design == "streaming" else 1 << 40)
    ks = jax.random.split(jax.random.key(window), 4)
    q, g = _rand((1, 14, Tq, 16), ks[0]), _rand((1, 14, Tq, 16), ks[3])
    k, v = _rand((1, 2, Tk, 16), ks[1]), _rand((1, 2, Tk, 16), ks[2])
    flash = lambda w: lambda q, k, v: flash_attention(
        q, k, v, qoff, koff, 0, 32, 32, True, None, w)
    names = _kernel_names(jax.grad(lambda *a: jnp.sum(flash(window)(*a) * g),
                                   argnums=(0, 1, 2)), q, k, v)
    assert names == ({"flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"}
                     if design == "streaming" else
                     {"flash_attn_fwd", "flash_attn_dq_dkv"})
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(functools.partial(
            _banded_einsum, window=window, q_offset=qoff, k_offset=koff),
            q, k, v)
        xla = causal_attention(q, k, v, qoff, koff, window=window)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                                   atol=2e-5)
        got, got_vjp = jax.vjp(flash(window), q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        for a, b, name in zip(got_vjp(g), ref_vjp(g), "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, err_msg=f"d{name}")
        if window >= Tk:
            plain, plain_vjp = jax.vjp(flash(0), q, k, v)
            assert np.array_equal(np.asarray(got), np.asarray(plain))
            for a, b in zip(got_vjp(g), plain_vjp(g)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
    if window >= Tk:
        text = lambda w: jax.jit(jax.grad(
            lambda *a: jnp.sum(flash(w)(*a) * g), argnums=(0, 1, 2))).lower(
                q, k, v).as_text()
        assert text(window) == text(0)
    else:
        assert not np.allclose(np.asarray(got), np.asarray(
            _banded_einsum(q, k, v, Tq + Tk + qoff, qoff, koff)), atol=1e-3)


def test_window_through_the_dispatch_and_the_lse():
    """The forced kernel takes causal_attention's window; flash_attention_lse
    takes it too, and its lse is the banded scores' logsumexp; a window with
    a prefix is refused on both paths."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = _rand((1, 4, 64, 16), ks[0])
    k, v = _rand((1, 2, 64, 16), ks[1]), _rand((1, 2, 64, 16), ks[2])
    with jax.default_matmul_precision("highest"):
        want = _banded_einsum(q, k, v, 24)
        got = transformer.causal_attention(q, k, v, backend="flash",
                                           window=24)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        o, lse = flash_attention_lse(q, k, v, 0, 0, 0, 16, 16, True, None, 24)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   atol=2e-5)
        pos = jnp.arange(64)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) / 4.0
        seen = (pos[None, :] <= pos[:, None]) & \
            (pos[None, :] > pos[:, None] - 24)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(
                jnp.where(seen, s, -jnp.inf), axis=-1)), atol=2e-5)
    for attend in (functools.partial(transformer.causal_attention,
                                     backend="flash"), causal_attention):
        with pytest.raises(ValueError, match="prefix"):
            attend(q, k, v, prefix_len=8, window=24)
    with pytest.raises(ValueError, match="negative"):
        flash_attention(q, k, v, 0, 0, 0, 16, 16, True, None, -1)


@pytest.mark.parametrize("T,bq,bk,window,qoff,koff,tiles", [
    (16384, 512, 512, 4096, 0, 0, (252, 528)),  # smallthinker-t16k-train
    (1024, 256, 256, 256, 0, 0, None), (1024, 128, 256, 300, 0, 0, None),
    (1024, 256, 128, 1, 0, 0, None), (128, 32, 32, 40, 64, 0, None),
    (64, 16, 16, 24, 40, 24, None), (64, 16, 16, 8, 0, 10, None)])
def test_window_sweeps_visit_the_live_tiles_alone(T, bq, bk, window, qoff,
                                                  koff, tiles):
    """By the bounds' own functions, no kernel run: the forward sweeps the K
    blocks [first, bound) of a Q block, the backward the Q blocks [start,
    end) of a K block; both visit exactly the tiles that hold a visible
    (query, key) pair. At the cell's shape that is 252 of the 528 causal
    tiles, and the pairs inside them are what ``flash_attn_banded.work``
    counts."""
    from benchmarks.kernels import flash_attn_banded

    nq, nk = T // bq, T // bk

    def live(i, j):  # some kp <= qp with kp > qp - window
        q_lo, k_lo = qoff + i * bq, koff + j * bk
        return k_lo <= q_lo + bq - 1 and k_lo + bk - 1 > q_lo - window

    fwd = {(i, j) for i in range(nq) for j in range(
        int(fa._window_kv_start(i, bq, qoff, koff, bk, nk, window)),
        int(fa._causal_kv_bound(qoff + (i + 1) * bq - 1, koff, bk, nk)))}
    bwd = {(i, j) for j in range(nk) for i in range(
        int(fa._first_q_block(koff + j * bk, qoff, bq, nq)),
        int(fa._window_q_end(j, bk, koff, qoff, bq, nq, window)))}
    want = {(i, j) for i in range(nq) for j in range(nk) if live(i, j)}
    assert fwd == want and bwd == want
    if tiles is None:
        return
    causal = {(i, j) for i in range(nq) for j in range(int(
        fa._causal_kv_bound(qoff + (i + 1) * bq - 1, koff, bk, nk)))}
    assert (len(want), len(causal)) == tiles
    inside = 0
    for i, j in want:
        qp = i * bq + np.arange(bq)[:, None]
        kp = j * bk + np.arange(bk)[None, :]
        inside += int(np.sum((kp <= qp) & (kp > qp - window)))
    assert inside == flash_attn_banded.pairs(T, window) == \
        window * T - window * (window - 1) // 2
    flops, nbytes = flash_attn_banded.work(1, 28, T, 128, window)
    assert flops == 6 * 2.0 * 28 * 128 * inside
    assert nbytes == 12.0 * 28 * T * 128 * 2
    assert flash_attn_banded.pairs(T, 0) == T * (T + 1) / 2 \
        == flash_attn_banded.pairs(T, T)
