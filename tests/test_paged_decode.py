"""Paged KV cache + flash-decode kernel (ops/paged_decode.py).

Oracle: the dense cached path — a [rows, H, L, dh] cache updated by
dynamic_update_slice and read by the masked full-length einsum
(models/transformer.attn_decode_op semantics). The paged structures must
reproduce it bit-for-bit in f32: writes land in the right page slots, the
copy-on-write reorder preserves exactly the histories a physical gather
would, and the Pallas kernel (interpret mode) matches the jnp reference.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.ops.paged_decode import (
    num_pages, paged_attention, paged_cache_init, paged_decode_write,
    paged_prefill_write, paged_reorder, _paged_attention_ref)

ROWS, H, DH, PAGE = 4, 2, 8, 4
L = 16  # 4 pages


def _rand(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _dense_attention(q, kd, vd, pos):
    """Masked full-length single-query attention (attn_decode_op oracle)."""
    scores = jnp.einsum("rhd,rhkd->rhk", q, kd) / math.sqrt(q.shape[-1])
    k_pos = jnp.arange(kd.shape[2])[None, None, :]
    scores = jnp.where(k_pos <= pos, scores, -jnp.inf)
    p = jax.nn.softmax(scores.astype(jnp.float32), -1)
    return jnp.einsum("rhk,rhkd->rhd", p, vd)


def _gather_pages(cache):
    """Densify: [rows, H, n_pages*page, dh] view of what the table exposes."""
    rows, npg = cache["table"].shape
    k = cache["pool_k"][cache["table"]]  # [rows, npg, page, H, dh]
    k = k.reshape(rows, npg * PAGE, H, DH)
    v = cache["pool_v"][cache["table"]].reshape(rows, npg * PAGE, H, DH)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def test_prefill_and_decode_writes_roundtrip():
    S = 6  # straddles a page boundary (pages of 4)
    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    k = _rand(0, ROWS, S, H, DH)
    v = _rand(1, ROWS, S, H, DH)
    cache = paged_prefill_write(cache, k, v, page=PAGE)
    kd, vd = _gather_pages(cache)
    np.testing.assert_allclose(kd[:, :, :S], k.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(vd[:, :, :S], v.transpose(0, 2, 1, 3))
    # sequential single-token writes continue the stream
    for t in range(S, L):
        k1 = _rand(10 + t, ROWS, 1, H, DH)
        cache = paged_decode_write(cache, k1, k1 * 2.0, t, page=PAGE)
        kd, vd = _gather_pages(cache)
        np.testing.assert_allclose(kd[:, :, t], k1[:, 0])
        np.testing.assert_allclose(vd[:, :, t], 2.0 * kd[:, :, t])


def test_chunked_prefill_matches_whole_prompt():
    """Prompt chunking (long-context serving): writing [0, 5) then [5, 11)
    then [11, 14) — chunk boundaries page-UNALIGNED (pages of 4) — must
    leave the pool identical to a single whole-prompt write."""
    k = _rand(90, ROWS, 14, H, DH)
    v = _rand(91, ROWS, 14, H, DH)
    whole = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    whole = paged_prefill_write(whole, k, v, page=PAGE)
    chunked = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    for lo, hi in ((0, 5), (5, 11), (11, 14)):
        chunked = paged_prefill_write(chunked, k[:, lo:hi], v[:, lo:hi],
                                      page=PAGE, start=lo)
    np.testing.assert_array_equal(np.asarray(chunked["pool_k"]),
                                  np.asarray(whole["pool_k"]))
    np.testing.assert_array_equal(np.asarray(chunked["pool_v"]),
                                  np.asarray(whole["pool_v"]))
    np.testing.assert_array_equal(np.asarray(chunked["table"]),
                                  np.asarray(whole["table"]))
    # and it must be jit-compatible (static start, traced chunk)
    jitted = jax.jit(functools.partial(paged_prefill_write, page=PAGE,
                                       start=5))
    chunk2 = jitted(whole, k[:, 5:11] * 2.0, v[:, 5:11] * 2.0)
    kd, _ = _gather_pages(chunk2)
    np.testing.assert_allclose(np.asarray(kd[:, :, 5:11]),
                               np.asarray(2.0 * k[:, 5:11].transpose(0, 2, 1, 3)))


def test_chunked_prefill_rejects_out_of_bounds_chunk():
    # a chunk running past the pool capacity would silently truncate KV
    # history through the clamped .at[].set scatter (advisor r5): the
    # bounds assert must reject it at trace time instead
    import pytest

    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    k = _rand(0, ROWS, 6, H, DH)
    v = _rand(1, ROWS, 6, H, DH)
    with pytest.raises(AssertionError, match="capacity"):
        paged_prefill_write(cache, k, v, page=PAGE, start=L - 4)
    # the last in-bounds chunk position still works
    paged_prefill_write(cache, k[:, :4], v[:, :4], page=PAGE, start=L - 4)


@pytest.mark.parametrize("pos,npl", [(3, 1), (7, 2), (10, 3), (14, 4)])
def test_paged_attention_ref_matches_dense(pos, npl):
    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    kfull = _rand(2, ROWS, L, H, DH)
    vfull = _rand(3, ROWS, L, H, DH)
    cache = paged_prefill_write(cache, kfull, vfull, page=PAGE)
    q = _rand(4, ROWS, H, DH)
    out = _paged_attention_ref(q, cache, pos, npl, page=PAGE)
    exp = _dense_attention(q, kfull.transpose(0, 2, 1, 3),
                           vfull.transpose(0, 2, 1, 3), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos,npl", [(3, 1), (10, 3), (15, 4)])
def test_paged_attention_kernel_matches_ref(pos, npl):
    """The flash-decode kernel (interpret mode) matches the jnp oracle."""
    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    cache = paged_prefill_write(cache, _rand(5, ROWS, L, H, DH),
                                _rand(6, ROWS, L, H, DH), page=PAGE)
    q = _rand(7, ROWS, H, DH)
    ref = _paged_attention_ref(q, cache, pos, npl, page=PAGE)
    out = paged_attention(q, cache, pos, npl, page=PAGE, interpret=True,
                          use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _serve_chunk_cache(npages_pool, rows, npl, seed=30):
    """A serving-style shared pool + table: ``rows`` table rows borrowing
    arbitrary (non-contiguous, shuffled) slots — the free-list layout the
    chunk kernel must walk through the table."""
    from ddlbench_tpu.ops.paged_decode import serve_pool_init

    pool = serve_pool_init(npages_pool, PAGE, H, DH, jnp.float32)
    pool = {
        "pool_k": _rand(seed, npages_pool, PAGE, H, DH),
        "pool_v": _rand(seed + 1, npages_pool, PAGE, H, DH),
    }
    rng = np.random.default_rng(seed)
    slots = rng.permutation(np.arange(1, npages_pool))[: rows * npl]
    table = jnp.asarray(slots.reshape(rows, npl), jnp.int32)
    return {**pool, "table": table}


@pytest.mark.parametrize("start,npl,C", [(0, 1, 4), (8, 3, 4), (4, 3, 8)])
def test_paged_chunk_attention_kernel_matches_ref(start, npl, C):
    """The chunked-prefill kernel (multi-query flash-decode analog) matches
    the gathered-page XLA reference through a shuffled serving table,
    within the flash-decode pin's tolerance."""
    from ddlbench_tpu.ops.paged_decode import (_paged_chunk_attention_ref,
                                               paged_chunk_attention)

    rows = 2
    cache = _serve_chunk_cache(16, rows, npl)
    q = _rand(33, rows, H, C, DH)
    ref = _paged_chunk_attention_ref(q, cache, start, npl, page=PAGE)
    out = paged_chunk_attention(q, cache, start, npl, page=PAGE,
                                interpret=True, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_chunk_kernel_refuses_oversized_score_product():
    """A compiled (non-interpret) chunk call whose [H, C, page, dh] score
    product cannot fit scoped VMEM is refused by name at trace time — on
    a TPU nothing may route to the jnp reference instead. The serving
    default (chunk = page = 16 at transformer_m's H=12, dh=64) is far
    inside the budget."""
    from ddlbench_tpu.ops.paged_decode import (CHUNK_PRODUCT_MAX_BYTES,
                                               _require_chunk_fits_vmem,
                                               paged_chunk_attention)

    _require_chunk_fits_vmem(12, 16, 16, 64)
    assert 4 * 12 * 16 * 16 * 64 < CHUNK_PRODUCT_MAX_BYTES
    with pytest.raises(ValueError, match="smaller prefill chunk"):
        _require_chunk_fits_vmem(8, 64, 64, 64)  # ran out of VMEM on v5e
    cache = _serve_chunk_cache(4, 1, 1)
    cache = {k: (jnp.zeros((4, 64, 8, 64), jnp.float32)
                 if k.startswith("pool") else v) for k, v in cache.items()}
    q = jnp.zeros((1, 8, 64, 64), jnp.float32)
    with pytest.raises(ValueError, match="paged_chunk_attention"):
        paged_chunk_attention(q, cache, 0, 1, page=64, use_kernel=True)


def test_paged_chunk_attention_per_row_start():
    """Per-row chunk starts (each serving row is its own request at its own
    prefill frontier): kernel and reference agree row-by-row with rows at
    DIFFERENT absolute positions."""
    from ddlbench_tpu.ops.paged_decode import (_paged_chunk_attention_ref,
                                               paged_chunk_attention)

    rows, C, npl = 3, 4, 3
    cache = _serve_chunk_cache(16, rows, npl, seed=44)
    q = _rand(45, rows, H, C, DH)
    starts = jnp.asarray([0, 4, 8], jnp.int32)
    ref = _paged_chunk_attention_ref(q, cache, starts, npl, page=PAGE)
    out = paged_chunk_attention(q, cache, starts, npl, page=PAGE,
                                interpret=True, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and each row equals a rows=1 reference at its own scalar start — the
    # per-row vector is not silently broadcasting row 0's start
    for r, s in enumerate([0, 4, 8]):
        one = _paged_chunk_attention_ref(
            q[r:r + 1], {**cache, "table": cache["table"][r:r + 1]},
            s, npl, page=PAGE)
        np.testing.assert_allclose(np.asarray(out[r:r + 1]),
                                   np.asarray(one), rtol=1e-5, atol=1e-5)


def test_paged_chunk_attention_ref_matches_dense_chunk():
    """The XLA chunk reference itself is pinned to a dense causal oracle:
    every query position c attends exactly keys [0, start + c]."""
    from ddlbench_tpu.ops.paged_decode import _paged_chunk_attention_ref

    rows, C, npl, start = 2, 4, 3, 6
    cache = _serve_chunk_cache(16, rows, npl, seed=50)
    q = _rand(51, rows, H, C, DH)
    out = _paged_chunk_attention_ref(q, cache, start, npl, page=PAGE)
    L = npl * PAGE
    kd = cache["pool_k"][cache["table"]].reshape(rows, L, H, DH)
    vd = cache["pool_v"][cache["table"]].reshape(rows, L, H, DH)
    for c in range(C):
        exp = _dense_attention(q[:, :, c], kd.transpose(0, 2, 1, 3),
                               vd.transpose(0, 2, 1, 3), start + c)
        np.testing.assert_allclose(np.asarray(out[:, :, c]), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)


def test_serve_page_copy():
    """COW primitive: dst slot becomes a bitwise copy of src, nothing else
    moves, and the op is jit-stable with traced slot indices."""
    from ddlbench_tpu.ops.paged_decode import serve_page_copy

    pool = {"pool_k": _rand(60, 8, PAGE, H, DH),
            "pool_v": _rand(61, 8, PAGE, H, DH)}
    out = jax.jit(serve_page_copy)(pool, jnp.int32(3), jnp.int32(6))
    for key in ("pool_k", "pool_v"):
        np.testing.assert_array_equal(np.asarray(out[key][6]),
                                      np.asarray(pool[key][3]))
        keep = np.array([i for i in range(8) if i != 6])
        np.testing.assert_array_equal(np.asarray(out[key][keep]),
                                      np.asarray(pool[key][keep]))


# ---------------------------------------------------------------------------
# Quantized (int8) serving pool: write-boundary quantization, the span
# write, and the fused-dequant kernels (ISSUE 13).
# ---------------------------------------------------------------------------


def _quant_cache(rows=2, npl=3, fill=True, seed=70):
    """An int8 serving pool + shuffled table, filled through the REAL
    page-aligned chunk-write path (per-page scale sidecar + stochastic
    rounding) so every pin below reads the layout the engine produces."""
    from ddlbench_tpu.ops.paged_decode import (paged_table_chunk_write,
                                               serve_pool_init)

    pool = serve_pool_init(16, PAGE, H, DH, jnp.int8)
    pool["kv_seed"] = jnp.int32(1)
    rng = np.random.default_rng(seed)
    slots = rng.permutation(np.arange(1, 16))[: rows * npl]
    cache = {**pool, "table": jnp.asarray(slots.reshape(rows, npl),
                                          jnp.int32)}
    k = v = None
    if fill:
        k = _rand(seed + 1, rows, npl * PAGE, H, DH)
        v = _rand(seed + 2, rows, npl * PAGE, H, DH)
        cache = paged_table_chunk_write(cache, k, v, jnp.int32(0), PAGE)
    return cache, k, v


def _dequant_rows(cache, npl):
    """Densify an int8 pool through the table + scale sidecar."""
    rows = cache["table"].shape[0]
    out = []
    for name in ("pool_k", "pool_v"):
        pages = np.asarray(cache[name], np.float32)[
            np.asarray(cache["table"])]
        scale = np.asarray(cache["scale_" + name[-1]])[
            np.asarray(cache["table"])]
        out.append((pages * scale[..., None, None])
                   .reshape(rows, npl * PAGE, H, DH))
    return out


def test_quantized_chunk_write_roundtrip_and_determinism():
    """int8 page writes: dequantized error bounded by one scale step per
    element (absmax/127 — ~1%), an all-zero position stays exactly zero,
    and the identical write replays bitwise (counter-based seeds)."""
    cache, k, v = _quant_cache()
    kd, vd = _dequant_rows(cache, 3)
    for got, ref in ((kd, k), (vd, v)):
        ref = np.asarray(ref)
        step = np.max(np.abs(ref), axis=(2, 3), keepdims=True) / 127.0
        assert np.max(np.abs(got - ref) / np.maximum(step, 1e-9)) <= 1.0 + 1e-5
    again, _, _ = _quant_cache()
    for key in ("pool_k", "pool_v", "scale_k", "scale_v"):
        np.testing.assert_array_equal(np.asarray(cache[key]),
                                      np.asarray(again[key]))


def test_quantized_span_write_matches_chunk_and_single_writes():
    """The three write paths agree byte-for-byte where their domains
    overlap: a page-aligned span write equals the chunk write, and an
    UNALIGNED span write equals the equivalent sequence of single-token
    writes — quantized bytes are a pure function of (values, position),
    never of which program wrote them."""
    from ddlbench_tpu.ops.paged_decode import (paged_table_span_write,
                                               paged_table_write)

    chunked, k, v = _quant_cache(seed=75)
    aligned, _, _ = _quant_cache(seed=75, fill=False)
    aligned = paged_table_span_write(
        aligned, k, v, jnp.zeros((2,), jnp.int32), PAGE)
    for key in ("pool_k", "pool_v", "scale_k", "scale_v"):
        np.testing.assert_array_equal(np.asarray(chunked[key]),
                                      np.asarray(aligned[key]))
    # unaligned span [5, 8) == single-token writes at 5, 6, 7
    spanned, _, _ = _quant_cache(seed=75)
    spanned = paged_table_span_write(
        spanned, k[:, 5:8], v[:, 5:8],
        jnp.full((2,), 5, jnp.int32), PAGE)
    single, _, _ = _quant_cache(seed=75)
    for t in range(5, 8):
        single = paged_table_write(single, k[:, t:t + 1], v[:, t:t + 1],
                                   jnp.full((2,), t, jnp.int32), PAGE)
    for key in ("pool_k", "pool_v", "scale_k", "scale_v"):
        np.testing.assert_array_equal(np.asarray(spanned[key]),
                                      np.asarray(single[key]))


def test_span_write_f32_and_overflow_to_scratch():
    """The span write on an UNQUANTIZED pool: values land verbatim at
    (page, offset) through the table, and positions past the table's
    columns resolve to the scratch slot (the padded-draft-tail contract,
    mirroring the chunk write's scratch extension)."""
    from ddlbench_tpu.ops.paged_decode import (paged_table_span_write,
                                               serve_pool_init)

    pool = serve_pool_init(8, PAGE, H, DH, jnp.float32)
    table = jnp.asarray([[3, 5]], jnp.int32)  # 2 pages -> capacity 8
    cache = {**pool, "table": table}
    W = 4
    k = _rand(80, 1, W, H, DH)
    v = _rand(81, 1, W, H, DH)
    # start at 6: positions 6, 7 live in page 1; 8, 9 overflow the table
    out = paged_table_span_write(cache, k, v,
                                 jnp.asarray([6], jnp.int32), PAGE)
    pk = np.asarray(out["pool_k"])
    np.testing.assert_array_equal(pk[5, 2], np.asarray(k)[0, 0])
    np.testing.assert_array_equal(pk[5, 3], np.asarray(k)[0, 1])
    # overflow went to scratch (slot 0), not into a live page
    np.testing.assert_array_equal(pk[3], np.zeros((PAGE, H, DH)))
    assert np.any(np.asarray(out["pool_k"])[0] != 0)


def test_quantized_flash_decode_kernel_matches_ref():
    """Fused-dequant flash-decode kernel (interpret mode) vs the XLA
    reference on an int8 pool, within the existing flash-decode
    tolerance."""
    cache, _, _ = _quant_cache(seed=85)
    q = _rand(86, 2, H, DH)
    pos = jnp.asarray([11, 7], jnp.int32)
    ref = _paged_attention_ref(q, cache, pos, 3, page=PAGE)
    out = paged_attention(q, cache, pos, 3, page=PAGE, interpret=True,
                          use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quantized_chunk_kernel_matches_ref():
    """Fused-dequant chunk-prefill kernel vs the XLA reference on an int8
    pool at per-row starts (the speculative verify read path)."""
    from ddlbench_tpu.ops.paged_decode import (_paged_chunk_attention_ref,
                                               paged_chunk_attention)

    cache, _, _ = _quant_cache(seed=90)
    C = 4
    q = _rand(91, 2, H, C, DH)
    starts = jnp.asarray([4, 7], jnp.int32)
    ref = _paged_chunk_attention_ref(q, cache, starts, 3, page=PAGE)
    out = paged_chunk_attention(q, cache, starts, 3, page=PAGE,
                                interpret=True, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quantized_page_copy_carries_scales():
    """serve_page_copy on a quantized pool: payload AND scale sidecar
    rows copy verbatim (a COW'd page dequantizes bit-identically), and
    the scalar kv_seed passes through untouched."""
    from ddlbench_tpu.ops.paged_decode import serve_page_copy

    cache, _, _ = _quant_cache(seed=95)
    pool = {k2: v2 for k2, v2 in cache.items() if k2 != "table"}
    src = int(np.asarray(cache["table"])[0, 1])
    out = jax.jit(serve_page_copy)(pool, jnp.int32(src), jnp.int32(15))
    for key in ("pool_k", "pool_v", "scale_k", "scale_v"):
        np.testing.assert_array_equal(np.asarray(out[key][15]),
                                      np.asarray(pool[key][src]))
    assert int(out["kv_seed"]) == int(pool["kv_seed"])


def test_cow_reorder_matches_physical_gather():
    """Random beam-parent chains: after every reorder+write, the table view
    must equal a physically gathered dense cache."""
    S = 4
    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    k0, v0 = _rand(8, ROWS, S, H, DH), _rand(9, ROWS, S, H, DH)
    cache = paged_prefill_write(cache, k0, v0, page=PAGE)
    # dense mirror [rows, L, H, dh]
    kd = jnp.zeros((ROWS, L, H, DH)).at[:, :S].set(k0)
    vd = jnp.zeros((ROWS, L, H, DH)).at[:, :S].set(v0)
    rng = np.random.default_rng(0)
    for t in range(S, L):
        parent = jnp.asarray(rng.integers(0, ROWS, ROWS), jnp.int32)
        cache = paged_reorder(cache, parent, t, page=PAGE)
        kd, vd = kd[parent], vd[parent]
        k1, v1 = _rand(20 + t, ROWS, 1, H, DH), _rand(40 + t, ROWS, 1, H, DH)
        cache = paged_decode_write(cache, k1, v1, t, page=PAGE)
        kd = kd.at[:, t].set(k1[:, 0])
        vd = vd.at[:, t].set(v1[:, 0])
        kp, vp = _gather_pages(cache)
        np.testing.assert_allclose(np.asarray(kp[:, :, : t + 1]),
                                   np.asarray(kd[:, : t + 1].transpose(0, 2, 1, 3)),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vp[:, :, : t + 1]),
                                   np.asarray(vd[:, : t + 1].transpose(0, 2, 1, 3)),
                                   rtol=1e-6, atol=1e-6)
        # attention over the live pages agrees with the dense oracle
        q = _rand(60 + t, ROWS, H, DH)
        npl = t // PAGE + 1
        out = _paged_attention_ref(q, cache, t, npl, page=PAGE)
        exp = _dense_attention(q, kd.transpose(0, 2, 1, 3),
                               vd.transpose(0, 2, 1, 3), t)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)


def test_reorder_under_jit_scan():
    """The CoW ops must be jit/scan-compatible (static shapes, dynamic pos)."""
    S = 4
    cache = paged_cache_init(ROWS, L, H, DH, jnp.float32, page=PAGE)
    cache = paged_prefill_write(cache, _rand(70, ROWS, S, H, DH),
                                _rand(71, ROWS, S, H, DH), page=PAGE)

    def body(t, cache):
        parent = (jnp.arange(ROWS, dtype=jnp.int32) + t) % ROWS
        cache = paged_reorder(cache, parent, t, page=PAGE)
        k1 = jnp.full((ROWS, 1, H, DH), 1.0 * t)
        return paged_decode_write(cache, k1, k1, t, page=PAGE)

    out = jax.jit(lambda c: jax.lax.fori_loop(S, L, body, c))(cache)
    kd, _ = _gather_pages(out)
    np.testing.assert_allclose(np.asarray(kd[:, :, L - 1]),
                               np.full((ROWS, H, DH), float(L - 1)))


def test_num_pages():
    assert num_pages(256, 64) == 4
    assert num_pages(257, 64) == 5
    assert num_pages(64, 64) == 1


# ---------------------------------------------------------------------------
# End-to-end: paged greedy/beam == dense cached path, token-identical (f32).
# PAGE is shrunk to 4 so the 16-token stream spans 4 segments — the paged
# loops, live_pages contexts, CoW reorder, and multi-segment compilation all
# exercised.
# ---------------------------------------------------------------------------


@pytest.fixture
def small_pages(monkeypatch):
    import ddlbench_tpu.ops.paged_decode as pd

    monkeypatch.setattr(pd, "PAGE", 4)


@pytest.fixture(scope="module")
def mt_model():
    import ddlbench_tpu.models.seq2seq as s2s
    from ddlbench_tpu.models.layers import init_model

    s2s._VARIANTS.setdefault("seq2seq_t",
                             dict(d_model=32, n_layers=2, n_heads=4))
    model = s2s.build_seq2seq("seq2seq_t", (16,), 64, 8,
                              attention_backend="xla")
    params, state, _ = init_model(model, jax.random.key(0))
    return model, params, state


@pytest.mark.slow
def test_paged_greedy_token_identical(mt_model, small_pages):
    import ddlbench_tpu.models.decode as dec

    model, params, state = mt_model
    assert dec.supports_paged(model)
    src = jax.random.randint(jax.random.key(4), (3, 8), 0, 64, jnp.int32)
    ref = dec.greedy_decode(model, params, state, src, 16)
    got = dec.greedy_decode(model, params, state, src, 16, paged=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.slow
def test_paged_beam_token_identical(mt_model, small_pages):
    import ddlbench_tpu.models.decode as dec

    model, params, state = mt_model
    src = jax.random.randint(jax.random.key(5), (2, 8), 0, 64, jnp.int32)
    ref_x, ref_s = dec.beam_search_decode(model, params, state, src, 16,
                                          beam=3)
    got_x, got_s = dec.beam_search_decode(model, params, state, src, 16,
                                          beam=3, paged=True)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(ref_x))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_paged_rejects_unsupported(small_pages):
    import ddlbench_tpu.models.decode as dec
    from ddlbench_tpu.models.lstm import build_lstm_seq2seq

    model = build_lstm_seq2seq("seq2seq_lstm_t", (16,), 64, 8)
    assert not dec.supports_paged(model)


@pytest.mark.slow
def test_paged_causal_lm_greedy_token_identical(small_pages):
    """Causal LMs (plain transformer blocks) share the paged protocol."""
    import ddlbench_tpu.models.decode as dec
    from ddlbench_tpu.models.layers import init_model
    from ddlbench_tpu.models.transformer import _VARIANTS, build_transformer

    _VARIANTS.setdefault("transformer_t",
                         dict(d_model=32, n_layers=2, n_heads=4))
    model = build_transformer("transformer_t", (16,), 64,
                              attention_backend="xla")
    params, state, _ = init_model(model, jax.random.key(3))
    assert dec.supports_paged(model)
    src = jax.random.randint(jax.random.key(6), (2, 5), 0, 64, jnp.int32)
    ref = dec.greedy_decode(model, params, state, src, 16)
    got = dec.greedy_decode(model, params, state, src, 16, paged=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.slow
def test_paged_moe_beam_token_identical(small_pages):
    """MoE blocks carry the paged protocol too (shared attention ops +
    per-token expert FFN)."""
    import ddlbench_tpu.models.decode as dec
    import ddlbench_tpu.models.moe as moe
    from ddlbench_tpu.models.layers import init_model

    moe._VARIANTS.setdefault(
        "transformer_moe_t", dict(d_model=32, n_layers=2, n_heads=4,
                                  n_experts=4))
    model = moe.build_transformer_moe("transformer_moe_t", (16,), 64,
                                      capacity_factor=8.0,
                                      attention_backend="xla")
    params, state, _ = init_model(model, jax.random.key(5))
    assert dec.supports_paged(model)
    src = jax.random.randint(jax.random.key(7), (2, 5), 0, 64, jnp.int32)
    ref_x, _ = dec.beam_search_decode(model, params, state, src, 16,
                                      beam=2)
    got_x, _ = dec.beam_search_decode(model, params, state, src, 16,
                                      beam=2, paged=True)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(ref_x))
