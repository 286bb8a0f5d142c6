"""Paged KV cache + single-query flash-decode kernel (beam inference fast path).

Profiling target (VERDICT r3 next #6): KV-cached beam-4 decode measured
3.2k tok/s — the weakest on-chip number. The dominant traffic is structural:
``beam_search_decode`` re-gathers EVERY layer's full [rows, H, max_len, dh]
K/V cache to follow the parent beam at every token (models/decode.py
``gather_caches``), and the attention einsum then reads the full masked
max_len even when only t positions are live. For the decodebench
configuration (seq2seq_s: 8 layers, rows=32, L=256, f32) the permutation
alone moves ~536 MB per token — read AND write — before any compute.

The paged design eliminates that:

* The cache is a POOL of fixed-size pages ([rows * n_pages, page, H, dh])
  plus a tiny int32 page TABLE per row. Every row owns one private slot per
  page index; completed pages are immutable (positions only grow), so a beam
  reorder copies POINTERS for completed pages and physically copies only the
  one partial page per row (``paged_reorder`` — copy-on-write). Per-token
  reorder traffic drops from O(rows * L) to O(rows * page).
* Attention walks only the LIVE pages through the table — the Pallas kernel
  (``paged_attention``) scalar-prefetches the table, DMAs each page block
  directly from the pool (no gathered copy in HBM), and accumulates an
  online softmax across pages, FlashAttention-style with a page-granular
  grid. The jnp reference path (``_paged_attention_ref``) materializes the
  gathered pages and is used on CPU and as the numerics oracle.

The lineage is vLLM's PagedAttention (Kwon et al., SOSP'23 — the serving
engine that introduced page tables for KV caches; not among the training
papers in PAPERS.md): here the copy-on-write table doubles as the
beam-search ancestry structure, which is what removes the reference-style
cache reshuffle (GNMT reorders its recurrent decoder state per expansion —
SURVEY.md §2 C13; the transformer analog is the cache gather this module
deletes). The serving half of that lineage — a SHARED pool whose slots are
free-list-allocated per request instead of statically owned per row — is
the ``serve_*``/``paged_table_*`` primitives below, driven by the
continuous-batching engine in ``serve/engine.py``.

The page count walked per step must be static under jit: callers run the
decode loop in SEGMENTS of one page (models/decode.py paged loops), so each
segment's kernel compiles with ``num_pages = p + 1``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ddlbench_tpu.ops.util import pallas_out_struct as _out_struct

NEG_INF = -1e30

# Positions per page; 64 * H * dh blocks DMA efficiently. Module-level so
# tests can shrink it (every entry point resolves the default at CALL time).
PAGE = 64


class live_pages:
    """Trace-time marker for how many pages are live in the current decode
    segment (the static page count the kernel grid needs). The paged decode
    loops (models/decode.py) trace each one-page segment's body under
    ``with live_pages(p + 1):``; attention layers read ``current()`` at
    trace time. Same idiom as models/layers.axis_context."""

    _stack: list = []

    def __init__(self, n: int):
        self.n = int(n)

    def __enter__(self):
        live_pages._stack.append(self.n)
        return self

    def __exit__(self, *exc):
        live_pages._stack.pop()
        return False

    @staticmethod
    def current():
        if not live_pages._stack:
            raise RuntimeError(
                "paged attention decode traced outside a live_pages(...) "
                "segment — use the paged loops in models/decode.py")
        return live_pages._stack[-1]


def num_pages(total_len: int, page: int | None = None) -> int:
    page = page or PAGE
    return -(-total_len // page)


def paged_cache_init(rows: int, total_len: int, n_heads: int, dh: int,
                     dtype, page: int | None = None):
    """Cache dict: pool_k/pool_v [rows*n_pages, page, H, dh] + table.

    ``table[r, q]`` is the pool slot holding row r's K/V for positions
    [q*page, (q+1)*page). Initially every row points at its own private
    slots (slot r*n_pages + q). Invariant maintained by ``paged_reorder``:
    entries for the current and future pages always point at the row's OWN
    slot, so decode writes never collide across rows.
    """
    page = page or PAGE
    npg = num_pages(total_len, page)
    shape = (rows * npg, page, n_heads, dh)
    own = (jnp.arange(rows, dtype=jnp.int32)[:, None] * npg
           + jnp.arange(npg, dtype=jnp.int32)[None, :])
    # NOTE: ``page`` is deliberately NOT in the dict — the cache is a traced
    # pytree in decode-loop carries, and the kernel's BlockSpecs need the
    # page size static. Callers pass it explicitly (layer closures carry it).
    return {
        "pool_k": jnp.zeros(shape, dtype),
        "pool_v": jnp.zeros(shape, dtype),
        "table": own,
    }


def _own_table(rows: int, npg: int) -> jax.Array:
    return (jnp.arange(rows, dtype=jnp.int32)[:, None] * npg
            + jnp.arange(npg, dtype=jnp.int32)[None, :])


def _pool5d(pool, rows: int):
    n, page, H, dh = pool.shape
    return pool.reshape(rows, n // rows, page, H, dh)


def paged_prefill_write(cache, k, v, page: int | None = None, start: int = 0):
    """Write a prompt chunk's K/V [rows, S, H, dh] at positions
    [start, start+S) into each row's own pages.

    ``start`` is static (a Python int): long-context serving chunks the
    prompt, calling this once per chunk. ``start == 0`` (the whole-prompt
    case) takes a dense reshape path; a later chunk — which may begin at a
    page-unaligned position inside a partially-filled page — scatters by
    (page, offset) index so existing positions in that page are preserved.
    """
    page = page or PAGE
    start = int(start)
    rows, S, H, dh = k.shape
    capacity = cache["table"].shape[1] * page
    # .at[...].set scatters with out-of-bounds indices silently dropped /
    # clamped, so a chunk running past the pool would truncate KV history
    # with no error (advisor r5) — reject it at trace time instead.
    assert start + S <= capacity, (
        f"prefill chunk [{start}, {start + S}) exceeds the paged cache "
        f"capacity {capacity} ({cache['table'].shape[1]} pages x {page}); "
        f"allocate the cache for the full prompt before chunked prefill")

    if start == 0:
        npg_s = num_pages(S, page)
        pad = npg_s * page - S

        def write(pool, x):
            p5 = _pool5d(pool, rows)
            xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            x5 = xp.reshape(rows, npg_s, page, H, dh).astype(pool.dtype)
            return p5.at[:, :npg_s].set(x5).reshape(pool.shape)
    else:
        pos = start + jnp.arange(S, dtype=jnp.int32)
        pg, off = pos // page, pos % page

        def write(pool, x):
            p5 = _pool5d(pool, rows)
            return p5.at[:, pg, off].set(x.astype(pool.dtype)).reshape(
                pool.shape)

    return {**cache, "pool_k": write(cache["pool_k"], k),
            "pool_v": write(cache["pool_v"], v)}


def paged_decode_write(cache, k1, v1, pos, page: int | None = None):
    """Write one token's K/V [rows, 1, H, dh] at dynamic position pos into
    each row's own slot for the current page."""
    page = page or PAGE
    rows = cache["table"].shape[0]
    p, off = pos // page, pos % page

    def write(pool, x):
        p5 = _pool5d(pool, rows)
        blk = x.astype(pool.dtype)[:, None]  # [rows, 1(page), 1(pos), H, dh]
        return lax.dynamic_update_slice(
            p5, blk, (0, p, off, 0, 0)).reshape(pool.shape)

    return {**cache, "pool_k": write(cache["pool_k"], k1),
            "pool_v": write(cache["pool_v"], v1)}


def paged_reorder(cache, parent, pos, page: int | None = None):
    """Copy-on-write beam reorder BEFORE decoding position pos.

    ``parent[r]`` = the row whose history row r continues. Completed pages
    (< pos // page) are pointer-copied through the table; the current page
    is physically copied from the parent's slot into r's own slot iff it is
    partially filled (pos % page > 0). Current-and-future table entries stay
    owned, preserving the write invariant.
    """
    page = page or PAGE
    rows, npg = cache["table"].shape
    p, off = pos // page, pos % page
    own = _own_table(rows, npg)
    page_idx = jnp.arange(npg, dtype=jnp.int32)[None, :]
    table = jnp.where(page_idx >= p, own, cache["table"][parent])

    def copy_partial(pool):
        src_slot = cache["table"][parent, p]  # parent owns its partial page
        blk = pool[src_slot][:, None]  # [rows, 1, page, H, dh]
        p5 = _pool5d(pool, rows)
        return lax.dynamic_update_slice(
            p5, blk, (0, p, 0, 0, 0)).reshape(pool.shape)

    def no_copy(pool):
        return pool

    pool_k, pool_v = lax.cond(
        off > 0,
        lambda: (copy_partial(cache["pool_k"]), copy_partial(cache["pool_v"])),
        lambda: (cache["pool_k"], cache["pool_v"]),
    )
    return {**cache, "pool_k": pool_k, "pool_v": pool_v, "table": table}


# ---------------------------------------------------------------------------
# Attention over the live pages.
# ---------------------------------------------------------------------------


def _gather_dequant(cache, name: str, tbl, dtype):
    """Gather the live pages of ``pool_k``/``pool_v`` through the table
    and return them in ``dtype`` — dequantizing an int8 pool with its
    per-page scale sidecar (q.astype(f32) * scale per position row; the
    fused Pallas kernels apply the same scale to the position's score and
    probability inside the online-softmax walk instead)."""
    pages = cache[name][tbl]  # [rows, np, page, H, dh]
    if pool_quantized(cache):
        scale = cache["scale_" + name[-1]][tbl]  # [rows, np, page]
        return (pages.astype(jnp.float32)
                * scale[..., None, None]).astype(dtype)
    return pages.astype(dtype)


def _paged_attention_ref(q, cache, pos, npages_live: int,
                         page: int | None = None):
    """jnp oracle: gather the live pages, mask, softmax. [rows, H, dh].

    ``pos`` is a scalar (every row at the same position — the beam/greedy
    decode loops) or a per-row [rows] vector (the continuous-batching
    serving engine, where every row is a different request at its own
    stream position).
    """
    page = page or PAGE
    rows, H, dh = q.shape
    tbl = cache["table"][:, :npages_live]  # [rows, np]
    kc = _gather_dequant(cache, "pool_k", tbl, q.dtype)
    vc = _gather_dequant(cache, "pool_v", tbl, q.dtype)
    L = npages_live * page
    kc = kc.reshape(rows, L, H, dh)
    vc = vc.reshape(rows, L, H, dh)
    scores = jnp.einsum("rhd,rkhd->rhk", q, kc) / math.sqrt(dh)
    k_pos = jnp.arange(L)[None, None, :]
    pos = jnp.asarray(pos)
    posb = pos[:, None, None] if pos.ndim == 1 else pos
    scores = jnp.where(k_pos <= posb, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("rhk,rkhd->rhd", probs, vc)


# The chunk kernel's broadcast products ([H, C, page, dh] f32) live in
# scoped VMEM (16 MiB on v5e). Mosaic for v5e (libtpu 0.0.34) compiled every
# pool dtype up to 3 MiB of product (H=12, C=page=32, dh=64) and ran out of
# VMEM at 8 MiB (H=8, C=page=64, dh=64, int8), so wider chunks are refused
# here by name — on a TPU backend nothing falls back to the jnp reference.
CHUNK_PRODUCT_MAX_BYTES = 4 * 1024 * 1024


def _require_chunk_fits_vmem(H: int, C: int, page: int, dh: int) -> None:
    product = 4 * H * C * page * dh
    if product > CHUNK_PRODUCT_MAX_BYTES:
        raise ValueError(
            f"paged_chunk_attention: chunk of {C} queries x page {page} "
            f"(H={H}, dh={dh}) needs a {product >> 20} MiB score product in "
            f"VMEM, over the {CHUNK_PRODUCT_MAX_BYTES >> 20} MiB the Pallas "
            f"TPU kernel compiles with; use a smaller prefill chunk or page")


def _scale_blocks(cache):
    """int8 pools: the per-position scale sidecars as [n_pages, 1, page]
    operands whose (1, 1, page) block rides its page's DMA — the trailing
    two block dims equal the array's, which is what Mosaic's (8, 128)
    block rule wants of a one-row block."""
    return [cache["scale_k"][:, None, :], cache["scale_v"][:, None, :]]


def _paged_attn_kernel(table_ref, t_ref, q_ref, pk_ref, pv_ref, *refs,
                       scale, page, npages, quantized=False):
    # quantized pools carry two extra per-page scale blocks; dequant is
    # FUSED here — the per-position scale multiplies the score / the
    # probability of its key position, so the f32 pool is never
    # materialized and the int8 page is what rides the DMA
    if quantized:
        sk_ref, sv_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        o_ref, m_sc, l_sc, acc_sc = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[:] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)  # [H, dh]
    k = pk_ref[0].astype(jnp.float32)  # [page, H, dh]
    v = pv_ref[0].astype(jnp.float32)
    # Broadcast/multiply/reduce, not dot_general: the heads are a batch
    # dimension sitting at position 1 of the [page, H, dh] page block, and
    # Mosaic takes batch dimensions leading only.
    # s[h, p] = sum_d q[h, d] * k[p, h, d]
    s = jnp.sum(q[None, :, :] * k, axis=2).T * scale  # [H, page]
    if quantized:
        s = s * sk_ref[0]  # [1, page] per-position K scales
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # t is per-row: the decode loops broadcast one scalar position to every
    # row; the serving engine hands each row its own stream position.
    s = jnp.where(k_pos <= t_ref[pl.program_id(0)], s, NEG_INF)

    m_prev, l_prev, acc_prev = m_sc[:], l_sc[:], acc_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p_blk = jnp.exp(s - m_new)  # [H, page]
    l_new = alpha * l_prev + jnp.sum(p_blk, axis=1, keepdims=True)
    pw = p_blk * sv_ref[0] if quantized else p_blk
    # pv[h, d] = sum_p p[h, p] * v[p, h, d]
    pv = jnp.sum(pw.T[:, :, None] * v, axis=0)  # [H, dh]
    m_sc[:], l_sc[:] = m_new, l_new
    acc_sc[:] = acc_prev * alpha + pv

    @pl.when(j == npages - 1)
    def _fini():
        l_safe = jnp.maximum(l_sc[:], 1e-20)
        o_ref[0, 0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)


def paged_attention(q, cache, pos, npages_live: int, page: int | None = None,
                    interpret: bool = False, use_kernel: bool | None = None):
    """Single-query attention of q [rows, H, dh] against the live pages.

    ``npages_live`` must be static (callers segment the decode loop by
    page); ``pos`` is the dynamic query position (mask: key pos <= pos),
    either a scalar (all rows at one position) or a per-row [rows] vector
    (continuous-batching serving). ``use_kernel=None`` picks the Pallas
    kernel on TPU, the jnp reference elsewhere.
    """
    from ddlbench_tpu.distributed import is_tpu_backend

    page = page or PAGE
    if use_kernel is None:
        use_kernel = is_tpu_backend()
    if not (use_kernel or interpret):
        return _paged_attention_ref(q, cache, pos, npages_live, page)
    from jax.experimental.pallas import tpu as pltpu

    rows, H, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    tbl = cache["table"][:, :npages_live]
    t32 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (rows,))
    quantized = pool_quantized(cache)

    page_spec = pl.BlockSpec((1, page, H, dh),
                             lambda r, j, tab, t: (tab[r, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, H, dh), lambda r, j, tab, t: (r, 0, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [tbl, t32, q[:, None], cache["pool_k"], cache["pool_v"]]
    if quantized:  # per-page scale sidecar rows ride their page's block
        scale_spec = pl.BlockSpec((1, 1, page),
                                  lambda r, j, tab, t: (tab[r, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += _scale_blocks(cache)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # table, t
        grid=(rows, npages_live),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, H, dh),
                               lambda r, j, tab, t: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, scale=scale, page=page, npages=npages_live,
            quantized=quantized),
        grid_spec=grid_spec,
        out_shape=_out_struct((rows, 1, H, dh), q.dtype, *operands),
        interpret=interpret,
        name="paged_decode_attn",
    )(*operands)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Shared-pool (serving) primitives. The beam structures above give every row
# a statically OWNED stripe of the pool; a serving engine instead allocates
# pool slots per request from a free list (serve/allocator.py), so rows
# borrow arbitrary slots and every access goes THROUGH the table. The cache
# dict shape is the same ({pool_k, pool_v, table}) — only the pool's leading
# dim is the total page budget rather than rows * n_pages — so
# ``paged_attention`` (and its Pallas kernel) reads a serving cache
# unchanged. Pool slot 0 is reserved as the SCRATCH page by convention:
# inactive rows' table entries point at it, so their masked writes land
# somewhere harmless instead of clobbering a live request's history.
#
# Write/refcount contract under CROSS-REQUEST PREFIX SHARING
# (serve/prefix.py): a slot may appear in MULTIPLE table rows at once —
# refcounted by the host allocator — and a shared slot is IMMUTABLE: the
# engine only ever binds fully-prefilled prompt pages (positions the
# request never writes again, since positions only grow), and any path
# that would write into a bound page (the full-hit fast path re-deriving
# the last prompt position through the decode program) must
# ``serve_page_copy`` it into a private slot first. ``paged_table_write``
# / ``paged_table_chunk_write`` therefore assume the table entries they
# resolve are PRIVATE to (or scratch for) their row; keeping that true is
# the allocator's refcount discipline, not a device-side check.
# ---------------------------------------------------------------------------

SCRATCH_SLOT = 0

# int8 KV pages (EQuARX-lite at the page-write boundary, PAPERS.md
# 2506.17615 — the PR 6 gradient-wire machinery applied to the serving
# pool). A quantized pool stores pool_k/pool_v as int8 plus a SCALE
# SIDECAR ``scale_k``/``scale_v`` [n_pages, page] f32 — one absmax/127
# scale per written position ROW of each page, stored page-structured so
# a page's scales travel with it verbatim through ``serve_page_copy`` and
# the prefix-cache bind path, and so incremental decode writes never
# requantize resident tokens (requant noise would otherwise accumulate
# every step). Rounding is unbiased stochastic
# (parallel/common.stochastic_round_int8 math) with COUNTER-BASED keys —
# fold(kv_seed, k/v tag, stream position) — so the quantized bytes of a
# position are a pure function of its values and its stream position:
# runs replay bitwise, and eviction/recompute regenerates identical
# pages. Dequantization is FUSED into the attention kernels/references
# (scale applied per page row inside the online-softmax walk — an f32
# pool is never materialized). The sidecar costs 8 bytes per position
# per layer (<2% of payload at H*dh >= 256) and is excluded from the
# ``bytes_per_page`` payload accounting (documented in ARCHITECTURE.md).

KV_QMAX = 127.0


def pool_quantized(cache_or_pool) -> bool:
    """True for an int8 serve pool (the scale sidecar is the marker)."""
    return "scale_k" in cache_or_pool


def pool_page_bytes(pool, page_axis: int = 0) -> int:
    """K/V payload bytes per page slot of ``pool`` (scale sidecars and
    the ``kv_seed`` scalar excluded — the ``bytes_per_page``
    convention). ``page_axis=1`` is the tp-stacked [tp, pages, ...]
    layout, whose per-slot bytes sum over shards to exactly the
    single-chip full-width page. An int8 pool reports exactly f32/4 —
    the invariant the handoff wire accounting (serve/handoff.py)
    inherits, since a ship is verbatim rows of this pool."""
    total = 0
    for name in ("pool_k", "pool_v"):
        arr = pool[name]
        total += int(arr.dtype.itemsize * math.prod(arr.shape)
                     // arr.shape[page_axis])
    return total


def pool_checksum_keys(pool) -> tuple:
    """Keys of ``pool`` covered by the SDC checksum ledger
    (serve/integrity.py): every per-slot array the three table-write
    primitives scatter — payload rows plus the quantized scale sidecars
    — in sorted order (the deterministic CRC chain order). The 0-dim
    ``kv_seed`` scalar is excluded: it is not per-slot state and no
    write primitive touches it."""
    return tuple(sorted(
        k for k, v in pool.items() if getattr(v, "ndim", 0)))


def serve_pool_init(n_pages: int, page: int, n_heads: int, dh: int, dtype):
    """A shared K/V pool of ``n_pages`` free-list-managed slots (slot 0 is
    the scratch page — serve/allocator.py never hands it out). ``dtype``
    int8 builds the QUANTIZED layout: int8 payload + the per-page scale
    sidecar (zeros: an unwritten position dequantizes to exactly 0, same
    as the f32 zero init)."""
    shape = (n_pages, page, n_heads, dh)
    pool = {"pool_k": jnp.zeros(shape, dtype),
            "pool_v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        pool["scale_k"] = jnp.zeros((n_pages, page), jnp.float32)
        pool["scale_v"] = jnp.zeros((n_pages, page), jnp.float32)
    return pool


def _kv_quantize(x, pos, kv_seed, tag: int):
    """Quantize K or V rows ``x`` [..., H, dh] (one leading axis per
    position) to (q int8 same shape, scale f32 [...]).

    Per-position absmax scale (the largest element maps to exactly
    +-127), unbiased stochastic rounding with a counter-based key
    ``fold(fold(PRNGKey(kv_seed), tag), position)`` — ``pos`` carries the
    absolute stream position of every row of x (same leading shape), so
    the quantized bytes depend only on (values, layer seed, k/v tag,
    position): recompute and prefix-cache re-derivations replay bitwise.
    """
    lead = x.shape[:-2]
    absmax = jnp.max(jnp.abs(x).astype(jnp.float32), axis=(-2, -1))
    scale = jnp.where(absmax > 0, absmax / KV_QMAX, jnp.float32(1.0))
    v = x.astype(jnp.float32) / scale[..., None, None]

    base = jax.random.fold_in(jax.random.PRNGKey(kv_seed), tag)

    def u_for(p):
        return jax.random.uniform(jax.random.fold_in(base, p),
                                  x.shape[-2:], jnp.float32)

    u = jax.vmap(u_for)(pos.reshape(-1)).reshape(x.shape)
    lo = jnp.floor(v)
    q = lo + (u < (v - lo)).astype(jnp.float32)
    return jnp.clip(q, -KV_QMAX, KV_QMAX).astype(jnp.int8), scale


def _pool_write(cache, k, v, pos, write_payload, write_scale):
    """Shared quantize-or-passthrough dispatch for the three table-write
    primitives: ``write_payload(pool, x)`` scatters value rows,
    ``write_scale(scales, s)`` scatters the matching scale rows (only
    called on a quantized pool). ``pos`` is the per-row absolute position
    tensor matching x's leading shape."""
    out = dict(cache)
    if pool_quantized(cache):
        seed = cache.get("kv_seed", 0)
        qk, sk = _kv_quantize(k, pos, seed, 0)
        qv, sv = _kv_quantize(v, pos, seed, 1)
        out["pool_k"] = write_payload(cache["pool_k"], qk)
        out["pool_v"] = write_payload(cache["pool_v"], qv)
        out["scale_k"] = write_scale(cache["scale_k"], sk)
        out["scale_v"] = write_scale(cache["scale_v"], sv)
    else:
        out["pool_k"] = write_payload(cache["pool_k"], k)
        out["pool_v"] = write_payload(cache["pool_v"], v)
    return out


def paged_table_write(cache, k1, v1, pos, page: int | None = None):
    """Write one token's K/V [rows, 1, H, dh] at per-row positions ``pos``
    ([rows] int32, or a scalar) through the TABLE: row r's token lands in
    pool slot ``table[r, pos_r // page]`` at offset ``pos_r % page``.
    Rows whose table row points at the scratch slot write garbage there
    harmlessly (the serving engine masks inactive rows this way). On a
    quantized pool the token quantizes at the write boundary and its
    scale lands in the page's sidecar row."""
    page = page or PAGE
    rows = cache["table"].shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (rows,))
    slots = jnp.take_along_axis(
        cache["table"], (pos // page)[:, None], axis=1)[:, 0]
    off = pos % page

    def write(pool, x):
        return pool.at[slots, off].set(x[:, 0].astype(pool.dtype))

    def write_scale(scales, s):
        return scales.at[slots, off].set(s[:, 0])

    return _pool_write(cache, k1, v1, pos[:, None], write, write_scale)


def paged_table_chunk_write(cache, k, v, start, page: int | None = None):
    """Write a prefill chunk's K/V [rows, C, H, dh] at positions
    [start, start + C) through the table. ``start`` may be a traced scalar
    but MUST be page-aligned and C a page multiple (the serving engine
    prefills in page-aligned chunks, padding the last one — padded
    positions are either overwritten by decode before any query can attend
    them, or land on un-allocated table entries, i.e. the scratch slot)."""
    page = page or PAGE
    rows, C, H, dh = k.shape
    assert C % page == 0, (
        f"chunk length {C} must be a multiple of the page size {page}")
    npg_c = C // page
    # scratch-extend the table before slicing: a multi-page chunk whose
    # padded tail runs past the last table column would otherwise be
    # CLAMPED by dynamic_slice onto earlier (live) pages of the same row,
    # silently corrupting the request's own KV history — with the pad,
    # overflow pages resolve to the scratch slot and the padded writes
    # land there harmlessly
    tbl = jnp.pad(cache["table"], ((0, 0), (0, npg_c)),
                  constant_values=SCRATCH_SLOT)
    slots = lax.dynamic_slice_in_dim(
        tbl, start // page, npg_c, axis=1)  # [rows, npg_c]

    def write(pool, x):
        x5 = x.reshape(rows, npg_c, page, H, dh).astype(pool.dtype)
        return pool.at[slots].set(x5)

    def write_scale(scales, s):
        return scales.at[slots].set(s.reshape(rows, npg_c, page))

    pos = (jnp.asarray(start, jnp.int32)
           + jnp.arange(C, dtype=jnp.int32))[None, :]  # [1, C] -> broadcast
    return _pool_write(cache, k, v, jnp.broadcast_to(pos, (rows, C)),
                       write, write_scale)


def paged_table_span_write(cache, k, v, pos0, page: int | None = None):
    """Write a SPAN of W tokens' K/V [rows, W, H, dh] at per-row positions
    [pos0_r, pos0_r + W) through the table — page-UNALIGNED, the write
    shape of the speculative-decoding verify pass (the pending token plus
    the drafts start mid-page). Each position scatters independently by
    (page, offset); positions whose page index runs past the table's
    columns resolve to the scratch slot, so a row's padded draft tail
    lands harmlessly exactly like the chunk write's padded tail."""
    page = page or PAGE
    rows, W, H, dh = k.shape
    npg = cache["table"].shape[1]
    pos = (jnp.asarray(pos0, jnp.int32).reshape(-1)[:, None]
           + jnp.arange(W, dtype=jnp.int32)[None, :])  # [rows, W]
    pg, off = pos // page, pos % page
    slots = jnp.take_along_axis(cache["table"],
                                jnp.clip(pg, 0, npg - 1), axis=1)
    slots = jnp.where(pg < npg, slots, SCRATCH_SLOT)

    def write(pool, x):
        return pool.at[slots, off].set(x.astype(pool.dtype))

    def write_scale(scales, s):
        return scales.at[slots, off].set(s)

    return _pool_write(cache, k, v, pos, write, write_scale)


def serve_page_copy(pool, src, dst):
    """Copy-on-write: physically copy pool slot ``src`` into slot ``dst``
    ({pool_k, pool_v} or any same-shaped pool dict; ``src``/``dst`` may be
    traced scalars, so ONE compiled program serves every copy). On a
    quantized pool the page's scale sidecar rows copy verbatim with the
    payload — a copied page dequantizes bit-identically to its source —
    and scalar entries (the layer's ``kv_seed``) pass through untouched.

    This is the serving analog of ``paged_reorder``'s partial-page copy:
    the prefix cache binds immutable shared pages into a new request's
    table row, and before the engine ever writes INTO a shared page (the
    full-hit fast path re-derives the last prompt position's K/V through
    the decode program) it must copy the page into a private slot — the
    two token streams would otherwise couple through last-ulp drift
    between the chunked and single-token K/V computations."""
    return {k: (v.at[dst].set(v[src]) if jnp.ndim(v) else v)
            for k, v in pool.items()}


def _paged_chunk_attention_ref(q, cache, start, npages_live: int,
                               page: int | None = None):
    """jnp/XLA oracle for chunk-prefill attention: gather the live pages,
    mask causally at absolute positions, softmax. [rows, H, C, dh].
    Serving prefill chunks are ordinary dense attention over a gathered
    [rows, L, H, dh] view, which XLA fuses well — this is the CPU path
    and the numerics reference the Pallas kernel is pinned against."""
    page = page or PAGE
    rows, H, C, dh = q.shape
    tbl = cache["table"][:, :npages_live]
    L = npages_live * page
    kc = (_gather_dequant(cache, "pool_k", tbl, q.dtype)
          .reshape(rows, L, H, dh).transpose(0, 2, 1, 3))  # [rows, H, L, dh]
    vc = (_gather_dequant(cache, "pool_v", tbl, q.dtype)
          .reshape(rows, L, H, dh).transpose(0, 2, 1, 3))
    scores = jnp.einsum("rhqd,rhkd->rhqk", q, kc) / math.sqrt(dh)
    start = jnp.asarray(start, jnp.int32).reshape(-1)  # scalar or [rows]
    q_pos = start[:, None] + jnp.arange(C)[None, :]  # [rows or 1, C]
    k_pos = jnp.arange(L)
    ok = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
    scores = jnp.where(ok, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("rhqk,rhkd->rhqd", probs, vc)


def _paged_chunk_attn_kernel(table_ref, s_ref, q_ref, pk_ref, pv_ref, *refs,
                             scale, page, npages, quantized=False):
    """Multi-query analog of ``_paged_attn_kernel``: one grid step attends
    ALL C chunk queries of row r against one live page j, accumulating an
    online softmax per (head, query). The causal mask is absolute — query
    c sits at stream position ``start_r + c`` (``s_ref`` is the per-row
    chunk start the scheduler prefetches) — so within-chunk causality and
    full visibility of earlier pages fall out of one comparison. A
    quantized pool's per-page scale blocks dequantize the page in-kernel,
    exactly like the flash-decode variant."""
    if quantized:
        sk_ref, sv_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        o_ref, m_sc, l_sc, acc_sc = refs
    r, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[:] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32)  # [H, C, dh]
    kt = pk_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # [H, page, dh]
    vt = pv_ref[0].astype(jnp.float32).transpose(1, 0, 2)
    # s[h, c, p] = sum_d q[h, c, d] * k[p, h, d]
    s = jnp.sum(q[:, :, None, :] * kt[:, None, :, :],
                axis=3) * scale  # [H, C, page]
    if quantized:
        s = s * sk_ref[0][None]  # [1, 1, page] per-position K scales
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    q_pos = s_ref[r] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_sc[:], l_sc[:], acc_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))  # [H, C]
    alpha = jnp.exp(m_prev - m_new)
    p_blk = jnp.exp(s - m_new[:, :, None])  # [H, C, page]
    l_new = alpha * l_prev + jnp.sum(p_blk, axis=2)
    pw = p_blk * sv_ref[0][None] if quantized else p_blk
    # pv[h, c, d] = sum_p p[h, c, p] * v[p, h, d]
    pv = jnp.sum(pw[:, :, :, None] * vt[:, None, :, :], axis=2)  # [H, C, dh]
    m_sc[:], l_sc[:] = m_new, l_new
    acc_sc[:] = acc_prev * alpha[:, :, None] + pv

    @pl.when(j == npages - 1)
    def _fini():
        l_safe = jnp.maximum(l_sc[:], 1e-20)
        o_ref[0] = (acc_sc[:] / l_safe[:, :, None]).astype(o_ref.dtype)


def paged_chunk_attention(q, cache, start, npages_live: int,
                          page: int | None = None, interpret: bool = False,
                          use_kernel: bool | None = None):
    """Causal attention of chunk queries q [rows, H, C, dh] at absolute
    positions ``start + [0, C)`` against the live pages (which must already
    contain the chunk's own K/V — write first, then attend, exactly like
    the single-token path). ``start`` is a dynamic scalar or a per-row
    [rows] vector (each serving row is its own request at its own chunk
    start). ``use_kernel=None`` picks the Pallas kernel on TPU — the
    multi-query analog of the flash-decode kernel, replacing the
    gathered-page XLA einsum on the chunk-prefill hot path — and the jnp
    reference elsewhere; on TPU a shape the kernel cannot take is an
    error, never a quiet reference run."""
    from ddlbench_tpu.distributed import is_tpu_backend

    page = page or PAGE
    if use_kernel is None:
        use_kernel = is_tpu_backend()
    if not (use_kernel or interpret):
        return _paged_chunk_attention_ref(q, cache, start, npages_live, page)

    from jax.experimental.pallas import tpu as pltpu

    rows, H, C, dh = q.shape
    if not interpret:
        _require_chunk_fits_vmem(H, C, page, dh)
    scale = 1.0 / math.sqrt(dh)
    tbl = cache["table"][:, :npages_live]
    s32 = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (rows,))
    quantized = pool_quantized(cache)

    page_spec = pl.BlockSpec((1, page, H, dh),
                             lambda r, j, tab, s: (tab[r, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, H, C, dh), lambda r, j, tab, s: (r, 0, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [tbl, s32, q, cache["pool_k"], cache["pool_v"]]
    if quantized:
        scale_spec = pl.BlockSpec((1, 1, page),
                                  lambda r, j, tab, s: (tab[r, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += _scale_blocks(cache)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # table, per-row chunk start
        grid=(rows, npages_live),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, C, dh),
                               lambda r, j, tab, s: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, C), jnp.float32),
            pltpu.VMEM((H, C), jnp.float32),
            pltpu.VMEM((H, C, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_chunk_attn_kernel, scale=scale, page=page,
            npages=npages_live, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=_out_struct((rows, H, C, dh), q.dtype, *operands),
        interpret=interpret,
        name="paged_chunk_attn",
    )(*operands)
