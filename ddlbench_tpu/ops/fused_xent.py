"""Fused LM-head loss: linear projection + softmax cross-entropy without ever
materializing the full [N, V] logits tensor in HBM.

Why: on the token workloads the vocabulary is 16k to 50k wide and a step has
16k rows (config.DATASETS; gpt2-small's 50304 and kanana-2's 16128-row slice
in the benchmark's cells), so the unfused path writes logits [B*T, V] (plus an
f32 log-softmax copy and an f32 gradient) — gigabytes per step that dwarf
every activation in the model. The reference has no analog (its classifiers
top out at 1000 classes — this is the sequence-workload equivalent of
SURVEY.md §2 D2's "hot op gets a custom kernel" rule). The fusion computes,
per row chunk,

    z_c = h_c @ W          (MXU, f32 accumulation)
    lse = logsumexp(z_c);  nll = lse - z_gold;  argmax for top-1

keeping only the per-row ``lse`` (O(N)) as the backward residual; the backward
recomputes z_c blockwise and forms

    dz = go*(p - (1-s)*onehot - s/V) + gce*(p - onehot)      (masked rows: 0)
    dh_c = dz @ W^T;   dW += h_c^T @ dz

so peak memory drops from O(N*V) to O(chunk*V) and the [N, V] round-trips
through HBM disappear. Label smoothing follows parallel/common.py
cross_entropy_loss semantics (GNMT-style: loss = (1-s)*NLL - s*mean_v logp_v);
rows with label < 0 are masked (the seq2seq source segment).

Two implementations of that mathematics: a chunked-XLA scan over row chunks
(_fxent_fwd / _fxent_bwd_xla: dp / tp / fsdp's plain jits, which GSPMD
partitions, and every off-TPU run) and two Pallas kernels (second half of
this file: ``fused_xent_fwd`` and the one-pass backward
``fused_xent_dh_dw``), whose blocks are sized by what the kernels hold in
a v5e core's 128 MiB of VMEM.

Returned values are SUMS over valid rows — (objective_sum, ce_sum, correct) —
so sequence-parallel callers can psum numerators and denominators separately.
Both obj_sum and ce_sum are differentiable (they coincide when smoothing=0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ddlbench_tpu.ops.util import (RESIDENT_VMEM_BUDGET, grid_params,
                                   takes_pallas, tile_bytes,
                                   vmem_limit_bytes)
from ddlbench_tpu.ops.util import pallas_out_struct as _pl_out

from ddlbench_tpu.compat import pcast_varying as _pcast_to
from ddlbench_tpu.compat import vma_of as _vma


def _pad_rows(h, labels, chunk: int):
    N = h.shape[0]
    rem = N % chunk
    if rem:
        pad = chunk - rem
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)], 0)
        labels = jnp.concatenate(
            [labels, jnp.full((pad,), -1, labels.dtype)], 0)
    return h, labels, h.shape[0] // chunk


def _row_stats(z, labels, smoothing: float):
    """Per-row (nll, smoothed objective, correct, mask) from f32 logits z."""
    m = jnp.max(z, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
    safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(z, safe[:, None], axis=-1)[:, 0]
    mask = labels >= 0
    nll = lse - gold
    if smoothing:
        obj = lse - (1.0 - smoothing) * gold - smoothing * jnp.mean(z, axis=-1)
    else:
        obj = nll
    correct = (jnp.argmax(z, axis=-1) == labels) & mask
    return nll, obj, correct, mask, lse


def _pallas_feasible(h, w, backend: str, interpret: bool) -> bool:
    """Whether the kernels can take this head: on real TPU the vocabulary
    has to be a multiple of 128 lanes (Mosaic's lane tiling; the blocks
    themselves need not divide it), and both kernels need blocks whose
    working set fits the VMEM budget (_blocks -> None: a very wide D, whose
    [D, 128] float32 dW blocks alone pass it). auto falls back to
    chunked-XLA; a forced "pallas" backend gets a clear error instead of a
    Mosaic one."""
    if interpret:
        return True
    N, (D, V) = h.shape[0], w.shape
    isz = max(h.dtype.itemsize, w.dtype.itemsize)
    if all(_blocks(k, N, D, V, isz, False) for k in ("fwd", "bwd")):
        return True
    if backend == "pallas":
        raise ValueError(
            f"fused_linear_xent: no feasible Pallas blocking for head "
            f"[D={D}, V={V}] — the vocab has to be a multiple of 128 lanes "
            f"and every kernel's block working set must fit its VMEM budget "
            f"({RESIDENT_VMEM_BUDGET >> 20} MiB); pad the vocab or use "
            f"backend='xla'")
    return False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_linear_xent(h, w, labels, smoothing: float = 0.0,
                      row_chunk: int = 512, backend: str = "auto",
                      interpret: bool = False):
    """(objective_sum, ce_sum, correct_count) over valid rows.

    h: [N, D] hidden rows (compute dtype); w: [D, V] head weights (compute
    dtype); labels: [N] int (-1 = masked). Objective uses ``smoothing``; ce is
    the unsmoothed CE (the headline metric). Gradients flow to h and w from
    BOTH sums. ``backend``: "auto" = Pallas kernels on TPU where they
    partition safely (ops/util.takes_pallas; dp/tp/fsdp's plain jits get the
    chunked-XLA scan, which GSPMD partitions natively), chunked-XLA scan
    elsewhere; "pallas"/"xla" force one (pallas off-TPU needs interpret=True).
    """
    out, _ = _fxent_fwd(h, w, labels, smoothing, row_chunk, backend, interpret)
    return out


def _fxent_fwd(h, w, labels, smoothing: float, row_chunk: int, backend: str,
               interpret: bool):
    if (takes_pallas(backend, "pallas", h, w, labels)
            and _pallas_feasible(h, w, backend, interpret)):
        return _fxent_fwd_pallas(h, w, labels, smoothing, interpret)
    N = h.shape[0]
    chunk = min(row_chunk, N)
    hp, lp, nc = _pad_rows(h, labels, chunk)
    hcs = hp.reshape(nc, chunk, hp.shape[1])
    lcs = lp.reshape(nc, chunk)

    def body(carry, xs):
        obj_s, ce_s, corr = carry
        h_c, l_c = xs
        z = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
        nll, obj, correct, mask, lse = _row_stats(z, l_c, smoothing)
        obj_s = obj_s + jnp.sum(jnp.where(mask, obj, 0.0))
        ce_s = ce_s + jnp.sum(jnp.where(mask, nll, 0.0))
        corr = corr + jnp.sum(correct.astype(jnp.int32))
        return (obj_s, ce_s, corr), lse

    axes = set(_vma(h)) | set(_vma(w)) | set(_vma(labels))
    init = tuple(
        _pcast_to(z, axes)
        for z in (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                  jnp.zeros((), jnp.int32))
    )
    (obj_s, ce_s, corr), lses = lax.scan(body, init, (hcs, lcs))
    return (obj_s, ce_s, corr), (h, w, labels, lses.reshape(-1)[:N])


def _fxent_bwd(smoothing: float, row_chunk: int, backend: str,
               interpret: bool, res, cots):
    h, w, labels, lses = res
    go, gce, _ = cots  # correct-count cotangent is float0 — ignored
    go = go.astype(jnp.float32)
    gce = gce.astype(jnp.float32)
    if (takes_pallas(backend, "pallas", h, w, labels)
            and _pallas_feasible(h, w, backend, interpret)):
        dh, dw = _fxent_bwd_pallas(h, w, labels, lses, go, gce, smoothing,
                                   interpret)
    else:
        dh, dw = _fxent_bwd_xla(h, w, labels, lses, go, gce, smoothing,
                                row_chunk)
    # Cotangents must carry their primals' VMA types: when w is invariant
    # over an axis the rows are sharded on (e.g. replicated head weights under
    # sequence parallelism), the true dw is the cross-shard sum.
    extra_w = tuple(a for a in _vma(dw) if a not in _vma(w))
    if extra_w:
        dw = lax.psum(dw, extra_w)
    extra_h = tuple(a for a in _vma(dh) if a not in _vma(h))
    if extra_h:
        dh = lax.psum(dh, extra_h)
    return dh.astype(h.dtype), dw.astype(w.dtype), None


def _fxent_bwd_xla(h, w, labels, lses, go, gce, smoothing: float,
                   row_chunk: int):
    N, D = h.shape
    V = w.shape[1]
    chunk = min(row_chunk, N)
    hp, lp, nc = _pad_rows(h, labels, chunk)
    lsep = jnp.pad(lses, (0, nc * chunk - N))
    hcs = hp.reshape(nc, chunk, D)
    lcs = lp.reshape(nc, chunk)
    lsec = lsep.reshape(nc, chunk)
    s = smoothing

    def body(dw, xs):
        h_c, l_c, lse_c = xs
        z = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
        p = jnp.exp(z - lse_c[:, None])
        mask = (l_c >= 0).astype(jnp.float32)[:, None]
        onehot = jax.nn.one_hot(jnp.maximum(l_c, 0), V, dtype=jnp.float32)
        # d(obj)/dz = p - (1-s)*onehot - s/V ; d(nll)/dz = p - onehot
        dz = (go + gce) * p - (go * (1.0 - s) + gce) * onehot
        if s:
            dz = dz - go * (s / V)
        dz = (dz * mask).astype(h.dtype)
        dh_c = jnp.dot(dz, w.T, preferred_element_type=jnp.float32)
        dw = dw + jnp.dot(h_c.T, dz, preferred_element_type=jnp.float32)
        return dw, dh_c.astype(h.dtype)

    axes = set(_vma(h)) | set(_vma(w)) | set(_vma(labels)) | set(_vma(go))
    dw, dhs = lax.scan(body, _pcast_to(jnp.zeros((D, V), jnp.float32), axes),
                       (hcs, lcs, lsec))
    dh = dhs.reshape(nc * chunk, D)[:N]
    return dh, dw


fused_linear_xent.defvjp(_fxent_fwd, _fxent_bwd)


def fused_linear_xent_eval(h, w, labels, k: int = 5, row_chunk: int = 512):
    """Eval-side fusion: (ce_sum, correct, correct_topk, valid) over valid
    rows, materializing only one [chunk, V] logit block at a time instead of
    the full [N, V] (at longctx shapes the full eval logits would be
    gigabytes).

    Top-k tie handling matches parallel/common.py correct_topk (torch.topk
    order: value descending, index ascending): the label ranks after every
    strictly-greater logit and after equal logits at smaller class indices.
    No gradients (plain function — eval only).
    """
    N, D = h.shape
    V = w.shape[1]
    k = min(k, V)
    chunk = min(row_chunk, N)
    hp, lp, nc = _pad_rows(h, labels, chunk)
    hcs = hp.reshape(nc, chunk, D)
    lcs = lp.reshape(nc, chunk)

    def body(carry, xs):
        ce_s, corr, corrk, cnt = carry
        h_c, l_c = xs
        z = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
        nll, _, correct, mask, _ = _row_stats(z, l_c, 0.0)
        # top-k rank: strictly-greater logits plus equal logits at smaller
        # class indices (torch.topk order)
        safe = jnp.maximum(l_c, 0)
        gold = jnp.take_along_axis(z, safe[:, None], axis=-1)
        idx = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        higher = jnp.sum((z > gold).astype(jnp.int32), axis=-1)
        tie_before = jnp.sum(
            ((z == gold) & (idx < safe[:, None])).astype(jnp.int32), axis=-1)
        ce_s = ce_s + jnp.sum(jnp.where(mask, nll, 0.0))
        corr = corr + jnp.sum(correct.astype(jnp.int32))
        corrk = corrk + jnp.sum(
            ((higher + tie_before < k) & mask).astype(jnp.int32))
        cnt = cnt + jnp.sum(mask.astype(jnp.int32))
        return (ce_s, corr, corrk, cnt), None

    axes = set(_vma(h)) | set(_vma(w)) | set(_vma(labels))
    init = tuple(
        _pcast_to(z, axes)
        for z in (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                  jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    )
    (ce_s, corr, corrk, cnt), _ = lax.scan(body, init, (hcs, lcs))
    return ce_s, corr, corrk, cnt


# ---------------------------------------------------------------------------
# Pallas TPU kernels — same math, zero logits traffic to HBM.
#
# Both kernels tile the N x V logits as [br, bv] grid steps and, inside a
# step, compute z = h W for SUB_ROWS rows at a time (the f32 z tile and the
# code Mosaic unrolls for it stay bounded while W's block is re-read only
# once per br rows). The vocabulary block need not divide V: the last one is
# cut by the column index (z masked for the statistics, dz and W's columns
# past V zeroed: what Pallas pads a cut block with is not defined).
#
# Forward (fused_xent_fwd): grid (row blocks, vocabulary blocks), the
# vocabulary inside. The running statistics are kept PER LANE: [br, 128]
# scratch for the running max (which is also the arg-max's value), the
# arg-max's 128-column chunk, the exp-sum, the gold logit and the logit sum,
# updated elementwise per 128-lane column of the z tile and reduced across
# the lanes once, on the last vocabulary block.
#
# Backward (fused_xent_dh_dw): ONE kernel, grid (vocabulary blocks, row
# blocks), the rows inside. Each z tile, its exp and dz are formed once and
# feed both products: dW_j = sum_i h_i^T dz stays in VMEM across the inner
# sweep (its output block does not move); dh_i += dz W_j^T is float32 in
# HBM, fetched, added to and written back within the grid step by the
# kernel's own DMAs, each started and waited for inside that step: Pallas's
# pipeline, which orders nothing between a block's write-back and a later
# prefetch of the same block, never touches dh.
#
# Measured on one v5e, bf16, N x D x V, device ms per call (PERF.md, PR 30),
# forward / backward; beside them the three kernels this file held before
# (forward / dh + dw: [256, 384] and [256, 896 | 384 | 128] tiles, blocks
# that had to divide V, five cross-lane reductions a tile, z and its exp
# formed twice):
#
#     16384 x  768 x 50304 (gpt2s-train)          7.34 / 20.54   15.58 / 30.35
#     16384 x 2048 x 16128 (kanana2-ep16-train)   5.93 / 17.24    6.84 / 26.14
#
# i.e. 88% / 94% and 93% / 96% of the MXU's peak for one / three N*D*V
# products. Every block tried between (512, 1024) and (2048, 2048) ran within
# 4% of these; the lane columns rolled into a real loop cost the forward 72%.
# ---------------------------------------------------------------------------

ROW_BLOCK = 1024  # rows of a grid step, at most: W is re-read per row block
V_BLOCK = 2048    # vocabulary columns of a grid step, at most
SUB_ROWS = 256    # rows of one z tile inside a grid step
NEG_INF = -1e30


def _round_up(n: int, m: int) -> int:
    return pl.cdiv(n, m) * m


def _lanes(bv: int) -> int:
    """Width of the per-lane statistics: a vreg's 128 lanes; the whole block
    where it is no multiple of them (interpret mode's small vocabularies)."""
    return 128 if bv % 128 == 0 else bv


def _v_block(V: int, cap: int, unit: int) -> int:
    """Vocabulary block of at most ``cap`` columns, a multiple of ``unit``,
    evened out over the blocks V needs: the last, cut one wastes least."""
    cap = max(unit, cap - cap % unit)
    return min(cap, _round_up(pl.cdiv(V, pl.cdiv(V, cap)), unit))


def _row_block(n: int, cap: int, interpret: bool) -> int:
    """Rows of a grid step: ``cap``, or all of a smaller n in whole z tiles
    (under one tile, on real TPU, in bf16's 16 sublanes; rows are padded up
    to a block multiple either way)."""
    if n >= cap:
        return cap
    return _round_up(n, SUB_ROWS if n > SUB_ROWS else 1 if interpret else 16)


def _sub_rows(br: int) -> int:
    return SUB_ROWS if br % SUB_ROWS == 0 else br


def _held_vmem_bytes(kernel: str, br: int, bv: int, D: int, isz: int) -> int:
    """What a kernel ("fwd" or "bwd") holds in VMEM at these blocks: its
    operand and output blocks as Mosaic pads and double-buffers them
    (util.tile_bytes), its scratch, and one float32 z tile (the backward
    also one tile's dh rows and h^T, which dW's product contracts over the
    rows; that product is added in place). Checked against the least
    ``vmem_limit_bytes`` Mosaic accepts (local v5e compile; PERF.md, PR 30)
    at six shapes a kernel — D 512..4096, blocks (256, 1024)..(1024, 2048),
    bf16 and f32, both cells' among them: the sum reads 1.03-1.22 of Mosaic's
    number forward and 1.05-1.15 backward (1.22 / 1.05 at gpt2s-train's
    shape, 1.08 / 1.05 at kanana2-ep16-train's)."""
    f32, sr = 4, _sub_rows(br)
    col = tile_bytes(br, 1, f32)  # a [br, 1] column pads to 128 lanes
    h, w = tile_bytes(br, D, isz), tile_bytes(D, bv, isz)
    z = tile_bytes(sr, bv, f32)
    if kernel == "fwd":
        blocks = 2 * (h + w + col) + 2 * 4 * col
        return blocks + 5 * tile_bytes(br, 128, f32) + z
    blocks = 2 * (h + w + 2 * col) + 2 * tile_bytes(D, bv, f32)
    scratch = tile_bytes(br, bv, isz) + tile_bytes(br, D, f32)  # dz, dh
    return blocks + scratch + z + tile_bytes(sr, D, f32) + h


def _blocks(kernel: str, N: int, D: int, V: int, isz: int, interpret: bool):
    """(br, bv) of a kernel: the caps, halved until what the kernel holds
    fits RESIDENT_VMEM_BUDGET. The forward keeps its rows while the
    vocabulary sweeps and re-reads W once per row block: its columns go
    first (to 512); the backward moves a float32 dh through HBM once per
    vocabulary block: its rows go first (to one z tile). None where even 128
    lanes by 16 rows do not fit (a very wide D), or V is no multiple of 128
    lanes on real TPU."""
    if interpret:  # any block runs; lane-aligned where the vocabulary is
        return (_row_block(N, ROW_BLOCK, True),
                _v_block(V, V_BLOCK, 1 if V % 128 else 128))
    if V % 128:
        return None
    rows, cols = ROW_BLOCK, V_BLOCK
    while True:
        br, bv = _row_block(N, rows, False), _v_block(V, cols, 128)
        if _held_vmem_bytes(kernel, br, bv, D, isz) <= RESIDENT_VMEM_BUDGET:
            return br, bv
        rows_first = br > SUB_ROWS if kernel == "bwd" else bv <= 512
        if br > 16 and (rows_first or bv == 128):
            rows = max(16, br // 32 * 16)  # halved, whole bf16 tiles
        elif bv > 128:
            cols = max(128, bv // 2)
        else:
            return None


def _lane_columns(nc: int, tail_c: int, body, carry):
    """``carry = body(c, cut, carry)`` over a tile's nc lane columns: the
    columns under ``tail_c``, which lie inside V in every vocabulary block,
    then the rest, which the last block cuts (``cut``: mask by the column
    index). Two loops that Mosaic unrolls whole — straight-line code, which
    is what lets it run a column's vector work under the next one's matmul
    (rolled: forward 12.6 against 7.3 ms at gpt2s-train's shape; PERF.md, PR
    30) — while jax traces each body once, whatever bv."""
    carry = jax.lax.fori_loop(0, tail_c, lambda c, x: body(c, False, x),
                              carry, unroll=True)
    return jax.lax.fori_loop(tail_c, nc, lambda c, x: body(c, True, x),
                             carry, unroll=True)


def _fx_fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, gold_ref, zsum_ref,
                   amax_ref, m_sc, l_sc, gold_sc, zsum_sc, ai_sc, z_sc, *,
                   bv: int, nv: int, V: int, smoothing: float):
    j = pl.program_id(1)
    br, (sr, _), lanes = h_ref.shape[0], z_sc.shape, _lanes(bv)
    nc = bv // lanes
    tail = V - (nv - 1) * bv  # columns of the last block that are in V
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, NEG_INF, f32)
        l_sc[:] = jnp.zeros(l_sc.shape, f32)
        gold_sc[:] = jnp.zeros(gold_sc.shape, f32)
        zsum_sc[:] = jnp.zeros(zsum_sc.shape, f32)
        ai_sc[:] = jnp.zeros(ai_sc.shape, jnp.int32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    limit = jnp.where(j == nv - 1, tail, bv)

    def column(c, cut: bool, fill):
        """The z tile's c-th lane column, ``fill`` past V where ``cut``."""
        zc = z_sc[:, pl.ds(pl.multiple_of(c * lanes, lanes), lanes)]
        return jnp.where(lane + c * lanes < limit, zc, fill) if cut else zc

    def tile(r, carry):
        rows = pl.ds(pl.multiple_of(r * sr, sr), sr)
        z_sc[:] = jax.lax.dot_general(
            h_ref[rows, :], w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)  # [sr, bv]
        lab = jnp.broadcast_to(lab_ref[rows, :] - j * bv, (sr, lanes))

        def stats(c, cut, carry):
            m, ai, gold, zsum = carry
            if smoothing:
                zsum = zsum + column(c, cut, 0.0)
            zc = column(c, cut, NEG_INF)
            gold = jnp.where(lane + c * lanes == lab, zc, gold)
            # strictly greater: the lowest column of a lane keeps a tie
            ai = jnp.where(zc > m, j * nc + c, ai)
            return jnp.maximum(m, zc), ai, gold, zsum

        m_old = m_sc[rows, :]
        m, ai, gold, zsum = _lane_columns(
            nc, tail // lanes, stats,
            (m_old, ai_sc[rows, :], gold_sc[rows, :], zsum_sc[rows, :]))
        l = _lane_columns(
            nc, tail // lanes,
            lambda c, cut, l: l + jnp.exp(column(c, cut, NEG_INF) - m),
            l_sc[rows, :] * jnp.exp(m_old - m))
        m_sc[rows, :], ai_sc[rows, :], l_sc[rows, :] = m, ai, l
        gold_sc[rows, :] = gold
        if smoothing:
            zsum_sc[rows, :] = zsum
        return carry

    jax.lax.fori_loop(0, br // sr, tile, 0)

    @pl.when(j == nv - 1)
    def _fini():
        m = m_sc[:]
        top = jnp.max(m, axis=1, keepdims=True)
        l = jnp.sum(l_sc[:] * jnp.exp(m - top), axis=1, keepdims=True)
        lse_ref[:] = top + jnp.log(jnp.maximum(l, 1e-20))
        gold_ref[:] = jnp.sum(gold_sc[:], axis=1, keepdims=True)
        zsum_ref[:] = jnp.sum(zsum_sc[:], axis=1, keepdims=True)
        # of the lanes that hold the maximum, the lowest column
        col = ai_sc[:] * lanes + lane
        amax_ref[:] = jnp.min(jnp.where(m == top, col, V), axis=1,
                              keepdims=True)


def _fxent_fwd_pallas(h, w, labels, smoothing: float, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    N, D = h.shape
    V = w.shape[1]
    isz = max(h.dtype.itemsize, w.dtype.itemsize)
    br, bv = _blocks("fwd", N, D, V, isz, interpret)
    # pad rows to a block multiple with masked labels
    hp, lp, nr = _pad_rows(h, labels, br)
    Np = hp.shape[0]
    nv = pl.cdiv(V, bv)
    lab2 = lp[:, None].astype(jnp.int32)

    f32 = jnp.float32
    col = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    stat = pltpu.VMEM((br, _lanes(bv)), f32)
    lse, gold, zsum, amax = pl.pallas_call(
        functools.partial(_fx_fwd_kernel, bv=bv, nv=nv, V=V,
                          smoothing=smoothing),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bv), lambda i, j: (0, j)),
            col,
        ],
        out_specs=[col] * 4,
        out_shape=[
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), jnp.int32, hp, w, lab2),
        ],
        scratch_shapes=[stat] * 4
        + [pltpu.VMEM((br, _lanes(bv)), jnp.int32),
           pltpu.VMEM((_sub_rows(br), bv), f32)],
        interpret=interpret,
        name="fused_xent_fwd",
        **grid_params(
            interpret, "parallel", "arbitrary",
            vmem_limit_bytes=vmem_limit_bytes(
                _held_vmem_bytes("fwd", br, bv, D, isz))),
    )(hp, w, lab2)

    lse = lse[:N, 0]
    gold = gold[:N, 0]
    amax = amax[:N, 0]
    mask = labels >= 0
    nll = lse - gold
    if smoothing:
        obj = lse - (1.0 - smoothing) * gold - smoothing * (zsum[:N, 0] / V)
    else:
        obj = nll
    obj_s = jnp.sum(jnp.where(mask, obj, 0.0))
    ce_s = jnp.sum(jnp.where(mask, nll, 0.0))
    corr = jnp.sum(((amax == labels) & mask).astype(jnp.int32))
    return (obj_s, ce_s, corr), (h, w, labels, lse)


def _fx_dh_dw_kernel(h_ref, w_ref, lab_ref, lse_ref, coef_ref,
                     dh_hbm, dw_ref, z_sc, dz_sc, dh_sc, sem, *, bv: int,
                     nv: int, V: int, smoothing: float):
    from jax.experimental.pallas import tpu as pltpu

    j, i = pl.program_id(0), pl.program_id(1)
    br, (sr, _), lanes = h_ref.shape[0], z_sc.shape, _lanes(bv)
    tail = V - (nv - 1) * bv  # columns of the last block that are in V
    f32 = jnp.float32

    def tile_rows(r):
        return pl.ds(pl.multiple_of(r * sr, sr), sr)

    def dh_copy(r, fetch: bool):
        """The DMA of tile r's dh rows: HBM -> dh_sc, or back."""
        there = dh_hbm.at[pl.ds(pl.multiple_of(i * br + r * sr, sr), sr), :]
        here = dh_sc.at[tile_rows(r), :]
        if fetch:
            return pltpu.make_async_copy(there, here, sem.at[0, r])
        return pltpu.make_async_copy(here, there, sem.at[1, r])

    def each_tile(f):
        def body(r, carry):
            f(r)
            return carry

        jax.lax.fori_loop(0, br // sr, body, 0)

    @pl.when(j > 0)  # what the earlier vocabulary blocks summed
    def _fetch_dh():
        each_tile(lambda r: dh_copy(r, True).start())

    if tail < bv:
        @pl.when(j == nv - 1)
        def _zero_w_past_v():  # dz is 0 there, and 0 * NaN is NaN
            w_ref[:, tail:] = jnp.zeros((w_ref.shape[0], bv - tail),
                                        w_ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    limit = jnp.where(j == nv - 1, tail, bv)
    c_p, c_oh, c_sm = coef_ref[0, 0], coef_ref[0, 1], coef_ref[0, 2]

    def tile(r):
        rows = tile_rows(r)
        z_sc[:] = jax.lax.dot_general(
            h_ref[rows, :], w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)  # [sr, bv]
        lab = lab_ref[rows, :]
        keep = (lab >= 0).astype(f32)  # masked rows: dz = 0
        lab = jnp.broadcast_to(lab - j * bv, (sr, lanes))
        lse = jnp.broadcast_to(lse_ref[rows, :], (sr, lanes))
        k_p = jnp.broadcast_to(c_p * keep, (sr, lanes))
        k_oh = jnp.broadcast_to(c_oh * keep, (sr, lanes))
        if smoothing:
            k_sm = jnp.broadcast_to(c_sm * keep, (sr, lanes))

        def dz_column(c, cut, carry):
            cols = pl.ds(pl.multiple_of(c * lanes, lanes), lanes)
            dz = k_p * jnp.exp(z_sc[:, cols] - lse) - jnp.where(
                lane + c * lanes == lab, k_oh, 0.0)
            if smoothing:
                dz = dz - k_sm
            if cut:
                dz = jnp.where(lane + c * lanes < limit, dz, 0.0)
            dz_sc[rows, cols] = dz.astype(dz_sc.dtype)
            return carry

        _lane_columns(bv // lanes, tail // lanes, dz_column, 0)
        dh = jax.lax.dot_general(
            dz_sc[rows, :], w_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)  # [sr, D]

        @pl.when(j > 0)
        def _wait_dh():
            dh_copy(r, True).wait()

        dh_sc[rows, :] = jnp.where(j > 0, dh_sc[rows, :], 0.0) + dh
        dh_copy(r, False).start()

    each_tile(tile)

    @pl.when(i == 0)
    def _init_dw():
        dw_ref[:] = jnp.zeros(dw_ref.shape, f32)

    dw_ref[:] += jax.lax.dot_general(
        h_ref[:], dz_sc[:], (((0,), (0,)), ((), ())),
        preferred_element_type=f32)
    # dh_sc is the next step's too: its rows have to be in HBM by then
    each_tile(lambda r: dh_copy(r, False).wait())


def _fxent_bwd_pallas(h, w, labels, lses, go, gce, smoothing: float,
                      interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    N, D = h.shape
    V = w.shape[1]
    isz = max(h.dtype.itemsize, w.dtype.itemsize)
    br, bv = _blocks("bwd", N, D, V, isz, interpret)
    hp, lp, nr = _pad_rows(h, labels, br)
    Np = hp.shape[0]
    nv = pl.cdiv(V, bv)
    sr = _sub_rows(br)
    lab2 = lp[:, None].astype(jnp.int32)
    # padded rows: lse=0 with z=0 gives p=1 — masked to 0 by the label test
    lse2 = jnp.pad(lses, (0, Np - N))[:, None]
    s = smoothing
    coef = jnp.stack([go + gce, go * (1.0 - s) + gce,
                      go * (s / V), jnp.float32(0.0)])[None, :]
    operands = (hp, w, lab2, lse2, coef)

    f32 = jnp.float32
    col = pl.BlockSpec((br, 1), lambda j, i: (i, 0))
    dh, dw = pl.pallas_call(
        functools.partial(_fx_dh_dw_kernel, bv=bv, nv=nv, V=V,
                          smoothing=smoothing),
        grid=(nv, nr),
        in_specs=[
            pl.BlockSpec((br, D), lambda j, i: (i, 0)),
            pl.BlockSpec((D, bv), lambda j, i: (0, j)),
            col,
            col,
            pl.BlockSpec((1, 4), lambda j, i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec((D, bv), lambda j, i: (0, j))],
        out_shape=[_pl_out((Np, D), f32, *operands),
                   _pl_out((D, V), f32, *operands)],
        scratch_shapes=[pltpu.VMEM((sr, bv), f32),
                        pltpu.VMEM((br, bv), h.dtype),
                        pltpu.VMEM((br, D), f32),
                        pltpu.SemaphoreType.DMA((2, br // sr))],
        interpret=interpret,
        name="fused_xent_dh_dw",
        **grid_params(
            interpret, "arbitrary", "arbitrary",
            vmem_limit_bytes=vmem_limit_bytes(
                _held_vmem_bytes("bwd", br, bv, D, isz))),
    )(*operands)
    return dh[:N], dw
