"""Fused LM-head loss: linear projection + softmax cross-entropy without ever
materializing the full [N, V] logits tensor in HBM.

Why: on the token workloads the vocabulary is 32k (config.DATASETS), so the
unfused path writes logits [B*T, V] (plus an f32 log-softmax copy and an f32
gradient) — gigabytes per step that dwarf every activation in the model. The
reference has no analog (its classifiers top out at 1000 classes — this is
the sequence-workload equivalent of SURVEY.md §2 D2's "hot op gets a custom
kernel" rule). The fusion computes, per row chunk,

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

from ddlbench_tpu.ops.util import pallas_out_struct as _pl_out
from ddlbench_tpu.ops.util import takes_pallas

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
    """Mosaic wants lane-dim blocks in multiples of 128 (a vocab with no
    such divisor can't run the compiled kernels), and every kernel's block
    working set must fit scoped VMEM even at the 128-lane floor — a very
    wide D blows the dW accumulator alone (_budget_v_block -> None). The
    budget is evaluated at the row block the kernels will actually use
    (small row counts shrink it, and the dh fixed cost with it). auto falls
    back to chunked-XLA; a forced "pallas" backend gets a clear error
    instead of a Mosaic one."""
    if interpret:
        return True
    D, V = w.shape
    # Price with the wider of the two dtypes: the launch sites size blocks
    # with h.dtype.itemsize (lines 442/555+), so a gate priced only on w
    # could pass while _budget_v_block returns None at launch (ADVICE r3).
    isz = max(h.dtype.itemsize, w.dtype.itemsize)
    br = _row_block(h.shape[0], interpret)
    ok = (
        _budget_v_block(V, D, br, isz, False) is not None  # fwd
        and _budget_v_block(V, D, br, isz, False,
                            **_dh_price(D, br, isz)) is not None
        and _budget_v_block(V, D, br, isz, False,
                            **_dw_price(D, br, isz)) is not None
    )
    if ok:
        return True
    if backend == "pallas":
        raise ValueError(
            f"fused_linear_xent: no feasible Pallas blocking for head "
            f"[D={D}, V={V}] — the vocab needs a 128-multiple block divisor "
            f"and every kernel's block working set must fit scoped VMEM "
            f"({VMEM_HARD >> 20} MiB); pad the vocab or use backend='xla'")
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
# Forward: grid (row_blocks, v_blocks), W streamed blockwise through VMEM
# (~16 MB/core, so [D, 32k] never fits whole); online-logsumexp scratch
# carried across the inner v sweep; per-row (lse, gold, zsum, argmax) written
# on the last v block and reduced to the three sums with trivial XLA ops.
# Backward: dh kernel accumulates dz @ W_j^T over the inner v sweep; dW kernel
# flips the grid and accumulates h_i^T @ dz over the inner row sweep (the
# two-kernel split of ops/flash_attention.py's streaming dq / dkv pair).
# ---------------------------------------------------------------------------

ROW_BLOCK = 256
V_BLOCK = 2048
# Per-kernel working-set target and hard ceiling. v5e gives ~16 MiB of
# scoped VMEM per core; target well under it so double-buffering + compiler
# temporaries fit (the dW kernel at (br=256, bv=2048, D=512) measures
# 18.2 MiB on-chip and is rejected by Mosaic, hence the budget-aware block
# choice below). A block between target and hard limit is best-effort
# (returned, may still compile); past VMEM_HARD even the 128-lane floor
# cannot fit and the caller must take the chunked-XLA path instead.
VMEM_BUDGET = 12 * 1024 * 1024
VMEM_HARD = 16 * 1024 * 1024


def _pick_block(t: int, preferred: int, unit: int = 1) -> Optional[int]:
    """Tile-aligned block divisor (ops/util.py:pick_block); ``unit`` is 128
    for the lane (vocab) dimension on real TPU."""
    from ddlbench_tpu.ops.util import pick_block

    return pick_block(t, preferred, unit)


def _budget_v_block(V: int, D: int, br: int, in_size: int, interpret: bool,
                    per_bv: int = 0, fixed: int = 0) -> int:
    """Largest 128-multiple vocab-block divisor of ``V`` whose kernel
    working set fits ``VMEM_BUDGET``.

    Shared terms for all three kernels: double-buffered input blocks
    (h [br, D], w [D, bv]) plus the recomputed f32 logit block [br, bv].
    ``per_bv`` prices kernel-specific bytes per vocab lane (dz blocks, the
    dW kernel's f32 [D, bv] scratch + double-buffered f32 out block);
    ``fixed`` prices bv-independent extras (the dh kernel's [br, D] f32
    accumulator and double-buffered out block).

    Returns None when even the smallest lane-aligned block exceeds
    VMEM_HARD (a very wide D — the bv-independent terms alone blow the
    scoped-VMEM limit); the caller falls back to the chunked-XLA path via
    _pallas_feasible. A pick between VMEM_BUDGET and VMEM_HARD is returned
    best-effort."""
    bv = _pick_block(V, V_BLOCK, 1 if interpret else 128)
    if interpret or bv is None:
        return bv

    def footprint(b: int) -> int:
        ins = 2 * (br * D + D * b) * in_size
        return ins + br * b * 4 + per_bv * b + fixed

    while bv > 128 and footprint(bv) > VMEM_BUDGET:
        smaller = _pick_block(V, bv // 2, 128)
        if smaller is None or smaller == bv:
            break
        bv = smaller
    if footprint(bv) > VMEM_HARD:
        return None
    return bv


def _dh_price(D: int, br: int, in_size: int) -> dict:
    """dh-kernel _budget_v_block terms: a dz block [br, bv] in the compute
    dtype per lane, plus the bv-independent f32 [br, D] accumulator and
    double-buffered [br, D] out block. One home for the formulas shared by
    the feasibility gate, the kernel launch, and tests/test_vmem_budget.py."""
    return dict(per_bv=br * in_size, fixed=br * D * (4 + 2 * in_size))


def _dw_price(D: int, br: int, in_size: int) -> dict:
    """dW-kernel terms: the dz block plus an f32 [D, bv] scratch accumulator
    and a double-buffered f32 [D, bv] out block (3 * D * 4 bytes per lane)."""
    return dict(per_bv=br * in_size + 3 * D * 4)


def _row_block(n: int, interpret: bool) -> int:
    """Row (sublane) block: ROW_BLOCK, shrunk for small n but kept a multiple
    of 8 on real TPU (rows are padded up to a block multiple either way)."""
    if n >= ROW_BLOCK:
        return ROW_BLOCK
    return n if interpret else -(-n // 8) * 8


def _fx_fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, gold_ref, zsum_ref,
                   amax_ref, m_sc, l_sc, gold_sc, zsum_sc, av_sc, ai_sc, *,
                   bv: int, nv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[:] = jnp.zeros(l_sc.shape, jnp.float32)
        gold_sc[:] = jnp.zeros(gold_sc.shape, jnp.float32)
        zsum_sc[:] = jnp.zeros(zsum_sc.shape, jnp.float32)
        av_sc[:] = jnp.full(av_sc.shape, NEG_INF, jnp.float32)
        ai_sc[:] = jnp.zeros(ai_sc.shape, jnp.int32)

    z = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [br, bv]
    lab = lab_ref[:]  # [br, 1]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
    match = col == lab
    gold_sc[:] += jnp.sum(jnp.where(match, z, 0.0), axis=1, keepdims=True)
    zsum_sc[:] += jnp.sum(z, axis=1, keepdims=True)
    bm = jnp.max(z, axis=1, keepdims=True)
    bi = j * bv + jnp.argmax(z, axis=1).astype(jnp.int32)[:, None]
    upd = bm > av_sc[:]
    ai_sc[:] = jnp.where(upd, bi, ai_sc[:])
    av_sc[:] = jnp.where(upd, bm, av_sc[:])
    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, bm)
    l_sc[:] = (l_sc[:] * jnp.exp(m_prev - m_new)
               + jnp.sum(jnp.exp(z - m_new), axis=1, keepdims=True))
    m_sc[:] = m_new

    @pl.when(j == nv - 1)
    def _fini():
        l_safe = jnp.maximum(l_sc[:], 1e-20)
        lse_ref[:] = m_sc[:] + jnp.log(l_safe)
        gold_ref[:] = gold_sc[:]
        zsum_ref[:] = zsum_sc[:]
        amax_ref[:] = ai_sc[:]


NEG_INF = -1e30


def _fxent_fwd_pallas(h, w, labels, smoothing: float, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    N, D = h.shape
    V = w.shape[1]
    br = _row_block(N, interpret)
    # pad rows to a block multiple with masked labels
    hp, lp, _ = _pad_rows(h, labels, br)
    Np = hp.shape[0]
    nr = Np // br
    bv = _budget_v_block(V, D, br,
                         max(h.dtype.itemsize, w.dtype.itemsize), interpret)
    nv = V // bv
    lab2 = lp[:, None].astype(jnp.int32)

    f32 = jnp.float32
    lse, gold, zsum, amax = pl.pallas_call(
        functools.partial(_fx_fwd_kernel, bv=bv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bv), lambda i, j: (0, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((br, 1), lambda i, j: (i, 0))] * 4,
        out_shape=[
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), f32, hp, w, lab2),
            _pl_out((Np, 1), jnp.int32, hp, w, lab2),
        ],
        scratch_shapes=[pltpu.VMEM((br, 1), f32)] * 5
        + [pltpu.VMEM((br, 1), jnp.int32)],
        interpret=interpret,
        name="fused_xent_fwd",
    )(hp, w, lab2)

    lse = lse[:N, 0]
    gold = gold[:N, 0]
    zsum = zsum[:N, 0]
    amax = amax[:N, 0]
    mask = labels >= 0
    nll = lse - gold
    if smoothing:
        obj = lse - (1.0 - smoothing) * gold - smoothing * (zsum / V)
    else:
        obj = nll
    obj_s = jnp.sum(jnp.where(mask, obj, 0.0))
    ce_s = jnp.sum(jnp.where(mask, nll, 0.0))
    corr = jnp.sum(((amax == labels) & mask).astype(jnp.int32))
    return (obj_s, ce_s, corr), (h, w, labels, lse)


def _fx_dz(z, lab, lse_col, coef, bv: int, j, dtype):
    """dz block [br, bv] from recomputed logits (shared by dh/dw kernels)."""
    p = jnp.exp(z - lse_col)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
    match = (col == lab).astype(jnp.float32)
    c_p, c_oh, c_sm = coef[0, 0], coef[0, 1], coef[0, 2]
    dz = c_p * p - c_oh * match - c_sm
    maskf = (lab >= 0).astype(jnp.float32)
    return (dz * maskf).astype(dtype)


def _fx_dh_kernel(h_ref, w_ref, lab_ref, lse_ref, coef_ref, dh_ref, acc_sc, *,
                  bv: int, nv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    z = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dz = _fx_dz(z, lab_ref[:], lse_ref[:], coef_ref[:], bv, j, h_ref.dtype)
    acc_sc[:] += jax.lax.dot_general(
        dz, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nv - 1)
    def _fini():
        dh_ref[:] = acc_sc[:].astype(dh_ref.dtype)


def _fx_dw_kernel(h_ref, w_ref, lab_ref, lse_ref, coef_ref, dw_ref, acc_sc, *,
                  bv: int, nr: int):
    i = pl.program_id(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    z = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dz = _fx_dz(z, lab_ref[:], lse_ref[:], coef_ref[:], bv, j, h_ref.dtype)
    acc_sc[:] += jax.lax.dot_general(
        h_ref[:], dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == nr - 1)
    def _fini():
        dw_ref[:] = acc_sc[:].astype(dw_ref.dtype)


def _fxent_bwd_pallas(h, w, labels, lses, go, gce, smoothing: float,
                      interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    N, D = h.shape
    V = w.shape[1]
    br = _row_block(N, interpret)
    hp, lp, _ = _pad_rows(h, labels, br)
    Np = hp.shape[0]
    nr = Np // br
    # dh's accumulator + double-buffered out block are [br, D]
    # (bv-independent); dW carries an f32 [D, bv] scratch plus a
    # double-buffered f32 [D, bv] out block, so its lane block must shrink
    # when D is wide (VMEM_BUDGET note above; formulas in _dh/_dw_price).
    isz = max(h.dtype.itemsize, w.dtype.itemsize)
    bv = _budget_v_block(V, D, br, isz, interpret, **_dh_price(D, br, isz))
    nv = V // bv
    bv_dw = _budget_v_block(V, D, br, isz, interpret,
                            **_dw_price(D, br, isz))
    nv_dw = V // bv_dw
    lab2 = lp[:, None].astype(jnp.int32)
    # padded rows: lse=0 with z=0 gives p=1 — masked to 0 by the label test
    lse2 = jnp.pad(lses, (0, Np - N))[:, None]
    s = smoothing
    coef = jnp.stack([go + gce, go * (1.0 - s) + gce,
                      go * (s / V), jnp.float32(0.0)])[None, :]

    f32 = jnp.float32
    dh = pl.pallas_call(
        functools.partial(_fx_dh_kernel, bv=bv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bv), lambda i, j: (0, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 4), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i, j: (i, 0)),
        out_shape=_pl_out((Np, D), h.dtype, hp, w, lab2, lse2, coef),
        scratch_shapes=[pltpu.VMEM((br, D), f32)],
        interpret=interpret,
        name="fused_xent_dh",
    )(hp, w, lab2, lse2, coef)

    dw = pl.pallas_call(
        functools.partial(_fx_dw_kernel, bv=bv_dw, nr=nr),
        grid=(nv_dw, nr),
        in_specs=[
            pl.BlockSpec((br, D), lambda j, i: (i, 0)),
            pl.BlockSpec((D, bv_dw), lambda j, i: (0, j)),
            pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((1, 4), lambda j, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((D, bv_dw), lambda j, i: (0, j)),
        out_shape=_pl_out((D, V), f32, hp, w, lab2, lse2, coef),
        scratch_shapes=[pltpu.VMEM((D, bv_dw), f32)],
        interpret=interpret,
        name="fused_xent_dw",
    )(hp, w, lab2, lse2, coef)

    return dh[:N], dw
