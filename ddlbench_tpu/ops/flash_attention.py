"""Fused causal attention (FlashAttention-2 style) as a Pallas TPU kernel.

This is the framework's hot-op kernel: the reference's only custom kernel is
the GNMT varlen pack_utils CUDA extension (SURVEY.md §2 D2); the modern
sequence workload's equivalent hot op is attention, so that is what gets the
hand-written kernel. The jnp fallback (models/transformer.py
causal_attention) materializes the [B, H, T, T] score matrix in HBM; this
kernel never does — it streams K/V blocks through VMEM with an
online-softmax accumulator, so HBM traffic drops from O(T^2) to O(T * d)
and the block matmuls run on the MXU.

Forward saves only O and the row logsumexp (LSE); backward recomputes the
probabilities blockwise, the standard FlashAttention-2 recipe, wired up with
jax.custom_vjp.

Two grid designs:

* **resident** (the fast path): the whole inner sequence lives in VMEM and
  a fori_loop sweeps it with causal bounds. Forward: grid (batch*head, Q
  block) over resident K/V. Backward: ONE kernel, grid (batch*head, K
  block) over resident Q/dO — per tile S, P, dP, dS are computed once and
  give dV, dK and dQ (5 matmuls, 1 exp; a dq kernel plus a dkv kernel did 7
  and 2). dK/dV ride the fori carry; dQ of the whole row block accumulates
  in f32 scratch while the K-block axis sweeps. The resident kernels keep
  their score tile TRANSPOSED, [bk, bq]: the per-query statistics (running
  max and sum, lse, delta) are then rows [1, bq] — 4 vregs at bq=512 where
  a [bq, 1] column takes 64 — reductions run down the sublanes, and no
  [bq, bk] tile is ever transposed for a matmul.
* **streaming**: grid (batch*head, outer block, inner block), the inner
  dimension arrives blockwise via BlockSpec with accumulators in VMEM
  scratch — every block shape is T-independent, so any sequence length
  compiles (T=32k on one chip). Forward, dq and dkv kernels, row-major
  tiles. Dead causal cells still pay their fetch.

_use_streaming picks by ONE accounting, _resident_vmem_bytes: what the
resident kernel would hold in VMEM at the call's shapes (its blocks as
Mosaic pads and double-buffers them, its scratch, its tile temporaries).
Resident while that sum fits RESIDENT_VMEM_BUDGET (ops/util.py), half the
chip's VMEM; streaming beyond; the same sum and a quarter more is the
kernel's ``vmem_limit_bytes``. The two benchmark shapes (bf16, 512x512),
forward / one-pass backward: T=1024 dh=64 (gpt2s-train) 4.1 / 6.5 MiB;
T=4096 q/k 192 v 128 (kanana2-ep16-train) 9.5 / 18.9 MiB. Block-level causal
skipping in both designs: resident bounds its fori, streaming skips dead
cells' compute under @pl.when. A sliding ``window`` bounds the sweeps from
the other side (_window_kv_start, _window_q_end): at T 16384, window 4096
and 512x512 tiles a head visits 252 of its 528 causal tiles.

Measured on one v5e, bf16, device ms per call (PERF.md: PR 25 for dh=64,
PR 28 for the rest), forward / backward, resident against streaming:

    B*H 192, T=1024, dh=64          0.78 /  1.34     1.21 /  2.59
    B*H  24, T=8192, dh=64          3.89 /  6.24     6.83 / 14.54
    B*H 128, T=4096, q/k 192 v 128  7.20 / 14.95    12.77 / 30.34
    B*H  16, T=16384, q/k 192 v 128 12.68 / 25.92   22.68 / 52.64
    B*H   8, T=32768, dh=64         19.38 / 31.07   36.17 / 75.73

(before PR 25's one-pass backward: row-major tiles and a resident dq + dkv
pair, 0.97 / 2.35 at T=1024.) No shape that compiles ran slower resident,
f32 operands, a prefix and (256, 1024) blocks included. 512x512 tiles won
every sweep: of {128, 256, 512}^2 at T=1024..8192 and dh=64, and at T=4096,
q/k 192, v 128 the one-pass backward takes 14.95 ms at 512x512, 16.11 at
(512, 256), 19.89 at 256x256, 20.82 at (256, 512) (forward 7.20, 8.02,
10.68, 9.04): the sweeps are a few iterations long and do not pipeline, so
a finer causal tiling loses more per tile than it saves in area.

``q_offset``/``k_offset`` give each block its absolute position — the same
convention as causal_attention — so the kernel also serves blocks of a
distributed sequence (parallel/sp.py ring attention).

Interpret mode (CPU tests) and the compiled TPU path share all code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ddlbench_tpu.ops.util import RESIDENT_VMEM_BUDGET  # patched by tests
from ddlbench_tpu.ops.util import grid_params as _grid_params
from ddlbench_tpu.ops.util import pallas_out_struct as _out_struct
from ddlbench_tpu.ops.util import tile_bytes as _tile_bytes
from ddlbench_tpu.ops.util import vmem_limit_bytes as _vmem_limit_bytes

NEG_INF = -1e30


def _resident_vmem_bytes(t_inner: int, dh: int, dv: int, itemsize: int,
                         bq: int, bk: int, backward: bool) -> int:
    """What a resident kernel holds in VMEM at these shapes, summed by its
    BlockSpecs as Mosaic lays them out (_tile_bytes; every input and output
    block twice, the pipeline's double buffer). ``dh`` is the q/k width,
    ``dv`` the v/o width; ``t_inner`` the resident side's length: Tk for the
    forward (_fwd_kernel_res), Tq for the one-pass backward
    (_dq_dkv_kernel_res). Checked against the least ``vmem_limit_bytes``
    Mosaic accepts for each kernel (v5e compile, B*H 128; PERF.md, PR 28) at
    22 shapes: T 768..16384, widths 64/64, 128/128, 192/128, bf16 and f32,
    tiles 128^2..1024^2. The sum reads 0.975-1.06 of Mosaic's number
    wherever the sweep is 8 tiles or longer and the tiles are 512 or less
    (1.008 for the backward at T 4096, 192/128), least at (256, 1024); up to
    1.36 at 1024^2 tiles and up to 2.0 at T <= 2048, whose short sweeps
    Mosaic holds in less."""
    f32 = 4  # bytes
    if backward:
        # K, V in and dK, dV out, blockwise
        blocks = 2 * 2 * (_tile_bytes(bk, dh, itemsize)
                          + _tile_bytes(bk, dv, itemsize))
        # Q and dO in, dQ out: whole rows of a head
        resident = 2 * (2 * _tile_bytes(t_inner, dh, itemsize)
                        + _tile_bytes(t_inner, dv, itemsize))
        # lse and delta: [1, Tq] f32 blocks are (1, 128)-tiled, not padded
        # to 8 sublanes
        rows = 2 * 2 * t_inner * f32
        scratch = _tile_bytes(dh, t_inner, f32)  # dQ^T
        # s -> p and dp -> ds in f32, p and ds in the operands' dtype for
        # their products; the dK / dV carry; K^T; one dQ^T tile
        temps = (bq * bk * (2 * f32 + 2 * itemsize)
                 + _tile_bytes(bk, dh, f32) + _tile_bytes(bk, dv, f32)
                 + _tile_bytes(dh, bk, itemsize) + _tile_bytes(dh, bq, f32))
        return blocks + resident + rows + scratch + temps
    # Q in, O and the lse row out, blockwise
    blocks = (2 * (_tile_bytes(bq, dh, itemsize) + _tile_bytes(bq, dv, itemsize))
              + 2 * bq * f32)
    resident = 2 * (_tile_bytes(t_inner, dh, itemsize)
                    + _tile_bytes(t_inner, dv, itemsize))  # K, V
    # s and p in f32, p in the operands' dtype for PV; the O^T accumulator
    temps = bq * bk * (2 * f32 + itemsize) + _tile_bytes(dv, bq, f32)
    return blocks + resident + temps


def _use_streaming(t_inner: int, dh: int, itemsize: int, bq: int, bk: int,
                   stream, backward: bool = False, interpret: bool = False,
                   dv: int | None = None) -> bool:
    """``dh`` is the q/k width and ``dv`` the v/o width (``dh`` when None);
    ``t_inner`` the length of the side a resident kernel would hold: Tk for
    the forward (K and V), Tq for the one-pass backward (``backward``: Q, dO,
    dQ and its f32 scratch)."""
    dv = dh if dv is None else dv
    if stream is not None:
        return bool(stream)
    if not interpret and bq % 128:
        # the resident kernels put the queries on the lanes: [bk, bq] tiles,
        # [1, bq] slices of the lse row
        return True
    return _resident_vmem_bytes(t_inner, dh, dv, itemsize, bq, bk,
                                backward) > RESIDENT_VMEM_BUDGET


def _pick_block(t: int, preferred: int, interpret: bool = False) -> int:
    """Largest divisor of t <= preferred tiling the sequence dimension; on
    real TPU it must also be a multiple of 8 (Mosaic sublane tile —
    ops/util.py:pick_block). Sequence lengths with no aligned divisor get a
    clear error instead of a raw Mosaic one; flash_dispatch below keeps
    "auto" away from such shapes."""
    from ddlbench_tpu.ops.util import pick_block

    b = pick_block(t, preferred, 1 if interpret else 8)
    if b is None:
        raise ValueError(
            f"flash_attention: sequence length {t} has no divisor that is a "
            f"multiple of 8; pad the sequence or use the XLA attention "
            f"backend")
    return b


# Where "auto" stops preferring XLA's fused attention to the kernel. The
# thresholds below come from ONE sweep (perf_runs/attn_crossover.json,
# 2026-07-31, before PR 1: v5e, bf16, H=8, dh=64, forward + backward) of
# kernels PR 25 replaced; nothing has re-measured them. They are kept because
# no cell sits within a factor of 1.5 of any of them (gpt2s-train T 1024,
# kanana2-ep16-train T 4096), and are to be re-measured with the first cell
# that does (ROADMAP.md S8; tools/attnbench.py sweeps, tools/attnpolicy.py
# reduces a sweep to a table).
FLASH_AUTO_MIN_SEQ = 640


def flash_pays_off(seq_len: int, batch: int, prefix_len: int) -> bool:
    """The "auto" backend's flash-or-XLA table over (local sequence length,
    batch, prefix-LM or not). Head widths are no input: every crossing was
    read at dh = 64, and the wider heads that ran on the chip (q/k 192,
    v 128 at T = 4096, PERF.md PR 27-28) sit far past it. What that sweep
    read, flash time over XLA time inverted:

    * T >= 768: flash ahead and growing (1.24x at 768, 2.06x at 2048, B=16
      causal) — flash.
    * T < 640: XLA ahead (0.82-0.96x) — xla.
    * [640, 768): sub-2 ms cells that swung run to run; flash only for the
      plain causal shape that read above 1.0 there (prefix == 0, B <= 32).
    * Prefix-LM at a large batch read 0.61x at B=64, T=256 (the synthmt
      shape): with prefix > 0 and B >= 64, flash from T >= 1024 only.
    """
    if seq_len >= 1024:
        return True
    if prefix_len > 0 and batch >= 64:
        return False
    if seq_len >= 768:
        return True
    if seq_len >= FLASH_AUTO_MIN_SEQ:
        return prefix_len == 0 and batch <= 32
    return False


def flash_dispatch(backend: str, q, k, v, prefix_len: int = 0):
    """(use the kernel, interpreted) for one attention call: the only place
    that decides it, from its arguments, the platform and the operands'
    shapes and vma. ``backend``: "xla" never, "flash" always (interpreted
    off a TPU — tests only, it is slow), "auto" on a TPU where the kernel
    partitions safely (ops/util.takes_pallas), the sequence blocks are
    8-aligned for Mosaic (_pick_block) and flash_pays_off says so for the
    LOCAL shapes (ring attention passes its per-shard blocks)."""
    from ddlbench_tpu.distributed import is_tpu_backend
    from ddlbench_tpu.ops.util import takes_pallas

    if not takes_pallas(backend, "flash", q, k, v):
        return False, False
    if backend == "flash":
        return True, not is_tpu_backend()
    if any(o.shape[2] % 8 for o in (q, k, v)):
        return False, False
    T = max(o.shape[2] for o in (q, k, v))
    B = max(o.shape[0] for o in (q, k, v))
    return flash_pays_off(T, B, prefix_len), False


def _causal_kv_bound(q_hi_pos, k_offset: int, block_k: int, num_k: int,
                     prefix_len: int = 0):
    """Number of leading K blocks any query position <= q_hi_pos can see.

    With a prefix (prefix-LM), K blocks overlapping [0, prefix_len) are
    visible to every query, so the bound is at least the prefix block count.
    """
    visible = q_hi_pos - k_offset + 1  # k positions strictly visible
    if prefix_len:
        visible = jnp.maximum(visible, prefix_len - k_offset)
    nb = (visible + block_k - 1) // block_k
    return jnp.clip(nb, 0, num_k)


def _first_q_block(k_lo, q_offset: int, block_q: int, num_q: int,
                   prefix_len: int = 0):
    """First Q block whose last position can see the K block starting at
    absolute position k_lo; a K block overlapping the prefix is visible to
    every Q block."""
    start = jnp.clip((k_lo - q_offset) // block_q, 0, num_q)
    if prefix_len:
        start = jnp.where(k_lo < prefix_len, 0, start)
    return start


def _block_mask(q_pos, k_pos, prefix_len: int, window: int = 0):
    mask = q_pos >= k_pos
    if prefix_len:
        mask = mask | (k_pos < prefix_len)
    if window:  # the query's own key and the window - 1 before it
        mask = mask & (k_pos > q_pos - window)
    return mask


def _window_kv_start(qi, block_q: int, q_offset: int, k_offset: int,
                     block_k: int, num_k: int, window: int):
    """First K block that holds a key the Q block ``qi`` sees under a
    window: the one with the key ``window - 1`` before the block's first
    query. The lower end of the sweep whose upper end is _causal_kv_bound;
    without a window (0) it is block 0 and nothing is traced."""
    if not window:
        return 0
    first_key = q_offset + qi * block_q - window + 1
    return jnp.clip((first_key - k_offset) // block_k, 0, num_k)


def _window_q_end(kj, block_k: int, k_offset: int, q_offset: int,
                  block_q: int, num_q: int, window: int):
    """One past the last Q block that still sees the K block ``kj`` under a
    window: the one with the query ``window - 1`` after the block's last
    key. The upper end of the sweep that starts at _first_q_block; without
    a window (0) it is num_q and nothing is traced."""
    if not window:
        return num_q
    last_query = k_offset + (kj + 1) * block_k - 1 + window - 1
    return jnp.clip((last_query - q_offset) // block_q + 1, 0, num_q)


# ---------------------------------------------------------------------------
# Block-step math of the streaming kernels: row-major [bq, bk] tiles.
# ---------------------------------------------------------------------------


def _fwd_block_step(q, k_blk, v_blk, m, l, acc, q_pos, k_pos, scale,
                    prefix_len: int, window: int = 0):
    """One online-softmax update of (m, l, acc) against a K/V block."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = _block_mask(q_pos, k_pos, prefix_len, window)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    # p cast to the input dtype so the PV matmul takes the fast MXU path
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _dq_block_step(q, do, lse, delta, k_blk, v_blk, q_pos, k_pos, scale,
                   prefix_len: int, window: int = 0):
    """This q block's dq contribution from one K/V block."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = _block_mask(q_pos, k_pos, prefix_len, window)
    # where() BEFORE the multiply: fully-masked rows have lse ~ -1e30 and
    # exp(s - lse) overflows to inf; inf * 0 would poison dq with NaN.
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    return jax.lax.dot_general(
        ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dkv_block_step(k, v, q_blk, do_blk, lse_blk, delta_blk, q_pos, k_pos,
                    scale, prefix_len: int, window: int = 0):
    """This k block's (dk, dv) contributions from one Q/dO block."""
    s = jax.lax.dot_general(
        q_blk, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = _block_mask(q_pos, k_pos, prefix_len, window)
    # see _dq_block_step: mask inside where() keeps inf out of the matmuls
    p = jnp.where(mask, jnp.exp(s - lse_blk), 0.0)  # [bq, bk]
    dv_add = jax.lax.dot_general(
        p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do_blk, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_blk) * scale
    dk_add = jax.lax.dot_general(
        ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dk_add, dv_add


# ---------------------------------------------------------------------------
# Resident kernels: whole inner sequence in VMEM, fori sweep with causal
# bounds, score tile transposed ([bk, bq]: keys down the sublanes, queries
# along the lanes). Fast path for shapes that fit.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel_res(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k,
                    q_offset, k_offset, num_k, prefix_len, window=0):
    bq = q_ref.shape[1]
    dv = v_ref.shape[2]
    q = q_ref[0]  # [bq, dh] native dtype; MXU accumulates f32 below
    qi = pl.program_id(1)
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    bound = _causal_kv_bound(q_offset + (qi + 1) * bq - 1, k_offset, block_k,
                             num_k, prefix_len)

    def body(j, carry):
        m, l, acc = carry  # [1, bq], [1, bq], [dv, bq]
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk = k_ref[0, rows, :]
        v_blk = v_ref[0, rows, :]
        k_pos = (k_offset + j * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0))
        s = _dot(k_blk, q, _NT) * scale  # [bk, bq]
        mask = _block_mask(q_pos, k_pos, prefix_len, window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=0, keepdims=True)
        # p cast to the input dtype so the PV matmul takes the fast MXU path
        acc_new = acc * corr + _dot(v_blk, p.astype(v_blk.dtype), _TN)
        return m_new, l_new, acc_new

    m0 = jnp.full((1, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, bq), jnp.float32)
    acc0 = jnp.zeros((dv, bq), jnp.float32)
    first = _window_kv_start(qi, bq, q_offset, k_offset, block_k, num_k,
                             window)
    m, l, acc = jax.lax.fori_loop(first, bound, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l_safe).T.astype(o_ref.dtype)
    # LSE of fully-masked rows stays NEG_INF-ish; backward p=exp(s-lse) uses
    # the same masking so those rows contribute nothing either way.
    lse_ref[0] = m + jnp.log(l_safe)


def _dq_dkv_kernel_res(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_sc, *, scale, block_q,
                       q_offset, k_offset, num_q, num_k, prefix_len,
                       window=0):
    """One-pass backward: per (K block, Q block) S, P, dP, dS once, and from
    them dV += P dO, dK += dS Q (the fori carry) and dQ^T += K^T dS, in the
    [dh, Tq] f32 scratch that stays put while the K-block grid axis sweeps
    and is written out, transposed back, on its last block."""
    bk = k_ref.shape[1]
    k = k_ref[0]
    v = v_ref[0]
    k_t = k.T  # [dh, bk], once per K block: dQ^T's tiles come out [dh, bq]
    kj = pl.program_id(1)
    k_lo = k_offset + kj * bk
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    start = _first_q_block(k_lo, q_offset, block_q, num_q, prefix_len)
    end = _window_q_end(kj, bk, k_offset, q_offset, block_q, num_q, window)

    @pl.when(kj == 0)
    def _init():
        dq_sc[:] = jnp.zeros(dq_sc.shape, jnp.float32)

    def body(i, carry):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q_blk = q_ref[0, rows, :]
        do_blk = do_ref[0, rows, :]
        q_pos = (q_offset + i * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1))
        s = _dot(k, q_blk, _NT) * scale  # [bk, bq]
        # where() on the exp, not a multiply: fully-masked rows have lse ~
        # -1e30, exp(s - lse) overflows to inf and inf * 0 would poison the
        # gradients with NaN
        p = jnp.where(_block_mask(q_pos, k_pos, prefix_len, window),
                      jnp.exp(s - lse_ref[0, :, rows]), 0.0)
        dp = _dot(v, do_blk, _NT)
        ds = (p * (dp - delta_ref[0, :, rows]) * scale).astype(k.dtype)
        dq_sc[:, rows] += _dot(k_t, ds, _NN)
        return (dk + _dot(ds, q_blk, _NN),
                dv + _dot(p.astype(do_blk.dtype), do_blk, _NN))

    dk, dv = jax.lax.fori_loop(
        start, end, body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kj == num_k - 1)
    def _fini():
        dq_ref[0] = dq_sc[:].T.astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Streaming kernels: grid (BH, outer, inner), inner blocks via BlockSpec,
# accumulators in VMEM scratch. Constant VMEM in T; any length compiles.
# ---------------------------------------------------------------------------


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                       acc_sc, *, scale, block_k, q_offset, k_offset, num_k,
                       prefix_len, window=0):
    bq = q_ref.shape[1]
    qi, j = pl.program_id(1), pl.program_id(2)
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    bound = _causal_kv_bound(q_offset + (qi + 1) * bq - 1, k_offset, block_k,
                             num_k, prefix_len)
    first = _window_kv_start(qi, bq, q_offset, k_offset, block_k, num_k,
                             window)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[:] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when((j >= first) & (j < bound) if window else j < bound)
    def _step():
        k_pos = (k_offset + j * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
        m, l, acc = _fwd_block_step(
            q_ref[0], k_ref[0], v_ref[0], m_sc[:], l_sc[:], acc_sc[:],
            q_pos, k_pos, scale, prefix_len, window)
        m_sc[:], l_sc[:], acc_sc[:] = m, l, acc

    @pl.when(j == num_k - 1)
    def _fini():
        l_safe = jnp.maximum(l_sc[:], 1e-20)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:] + jnp.log(l_safe)


def _dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                      acc_sc, *, scale, block_k, q_offset, k_offset, num_k,
                      prefix_len, window=0):
    bq = q_ref.shape[1]
    qi, j = pl.program_id(1), pl.program_id(2)
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    bound = _causal_kv_bound(q_offset + (qi + 1) * bq - 1, k_offset, block_k,
                             num_k, prefix_len)
    first = _window_kv_start(qi, bq, q_offset, k_offset, block_k, num_k,
                             window)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when((j >= first) & (j < bound) if window else j < bound)
    def _step():
        k_pos = (k_offset + j * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
        acc_sc[:] += _dq_block_step(
            q_ref[0], do_ref[0], lse_ref[0], delta_ref[0], k_ref[0], v_ref[0],
            q_pos, k_pos, scale, prefix_len, window)

    @pl.when(j == num_k - 1)
    def _fini():
        dq_ref[0] = acc_sc[:].astype(dq_ref.dtype)


def _dkv_kernel_stream(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sc, dv_sc, *, scale, block_q,
                       q_offset, k_offset, num_q, prefix_len, window=0):
    bk = k_ref.shape[1]
    kj, i = pl.program_id(1), pl.program_id(2)
    k_pos = (k_offset + kj * bk
             + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
    start = _first_q_block(k_offset + kj * bk, q_offset, block_q, num_q,
                           prefix_len)
    end = _window_q_end(kj, bk, k_offset, q_offset, block_q, num_q, window)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[:] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when((i >= start) & (i < end) if window else i >= start)
    def _step():
        q_pos = (q_offset + i * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
        dk_add, dv_add = _dkv_block_step(
            k_ref[0], v_ref[0], q_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            q_pos, k_pos, scale, prefix_len, window)
        dk_sc[:] += dk_add
        dv_sc[:] += dv_add

    @pl.when(i == num_q - 1)
    def _fini():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bh(x):
    B, H, T, dh = x.shape
    return x.reshape(B * H, T, dh)


def _kv_row(q, k, v):
    """Row of the folded [B * K, T, .] keys and values that row ``b`` of the
    folded [B * H, T, .] queries reads: query head j attends to key/value
    head j // (H / K). With as many key/value heads as query heads it is
    ``b`` itself, and the kernels' index maps are what they were."""
    H, K = q.shape[1], k.shape[1]
    if v.shape[1] != K or H % K:
        raise ValueError(
            f"flash_attention: {H} query heads over {K} key and "
            f"{v.shape[1]} value heads; k and v share a head count that "
            f"divides q's")
    if K == H:
        return lambda b: b
    G = H // K
    return lambda b: b // H * K + b % H // G


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def flash_attention(q, k, v, q_offset=0, k_offset=0, prefix_len=0,
                    block_q=512, block_k=512, interpret=False, stream=None,
                    window=0):
    """Causal / prefix-LM attention, [B, H, T, dh] -> [B, H, Tq, dh], fused.
    q and k share a width (which sets the scale, 1/sqrt of it); v and the
    output may have another (latent attention: q/k 192 wide, v 128).
    **Head counts:** q is [B, H, Tq, dh]; k and v are [B, K, Tk, .] with ONE
    count K that divides H, and query head j attends to key/value head
    j // (H / K) (grouped queries; K == H is plain multi-head attention and
    lowers to the same kernels as before K could differ). Nothing is
    repeated in HBM: the kernels' index maps send the H / K query heads of
    a group to the same K, V blocks (the resident forward fetches a group's
    K, V once, its block index standing still over the group's rows); the
    backward writes dK, dV per QUERY head and XLA sums each group's, in
    float32, into the [B, K, Tk, .] gradients.

    Semantics match models/transformer.py causal_attention (including the
    q_offset/k_offset absolute-position convention and the prefix-LM rule:
    absolute key positions < prefix_len are visible to every query — the
    seq2seq source segment); fully-masked rows return 0. ``block_q`` /
    ``block_k`` are upper bounds: blocks shrink to divide the sequence. The
    default 512x512 was the fastest of {128, 256, 512}^2 for the forward and
    for the one-pass backward at T=1024, 2048, 4096 and 8192 (dh=64, bf16,
    one v5e; PERF.md, PR 25) and of {256, 512}^2 at T=4096, q/k 192, v 128
    (PR 28). ``stream`` forces the streaming (True) or resident (False) grid
    design; None picks per kernel, resident while what the kernel would hold
    in VMEM fits the budget (_use_streaming; module docstring for the
    accounted bytes and the measured ms of both designs).

    ``window`` > 0 is a sliding window on top of the causal rule: the query
    at absolute position i sees the keys j with i - window < j <= i,
    ``window`` keys with its own among them (models/smallthinker.py). Every
    kernel takes it in its tile mask and in its sweep: the forward and dq
    sweeps start at the first key block that holds a visible key, the dK/dV
    sweeps end at the last query block that still sees the key block, so a
    tile wholly outside the band costs nothing in the resident kernels and
    its fetch alone in the streaming ones. A window that reaches every key
    anyway (``_effective_window``) and ``window`` 0 are the same call as
    without the argument, down to the lowered text; a window with a prefix
    raises (no model has both).
    """
    o, _ = _flash_fwd_impl(q, k, v, q_offset, k_offset, prefix_len, block_q,
                           block_k, interpret, stream, window)
    return o


def _effective_window(window: int, q, k, q_offset: int, k_offset: int,
                      prefix_len: int) -> int:
    """``window`` as the kernels get it: 0 where it is 0 or masks nothing
    (the last query still sees the first key), so that such a call traces
    the kernels it traced before the argument existed."""
    if window < 0:
        raise ValueError(f"flash_attention: window {window} is negative")
    if window and prefix_len:
        raise ValueError(
            "flash_attention: a window with a prefix is not written (the "
            "prefix's keys are visible to every query, the window's are not)")
    if q_offset + q.shape[2] - 1 - k_offset < window:
        return 0
    return window


def _flash_fwd_impl(q, k, v, q_offset, k_offset, prefix_len, block_q, block_k,
                    interpret, stream, window=0):
    """-> (o [B, H, Tq, dh], lse [B*H, 1, Tq] f32). The lse is kept as ROWS:
    dense in HBM (a [.., Tq, 1] f32 array is tiled (8, 128) on its last two
    dimensions, 128 times its size) and what the resident kernels read."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, dh = q.shape  # dh: the q/k width, which sets the scale
    Tk, dv = k.shape[2], v.shape[3]  # dv: the v/o width
    bq = _pick_block(Tq, block_q, interpret)
    bk = _pick_block(Tk, block_k, interpret)
    num_q, num_k = Tq // bq, Tk // bk
    scale = 1.0 / math.sqrt(dh)
    qr, kr, vr = _bh(q), _bh(k), _bh(v)
    kv = _kv_row(q, k, v)
    BH = B * H
    isz = q.dtype.itemsize
    streaming = _use_streaming(Tk, dh, isz, bq, bk, stream,
                               interpret=interpret, dv=dv)
    f32 = jnp.float32

    kw = dict(scale=scale, block_k=bk, q_offset=q_offset, k_offset=k_offset,
              num_k=num_k, prefix_len=prefix_len)
    window = _effective_window(window, q, k, q_offset, k_offset, prefix_len)
    if window:
        kw["window"] = window
    if streaming:
        kern = functools.partial(_fwd_kernel_stream, **kw)
        grid = (BH, num_q, num_k)
        in_specs = [
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (kv(b), j, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            # [T, 1] (not [T]): TPU block tiling wants two trailing dims
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ]
        lse_shape = (BH, Tq, 1)
        scratch = [pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32),
                   pltpu.VMEM((bq, dv), f32)]
        semantics = ("parallel", "parallel", "arbitrary")
        vmem_limit = None  # T-independent blocks: Mosaic's default holds them
    else:
        kern = functools.partial(_fwd_kernel_res, **kw)
        grid = (BH, num_q)
        in_specs = [
            pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tk, dh), lambda b, i: (kv(b), 0, 0)),
            pl.BlockSpec((1, Tk, dv), lambda b, i: (kv(b), 0, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ]
        lse_shape = (BH, 1, Tq)
        scratch = []
        semantics = ("parallel", "parallel")
        vmem_limit = _vmem_limit_bytes(_resident_vmem_bytes(
            Tk, dh, dv, isz, bq, bk, backward=False))

    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            _out_struct((BH, Tq, dv), q.dtype, q, k, v),
            _out_struct(lse_shape, jnp.float32, q, k, v),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attn_fwd",
        **_grid_params(interpret, *semantics, vmem_limit_bytes=vmem_limit),
    )(qr, kr, vr)
    return o.reshape(B, H, Tq, dv), lse.reshape(BH, 1, Tq)


def _flash_fwd(q, k, v, q_offset, k_offset, prefix_len, block_q, block_k,
               interpret, stream, window):
    o, lse = _kept(*_flash_fwd_impl(q, k, v, q_offset, k_offset, prefix_len,
                                    block_q, block_k, interpret, stream,
                                    window))
    return o, (q, k, v, o, lse)


def _flash_bwd(q_offset, k_offset, prefix_len, block_q, block_k, interpret,
               stream, window, res, g):
    return _flash_bwd_core(q_offset, k_offset, prefix_len, block_q, block_k,
                           interpret, stream, res, g, None, window)


def _flash_bwd_core(q_offset, k_offset, prefix_len, block_q, block_k,
                    interpret, stream, res, g, g_lse, window=0):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse = res
    B, H, Tq, dh = q.shape  # dh: the q/k width; dv: the v/o width
    Tk, dv = k.shape[2], v.shape[3]
    bq = _pick_block(Tq, block_q, interpret)
    bk = _pick_block(Tk, block_k, interpret)
    num_q, num_k = Tq // bq, Tk // bk
    BH = B * H
    isz = q.dtype.itemsize

    # delta = rowsum(dO * O) — cheap elementwise+reduce, XLA fuses it. The
    # lse cotangent (flash_attention_lse) enters every ds exactly like -delta
    # (both multiply p rowwise: ds = p∘(dp - delta + lse_bar)), so it is a
    # delta shift and the kernels are shared.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    qr, kr, vr, gr = _bh(q), _bh(k), _bh(v), _bh(g)
    kv = _kv_row(q, k, v)
    K = k.shape[1]
    f32 = jnp.float32
    shape4 = lambda x, T: x.reshape(B, H, T, x.shape[-1])
    # dK and dV leave the kernels per QUERY head ([B * H, Tk, .]); each
    # group's H / K are summed here, in float32 (nothing to sum at K == H)
    grad_of = lambda x: _out_struct((BH,) + x.shape[1:], x.dtype, qr, kr, vr,
                                    gr)

    def kv_grad(x):
        x = shape4(x, Tk)
        if K == H:
            return x
        return jnp.sum(x.reshape(B, K, H // K, Tk, -1).astype(f32),
                       axis=2).astype(x.dtype)

    kw = dict(scale=1.0 / math.sqrt(dh), q_offset=q_offset,
              k_offset=k_offset, prefix_len=prefix_len)
    window = _effective_window(window, q, k, q_offset, k_offset, prefix_len)
    if window:
        kw["window"] = window

    # the one-pass kernel keeps the Q side resident: Q, dO, lse, delta, dQ
    if not _use_streaming(Tq, dh, isz, bq, bk, stream, backward=True,
                          interpret=interpret, dv=dv):
        k_blk = pl.BlockSpec((1, bk, dh), lambda b, j: (kv(b), j, 0))
        v_blk = pl.BlockSpec((1, bk, dv), lambda b, j: (kv(b), j, 0))
        dk_blk = pl.BlockSpec((1, bk, dh), lambda b, j: (b, j, 0))
        dv_blk = pl.BlockSpec((1, bk, dv), lambda b, j: (b, j, 0))
        q_all = pl.BlockSpec((1, Tq, dh), lambda b, j: (b, 0, 0))
        do_all = pl.BlockSpec((1, Tq, dv), lambda b, j: (b, 0, 0))
        row = pl.BlockSpec((1, 1, Tq), lambda b, j: (b, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dq_dkv_kernel_res, block_q=bq, num_q=num_q,
                              num_k=num_k, **kw),
            grid=(BH, num_k),
            in_specs=[k_blk, v_blk, q_all, do_all, row, row],
            out_specs=[q_all, dk_blk, dv_blk],
            out_shape=[grad_of(qr), grad_of(kr), grad_of(vr)],
            scratch_shapes=[pltpu.VMEM((dh, Tq), f32)],
            interpret=interpret,
            # benchmarks/kernels/flash_attn.py finds the flash kernels'
            # trace events by substring: "flash_attn_dq" has to be in it
            name="flash_attn_dq_dkv",
            **_grid_params(
                interpret, "parallel", "arbitrary",
                vmem_limit_bytes=_vmem_limit_bytes(_resident_vmem_bytes(
                    Tq, dh, dv, isz, bq, bk, backward=True))),
        )(kr, vr, qr, gr, lse, delta.reshape(BH, 1, Tq))
        return shape4(dq, Tq), kv_grad(dk), kv_grad(dv)

    # the streaming pair reads lse and delta as columns, blockwise
    lse_c, delta_c = lse.reshape(BH, Tq, 1), delta.reshape(BH, Tq, 1)
    semantics = ("parallel", "parallel", "arbitrary")
    q_blk = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    do_blk = pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0))
    q_col = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    k_blk = pl.BlockSpec((1, bk, dh), lambda b, i, j: (kv(b), j, 0))
    v_blk = pl.BlockSpec((1, bk, dv), lambda b, i, j: (kv(b), j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_stream, block_k=bk, num_k=num_k, **kw),
        grid=(BH, num_q, num_k),
        in_specs=[q_blk, k_blk, v_blk, do_blk, q_col, q_col],
        out_specs=q_blk,
        out_shape=grad_of(qr),
        scratch_shapes=[pltpu.VMEM((bq, dh), f32)],
        interpret=interpret,
        name="flash_attn_dq",
        **_grid_params(interpret, *semantics),
    )(qr, kr, vr, gr, lse_c, delta_c)

    # the dkv kernel streams Q-side operands: Q, dO, lse, delta
    k_blk = pl.BlockSpec((1, bk, dh), lambda b, j, i: (kv(b), j, 0))
    v_blk = pl.BlockSpec((1, bk, dv), lambda b, j, i: (kv(b), j, 0))
    dk_blk = pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0))
    dv_blk = pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0))
    q_blk = pl.BlockSpec((1, bq, dh), lambda b, j, i: (b, i, 0))
    do_blk = pl.BlockSpec((1, bq, dv), lambda b, j, i: (b, i, 0))
    q_col = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_stream, block_q=bq, num_q=num_q, **kw),
        grid=(BH, num_k, num_q),
        in_specs=[k_blk, v_blk, q_blk, do_blk, q_col, q_col],
        out_specs=[dk_blk, dv_blk],
        out_shape=[grad_of(kr), grad_of(vr)],
        scratch_shapes=[pltpu.VMEM((bk, dh), f32), pltpu.VMEM((bk, dv), f32)],
        interpret=interpret,
        name="flash_attn_dkv",
        **_grid_params(interpret, *semantics),
    )(kr, vr, qr, gr, lse_c, delta_c)
    return shape4(dq, Tq), kv_grad(dk), kv_grad(dv)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# What a rematerialized layer keeps of this module (models/layers.apply_slice
# saves the values of these names through its jax.checkpoint): the backward
# kernels read (q, k, v, o, lse), and o and lse are all the forward kernel
# computes, so with both kept the rematerialized computation has no reader
# left for flash_attn_fwd and drops it. They are H * dv / d of the layer's
# input, which the checkpoint keeps anyway. Outside a checkpoint with such a
# policy a name is an identity and lowers to no operation.
REMAT_KEPT_NAMES = ("flash_attn_o", "flash_attn_lse")


def _kept(o, lse):
    """The forward kernel's outputs under their names. A forward rule hands
    THESE on, as its primal output and in its residuals alike."""
    from jax.ad_checkpoint import checkpoint_name

    name_o, name_lse = REMAT_KEPT_NAMES
    return checkpoint_name(o, name_o), checkpoint_name(lse, name_lse)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention_lse(q, k, v, q_offset=0, k_offset=0, prefix_len=0,
                        block_q=512, block_k=512, interpret=False,
                        stream=None, window=0):
    """flash_attention that ALSO returns the per-row logsumexp: (o, lse) with
    lse [B, H, Tq] f32.

    This is the building block for blockwise/ring attention over a
    distributed sequence: partial results (o_i, lse_i) against different K/V
    blocks combine exactly as o = Σ_i exp(lse_i - lse_tot) o_i with
    lse_tot = logaddexp_i(lse_i) (models/transformer.py ring_attention).
    Both outputs are differentiable: d lse/d scores = p, which folds into the
    existing backward kernels as a delta shift (ds = p∘(dp - (delta - lse_bar))),
    so the backward kernels are reused unchanged. ``window``: as
    flash_attention's.
    """
    out, _ = _flash_lse_fwd(q, k, v, q_offset, k_offset, prefix_len, block_q,
                            block_k, interpret, stream, window)
    return out


def _flash_lse_fwd(q, k, v, q_offset, k_offset, prefix_len, block_q, block_k,
                   interpret, stream, window):
    o, lse = _kept(*_flash_fwd_impl(q, k, v, q_offset, k_offset, prefix_len,
                                    block_q, block_k, interpret, stream,
                                    window))
    B, H, Tq, _ = q.shape
    return (o, lse.reshape(B, H, Tq)), (q, k, v, o, lse)


def _flash_lse_bwd(q_offset, k_offset, prefix_len, block_q, block_k,
                   interpret, stream, window, res, cots):
    g_o, g_lse = cots
    return _flash_bwd_core(q_offset, k_offset, prefix_len, block_q, block_k,
                           interpret, stream, res, g_o, g_lse, window)


flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)
