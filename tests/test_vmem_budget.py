"""Block selection of the fused-xent kernels from what they hold in VMEM.

Pure-Python and fast (in the default commit gate): the sizing only matters on
real TPU hardware — interpret-mode kernel tests never reach it — and its
first regression surfaced only as an on-chip Mosaic scoped-VMEM rejection
(perf_runs, round 3). These tests pin the arithmetic off-chip: one sum of what
a kernel holds (``_held_vmem_bytes``), blocks shrunk from their caps until it
fits ``ops/util.RESIDENT_VMEM_BUDGET`` (64 MiB, the budget flash attention
reads too), that sum and a quarter as ``vmem_limit_bytes``. The same sum
against the least limit Mosaic itself accepts is compiled for a described
v5e in tests/test_v5e_scope_kernels.py (the one file that loads the TPU's
compiler).
"""

import jax.numpy as jnp
import pytest

from ddlbench_tpu.ops import fused_xent as fx
from ddlbench_tpu.ops.util import RESIDENT_VMEM_BUDGET, vmem_limit_bytes

MiB = 1 << 20
N = 16384  # both cells: 16 x 1024 and 4 x 4096 tokens a step
GPT2S = (N, 768, 50304)      # gpt2s-train's head
KANANA2 = (N, 2048, 16128)   # kanana2-ep16-train's


@pytest.mark.parametrize("shape,fwd,bwd", [
    (GPT2S, (1024, 2048), (1024, 2048)),
    (KANANA2, (1024, 2048), (256, 2048)),  # [D, bv] f32 dW blocks: fewer rows
])
def test_the_cells_blocks_fit_the_budget_with_their_margin(shape, fwd, bwd):
    n, D, V = shape
    assert fx._blocks("fwd", n, D, V, 2, False) == fwd
    assert fx._blocks("bwd", n, D, V, 2, False) == bwd
    for kernel, (br, bv) in (("fwd", fwd), ("bwd", bwd)):
        held = fx._held_vmem_bytes(kernel, br, bv, D, 2)
        assert 8 * MiB < held <= RESIDENT_VMEM_BUDGET == 64 * MiB
        # the limit passed to Mosaic: the sum and a quarter, inside the
        # 128 MiB a v5e core has
        assert vmem_limit_bytes(held) == held + held // 4 <= 80 * MiB


def test_the_vocabulary_block_is_freed_from_vs_divisors():
    # 50304 = 128 * 3 * 131: its lane-aligned divisors are 128, 384, 16768
    # and 50304, which held every kernel of gpt2s-train at bv 384
    for kernel in ("fwd", "bwd"):
        br, bv = fx._blocks(kernel, *GPT2S, 2, False)
        assert bv > 384 and bv % 128 == 0 and 50304 % bv
        nv = -(-50304 // bv)
        assert (nv - 1) * bv < 50304 <= nv * bv  # the last block is cut


@pytest.mark.parametrize("V", [32768, 50304, 16128, 1024, 384, 128])
@pytest.mark.parametrize("D", [128, 512, 1024, 4096])
def test_every_pick_is_lane_aligned_and_evened_out(V, D):
    for kernel in ("fwd", "bwd"):
        for isz in (2, 4):
            br, bv = fx._blocks(kernel, N, D, V, isz, False)
            assert bv % 128 == 0 and 128 <= bv <= max(128, fx.V_BLOCK)
            assert br % fx.SUB_ROWS == 0 or br < fx.SUB_ROWS and br % 16 == 0
            nv = -(-V // bv)
            # evened out over the blocks V needs: the cut wastes < 128 a block
            assert nv * bv - V < 128 * nv
            assert fx._held_vmem_bytes(kernel, br, bv, D,
                                       isz) <= RESIDENT_VMEM_BUDGET


def test_f32_operands_take_smaller_blocks_than_bf16():
    V, D = 32768, 2048
    b16, b32 = (fx._blocks("bwd", N, D, V, isz, False) for isz in (2, 4))
    assert b32[0] * b32[1] < b16[0] * b16[1]
    assert fx._held_vmem_bytes("bwd", *b32, D, 4) <= RESIDENT_VMEM_BUDGET


def test_few_rows_take_one_row_block():
    # a small batch is one row block, rounded to bf16's 16 sublanes under a
    # z tile and to whole z tiles above it
    assert fx._blocks("fwd", 100, 768, 50304, 2, False)[0] == 112
    assert fx._blocks("bwd", 100, 768, 50304, 2, False)[0] == 112
    assert fx._blocks("bwd", 600, 768, 50304, 2, False)[0] == 768


def test_interpret_and_odd_vocab_paths():
    # interpret: any block runs (CPU has no VMEM); lane-aligned where the
    # vocabulary is, so that the tests walk the kernels' 128-lane columns
    assert fx._blocks("fwd", 7, 16, 40, 4, True) == (7, 40)
    assert fx._blocks("bwd", 7, 16, 896, 4, True) == (7, 896)
    # real TPU: a vocabulary that is no multiple of 128 lanes cannot be
    # tiled (caller falls back to XLA)
    assert fx._blocks("fwd", N, 512, 32770, 2, False) is None
    assert fx._blocks("bwd", N, 512, 32770, 2, False) is None


def test_very_wide_d_is_infeasible_at_any_block():
    # D = 65536: dW's double-buffered float32 [D, 128] blocks alone are
    # 64 MiB; the forward's [D, 128] W blocks still fit
    assert fx._blocks("bwd", N, 65536, 32768, 2, False) is None
    assert fx._blocks("fwd", N, 65536, 32768, 2, False) is not None
    # a wide D that does fit: rows and columns both shrunk from their caps
    br, bv = fx._blocks("bwd", N, 8192, 32768, 2, False)
    assert br < fx.ROW_BLOCK and bv < fx.V_BLOCK


def test_feasibility_gate_falls_back_for_wide_d():
    import jax

    # only shapes and dtypes are read
    rows = jax.ShapeDtypeStruct((N, 1), jnp.bfloat16)
    ok = jax.ShapeDtypeStruct((512, 32768), jnp.bfloat16)
    wide = jax.ShapeDtypeStruct((65536, 32768), jnp.bfloat16)
    assert fx._pallas_feasible(rows, ok, "auto", False)
    assert not fx._pallas_feasible(rows, wide, "auto", False)  # chunked-XLA
    with pytest.raises(ValueError, match="no feasible Pallas blocking"):
        fx._pallas_feasible(rows, wide, "pallas", False)
    assert fx._pallas_feasible(rows, wide, "pallas", True)  # interpret


def test_feasibility_gate_prices_the_wider_dtype():
    """A float32 head under bf16 rows is sized at 4 bytes (the launch sites
    size with the wider of the two, and so must the gate)."""
    import jax

    odd = jax.ShapeDtypeStruct((512, 32770), jnp.float32)
    assert not fx._pallas_feasible(
        jax.ShapeDtypeStruct((N, 1), jnp.bfloat16), odd, "auto", False)
    V, D = 32768, 4096
    assert (fx._blocks("bwd", N, D, V, 4, False)
            != fx._blocks("bwd", N, D, V, 2, False))
