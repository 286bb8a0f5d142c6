"""Which kernel a call runs is decided from what the call is given.

The attention backend is a value a model is built with (zoo.get_model ->
the builders' closures -> causal_attention), and "this trace is a plain jit
over GSPMD-sharded operands" is a scoped mark (ops/util.gspmd_jit):
nothing a strategy, a tool or a test did earlier in the process takes part.
"""

import jax
import jax.numpy as jnp
import pytest

from ddlbench_tpu import config as pcfg
from ddlbench_tpu.ops.util import (gspmd_jit, pallas_partitions_safely,
                                   takes_pallas)
from ddlbench_tpu.parallel import make_strategy

BATCH = 2


def _strategy(backend):
    cfg = pcfg.RunConfig(benchmark="synthtext", arch="transformer_t",
                         strategy="single", compute_dtype="float32",
                         batch_size=BATCH, attention_backend=backend)
    return make_strategy(cfg)


def _lowered_text(strategy):
    ds = pcfg.DATASETS["synthtext"]
    state = jax.eval_shape(strategy.init, jax.random.key(0))
    x = y = jax.ShapeDtypeStruct((BATCH, *ds.image_size), jnp.int32)
    return strategy.train_step.lower(
        state, x, y, jax.ShapeDtypeStruct((), jnp.float32)).as_text()


def test_a_strategy_compiles_with_the_backend_it_was_built_with():
    """A step is traced at its first call, not where its strategy is made:
    building a second strategy in between must not change the first."""
    alone = _lowered_text(_strategy("xla"))
    a = _strategy("xla")
    b = _strategy("flash")  # off the TPU: the interpreted kernel
    assert _lowered_text(a) == alone
    assert _lowered_text(b) != alone


def test_the_gspmd_mark_nests():
    x = jnp.zeros((8, 8))
    assert pallas_partitions_safely(x)
    with gspmd_jit():
        assert not pallas_partitions_safely(x)
        with gspmd_jit():
            assert not pallas_partitions_safely(x)
        # the inner exit leaves the rest of the outer body marked
        assert gspmd_jit.active()
        assert not pallas_partitions_safely(x)
    assert not gspmd_jit.active() and pallas_partitions_safely(x)


def test_the_gspmd_mark_is_popped_when_the_body_raises():
    with pytest.raises(RuntimeError):
        with gspmd_jit():
            raise RuntimeError("traced body failed")
    assert not gspmd_jit.active()


@pytest.mark.parametrize("forced", ["flash", "pallas"])
def test_the_shared_rule(monkeypatch, forced):
    """What flash_dispatch and fused_linear_xent share (ops/util.py)."""
    import ddlbench_tpu.distributed as dist

    x = jnp.zeros((8, 8))
    assert takes_pallas(forced, forced, x)
    assert not takes_pallas("xla", forced, x)
    assert not takes_pallas("auto", forced, x)  # the CPU: never by itself
    monkeypatch.setattr(dist, "is_tpu_backend", lambda: True)
    assert takes_pallas("auto", forced, x)
    assert not takes_pallas("xla", forced, x)
    with gspmd_jit():
        assert not takes_pallas("auto", forced, x)
        assert takes_pallas(forced, forced, x)  # forcing is the caller's
    with pytest.raises(ValueError, match="backend"):
        takes_pallas("cuda", forced, x)
