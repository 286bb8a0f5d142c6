"""Where an attention call takes the Pallas kernel: flash_dispatch
(ops/flash_attention.py), the one rule, over ops/util.takes_pallas.

Pure logic over its arguments, the platform and the operands' shapes —
testable off-TPU by monkeypatching the backend probe. Pins the rule: on TPU,
"auto" takes the flash kernel only for 8-aligned local sequences past
FLASH_AUTO_MIN_SEQ (the table's provenance stands beside it), and a forced
"flash"/"xla" bypasses the heuristics entirely.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.models import transformer as tfm
from ddlbench_tpu.ops.flash_attention import (FLASH_AUTO_MIN_SEQ,
                                              flash_dispatch, flash_pays_off)


@pytest.fixture
def on_tpu(monkeypatch):
    import ddlbench_tpu.distributed as dist

    monkeypatch.setattr(dist, "is_tpu_backend", lambda: True)


def _qkv(T, B=2, H=4, dh=8):
    x = jnp.zeros((B, H, T, dh), jnp.bfloat16)
    return x, x, x


def test_auto_short_seq_takes_xla(on_tpu):
    use_flash, _ = flash_dispatch("auto", *_qkv(256))
    assert not use_flash


def test_auto_long_seq_takes_flash(on_tpu):
    use_flash, interpret = flash_dispatch("auto", *_qkv(1024))
    assert use_flash and not interpret


def test_auto_threshold_boundary(on_tpu):
    T = FLASH_AUTO_MIN_SEQ
    assert flash_dispatch("auto", *_qkv(T))[0]
    assert not flash_dispatch("auto", *_qkv(T - 8))[0]


def test_auto_unaligned_seq_takes_xla(on_tpu):
    use_flash, _ = flash_dispatch("auto", *_qkv(1027))
    assert not use_flash


def test_policy_prefix_lm_large_batch_takes_xla(on_tpu):
    """The strongest measured XLA signal: prefix-LM at B=64 (synthmt shape,
    0.61x flash) stays on XLA through the noise band; plain causal at the
    same length flips to flash."""
    assert not flash_dispatch("auto", *_qkv(768, B=64), prefix_len=128)[0]
    assert flash_dispatch("auto", *_qkv(768, B=64), prefix_len=0)[0]
    # but 1024+ is a flash win in every measured configuration
    assert flash_dispatch("auto", *_qkv(1024, B=64), prefix_len=128)[0]


def test_policy_noise_band_is_conservative(on_tpu):
    """[640, 768): flash only for the plain causal small-batch shape."""
    assert flash_dispatch("auto", *_qkv(640, B=16))[0]
    assert not flash_dispatch("auto", *_qkv(640, B=64))[0]
    assert not flash_dispatch("auto", *_qkv(640, B=16), prefix_len=64)[0]


def test_policy_table_is_monotone_in_seq_len():
    """Sanity: for any fixed (B, prefix), longer sequences never flip flash
    back OFF — the table must stay a crossover, not an interval."""
    for B in (2, 16, 32, 64, 128):
        for prefix in (0, 128):
            decisions = [flash_pays_off(T, B, prefix)
                         for T in (128, 256, 512, 640, 768, 1024, 2048, 8192)]
            assert decisions == sorted(decisions), (B, prefix, decisions)


def test_forced_flash_ignores_threshold(on_tpu):
    use_flash, interpret = flash_dispatch("flash", *_qkv(256))
    assert use_flash and not interpret


def test_forced_xla_ignores_length(on_tpu):
    assert not flash_dispatch("xla", *_qkv(4096))[0]


def test_off_tpu_auto_never_flash():
    assert not flash_dispatch("auto", *_qkv(4096))[0]


def test_values_match_across_backends():
    # policy change must not change numerics: xla vs flash-interpret on CPU
    import jax

    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (1, 2, 16, 8), jnp.float32)
    k = jax.random.normal(k2, (1, 2, 16, 8), jnp.float32)
    v = jax.random.normal(k3, (1, 2, 16, 8), jnp.float32)
    ref = tfm.causal_attention(q, k, v)
    from ddlbench_tpu.ops.flash_attention import flash_attention

    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
