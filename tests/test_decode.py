"""KV-cached incremental decoding vs the full-forward reference decoders.

The cached path (models/decode.py) must produce identical token streams and
scores to the full-forward loops in models/seq2seq.py, for both the seq2seq
(prefix-LM) and causal-LM model families.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # compile-heavy (see conftest --runslow)

import ddlbench_tpu.models.seq2seq as s2s
import ddlbench_tpu.models.decode as dec
from ddlbench_tpu.models.layers import apply_model, init_model

TINY = dict(d_model=32, n_layers=2, n_heads=4)
s2s._VARIANTS["seq2seq_t"] = TINY
T_TOTAL, SRC, VOCAB = 16, 8, 64


# the full-forward reference path and cached path must share numerics: both
# models are built on the einsum attention
@pytest.fixture(scope="module")
def mt_model():
    model = s2s.build_seq2seq("seq2seq_t", (T_TOTAL,), VOCAB, SRC,
                              attention_backend="xla")
    params, state, _ = init_model(model, jax.random.key(0))
    return model, params, state


@pytest.fixture(scope="module")
def lm_model():
    from ddlbench_tpu.models.transformer import build_transformer, _VARIANTS

    _VARIANTS["transformer_t"] = TINY
    model = build_transformer("transformer_t", (T_TOTAL,), VOCAB,
                              attention_backend="xla")
    params, state, _ = init_model(model, jax.random.key(3))
    return model, params, state


def test_supports_cache(mt_model, lm_model):
    assert dec.supports_cache(mt_model[0])
    assert dec.supports_cache(lm_model[0])
    from ddlbench_tpu.models.zoo import get_model

    assert not dec.supports_cache(get_model("resnet18", "mnist"))


def test_prefill_matches_full_forward(mt_model):
    model, params, state = mt_model
    src = jax.random.randint(jax.random.key(1), (2, SRC), 0, VOCAB, jnp.int32)
    caches = dec.init_caches(model, params, 2, T_TOTAL, jnp.float32)
    logits, caches = dec.prefill(model, params, state, caches, src)
    ref, _ = apply_model(model, params, state, src, False)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_decode_one_matches_full_forward(mt_model):
    model, params, state = mt_model
    x = jax.random.randint(jax.random.key(2), (2, SRC + 3), 0, VOCAB, jnp.int32)
    # prefill the first SRC tokens, then decode 3 tokens one at a time
    caches = dec.init_caches(model, params, 2, T_TOTAL, jnp.float32)
    logits, caches = dec.prefill(model, params, state, caches, x[:, :SRC])
    step_logits = [logits[:, -1]]
    for t in range(SRC, SRC + 3):
        lg, caches = dec.decode_one(model, params, state, caches,
                                    x[:, t:t + 1], t)
        step_logits.append(lg[:, 0])
    # reference: full forward over the SRC+3 prefix, padded to T
    pad = jnp.zeros((2, T_TOTAL - (SRC + 3)), jnp.int32)
    ref, _ = apply_model(model, params, state,
                         jnp.concatenate([x, pad], axis=1), False)
    for i, lg in enumerate(step_logits):
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(ref[:, SRC - 1 + i]),
                                   rtol=2e-5, atol=2e-5)


def test_cached_greedy_equals_reference(mt_model):
    model, params, state = mt_model
    src = jax.random.randint(jax.random.key(4), (3, SRC), 0, VOCAB, jnp.int32)
    ref = s2s.greedy_decode(model, params, state, src, T_TOTAL, use_cache=False)
    got = s2s.greedy_decode(model, params, state, src, T_TOTAL, use_cache=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_cached_beam_equals_reference(mt_model):
    model, params, state = mt_model
    src = jax.random.randint(jax.random.key(5), (2, SRC), 0, VOCAB, jnp.int32)
    ref_x, ref_s = s2s.beam_search_decode(model, params, state, src, T_TOTAL,
                                          beam=3, use_cache=False)
    got_x, got_s = s2s.beam_search_decode(model, params, state, src, T_TOTAL,
                                          beam=3, use_cache=True)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(ref_x))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                               rtol=1e-4, atol=1e-5)


def test_causal_lm_cached_greedy(lm_model):
    """The cached decoder also serves causal LMs (arbitrary prompt length)."""
    model, params, state = lm_model
    prompt = jax.random.randint(jax.random.key(6), (2, 5), 0, VOCAB, jnp.int32)
    out = dec.greedy_decode(model, params, state, prompt, T_TOTAL)
    assert out.shape == (2, T_TOTAL)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))
    # reference: manual full-forward greedy
    x = jnp.zeros((2, T_TOTAL), jnp.int32).at[:, :5].set(prompt)
    for t in range(5, T_TOTAL):
        logits, _ = apply_model(model, params, state, x, False)
        x = x.at[:, t].set(jnp.argmax(logits[:, t - 1], -1).astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_unsupported_model_raises(mt_model):
    from ddlbench_tpu.models.zoo import get_model

    cnn = get_model("resnet18", "mnist")
    params, state, _ = init_model(cnn, jax.random.key(0))
    with pytest.raises(NotImplementedError, match="without cached-decode"):
        dec.greedy_decode(cnn, params, state,
                          jnp.zeros((1, 8), jnp.int32), 16)


def test_decodebench_tool(capsys):
    import json
    import ddlbench_tpu.models.seq2seq as s2s_mod
    from ddlbench_tpu.config import DATASETS, DatasetSpec
    from ddlbench_tpu.tools import decodebench

    # register a tiny variant + benchmark so the tool runs fast on CPU
    s2s_mod._VARIANTS["seq2seq_bench_t"] = TINY
    tiny_spec = DatasetSpec("tinymtb", (T_TOTAL,), VOCAB, 100, 10,
                            kind="seq2seq", src_len=SRC)
    patched = dict(DATASETS)
    patched["tinymtb"] = tiny_spec
    import unittest.mock as mock
    with mock.patch.dict("ddlbench_tpu.config.DATASETS", patched):
        rc = decodebench.main(["-m", "seq2seq_bench_t", "-b", "tinymtb",
                               "--batch", "2", "--beam", "2",
                               "--repeats", "1", "--platform", "cpu"])
    assert rc == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 6
    modes = {(l["mode"], l["variant"]) for l in lines}
    assert modes == {("greedy", "paged"), ("beam", "paged"),
                     ("greedy", "cached"), ("beam", "cached"),
                     ("greedy", "full"), ("beam", "full")}
    assert all(l["tokens_per_sec"] > 0 for l in lines)
    # provenance rides every row (distributed.backend_provenance), so a
    # cpu-fallback run can never masquerade as an on-chip measurement
    assert all(l["jax_backend"] == "cpu" for l in lines)
    assert all(l["cpu_fallback"] is False for l in lines)  # cpu was pinned


def test_moe_cached_decode_matches_full_forward():
    """MoE cached decode: per-token top-1 expert FFN equals the training
    apply whenever capacity doesn't drop tokens (ample capacity_factor)."""
    from tiny_models import tiny_moe, TINY_LM

    model = tiny_moe()  # capacity_factor = n_experts: nothing ever drops
    assert dec.supports_cache(model)
    params, state, _ = init_model(model, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(7), (2, 6), 0,
                                TINY_LM.num_classes, jnp.int32)

    out = dec.greedy_decode(model, params, state, prompt, 12)
    assert out.shape == (2, 12)
    # reference: full-forward greedy over the UNPADDED prefix each step
    # (padding would perturb MoE routing/capacity, unlike dense models)
    x = prompt
    for t in range(6, 12):
        logits, _ = apply_model(model, params, state, x, False)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        x = jnp.concatenate([x, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
