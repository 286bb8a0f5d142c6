"""Seq2seq (GNMT analog) workload: prefix-LM mask semantics, label smoothing,
greedy/beam decode, and training under multiple strategies.

Reference parity target: SURVEY.md §2 C13 (translation workload) — see
models/seq2seq.py for the TPU-first redesign rationale.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # compile-heavy (see conftest --runslow)

from ddlbench_tpu.config import DatasetSpec, RunConfig
import ddlbench_tpu.models.seq2seq as s2s
from ddlbench_tpu.models.layers import init_model, apply_model
from ddlbench_tpu.parallel.common import cross_entropy_loss

TINY_MT = DatasetSpec("tinymt", (16,), 64, 1000, 100, kind="seq2seq", src_len=8)
s2s._VARIANTS["seq2seq_t"] = dict(d_model=32, n_layers=2, n_heads=4)


def tiny_seq2seq(attention_backend="auto"):
    return s2s.build_seq2seq("seq2seq_t", TINY_MT.image_size,
                             TINY_MT.num_classes, TINY_MT.src_len,
                             attention_backend)


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_seq2seq()
    params, state, _ = init_model(model, jax.random.key(0))
    return model, params, state


def _logits(model, params, state, x):
    out, _ = apply_model(model, params, state, x, False)
    return out


def test_prefix_mask_semantics(model_and_params):
    model, params, state = model_and_params
    S, T = TINY_MT.src_len, TINY_MT.image_size[0]
    x = jax.random.randint(jax.random.key(1), (2, T), 0, 64, jnp.int32)
    base = _logits(model, params, state, x)

    # (a) bidirectional within source: changing a LATER source token changes
    # logits at an EARLIER source position (causal models can't do this)
    x2 = x.at[:, S - 1].set((x[:, S - 1] + 1) % 64)
    assert not np.allclose(base[:, 0], _logits(model, params, state, x2)[:, 0])

    # (b) causal within target: changing a later target token leaves earlier
    # target positions unchanged
    x3 = x.at[:, T - 1].set((x[:, T - 1] + 1) % 64)
    np.testing.assert_allclose(
        np.asarray(base[:, : T - 2]),
        np.asarray(_logits(model, params, state, x3)[:, : T - 2]),
        rtol=1e-5, atol=1e-5,
    )

    # (c) cross-attention: changing a source token changes target logits
    x4 = x.at[:, 0].set((x[:, 0] + 1) % 64)
    assert not np.allclose(base[:, S:], _logits(model, params, state, x4)[:, S:])

    # (d) target does NOT leak into source: changing a target token leaves
    # every source-position logit unchanged
    x5 = x.at[:, S].set((x[:, S] + 1) % 64)
    np.testing.assert_allclose(
        np.asarray(base[:, : S - 1]),
        np.asarray(_logits(model, params, state, x5)[:, : S - 1]),
        rtol=1e-5, atol=1e-5,
    )


def test_label_smoothing_math():
    logits = jnp.array([[2.0, 0.5, -1.0]])
    y = jnp.array([0])
    s = 0.2
    logp = jax.nn.log_softmax(logits, -1)
    want = -(1 - s) * logp[0, 0] - s * jnp.mean(logp[0])
    got = cross_entropy_loss(logits, y, smoothing=s)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # s=0 reduces to plain CE
    np.testing.assert_allclose(
        float(cross_entropy_loss(logits, y)), float(-logp[0, 0]), rtol=1e-6)


def test_masked_labels_ignored():
    logits = jnp.ones((2, 4, 8))
    y = jnp.array([[1, 2, 3, 4], [1, 2, 3, 4]], jnp.int32)
    y_masked = y.at[:, :2].set(-1)
    # loss over masked labels equals loss over only the surviving positions
    want = cross_entropy_loss(logits[:, 2:], y[:, 2:])
    got = cross_entropy_loss(logits, y_masked)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_synthetic_seq2seq_batch():
    from ddlbench_tpu.data.synthetic import make_synthetic

    data = make_synthetic(TINY_MT, 4, steps_per_epoch=2)
    x, y = data.batch(0, 0)
    S, T = TINY_MT.src_len, TINY_MT.image_size[0]
    assert x.shape == (4, T) and y.shape == (4, T)
    y = np.asarray(y)
    assert (y[:, : S - 1] == -1).all()
    assert (y[:, S - 1:] >= 0).all()
    # next-token alignment on the unmasked span
    x = np.asarray(x)
    np.testing.assert_array_equal(y[:, S - 1:-1], x[:, S:])


def test_greedy_and_beam_decode(model_and_params):
    model, params, state = model_and_params
    S, T = TINY_MT.src_len, TINY_MT.image_size[0]
    src = jax.random.randint(jax.random.key(2), (2, S), 0, 64, jnp.int32)
    out = s2s.greedy_decode(model, params, state, src, T)
    assert out.shape == (2, T)
    np.testing.assert_array_equal(np.asarray(out[:, :S]), np.asarray(src))
    # deterministic
    out2 = s2s.greedy_decode(model, params, state, src, T)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))

    # beam=1 equals greedy
    b1, score = s2s.beam_search_decode(model, params, state, src, T, beam=1)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(out))
    assert np.isfinite(np.asarray(score)).all()

    # wider beam: length-normalized score must be >= beam-1's
    b4, score4 = s2s.beam_search_decode(model, params, state, src, T, beam=4)
    assert (np.asarray(score4) >= np.asarray(score) - 1e-4).all()


@pytest.mark.parametrize("strategy,devices", [("single", 1), ("dp", 8),
                                              ("gpipe", 4)])
def test_training_strategies(strategy, devices):
    # pin sgd: these assert strategy equivalence / lr-specific descent,
    # written against SGD math (synthmt now defaults to adam)
    cfg = RunConfig(
        benchmark="synthmt", strategy=strategy, arch="seq2seq_t",
        num_devices=devices, epochs=1, steps_per_epoch=2, log_interval=1,
        compute_dtype="float32", optimizer="sgd",
        batch_size=8 if strategy != "gpipe" else None,
        micro_batch_size=2 if strategy == "gpipe" else None,
        num_microbatches=4 if strategy == "gpipe" else None,
        num_stages=4 if strategy == "gpipe" else None,
    )
    import ddlbench_tpu.models.zoo as zoo
    from ddlbench_tpu.parallel.api import make_strategy
    from ddlbench_tpu.data.synthetic import make_synthetic

    model = tiny_seq2seq()
    if strategy == "single":
        from ddlbench_tpu.parallel.single import SingleStrategy
        st = SingleStrategy(model, cfg)
    elif strategy == "dp":
        from ddlbench_tpu.parallel.dp import DPStrategy
        st = DPStrategy(model, cfg)
    else:
        from ddlbench_tpu.parallel.gpipe import GPipeStrategy
        st = GPipeStrategy(model, cfg)

    ts = st.init(jax.random.key(0))
    data = make_synthetic(TINY_MT, cfg.global_batch(), steps_per_epoch=2)
    losses = []
    for step in range(4):
        x, y = st.shard_batch(*data.batch(0, step % 2))
        ts, m = st.train_step(ts, x, y, jnp.float32(0.05))
        losses.append(float(m["loss"]))
        assert 0.0 <= float(m["accuracy"]) <= 1.0
    assert all(np.isfinite(losses))
    # training moves the (unsmoothed) CE down on this tiny repeated stream
    assert losses[-1] < losses[0]

    ev = st.eval_step(ts, *st.shard_batch(*data.batch(0, 0, train=False)))
    T, S = TINY_MT.image_size[0], TINY_MT.src_len
    expected_valid = cfg.global_batch() * (T - (S - 1))
    assert int(ev["count"]) == expected_valid


def test_decode_rejects_wrong_src_width(model_and_params):
    model, params, state = model_and_params
    bad = jnp.zeros((2, TINY_MT.src_len - 2), jnp.int32)
    with pytest.raises(ValueError, match="src_len"):
        s2s.greedy_decode(model, params, state, bad, TINY_MT.image_size[0])
    with pytest.raises(ValueError, match="src_len"):
        s2s.beam_search_decode(model, params, state, bad, TINY_MT.image_size[0])
    # non-seq2seq model rejected too
    from tiny_models import tiny_transformer
    lm = tiny_transformer()
    from ddlbench_tpu.models.layers import init_model as im
    p2, s2_, _ = im(lm, jax.random.key(0))
    with pytest.raises(ValueError, match="not a seq2seq"):
        s2s.greedy_decode(lm, p2, s2_, jnp.zeros((1, 8), jnp.int32), 16)


def test_seq2seq_flash_backend_matches_xla(model_and_params):
    _, params, state = model_and_params
    x = jax.random.randint(jax.random.key(9), (2, TINY_MT.image_size[0]),
                           0, 64, jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = _logits(tiny_seq2seq("xla"), params, state, x)
        # interpret-mode kernel off-TPU
        got = _logits(tiny_seq2seq("flash"), params, state, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_decode_rejects_bad_total_len(model_and_params):
    model, params, state = model_and_params
    src = jnp.zeros((1, TINY_MT.src_len), jnp.int32)
    for bad in (TINY_MT.src_len, TINY_MT.image_size[0] + 1):
        with pytest.raises(ValueError, match="total_len"):
            s2s.greedy_decode(model, params, state, src, bad)


def test_spec_requires_src_len():
    with pytest.raises(ValueError, match="src_len"):
        DatasetSpec("badmt", (16,), 64, 10, 10, kind="seq2seq")
    with pytest.raises(ValueError, match="src_len"):
        DatasetSpec("badmt", (16,), 64, 10, 10, kind="seq2seq", src_len=16)


def test_sp_seq2seq_matches_single(devices):
    """Sequence-parallel translation: ring attention with the prefix-LM rule
    on absolute key positions must reproduce the single-device step even when
    the source segment spans multiple sequence shards."""
    from jax.flatten_util import ravel_pytree
    from ddlbench_tpu.parallel.single import SingleStrategy
    from ddlbench_tpu.parallel.sp import SPStrategy

    model = tiny_seq2seq()  # T=16, src_len=8: 4 shards of 4 -> source spans 2
    B = 2
    cfg = RunConfig(strategy="sp", benchmark="synthmt", arch="seq2seq_t",
                    num_devices=4, compute_dtype="float32", optimizer="sgd",
                    momentum=0.5, weight_decay=0.0)
    sp = SPStrategy(model, cfg)
    single = SingleStrategy(model, cfg.replace(strategy="single", num_devices=1))

    from ddlbench_tpu.data.synthetic import make_synthetic

    data = make_synthetic(TINY_MT, B, steps_per_epoch=1)
    x, y = data.batch(0, 0)
    lr = jnp.float32(0.1)

    ts_sp = sp.init(jax.random.key(0))
    ts_1 = single.init(jax.random.key(0))
    ts_sp2, m_sp = sp.train_step(ts_sp, *sp.shard_batch(x, y), lr)
    ts_12, m_1 = single.train_step(ts_1, x, y, lr)

    np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m_sp["accuracy"]),
                               float(m_1["accuracy"]), atol=1e-6)
    a = ravel_pytree(jax.device_get(ts_sp2.params))[0]
    b = ravel_pytree(ts_12.params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-6)

    # masked eval counts must be global (source positions excluded)
    ev = sp.eval_step(ts_sp2, *sp.shard_batch(*data.batch(0, 0, train=False)))
    T, S = TINY_MT.image_size[0], TINY_MT.src_len
    assert int(ev["count"]) == B * (T - (S - 1))
