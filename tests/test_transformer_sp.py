"""Transformer workload + sequence-parallel ring attention.

Key equivalences: ring attention must match full causal attention bit-for-bit
(up to f32 accumulation order), and the SP strategy's train step must match
the single-device step on the identical model/batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy (see conftest --runslow)
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.transformer import causal_attention, ring_attention
from ddlbench_tpu.models import init_model, apply_model
from ddlbench_tpu.parallel.gpipe import _shard_map
from ddlbench_tpu.parallel.single import SingleStrategy
from ddlbench_tpu.parallel.sp import SPStrategy
from tiny_models import tiny_transformer


def test_forward_and_causality():
    model = tiny_transformer()
    params, state, shapes = init_model(model, jax.random.key(0))
    assert shapes[-1] == (32, 64)
    x = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)
    logits, _ = apply_model(model, params, state, x, train=True)
    assert logits.shape == (2, 32, 64)
    # causality: perturbing future tokens must not change earlier logits
    x2 = x.at[:, 20:].set((x[:, 20:] + 7) % 64)
    logits2, _ = apply_model(model, params, state, x2, train=True)
    np.testing.assert_allclose(
        np.asarray(logits[:, :20]), np.asarray(logits2[:, :20]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits[:, 20:]), np.asarray(logits2[:, 20:]))


def test_ring_attention_matches_full(devices):
    B, H, T, dh, n = 2, 4, 32, 8, 4
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (B, H, T, dh))
    k = jax.random.normal(k2, (B, H, T, dh))
    v = jax.random.normal(k3, (B, H, T, dh))
    full = causal_attention(q, k, v)

    import numpy as onp

    mesh = Mesh(onp.array(jax.devices()[:n]), ("seq",))

    def ring(ql, kl, vl):
        return ring_attention(ql, kl, vl, "seq")

    ringed = _shard_map(
        ring, mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"), P(None, None, "seq")),
        out_specs=P(None, None, "seq"),
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ringed),
                               rtol=2e-5, atol=2e-5)


def test_sp_matches_single(devices):
    model = tiny_transformer()
    B, T = 2, 32
    cfg_sp = RunConfig(strategy="sp", benchmark="synthtext", num_devices=4,
                       compute_dtype="float32", momentum=0.5, weight_decay=0.0)
    sp = SPStrategy(model, cfg_sp)
    cfg_1 = cfg_sp.replace(strategy="single", num_devices=1)
    single = SingleStrategy(model, cfg_1)

    x = jax.random.randint(jax.random.key(1), (B, T), 0, 64)
    y = jax.random.randint(jax.random.key(2), (B, T), 0, 64)
    lr = jnp.float32(0.1)

    ts_sp = sp.init(jax.random.key(0))
    ts_1 = single.init(jax.random.key(0))
    ts_sp2, m_sp = sp.train_step(ts_sp, *sp.shard_batch(x, y), lr)
    ts_12, m_1 = single.train_step(ts_1, x, y, lr)

    np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(m_sp["accuracy"]), float(m_1["accuracy"]), atol=1e-6)
    a = ravel_pytree(ts_sp2.params)[0]
    b = ravel_pytree(ts_12.params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_transformer_under_gpipe(devices):
    from ddlbench_tpu.models.layers import apply_slice
    from ddlbench_tpu.parallel.common import cross_entropy_loss
    from ddlbench_tpu.parallel.gpipe import GPipeStrategy

    model = tiny_transformer()  # 4 layers: embed, 2 blocks, head
    S, M, mb = 4, 4, 2
    cfg = RunConfig(strategy="gpipe", benchmark="synthtext", num_devices=S,
                    num_stages=S, micro_batch_size=mb, num_microbatches=M,
                    compute_dtype="float32", momentum=0.0, weight_decay=0.0)
    strat = GPipeStrategy(model, cfg, stage_bounds=[0, 1, 2, 3, 4])
    ts = strat.init(jax.random.key(0))
    B = M * mb
    x = jax.random.randint(jax.random.key(1), (B, 32), 0, 64)
    y = jax.random.randint(jax.random.key(2), (B, 32), 0, 64)
    xs, ys = strat.shard_batch(x, y)
    ts2, metrics = strat.train_step(ts, xs, ys, jnp.float32(0.1))

    params_list, state_list, _ = init_model(model, jax.random.key(0))

    def loss_fn(p):
        logits, _ = apply_slice(model.layers, p, state_list, x, True)
        return cross_entropy_loss(logits, y)

    ref_loss, grads = jax.value_and_grad(loss_fn)(params_list)
    ref_params = jax.tree.map(lambda p, g: p - 0.1 * g, params_list, grads)
    np.testing.assert_allclose(float(metrics["loss"]), float(ref_loss), rtol=1e-5)
    for s in range(S):
        got = np.asarray(ts2.params[s][: strat._p_lens[s]])
        want = np.asarray(ravel_pytree(ref_params[s:s + 1])[0])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_ring_attention_flash_matches_full(devices):
    """The TPU ring path (fused kernel per visiting block + logsumexp
    combination) must equal full causal attention — values AND grads.
    Forced 'flash' backend runs the kernels in interpret mode on CPU."""
    B, H, T, dh, n = 1, 2, 32, 8, 4
    k1, k2, k3, k4 = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(k1, (B, H, T, dh))
    k = jax.random.normal(k2, (B, H, T, dh))
    v = jax.random.normal(k3, (B, H, T, dh))
    g = jax.random.normal(k4, (B, H, T, dh))

    import numpy as onp

    mesh = Mesh(onp.array(jax.devices()[:n]), ("seq",))
    spec = P(None, None, "seq")

    def ringed(q, k, v):
        # check_vma=False: interpret-mode pallas bodies are discharged to
        # plain JAX ops whose mixed varying/invariant operands trip the VMA
        # checker (JAX suggests this exact workaround); the compiled TPU path
        # runs under the default checked shard_map via the kernels'
        # vma-annotated out_shapes.
        return _shard_map(
            lambda ql, kl, vl: ring_attention(ql, kl, vl, "seq",
                                              backend="flash"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = ringed(q, k, v)
        got_g = jax.grad(
            lambda *a: jnp.sum(ringed(*a) * g), argnums=(0, 1, 2)
        )(q, k, v)
        ref = causal_attention(q, k, v, backend="xla")
        ref_g = jax.grad(
            lambda *a: jnp.sum(causal_attention(*a, backend="xla") * g),
            argnums=(0, 1, 2)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
