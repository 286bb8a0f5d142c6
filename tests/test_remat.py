"""Per-layer rematerialization (cfg.remat_layers) is numerically invisible.

jax.checkpoint trades backward-pass FLOPs for activation memory; the loss
and gradients must be bit-comparable to the unremat'd step. On-chip this is
what lets XLA-attention long-context configs fit one v5e (lmbench retries
an OOM'd cell with remat=True); here we pin the equivalence on CPU with a
tiny model, plus the MoE validation gate.

What a rematerialized layer KEEPS (models/layers.apply_slice): the values
its kernels name (ops/flash_attention.REMAT_KEPT_NAMES), so that the flash
forward kernel runs once a layer and not a second time in the backward.
Counted here in the gradient program's jaxpr, kernels interpreted.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import (Layer, LayerModel, apply_slice, dense,
                                        flatten, init_model)
from ddlbench_tpu.parallel.common import loss_and_grads
from test_flash_attention import pallas_calls


def _tiny_model(num_classes=4):
    layers = [flatten(), dense("fc1", 8, relu=True),
              dense("fc2", 8, relu=True), dense("fc3", num_classes)]
    return LayerModel("tiny", layers, (4, 4, 1), num_classes)


def _cfg(**kw):
    base = dict(benchmark="mnist", strategy="single",
                compute_dtype="float32", momentum=0.0, weight_decay=0.0)
    base.update(kw)
    return RunConfig(**base)


@pytest.mark.parametrize("accum", [1, 2])
def test_remat_matches_plain(accum):
    model = _tiny_model()
    params, state, _ = init_model(model, jax.random.key(0))
    kx, ky = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (8, 4, 4, 1))
    y = jax.random.randint(ky, (8,), 0, 4)

    outs = {}
    for remat in (False, True):
        cfg = _cfg(remat_layers=remat, grad_accum_steps=accum)
        ce, (corr, valid), _, grads = loss_and_grads(
            model, cfg, params, state, x, y, jnp.float32, 0.0)
        outs[remat] = (float(ce), int(corr), grads)

    assert outs[False][0] == pytest.approx(outs[True][0], rel=1e-6)
    assert outs[False][1] == outs[True][1]
    for a, b in zip(jax.tree.leaves(outs[False][2]),
                    jax.tree.leaves(outs[True][2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_remat_rejects_moe():
    with pytest.raises(ValueError, match="remat_layers is incompatible"):
        _cfg(benchmark="synthtext", arch="transformer_moe_s",
             remat_layers=True).validate()


def test_remat_rejects_pipeline_strategies():
    with pytest.raises(ValueError, match="remat_layers applies to"):
        _cfg(strategy="gpipe", num_devices=2, num_stages=2,
             remat_layers=True).validate()


# ---- what a rematerialized layer keeps --------------------------------------

T, D = 64, 32


def _flash_layer(name, heads, kv_heads, dh, dv, lse):
    """Projections around the interpreted flash kernel, a residual block on
    [B, T, D]. With ``lse`` the block is ``flash_attention_lse`` and the
    logsumexp reaches the output, so its cotangent is no symbolic zero."""
    from ddlbench_tpu.ops.flash_attention import (flash_attention,
                                                  flash_attention_lse)

    def init(key, in_shape):
        keys = jax.random.split(key, 4)
        w = lambda k, *shape: jax.random.normal(k, shape) / shape[0] ** 0.5
        return dict(wq=w(keys[0], D, heads * dh),
                    wk=w(keys[1], D, kv_heads * dh),
                    wv=w(keys[2], D, kv_heads * dv),
                    wo=w(keys[3], heads * dv, D)), {}, in_shape

    def apply(p, s, x, train):
        B = x.shape[0]
        split = lambda y, n: y.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
        q, k, v = (split(x @ p["wq"], heads), split(x @ p["wk"], kv_heads),
                   split(x @ p["wv"], kv_heads))
        if lse:
            o, rows = flash_attention_lse(q, k, v, 0, 0, 0, 32, 32, True)
            o = o * jnp.tanh(rows)[..., None]
        else:
            o = flash_attention(q, k, v, 0, 0, 0, 32, 32, True)
        return x + o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"], s

    return Layer(name, init, apply)


def _bare_slice(layers, params, states, x, train):
    """``apply_slice(..., remat=True)`` as it was: nothing kept."""
    for layer, p, s in zip(layers, params, states):
        x, _ = jax.checkpoint(functools.partial(layer.apply,
                                                train=train))(p, s, x)
    return x, states


FLASH_BLOCKS = {  # heads, key/value heads, q/k width, v width, lse
    "equal-heads": (4, 4, 16, 16, False),
    "8-over-2-heads": (8, 2, 16, 16, False),
    "qk192-v128": (2, 2, 192, 128, False),
    "flash_attention_lse": (4, 4, 16, 16, True),
}


@pytest.mark.parametrize("block", sorted(FLASH_BLOCKS))
def test_a_rematerialized_layer_runs_its_flash_forward_once(block):
    """Two layers: two forward calls and two backward calls in the gradient
    program, as without any checkpoint (a bare jax.checkpoint runs four
    forwards: the control, so that this way of counting is known to see the
    second one), and the gradients are the un-rematerialized ones bit for
    bit: the backward reads the o and lse the forward wrote."""
    layers = [_flash_layer(f"block{i}", *FLASH_BLOCKS[block])
              for i in (1, 2)]
    model = LayerModel("flash_blocks", layers, (T, D), D)
    params, states, _ = init_model(model, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, T, D))

    def grads_of(run):
        def loss(params, x):
            y, _ = run(layers, params, states, x, True)
            return jnp.sum(y * y)
        return jax.grad(loss, argnums=(0, 1))

    kept = grads_of(lambda *a: apply_slice(*a, remat=True))
    plain = grads_of(apply_slice)
    once = {"flash_attn_fwd": 2, "flash_attn_dq_dkv": 2}
    assert pallas_calls(jax.make_jaxpr(kept)(params, x).jaxpr) == once
    assert pallas_calls(jax.make_jaxpr(plain)(params, x).jaxpr) == once
    assert pallas_calls(jax.make_jaxpr(grads_of(_bare_slice))(
        params, x).jaxpr) == {"flash_attn_fwd": 4, "flash_attn_dq_dkv": 2}
    for a, b in zip(jax.tree.leaves(jax.jit(kept)(params, x)),
                    jax.tree.leaves(jax.jit(plain)(params, x))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_kept_names_are_the_ones_the_kernels_carry():
    """A policy over names nothing carries is the bare checkpoint, and a
    name without a policy an identity: the list ``apply_slice`` keeps is the
    list the forward rules name, each name once a call."""
    from ddlbench_tpu.ops import flash_attention as fa

    q = jnp.ones((1, 2, T, 16))
    for f in (fa.flash_attention, fa.flash_attention_lse):
        out = lambda q: jax.tree.leaves(f(q, q, q, 0, 0, 0, 32, 32, True))[0]
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(out(q))))(q)
        names = [e.params["name"] for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "name"]
        assert sorted(names) == sorted(fa.REMAT_KEPT_NAMES), names
    assert fa.REMAT_KEPT_NAMES == ("flash_attn_o", "flash_attn_lse")


@pytest.mark.parametrize("model", ["dense", "xla-attention", "resnet-block"])
def test_a_layer_without_a_flash_kernel_lowers_as_under_a_bare_checkpoint(
        model):
    """No value of the kept names in the layer: the policy keeps nothing,
    and the gradient program is the text the bare jax.checkpoint gave."""
    if model == "dense":
        m = _tiny_model()
        x = jax.ShapeDtypeStruct((8, 4, 4, 1), jnp.float32)
    elif model == "xla-attention":
        from ddlbench_tpu.models.transformer import transformer_block

        m = LayerModel("blocks", [transformer_block(
            f"block{i}", D, 4, attention_backend="xla") for i in (1, 2)],
            (T, D), D)
        x = jax.ShapeDtypeStruct((2, T, D), jnp.float32)
    else:
        from ddlbench_tpu.models.layers import basic_block

        m = LayerModel("blocks", [basic_block("group1_block1", 8)],
                       (8, 8, 8), 8)
        x = jax.ShapeDtypeStruct((2, 8, 8, 8), jnp.float32)
    params, states, _ = init_model(m, jax.random.key(0))

    def text(run):
        def loss(params, x):
            y, _ = run(m.layers, params, states, x, True)
            return jnp.sum(y * y)
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).as_text()

    bare = text(_bare_slice)
    assert text(lambda *a: apply_slice(*a, remat=True)) == bare
    assert "optimization_barrier" in bare  # a checkpoint is in it
