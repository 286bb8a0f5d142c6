"""models/kanana2.py against its plain reference (benchmarks/reference/
kanana2.py, which imports nothing of the program), at the family's test size
in float32 on the CPU: loss and every leaf's gradient with and without
remat_layers, the share a chip holds, the routing semantics one by one,
interleaved RoPE, and the arch string that carries the share."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu import config as pcfg
from ddlbench_tpu.models import dropless, kanana2
from ddlbench_tpu.models.layers import init_model, param_count
from ddlbench_tpu.models.zoo import arch_name, collects_aux_loss, get_model
from ddlbench_tpu.parallel import make_strategy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, VOCAB, BATCH = 64, 128, 2
DIMS = kanana2.FAMILY["kanana2_t"]


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "kanana2.py")
    spec = importlib.util.spec_from_file_location("ref_kanana2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def ref_config(dims=DIMS, layers=None, held=None, first=0):
    """The reference's configuration keys (HF names) of a Dims."""
    return {
        "hidden_size": dims.d_model, "num_attention_heads": dims.n_heads,
        "qk_nope_head_dim": dims.qk_nope, "qk_rope_head_dim": dims.qk_rope,
        "v_head_dim": dims.v_head, "kv_lora_rank": dims.kv_latent,
        "intermediate_size": dims.dense_ff,
        "moe_intermediate_size": dims.expert_ff,
        "n_routed_experts": dims.n_experts,
        "n_shared_experts": dims.n_shared,
        "num_experts_per_tok": dims.top_k,
        "routed_scaling_factor": dims.route_scale,
        "first_k_dense_replace": dims.first_dense,
        "rms_norm_eps": dims.rms_eps, "rope_theta": dims.rope_theta,
        "n_layer": layers or dims.n_layers,
        "n_routed_experts_held": held or dims.n_experts,
        "first_expert_held": first, "n_positions": T,
        "padded_vocab_size": VOCAB}


@pytest.fixture(scope="module")
def dataset():
    name = "kanana2-test-64"
    pcfg.DATASETS[name] = pcfg.DatasetSpec(name, (T,), VOCAB, 1 << 20,
                                           1 << 10, kind="tokens")
    yield name
    del pcfg.DATASETS[name]


def flat_of(params, model):
    out = {}
    for layer, p in zip(model.layers, params):
        for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
            out["/".join([layer.name] + [k.key for k in path])] = leaf
    return out


def seeded(model, key=0):
    """Random weights in the program's tree, every leaf (norm scales and the
    selection bias too)."""
    params = jax.eval_shape(lambda k: init_model(model, k)[0],
                            jax.random.key(0))
    leaves, treedef = jax.tree.flatten(params)
    ks = jax.random.split(jax.random.key(key), len(leaves))
    new = []
    for k, leaf in zip(ks, leaves):
        n = jax.random.normal(k, leaf.shape, jnp.float32)
        new.append(n * 0.1 if leaf.ndim >= 2 else
                   1.0 + 0.1 * n if leaf.shape[0] != DIMS.n_experts
                   else 0.05 * n)
    return jax.tree.unflatten(treedef, new)


def batch(seed=0):
    seq = jax.random.randint(jax.random.key(seed), (BATCH, T + 1), 0, VOCAB)
    return seq[:, :-1], seq[:, 1:]


def program_loss_and_grads(dataset, arch, params, x, y, remat):
    cfg = pcfg.RunConfig(benchmark=dataset, arch=arch, strategy="single",
                         num_devices=1, batch_size=BATCH,
                         compute_dtype="float32", remat_layers=remat,
                         optimizer="sgd", lr=1.0, momentum=0.0,
                         weight_decay=0.0)
    cfg.validate()
    strategy = make_strategy(cfg)
    # the step donates its state: it gets a copy
    ts = strategy.init(jax.random.key(0))._replace(
        params=jax.tree.map(lambda a: a.copy(), params))
    ts, m = strategy.train_step(ts, x, y, jnp.float32(1.0))
    grads = jax.tree.map(lambda a, b: a - b, params, ts.params)  # lr 1
    return float(m["loss"]), flat_of(grads, strategy.model), m


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("arch", ["kanana2_t", "kanana2_t-e4r1"])
def test_program_matches_the_reference(dataset, arch, remat):
    """Loss and every leaf's gradient, through cli's own strategy."""
    model = get_model(arch, dataset)
    params = seeded(model)
    x, y = batch()
    _, layers, (first, held) = kanana2.parse_arch(arch)
    loss, grads, m = program_loss_and_grads(dataset, arch, params, x, y,
                                            remat)
    cfg = ref_config(layers=layers, held=held, first=first)
    rl, rg, _ = jax.jit(lambda P, x, y: REF.loss_and_grads(P, x, y, cfg))(
        flat_of(params, model), x, y)
    assert loss == pytest.approx(float(rl), rel=2e-5)
    assert set(grads) == set(rg)
    for k in rg:
        if k.endswith("router_bias"):
            assert not np.any(np.asarray(rg[k])) and \
                not np.any(np.asarray(grads[k]))
            continue
        scale = float(jnp.max(jnp.abs(rg[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(grads[k]) / scale,
                                   np.asarray(rg[k]) / scale, atol=2e-4,
                                   err_msg=k)
    # the counters: slots that reached a held expert, all expert layers
    slots = BATCH * T * DIMS.top_k * (layers - DIMS.first_dense)
    if held == DIMS.n_experts:
        assert float(m["moe_held_slots"]) == slots
    else:
        assert 0 < float(m["moe_held_slots"]) < slots
    assert float(m["moe_load_max_over_mean"]) >= 1.0


def _expert_layer(held):
    return kanana2.expert_block("b", DIMS, held, "auto")


def test_the_shares_add_up_to_the_uncut_layer(dataset):
    """Guide section 4: the parts of the result that all the shares give,
    with what every chip computes alike (attention, the shared experts)
    counted once, add up to what the uncut layer gives."""
    whole = _expert_layer((0, DIMS.n_experts))
    p, s, _ = whole.init(jax.random.key(1), (T, DIMS.d_model))
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.key(2),
                                                (DIMS.n_experts,))
    x = jax.random.normal(jax.random.key(3), (BATCH, T, DIMS.d_model))
    full, _ = whole.apply(p, s, x, True)
    n_shares = 4
    per = DIMS.n_experts // n_shares
    parts, slots = [], 0.0
    for r in range(n_shares):
        pr = dict(p, experts=jax.tree.map(
            lambda a: a[r * per:(r + 1) * per], p["experts"]))
        y, st = _expert_layer((r * per, per)).apply(pr, s, x, True)
        parts.append(y)
        slots += float(st["moe"]["held_slots"])
    # alike on every chip: the layer with no routed expert's term at all
    # (a share whose experts' down-projections are nought)
    mute = dict(p, experts=jax.tree.map(
        lambda a: jnp.zeros_like(a[:per]), p["experts"]))
    alike, _ = _expert_layer((0, per)).apply(mute, s, x, True)
    np.testing.assert_allclose(
        np.asarray(alike + sum(y - alike for y in parts)), np.asarray(full),
        atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - alike))) > 1e-3
    assert slots == BATCH * T * DIMS.top_k  # every slot on exactly one chip
    # and against the reference given the same share
    cfg = ref_config(held=per, first=per)
    flat = {f"block2/{'/'.join(k.key for k in path)}": leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                dict(p, experts=jax.tree.map(lambda a: a[per:2 * per],
                                             p["experts"])))[0]}
    want = jnp.stack([REF._block(flat, 2, x[b], cfg, lambda a: a)
                      for b in range(BATCH)])
    np.testing.assert_allclose(np.asarray(parts[1]), np.asarray(want),
                               atol=2e-5)


def _router(seed=0, bias=None):
    d = DIMS.d_model
    p = {"router": 0.3 * jax.random.normal(jax.random.key(seed),
                                           (d, DIMS.n_experts)),
         "router_bias": jnp.zeros((DIMS.n_experts,)) if bias is None
         else bias}
    h = jax.random.normal(jax.random.key(seed + 1), (32, d))
    return p, h


def test_the_bias_moves_the_choice_and_not_the_weight():
    p, h = _router()
    idx0, w0 = kanana2.route(p, h, DIMS)
    pb = dict(p, router_bias=jnp.zeros((DIMS.n_experts,)).at[5].set(10.0))
    idx1, w1 = kanana2.route(pb, h, DIMS)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))  # chosen everywhere
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    # the weight of expert 5 is its sigmoid score, renormalised: no bias
    s = jax.nn.sigmoid(h @ p["router"])
    chosen = jnp.take_along_axis(s, idx1, axis=-1)
    want = chosen / jnp.sum(chosen, -1, keepdims=True) * DIMS.route_scale
    np.testing.assert_allclose(np.asarray(w1), np.asarray(want), rtol=1e-5)
    # and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(kanana2.route(
        dict(p, router_bias=b), h, DIMS)[1] ** 2))(pb["router_bias"])
    assert not np.any(np.asarray(g))


def test_weights_are_renormalised_over_all_chosen_and_scaled():
    p, h = _router(3)
    idx, w = kanana2.route(p, h, DIMS)
    assert idx.shape == w.shape == (32, DIMS.top_k)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)),
                               DIMS.route_scale, rtol=1e-5)
    # held or not: a share's weights are the same numbers, not renormalised
    # over the held ones
    y_all = kanana2.routed_experts(
        _experts_params(p), h, DIMS, (0, DIMS.n_experts))[0]
    y_parts = sum(kanana2.routed_experts(
        _experts_params(p, r), h, DIMS, (4 * r, 4))[0] for r in range(4))
    np.testing.assert_allclose(np.asarray(y_parts), np.asarray(y_all),
                               atol=1e-5)


def _experts_params(p, rank=None):
    d, f, E = DIMS.d_model, DIMS.expert_ff, DIMS.n_experts
    ks = jax.random.split(jax.random.key(9), 3)
    ex = {"w_gate": 0.2 * jax.random.normal(ks[0], (E, d, f)),
          "w_up": 0.2 * jax.random.normal(ks[1], (E, d, f)),
          "w_down": 0.2 * jax.random.normal(ks[2], (E, f, d))}
    if rank is not None:
        ex = jax.tree.map(lambda a: a[4 * rank:4 * rank + 4], ex)
    return dict(p, experts=ex)


def test_no_token_is_dropped_under_a_skewed_router():
    """Every token chooses the held experts 0..2: sixteen times the balanced
    load, past the small buffer — the second branch — and every slot is
    computed, as the reference's dense sum says."""
    bias = jnp.zeros((DIMS.n_experts,)).at[:DIMS.top_k].set(10.0)
    p, h = _router(5, bias)
    h = jnp.tile(h, (64, 1)) + 0.01 * jax.random.normal(
        jax.random.key(7), (2048, DIMS.d_model))  # 2048 tokens
    p = _experts_params(p, 0)
    S = h.shape[0]
    assert dropless.buffer_rows(S * DIMS.top_k, DIMS.n_experts, 4) < \
        S * DIMS.top_k
    y, counters = jax.jit(lambda p, h: kanana2.routed_experts(
        p, h, DIMS, (0, 4)))(p, h)
    assert float(counters["held_slots"]) == S * DIMS.top_k
    assert float(counters["load_max_over_mean"]) == pytest.approx(
        DIMS.n_experts / DIMS.top_k)
    idx, w = kanana2.route(p, h, DIMS)
    want = jnp.zeros_like(h)
    for e in range(4):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        pe = jax.tree.map(lambda a: a[e], p["experts"])
        want = want + we[:, None] * kanana2.swiglu(pe, h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    # the gradients of the large branch too
    g = jax.grad(lambda h: jnp.sum(kanana2.routed_experts(
        p, h, DIMS, (0, 4))[0] ** 2))(h)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.max(jnp.abs(g))) > 0


def test_rope_is_the_pairwise_rotation():
    r, theta = DIMS.qk_rope, 1e6
    x = jax.random.normal(jax.random.key(0), (2, 3, 16, r))
    pos = jnp.arange(16)
    got = np.asarray(kanana2.rope_interleaved(x, pos, theta))
    xn = np.asarray(x)
    for i in range(r // 2):
        ang = np.arange(16) * theta ** (-2.0 * i / r)
        a, b = xn[..., 2 * i], xn[..., 2 * i + 1]
        np.testing.assert_allclose(got[..., 2 * i],
                                   a * np.cos(ang) - b * np.sin(ang),
                                   atol=1e-5)
        np.testing.assert_allclose(got[..., 2 * i + 1],
                                   a * np.sin(ang) + b * np.cos(ang),
                                   atol=1e-5)
    # position 0 is the identity, and the rotation keeps each pair's norm
    np.testing.assert_allclose(got[..., 0, :], xn[..., 0, :], atol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(got.reshape(2, 3, 16, r // 2, 2), axis=-1),
        np.linalg.norm(xn.reshape(2, 3, 16, r // 2, 2), axis=-1), rtol=1e-5)
    # the reference's own rotation, on its [T, ..., r] layout
    np.testing.assert_allclose(
        np.asarray(REF._rope(jnp.moveaxis(x, 2, 0), theta)),
        np.moveaxis(got, 2, 0), atol=1e-5)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_moves_the_reference(dataset, fault):
    """What the chip readings plant (benchmarks/readings_faults.py) is no
    no-op: the reference with the fault gives another loss."""
    model = get_model("kanana2_t-e4", dataset)
    flat = flat_of(seeded(model, 1), model)
    x, y = batch(1)
    cfg = ref_config(held=4)
    sound = float(REF.loss_and_grads(flat, x, y, cfg)[0])
    broken = float(REF.loss_and_grads(flat, x, y,
                                      dict(cfg, fault=fault))[0])
    assert abs(broken - sound) > 1e-6 * abs(sound)


def test_the_arch_string_carries_the_share():
    dims, layers, held = kanana2.parse_arch("kanana2_30b_a3b-l5-e8")
    assert (dims.d_model, dims.n_experts, layers, held) == (2048, 128, 5,
                                                            (0, 8))
    assert kanana2.parse_arch("kanana2_30b_a3b-e8r3")[2] == (24, 8)
    assert kanana2.parse_arch("kanana2_30b_a3b")[1:] == (48, (0, 128))
    assert kanana2.parse_arch("transformer_m") is None
    assert arch_name("kanana2_t-l2-e2r7") == "kanana2_t-l2-e2r7"
    for bad in ("kanana2_t-e3", "kanana2_t-e4r4", "kanana2_t-l9",
                "kanana2_t-l0"):
        with pytest.raises(ValueError):
            kanana2.parse_arch(bad)
    with pytest.raises(ValueError):
        arch_name("kanana3")
    assert collects_aux_loss("transformer_moe_s")
    assert not collects_aux_loss("kanana2_30b_a3b-l5-e8")


def test_published_parameter_counts():
    """ISSUE 27's arithmetic: 425.4 M parameters in the benchmark's cut."""
    model = get_model("kanana2_30b_a3b-l5-e8", pcfg.DatasetSpec(
        "x", (4096,), 16128, 1, 1, kind="tokens"))
    shapes = jax.eval_shape(lambda k: init_model(model, k)[0],
                            jax.random.key(0))
    by_layer = [param_count(p) for p in shapes]
    norms = 2 * 2048  # ln1, ln2 (the latent's norm is in the MLA count)
    assert by_layer[1] == 26_345_984 + 3 * 2048 * 6144 + norms  # dense
    assert by_layer[2] == (26_345_984 + norms + 2048 * 128 + 128
                           + 3 * 2048 * 1536 + 8 * 3 * 2048 * 768)
    assert sum(by_layer) == 425_354_240


def test_validate_refuses_what_is_not_brought_up(dataset):
    ok = pcfg.RunConfig(benchmark=dataset, arch="kanana2_t",
                        strategy="single", num_devices=1, batch_size=2,
                        remat_layers=True)
    ok.validate()  # remat_layers: this router collects no auxiliary loss
    assert get_model("kanana2_t", dataset).strategies == ("single",)
    with pytest.raises(ValueError, match="brought up on single only"):
        dataclasses.replace(ok, strategy="dp", num_devices=2).validate()
    with pytest.raises(ValueError, match="auxiliary loss"):
        pcfg.RunConfig(benchmark="synthtext", arch="transformer_moe_s",
                       strategy="single", num_devices=1,
                       remat_layers=True).validate()


def test_serving_refuses_the_family_by_name(dataset):
    from ddlbench_tpu.serve.engine import _require_serve_support

    with pytest.raises(NotImplementedError, match="kanana2_t"):
        _require_serve_support(get_model("kanana2_t", dataset))


def test_the_router_stays_float32_through_the_cast(dataset):
    """The expert layer names its float32 parameters; the step's cast, given
    the layers, leaves those and casts every other leaf, and without the
    layers (or for a layer that names none) casts all."""
    from ddlbench_tpu.parallel.common import cast_params

    model = get_model("kanana2_t", dataset)
    assert [l.f32_params for l in model.layers] == [
        (), (), ("router", "router_bias"), ("router", "router_bias"), ()]
    params = init_model(model, jax.random.key(0))[0]
    cast = cast_params(params, jnp.bfloat16, model.layers)
    assert jax.tree.structure(cast) == jax.tree.structure(params)
    for layer, p in zip(model.layers, cast):
        for key, sub in p.items():
            want = jnp.float32 if key in layer.f32_params else jnp.bfloat16
            assert all(x.dtype == want for x in jax.tree.leaves(sub)), key
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(
        cast_params(params, jnp.bfloat16)))
    assert cast_params(params, None, model.layers) is params


def test_the_pallas_grouped_product_is_ragged_dot():
    """The chip's grouped product (megablox, here in interpret mode) against
    XLA's ragged_dot on the rows that belong to a group, forward and both
    gradients, with rows past the groups' sum in the buffer — and the
    SwiGLU over it leaves those rows nought, with no gradient."""
    G, k, n, M = 4, 256, 128, 512
    ks = jax.random.split(jax.random.key(0), 3)
    a = jax.random.normal(ks[0], (M, k), jnp.float32)
    w = jax.random.normal(ks[1], (G, k, n), jnp.float32) * 0.1
    sizes = jnp.array([100, 0, 130, 70], jnp.int32)  # 300 of 512 rows
    valid = int(sizes.sum())
    dot = lambda a, w, interpret=False: dropless.grouped_dot(
        a, w, sizes, (128, 128, 128), interpret)
    with jax.default_matmul_precision("highest"):
        f = lambda interpret: lambda a, w: jnp.sum(jnp.sin(
            dot(a, w, interpret)[:valid]))
        for got, want in zip(jax.grad(f(True), (0, 1))(a, w),
                             jax.grad(f(False), (0, 1))(a, w)):
            np.testing.assert_allclose(np.asarray(got[:valid]),
                                       np.asarray(want[:valid]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dot(a, w, True)[:valid]),
                                   np.asarray(dot(a, w)[:valid]), atol=1e-4)
    pe = {"w_gate": w, "w_up": w + 0.05,
          "w_down": jnp.swapaxes(w, 1, 2) * 0.5}
    y, vjp = jax.vjp(lambda a: dropless._grouped_glu(
        pe, a, sizes, kanana2.GMM_TILING, jax.nn.silu), a)
    assert not np.any(np.asarray(y[valid:]))
    assert not np.any(np.asarray(vjp(jnp.ones_like(y))[0][valid:]))


def test_the_grouped_swiglu_keeps_out_what_the_products_leave_past_the_runs(
        monkeypatch):
    """The chip's grouped products stop at the runs' sum and leave the rows
    past it undefined, forward and in the rows' gradient. With NaN planted
    there, the SwiGLU's output and every gradient are those of the clean
    product: nothing of the undefined rows gets out."""
    G, d, f, M = 4, 32, 16, 64
    ks = jax.random.split(jax.random.key(1), 4)
    rows = jax.random.normal(ks[0], (M, d), jnp.float32)
    pe = {"w_gate": jax.random.normal(ks[1], (G, d, f)) * 0.2,
          "w_up": jax.random.normal(ks[2], (G, d, f)) * 0.2,
          "w_down": jax.random.normal(ks[3], (G, f, d)) * 0.2}
    sizes = jnp.array([10, 0, 17, 9], jnp.int32)  # 36 of 64 rows
    clean = dropless.grouped_dot

    def stops_at_the_sum(a, w, sizes, tiling, interpret=False):
        live = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        dirty = lambda x: jnp.where(live, x, jnp.nan)

        @jax.custom_vjp
        def dot(a, w):
            return dirty(clean(jnp.where(live, a, 0), w, sizes, tiling))

        def fwd(a, w):
            return dot(a, w), (a, w)

        def bwd(res, ct):  # reads the live rows, leaves the others undefined
            a, w = res
            da, dw = jax.vjp(lambda a, w: clean(a, w, sizes, tiling),
                             jnp.where(live, a, 0), w)[1](
                                 jnp.where(live, ct, 0))
            return dirty(da), dw

        dot.defvjp(fwd, bwd)
        return dot(a, w)

    swiglu = lambda pe, rows: dropless._grouped_glu(
        pe, rows, sizes, kanana2.GMM_TILING, jax.nn.silu)
    loss = lambda pe, rows: jnp.sum(jnp.sin(swiglu(pe, rows)))
    want = jax.value_and_grad(loss, (0, 1))(pe, rows)
    monkeypatch.setattr(dropless, "grouped_dot", stops_at_the_sum)
    got = jax.value_and_grad(loss, (0, 1))(pe, rows)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)
    y = swiglu(pe, rows)
    assert not np.any(np.asarray(y[int(sizes.sum()):]))
