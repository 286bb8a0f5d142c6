"""models/zaya.py against its plain reference (benchmarks/reference/zaya.py,
which imports nothing of the program), at the family's test size on the CPU:
logits, loss and every leaf's gradient in float32 with and without
remat_layers and through the bfloat16 step; the shares of the two chips of
the pair add up to the uncut layer; causality of the value shift and of both
convolutions; the carried router state; the tied embedding (one leaf, both
gradients, one optimizer slot); the arch string; and what the move of the
dropless layer and the second head count left as they were."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from ddlbench_tpu import config as pcfg
from ddlbench_tpu.models import dropless, zaya
from ddlbench_tpu.models.layers import (apply_model, init_model, param_count,
                                        resolve_ties)
from ddlbench_tpu.models.zoo import arch_name, collects_aux_loss, get_model
from ddlbench_tpu.parallel import make_strategy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, VOCAB, BATCH = 64, 128, 2
DIMS = zaya.FAMILY["zaya_t"]


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "zaya.py")
    spec = importlib.util.spec_from_file_location("ref_zaya", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def ref_config(dims=DIMS, layers=None, held=None, first=0, **more):
    """The reference's configuration keys (HF names) of a Dims."""
    return dict({
        "hidden_size": dims.d_model, "num_attention_heads": dims.n_heads,
        "num_key_value_heads": dims.n_kv_heads, "head_dim": dims.head_dim,
        "cca_time0": dims.conv_taps[0], "cca_time1": dims.conv_taps[1],
        "rope_parameters": {"hybrid": {
            "partial_rotary_factor": dims.rotary / dims.head_dim,
            "rope_theta": dims.rope_theta}},
        "router_hidden_size": dims.router_dim,
        "moe_intermediate_size": dims.expert_ff,
        "num_experts": dims.n_experts, "num_experts_per_tok": 1,
        "rms_norm_eps": dims.rms_eps,
        "n_layer": layers or dims.n_layers,
        "num_experts_held": held or dims.n_experts,
        "first_expert_held": first,
        "select_bias_update_rate": zaya.BIAS_UPDATE_RATE,
        "select_bias_updates_per_step": zaya.BIAS_UPDATES_PER_STEP,
        "n_positions": T,
        "padded_vocab_size": VOCAB}, **more)


@pytest.fixture(scope="module")
def dataset():
    name = "zaya-test-64"
    pcfg.DATASETS[name] = pcfg.DatasetSpec(name, (T,), VOCAB, 1 << 20,
                                           1 << 10, kind="tokens")
    yield name
    del pcfg.DATASETS[name]


def names_of(model):
    return [l.name for l in model.layers]


def seeded(model, key=0):
    """Random weights in the program's tree by the benchmark's own rules
    (every leaf: norm scales, merge vectors, biases, temperatures too)."""
    like = jax.eval_shape(lambda k: init_model(model, k)[0],
                          jax.random.key(0))
    flat = weights.make_weights(key, weights.flat_specs(like, names_of(model)),
                                {"matrix": 0.1, "scale_jitter": 0.1,
                                 "bias_std": 0.05})
    return weights.unflatten(flat, like, names_of(model)), flat


def batch(seed=0):
    seq = jax.random.randint(jax.random.key(seed), (BATCH, T + 1), 0, VOCAB)
    return seq[:, :-1], seq[:, 1:]


def run_config(dataset, arch, remat, dtype="float32", **more):
    cfg = pcfg.RunConfig(benchmark=dataset, arch=arch, strategy="single",
                         num_devices=1, batch_size=BATCH, compute_dtype=dtype,
                         remat_layers=remat, optimizer="sgd", lr=1.0,
                         momentum=0.0, weight_decay=0.0, **more)
    cfg.validate()
    return cfg


def program_loss_and_grads(cfg, params, x, y):
    strategy = make_strategy(cfg)
    # the step donates its state: it gets a copy
    ts = strategy.init(jax.random.key(0))._replace(
        params=jax.tree.map(lambda a: a.copy(), params))
    ts, m = strategy.train_step(ts, x, y, jnp.float32(1.0))
    grads = jax.tree.map(lambda a, b: a - b, params, ts.params)  # lr 1
    return float(m["loss"]), weights.flat_leaves(
        grads, names_of(strategy.model)), m


def reference_loss_and_grads(flat, x, y, cfg):
    with jax.default_matmul_precision("highest"):
        loss, grads, _ = jax.jit(
            lambda P, x, y: REF.loss_and_grads(P, x, y, cfg))(flat, x, y)
    return float(loss), grads


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("arch", ["zaya_t", "zaya_t-e4r1"])
def test_program_matches_the_reference(dataset, arch, remat):
    """Loss and every leaf's gradient, through cli's own strategy."""
    model = get_model(arch, dataset)
    params, flat = seeded(model)
    x, y = batch()
    _, layers, (first, held) = zaya.parse_arch(arch)
    with jax.default_matmul_precision("highest"):
        loss, grads, m = program_loss_and_grads(
            run_config(dataset, arch, remat), params, x, y)
    want, want_grads = reference_loss_and_grads(
        flat, x, y, ref_config(layers=layers, held=held, first=first))
    assert loss == pytest.approx(want, rel=2e-5)
    assert set(grads) == set(want_grads)
    for k in sorted(want_grads):
        np.testing.assert_allclose(
            np.asarray(grads[k]), np.asarray(want_grads[k]), rtol=2e-3,
            # the gradient is read off an lr-1 SGD step: params of size 1
            # round it at 1e-7
            atol=3e-7 + 2e-5 * float(jnp.max(jnp.abs(want_grads[k]))),
            err_msg=k)
    slots = BATCH * T * layers
    if held == DIMS.n_experts:
        assert float(m["moe_held_slots"]) == slots
    else:
        assert 0 < float(m["moe_held_slots"]) < slots
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert 1.0 / DIMS.n_experts <= float(m["moe_top1_weight_mean"]) <= 1.0


def test_logits_match_the_reference(dataset):
    model = get_model("zaya_t", dataset)
    params, flat = seeded(model, 3)
    x, _ = batch(3)
    _, states, _ = init_model(model, jax.random.key(0))
    with jax.default_matmul_precision("highest"):
        got, _ = apply_model(model, resolve_ties(model.ties, params), states,
                             x, train=True)
        want = jnp.stack([REF.logits(flat, row, ref_config()) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_the_bfloat16_step_stays_near_the_reference(dataset):
    """The step as the cell runs it (bfloat16 compute, remat, fused head,
    float32 router): the loss within bfloat16's rounding of the float32
    reference, the median leaf's gradient within a few percent."""
    arch = "zaya_t-e4"
    model = get_model(arch, dataset)
    params, flat = seeded(model, 1)
    x, y = batch(1)
    loss, grads, _ = program_loss_and_grads(
        run_config(dataset, arch, True, "bfloat16", fused_head_loss=True),
        params, x, y)
    want, want_grads = reference_loss_and_grads(
        flat, x, y, ref_config(held=4))
    assert loss == pytest.approx(want, rel=5e-3)
    rel = [float(jnp.linalg.norm(grads[k] - want_grads[k])
                 / (jnp.linalg.norm(want_grads[k]) + 1e-9))
           for k in want_grads]
    assert np.median(rel) < 0.06


def test_the_shares_add_up_to_the_uncut_layer():
    """One block's expert sublayer: what rank 0 and rank 1 of the pair give
    (each its own four experts' part; the router and everything before it
    computed alike on both and counted once) adds up to what the uncut
    reference gives for the whole layer, and no token is served by both."""
    S, half = 96, DIMS.n_experts // 2
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (S, DIMS.d_model), jnp.float32)
    router = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(ks[1], a.shape),
        zaya._router_init(ks[1], DIMS, carried=False))
    experts = jax.tree.map(lambda a: a * 5.0, jax.vmap(
        lambda k: zaya._swiglu_init(k, DIMS.d_model, DIMS.expert_ff))(
            jax.random.split(ks[2], DIMS.n_experts)))
    # the reference's sublayer: RMSNorm with a scale of ones, then a merge
    # that adds (scales 1, biases 0)
    P = {f"block1/router/{'/'.join(str(k.key) for k in path)}": leaf
         for path, leaf in jax.tree_util.tree_flatten_with_path(router)[0]}
    P.update({f"block1/experts/{k}": v for k, v in experts.items()})
    P["block1/ln2/scale"] = jnp.ones((DIMS.d_model,))
    P.update({f"block1/merge_moe/{a}/{b}": jnp.full((DIMS.d_model,), v)
              for a in "xf" for b, v in (("scale", 1.0), ("bias", 0.0))})
    with jax.default_matmul_precision("highest"):
        whole, _ = REF._moe(P, "block1", x, None, ref_config(layers=1),
                            REF.exact)
        h = zaya._rms_norm({"scale": jnp.ones((DIMS.d_model,))}, x,
                           DIMS.rms_eps)
        idx, w, _, _ = zaya.route(router, h, None, jnp.zeros(DIMS.n_experts),
                               DIMS.rms_eps)
        parts, held_slots = [], 0.0
        for rank in (0, 1):
            y, counters = dropless.routed_experts(
                jax.tree.map(lambda a: a[rank * half:(rank + 1) * half],
                             experts),
                h, idx, w, (rank * half, half), DIMS.n_experts,
                zaya.GMM_TILING)
            parts.append(y)
            held_slots += float(counters["held_slots"])
    assert held_slots == S  # every token's one expert is on one of the two
    assert float(jnp.max(jnp.abs(parts[0]) * jnp.abs(parts[1]))) == 0.0
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole - x), atol=2e-4)


# ---------------------------------------------------------------------------
# causality, the value shift, the convolutions, the carried state
# ---------------------------------------------------------------------------


def _block_output(model, params, x):
    _, states, _ = init_model(model, jax.random.key(0))
    out, _ = apply_model(model, resolve_ties(model.ties, params), states, x,
                         train=True)
    return out


def test_perturbing_token_t_moves_no_output_before_t(dataset):
    model = get_model("zaya_t", dataset)
    params, _ = seeded(model, 4)
    x, _ = batch(4)
    t = 23
    moved = x.at[:, t].set((x[:, t] + 1) % VOCAB)
    a, b = _block_output(model, params, x), _block_output(model, params, moved)
    assert float(jnp.max(jnp.abs(a[:, :t] - b[:, :t]))) == 0.0
    assert float(jnp.max(jnp.abs(a[:, t:] - b[:, t:]))) > 0.0


def test_the_value_shift_and_both_convolutions_at_the_first_token():
    """At t = 0 the previous token is nought: the shifted value head reads
    zeros, the depthwise convolution its newest tap alone, and the per-head
    convolution pads with zeros, not with the first one's bias."""
    H, d, Tn = 3, 8, 5
    ks = jax.random.split(jax.random.key(5), 5)
    u = jax.random.normal(ks[0], (1, H, Tn, d))
    w_dw = jax.random.normal(ks[1], (2, H, d))
    b_dw = jax.random.normal(ks[2], (H, d))
    w_head = jax.random.normal(ks[3], (H, 2, d, d))
    b_head = jax.random.normal(ks[4], (H, d))
    with jax.default_matmul_precision("highest"):
        got = zaya.causal_convs(u, w_dw, b_dw, w_head, b_head)
        c1_0 = b_dw + w_dw[1] * u[0, :, 0]
        want0 = b_head + jnp.einsum("gc,gce->ge", c1_0, w_head[:, 1])
        c1_1 = b_dw + w_dw[0] * u[0, :, 0] + w_dw[1] * u[0, :, 1]
        want1 = (b_head + jnp.einsum("gc,gce->ge", c1_0, w_head[:, 0])
                 + jnp.einsum("gc,gce->ge", c1_1, w_head[:, 1]))
    np.testing.assert_allclose(np.asarray(got[0, :, 0]), np.asarray(want0),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[0, :, 1]), np.asarray(want1),
                               atol=1e-5)
    x = jax.random.normal(ks[0], (2, Tn, d))
    shifted = zaya._previous(x)
    assert not np.any(np.asarray(shifted[:, 0]))
    np.testing.assert_array_equal(np.asarray(shifted[:, 1:]),
                                  np.asarray(x[:, :-1]))


def test_rope_turns_halves_of_the_rotary_slice_only():
    x = jax.random.normal(jax.random.key(6), (1, 2, 7, 16))
    pos = jnp.arange(7)
    got = zaya.rope_halves(x, pos, 1e4, 8)
    want = REF._rope_halves(x[0].transpose(1, 0, 2), 1e4, 8).transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(np.asarray(got[:, :, 0]), np.asarray(x[:, :, 0]),
                               atol=1e-7)  # position 0 is not turned


def test_the_carried_state_reaches_the_next_router(dataset):
    """The second block's choice reads what the first passed on: with the
    carry's scale at nought the program equals the reference's ``no_carry``
    fault, and with it the sound reference."""
    model = get_model("zaya_t", dataset)
    params, flat = seeded(model, 7)
    x, y = batch(7)
    with jax.default_matmul_precision("highest"):
        loss, _, _ = program_loss_and_grads(
            run_config(dataset, "zaya_t", False), params, x, y)
    sound, _ = reference_loss_and_grads(flat, x, y, ref_config())
    dropped, _ = reference_loss_and_grads(flat, x, y,
                                          ref_config(fault="no_carry"))
    assert loss == pytest.approx(sound, rel=2e-5)
    assert abs(dropped - sound) > 1e-4 * abs(sound)
    assert "block1/router/carry/scale" not in flat
    assert "block2/router/carry/scale" in flat


def test_remat_layers_carries_the_pair_through_checkpoint(dataset):
    """The rematerialized step equals the plain one to rounding: the pair
    (stream, router state) crosses jax.checkpoint as any pytree does, and the
    recomputed router chooses as the first pass did."""
    model = get_model("zaya_t-e4", dataset)
    params, _ = seeded(model, 8)
    x, y = batch(8)
    with jax.default_matmul_precision("highest"):
        a = program_loss_and_grads(run_config(dataset, "zaya_t-e4", False),
                                   params, x, y)
        b = program_loss_and_grads(run_config(dataset, "zaya_t-e4", True),
                                   params, x, y)
    assert a[0] == pytest.approx(b[0], rel=1e-6)
    for k in a[1]:
        np.testing.assert_allclose(np.asarray(a[1][k]), np.asarray(b[1][k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)
    assert float(a[2]["moe_held_slots"]) == float(b[2]["moe_held_slots"])


# ---------------------------------------------------------------------------
# the selection bias: layer state that every training step moves by the loads
# ---------------------------------------------------------------------------


def _biases(ts):
    return [np.asarray(s["select_bias"]) for s in ts.model_state
            if isinstance(s, dict) and "select_bias" in s]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_selection_bias_follows_the_loads(dataset, remat, monkeypatch):
    """Three steps of the program against the reference with the state
    carried by hand (``next_select_bias`` into ``P``): the same biases
    after every step, the same losses, the same parameters at the end — and
    other losses where the reference is left at beta = 0, as the benchmark's
    harness leaves it. At a rate large enough to move choices at this
    size."""
    rate, lr = 0.02, 0.05
    monkeypatch.setattr(zaya, "BIAS_UPDATE_RATE", rate)
    cfg = ref_config(select_bias_update_rate=rate)
    model = get_model("zaya_t", dataset)
    params, flat = seeded(model, 11)
    strategy = make_strategy(run_config(dataset, "zaya_t", remat))
    ts = strategy.init(jax.random.key(0))._replace(
        params=jax.tree.map(lambda a: a.copy(), params))
    assert all(not b.any() for b in _biases(ts))
    P, P0, state = dict(flat), dict(flat), {}
    ref = jax.jit(lambda P, x, y: REF.loss_and_grads(P, x, y, cfg)[:2])
    nxt = jax.jit(lambda P, x: REF.next_select_bias(P, x, cfg))
    losses, carried, fixed = [], [], []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            x, y = batch(20 + i)
            ts, m = strategy.train_step(ts, x, y, jnp.float32(lr))
            losses.append(float(m["loss"]))
            loss, grads = ref({**P, **state}, x, y)
            carried.append(float(loss))
            state = nxt({**P, **state}, x)
            P = {k: P[k] - lr * grads[k] for k in P}
            loss0, grads0 = ref(P0, x, y)
            fixed.append(float(loss0))
            P0 = {k: P0[k] - lr * grads0[k] for k in P0}
            got = _biases(ts)
            for j, b in enumerate(got):
                np.testing.assert_allclose(
                    b, np.asarray(state[f"block{j + 1}/router/select_bias"]),
                    atol=1e-7)
            # every expert's bias moved by the rate, up or down, or stayed
            # (an expert at the mean load)
            assert np.allclose(np.abs(got[0]) / rate,
                               np.round(np.abs(got[0]) / rate), atol=1e-4)
    assert losses == pytest.approx(carried, rel=2e-5)
    assert losses[0] == pytest.approx(fixed[0], rel=2e-5)
    assert abs(losses[2] - fixed[2]) > 1e-4 * abs(fixed[2])
    end = weights.flat_leaves(ts.params, names_of(model))
    for k in sorted(P):
        np.testing.assert_allclose(np.asarray(end[k]), np.asarray(P[k]),
                                   rtol=2e-3, atol=2e-6, err_msg=k)


def test_evaluation_leaves_the_selection_bias_alone(dataset):
    model = get_model("zaya_t", dataset)
    params, _ = seeded(model, 12)
    x, _ = batch(12)
    _, states, _ = init_model(model, jax.random.key(0))
    tied = resolve_ties(model.ties, params)
    _, evaluated = apply_model(model, tied, states, x, train=False)
    _, trained = apply_model(model, tied, states, x, train=True)
    for before, ev, tr in zip(states, evaluated, trained):
        if "select_bias" not in before:
            continue
        assert not np.asarray(ev["select_bias"]).any()
        moved = np.asarray(tr["select_bias"])
        # whole rates, at most one a repeat
        steps = moved / zaya.BIAS_UPDATE_RATE
        assert np.allclose(steps, np.round(steps), atol=1e-3) and moved.any()
        assert np.abs(steps).max() <= zaya.BIAS_UPDATES_PER_STEP + 1e-3


def test_balancing_spreads_a_collapsed_router(dataset, monkeypatch):
    """A router whose last matrix favours one expert for every token sends
    it everything; with the parameters held still (lr 0) the biases alone
    spread the loads again: the favoured expert's goes down a rate an
    update, sixteen updates a step, until the others' choices count. (128 tokens a layer over 8 experts, the
    most uneven of 3 layers: a sample this small stays well off 1.)"""
    monkeypatch.setattr(zaya, "BIAS_UPDATE_RATE", 0.01)
    model = get_model("zaya_t", dataset)
    params, _ = seeded(model, 13)
    for block in params[1:-1]:
        router = block["router"]
        router["b_2"] = jnp.full_like(router["b_2"], 1.0)
        router["w_3"] = (5.0 * router["w_3"]).at[:, 0].set(
            jnp.abs(router["w_3"][:, 0]) + 0.02)
    strategy = make_strategy(run_config(dataset, "zaya_t", False))
    ts = strategy.init(jax.random.key(0))._replace(params=params)
    loads = []
    for i in range(24):
        ts, m = strategy.train_step(ts, *batch(30 + i), jnp.float32(0.0))
        loads.append(float(m["moe_load_max_over_mean"]))
    # every token of a layer to one expert, until its bias is low enough
    assert loads[0] == DIMS.n_experts
    assert np.mean(loads[-10:]) < 0.5 * DIMS.n_experts
    first = _biases(ts)[0]
    assert first.argmin() == 0 and first[0] < first[1:].min() - 0.2


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_moves_the_reference(fault):
    """A fault that moved nothing could set no limit."""
    model_cfg = ref_config(held=4)
    like = jax.eval_shape(
        lambda k: init_model(zaya.build("zaya_t-e4", (T,), VOCAB), k)[0],
        jax.random.key(0))
    names = ["embed", "block1", "block2", "block3", "lm_head"]
    flat = weights.make_weights(9, weights.flat_specs(like, names),
                                {"matrix": 0.1, "bias_std": 0.05})
    x, y = batch(9)
    sound, sg = reference_loss_and_grads(flat, x, y, model_cfg)
    faulty, fg = reference_loss_and_grads(flat, x, y,
                                          dict(model_cfg, fault=fault))
    moved = max(float(jnp.max(jnp.abs(sg[k] - fg[k]))) for k in sg)
    assert moved > 1e-6 or abs(sound - faulty) > 1e-6


# ---------------------------------------------------------------------------
# the tied embedding
# ---------------------------------------------------------------------------


def test_the_tied_leaf_is_one_leaf_with_both_gradients(dataset):
    """``embed/tok`` is the only matrix of the vocabulary's size in the
    tree, ``flat_specs`` lists it once, Adam keeps one moment pair for it,
    and its gradient is the sum of the lookup's and the head's."""
    model = get_model("zaya_t", dataset)
    params, flat = seeded(model, 10)
    specs = weights.flat_specs(params, names_of(model))
    vocab_sized = [k for k, shape in specs.items() if VOCAB in shape]
    assert vocab_sized == ["embed/tok"]
    assert sorted(params[-1]) == ["norm"]
    cfg = pcfg.RunConfig(benchmark=dataset, arch="zaya_t", strategy="single",
                         num_devices=1, batch_size=BATCH,
                         compute_dtype="float32", optimizer="adam", lr=1e-3)
    ts = make_strategy(cfg).init(jax.random.key(0))
    for moment in ("m", "v"):
        slots = weights.flat_specs(ts.opt[moment], names_of(model))
        assert [k for k, shape in slots.items() if VOCAB in shape] == \
            ["embed/tok"]
    assert param_count(ts.params) == param_count(ts.opt["m"])

    x, y = batch(10)
    cfg_ref = ref_config()
    with jax.default_matmul_precision("highest"):
        _, grads, _ = program_loss_and_grads(
            run_config(dataset, "zaya_t", False), params, x, y)

        def part(use):
            """The reference's gradient through ONE use of the matrix."""
            def loss(E):
                P = dict(flat)
                tot = 0.0
                for row, lab in zip(x, y):
                    h = jnp.take(E if use == "lookup" else flat["embed/tok"],
                                 row, axis=0)
                    r = jnp.zeros((T, DIMS.router_dim))
                    for i in range(1, DIMS.n_layers + 1):
                        h, r = REF._block(P, i, h, r, cfg_ref, REF.exact)
                    h = REF._rms(P["lm_head/norm/scale"], h, DIMS.rms_eps)
                    head = E if use == "head" else flat["embed/tok"]
                    tot = tot + REF.cross_entropy_sum(h @ head.T, lab)
                return tot / y.size
            return jax.grad(loss)(flat["embed/tok"])

        lookup, head = part("lookup"), part("head")
    assert float(jnp.linalg.norm(lookup)) > 0 < float(jnp.linalg.norm(head))
    np.testing.assert_allclose(np.asarray(grads["embed/tok"]),
                               np.asarray(lookup + head), rtol=2e-3,
                               atol=1e-6)


def test_a_model_without_ties_is_handed_through():
    params = [{"a": 1}, {"b": 2}]
    assert resolve_ties((), params) is params


# ---------------------------------------------------------------------------
# the arch string, and what stays refused
# ---------------------------------------------------------------------------


def test_arch_strings_carry_the_share():
    dims, layers, held = zaya.parse_arch("zaya1_8b-l5-e8")
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim) == \
        (2048, 8, 2, 128)
    assert (layers, held) == (5, (0, 8))
    assert zaya.parse_arch("zaya1_8b-l5-e8r1")[2] == (8, 8)
    assert zaya.parse_arch("zaya1_8b")[1:] == (40, (0, 16))
    assert zaya.parse_arch("kanana2_30b_a3b") is None
    assert arch_name("zaya1_8b-l5-e8") == "zaya1_8b-l5-e8"
    assert not collects_aux_loss("zaya1_8b-l5-e8")
    for bad in ("zaya1_8b-e3", "zaya1_8b-e8r2", "zaya1_8b-l41"):
        with pytest.raises(ValueError):
            arch_name(bad)


def test_the_published_share_has_the_parameters_the_issue_reckoned():
    """Shapes alone (no array is made): one layer of the 8-expert share and
    the 5-layer cut the issue asked for (the cell keeps 4: its fallback)."""
    model = zaya.build("zaya1_8b-l5-e8", (8192,), 32896)
    like = jax.eval_shape(lambda k: init_model(model, k)[0],
                          jax.random.key(0))
    per_layer = param_count(like[2])
    experts, attention, router = 8 * 3 * 2048 * 2048, 5_575_682, 660_736
    # two norms and the two merges' four vectors each
    assert per_layer == experts + attention + router + (2 + 8) * 2048
    # the first block carries no state in: no gamma; the head owns a norm
    assert param_count(like) == 32896 * 2048 + 5 * per_layer - 256 + 2048
    assert param_count(like) == 601_973_770


@pytest.mark.parametrize("strategy", ["dp", "gpipe", "pipedream", "tp",
                                      "fsdp"])
def test_validate_refuses_strategies_the_model_is_not_brought_up_on(
        dataset, strategy):
    cfg = pcfg.RunConfig(benchmark=dataset, arch="zaya_t", strategy=strategy,
                         num_devices=2, batch_size=2)
    with pytest.raises(ValueError, match="brought up on single"):
        cfg.validate()


def test_the_profiler_refuses_a_pair_valued_boundary(dataset):
    from ddlbench_tpu.profiler.profile import profile_model

    with pytest.raises(ValueError, match="one array"):
        profile_model(get_model("zaya_t", dataset), batch_size=2)


# ---------------------------------------------------------------------------
# what the shared code left as it was
# ---------------------------------------------------------------------------

# sha256 of kanana2's lowered step (StableHLO text, no locations) AT THE
# PARENT of the PR that moved the dropless layer to models/dropless.py and
# gave flash_attention a second head count (e009dd1): neither changed an
# operation of that family's step. Recorded again when the dropless
# layer's common buffer came to move its rows by gathers alone and its
# layers to count ``buffer_fill``: both changed the step's text. Recorded
# again when the token sum's empty slots came to read a row each of their
# own in place of row 0 (``dropless._read_rows``) and the layer's row
# gathers to clamp their indices (``mode="clip"``): both changed the text.
KANANA2_STEP_AT_PARENT = {
    ("kanana2_t", False, "float32"):
        "e2e4d0cd27634e4d05e19d7acee5e9a09cb0dfe0a7d77664d40c166167dd60f7",
    ("kanana2_t", True, "float32"):
        "a3463b3b8e97163f6fa281dfd67c8276ea7a84d2c2b5bdea6fec0bdbf1d7182e",
    ("kanana2_t-e4r1", False, "float32"):
        "2c10a1dbd7d884eb0a2762909cdab0e528e08ce2fd4d0612dbedd9df982173d9",
    ("kanana2_t-e4r1", True, "float32"):
        "f844e5672b5f6f95c2da53e3b7362b50d971b7111e55ff9d862b4db4f0a29e20",
    ("kanana2_t-e4r1", True, "bfloat16"):
        "11e4049d3b44e93c5dc6f8e47079e26fc8e4169d2b7c0a86cab056f6f6cca855",
}


@pytest.mark.parametrize("case", sorted(KANANA2_STEP_AT_PARENT),
                         ids=lambda c: f"{c[0]}-{'remat' if c[1] else 'plain'}"
                                       f"-{c[2]}")
def test_kanana2s_step_is_the_text_it_was(case):
    arch, remat, dtype = case
    name = "kanana2-test-64"
    pcfg.DATASETS[name] = pcfg.DatasetSpec(name, (64,), 128, 1 << 20, 1 << 10,
                                           kind="tokens")
    try:
        cfg = pcfg.RunConfig(benchmark=name, arch=arch, strategy="single",
                             num_devices=1, batch_size=2, compute_dtype=dtype,
                             remat_layers=remat, fused_head_loss=True,
                             optimizer="adam", lr=1e-3)
        cfg.validate()
        s = make_strategy(cfg)
        state = jax.eval_shape(s.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
        text = s.train_step.lower(
            state, x, x, jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    finally:
        del pcfg.DATASETS[name]
    assert hashlib.sha256(text.encode()).hexdigest() == \
        KANANA2_STEP_AT_PARENT[case], (
            "kanana2's lowered step differs from the one recorded before the "
            "dropless layer moved. If a later change to that model or to jax "
            "is what moved it, record the new hash here.")
