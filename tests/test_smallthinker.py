"""models/smallthinker.py against its plain reference
(benchmarks/reference/smallthinker.py, which imports nothing of the program),
at the family's test size on the CPU: logits, loss and every leaf's gradient
in float32 with and without remat_layers, three Adam steps, and through the
bfloat16 step; the four shares add up to the uncut layer; the router reads
the layer's input and nothing of attention; a window layer is not the same
layer without its window, a global layer carries no positions; the arch
string; and what the gate's activation in models/dropless.py left as it
was."""

import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from benchmarks.reference import common
from ddlbench_tpu import config as pcfg
from ddlbench_tpu.models import dropless, smallthinker
from ddlbench_tpu.models.layers import apply_model, init_model, param_count
from ddlbench_tpu.models.zoo import (ARCH_HELP, MODEL_NAMES, arch_name,
                                     collects_aux_loss, get_model)
from ddlbench_tpu.parallel import make_strategy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, VOCAB, BATCH = 64, 128, 2
DIMS = smallthinker.FAMILY["smallthinker_t"]


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "smallthinker.py")
    spec = importlib.util.spec_from_file_location("ref_smallthinker", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def ref_config(dims=DIMS, layers=None, held=None, first=0, **more):
    """The reference's configuration keys (config.json's names) of a Dims."""
    return dict({
        "hidden_size": dims.d_model, "num_attention_heads": dims.n_heads,
        "num_key_value_heads": dims.n_kv_heads, "head_dim": dims.head_dim,
        "moe_ffn_hidden_size": dims.expert_ff,
        "moe_num_primary_experts": dims.n_experts,
        "moe_num_active_primary_experts": dims.top_k,
        "sliding_window_size": dims.window,
        "sliding_window_layout": list(dims.layout),
        "rope_layout": list(dims.layout), "rope_theta": dims.rope_theta,
        "rms_norm_eps": dims.rms_eps,
        "n_layer": layers or dims.n_layers,
        "moe_num_primary_experts_held": held or dims.n_experts,
        "first_expert_held": first,
        "n_positions": T, "padded_vocab_size": VOCAB}, **more)


@pytest.fixture(scope="module")
def dataset():
    name = "smallthinker-test-64"
    pcfg.DATASETS[name] = pcfg.DatasetSpec(name, (T,), VOCAB, 1 << 20,
                                           1 << 10, kind="tokens")
    yield name
    del pcfg.DATASETS[name]


def names_of(model):
    return [l.name for l in model.layers]


def seeded(model, key=0):
    """Random weights in the program's tree by the benchmark's own rules."""
    like = jax.eval_shape(lambda k: init_model(model, k)[0],
                          jax.random.key(0))
    flat = weights.make_weights(key, weights.flat_specs(like, names_of(model)),
                                {"matrix": 0.1, "scale_jitter": 0.1})
    return weights.unflatten(flat, like, names_of(model)), flat


def batch(seed=0):
    seq = jax.random.randint(jax.random.key(seed), (BATCH, T + 1), 0, VOCAB)
    return seq[:, :-1], seq[:, 1:]


def run_config(dataset, arch, remat, dtype="float32", **more):
    more = dict(dict(optimizer="sgd", lr=1.0, momentum=0.0), **more)
    cfg = pcfg.RunConfig(benchmark=dataset, arch=arch, strategy="single",
                         num_devices=1, batch_size=BATCH, compute_dtype=dtype,
                         remat_layers=remat, weight_decay=0.0, **more)
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
@pytest.mark.parametrize("arch", ["smallthinker_t", "smallthinker_t-l4-e2r3"])
def test_program_matches_the_reference(dataset, arch, remat, monkeypatch):
    """Loss and every leaf's gradient, through cli's own strategy: both
    periods whole, and one period with the share of rank 3 of 4; with every
    layer rematerialized, as the family asks (LayerModel.remat_layers), and
    with none."""
    build = smallthinker.build
    monkeypatch.setattr(smallthinker, "build", lambda *a, **k: dataclasses.
                        replace(build(*a, **k), remat_layers=remat))
    model = get_model(arch, dataset)
    params, flat = seeded(model)
    x, y = batch()
    _, layers, (first, held) = smallthinker.parse_arch(arch)
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
    slots = BATCH * T * DIMS.top_k * layers
    if held == DIMS.n_experts:
        assert float(m["moe_held_slots"]) == slots
    else:
        assert 0 < float(m["moe_held_slots"]) < slots
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert 1.0 / DIMS.top_k <= float(m["moe_top1_weight_mean"]) <= 1.0


def test_logits_match_the_reference(dataset):
    model = get_model("smallthinker_t", dataset)
    params, flat = seeded(model, 3)
    x, _ = batch(3)
    _, states, _ = init_model(model, jax.random.key(0))
    with jax.default_matmul_precision("highest"):
        got, _ = apply_model(model, params, states, x, train=True)
        want = jnp.stack([REF.logits(flat, row, ref_config()) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_three_adam_steps_follow_the_reference(dataset):
    """The cell's optimizer (Adam 3e-4 / 0.9 / 0.95) through three steps of
    the rematerialized step with the fused head: the losses and every
    leaf's change against the reference's own Adam."""
    arch = "smallthinker_t-l4-e4"
    hp = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
          "weight_decay": 0.0}
    model = get_model(arch, dataset)
    params, flat = seeded(model, 5)
    strategy = make_strategy(run_config(
        dataset, arch, True, fused_head_loss=True, optimizer="adam",
        lr=hp["lr"], adam_beta1=0.9, adam_beta2=0.95, adam_eps=1e-8))
    ts = strategy.init(jax.random.key(0))._replace(
        params=jax.tree.map(lambda a: a.copy(), params))
    cfg = ref_config(layers=4, held=4)
    p = dict(flat)
    m = {k: jnp.zeros_like(a) for k, a in flat.items()}
    v = {k: jnp.zeros_like(a) for k, a in flat.items()}
    with jax.default_matmul_precision("highest"):
        for t in range(1, 4):
            x, y = batch(10 + t)
            ts, out = strategy.train_step(ts, x, y, jnp.float32(hp["lr"]))
            want, g = reference_loss_and_grads(p, x, y, cfg)
            p, m, v = common.adam(p, g, m, v, jnp.float32(t), hp)
            assert float(out["loss"]) == pytest.approx(want, rel=2e-5), t
    got = weights.flat_leaves(ts.params, names_of(model))
    for k in sorted(flat):
        moved, want = got[k] - flat[k], p[k] - flat[k]
        assert float(jnp.linalg.norm(moved - want)) <= \
            0.02 * float(jnp.linalg.norm(want)) + 1e-9, k


def test_the_bfloat16_step_stays_near_the_reference(dataset):
    """The step as the cell runs it (bfloat16 compute, remat, fused head,
    float32 router): the loss within bfloat16's rounding of the float32
    reference, the median leaf's gradient within a few percent."""
    arch = "smallthinker_t-l4-e4"
    model = get_model(arch, dataset)
    params, flat = seeded(model, 1)
    x, y = batch(1)
    loss, grads, _ = program_loss_and_grads(
        run_config(dataset, arch, True, "bfloat16", fused_head_loss=True),
        params, x, y)
    want, want_grads = reference_loss_and_grads(
        flat, x, y, ref_config(layers=4, held=4))
    assert loss == pytest.approx(want, rel=5e-3)
    rel = [float(jnp.linalg.norm(grads[k] - want_grads[k])
                 / (jnp.linalg.norm(want_grads[k]) + 1e-9))
           for k in want_grads]
    assert np.median(rel) < 0.06


def _one_layer(seed, scale=5.0):
    """x [S, D], a router and all 8 experts' weights, and the reference's
    names for them in ``block1`` (norm scales of ones)."""
    S = 96
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (S, DIMS.d_model), jnp.float32)
    router = jax.random.normal(ks[1], (DIMS.d_model, DIMS.n_experts)) * 0.3
    stack = lambda k, a, b: scale * jax.vmap(
        lambda kk: smallthinker._dense_init(kk, a, b))(
            jax.random.split(k, DIMS.n_experts))
    experts = {"w_gate": stack(ks[2], DIMS.d_model, DIMS.expert_ff),
               "w_up": stack(ks[3], DIMS.d_model, DIMS.expert_ff),
               "w_down": stack(ks[4], DIMS.expert_ff, DIMS.d_model)}
    P = {"block1/router": router,
         "block1/ln2/scale": jnp.ones((DIMS.d_model,))}
    P.update({f"block1/experts/{k}": v for k, v in experts.items()})
    return x, router, experts, P


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One block's expert sublayer: what ranks 0..3 of the group give (each
    its own two experts' part; the router and the norm computed alike on
    all four and counted once) adds up to what the uncut reference gives
    for the whole layer, and every slot is served by exactly one chip."""
    x, router, experts, P = _one_layer(2)
    S, quarter = x.shape[0], DIMS.n_experts // 4
    cfg = ref_config(layers=1)
    with jax.default_matmul_precision("highest"):
        ridx, rw = REF.route(P, "block1", x, cfg)
        whole = REF.held_experts(P, "block1", x, ridx, rw, cfg, REF.exact)
        idx, w = smallthinker.route({"router": router}, x, DIMS)
        parts, held_slots = [], 0.0
        for rank in range(4):
            lo = rank * quarter
            y, counters = dropless.routed_experts(
                jax.tree.map(lambda a: a[lo:lo + quarter], experts), x, idx,
                w, (lo, quarter), DIMS.n_experts, smallthinker.GMM_TILING,
                act=jax.nn.relu)
            parts.append(y)
            held_slots += float(counters["held_slots"])
            alone = REF.held_experts(
                {k: (v[lo:lo + quarter] if "experts" in k else v)
                 for k, v in P.items()}, "block1", x, ridx, rw,
                dict(cfg, moe_num_primary_experts_held=quarter,
                     first_expert_held=lo), REF.exact)
            np.testing.assert_allclose(np.asarray(y), np.asarray(alone),
                                       atol=2e-4)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    assert held_slots == S * DIMS.top_k
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=4e-4)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, axis=-1)), 1.0,
                               atol=1e-6)  # the softmax over the six chosen


def _apply(model, params, x):
    _, states, _ = init_model(model, jax.random.key(0))
    return apply_model(model, params, states, x, train=True)


def test_the_router_reads_the_layer_s_input_and_nothing_of_attention(
        dataset):
    """With every attention weight of block 1 changed, block 1 sends each
    token to the experts it sent it to before (its router read x, which did
    not move) — the counters of the layer say so — while the layer's output
    and block 2's choices do move. The reference's ``router_late`` fault,
    which reads h2, is not what the program computes."""
    model = get_model("smallthinker_t-l2", dataset)
    params, flat = seeded(model, 6)
    x, y = batch(6)
    other = [dict(p) for p in params]
    for key in ("wq", "wk", "wv", "wo"):
        other[1][key] = params[1][key] * -1.5
    with jax.default_matmul_precision("highest"):
        a, sa = _apply(model, params, x)
        b, sb = _apply(model, other, x)
        emb = jnp.take(params[0]["tok"], x, axis=0).reshape(-1, DIMS.d_model)
        idx, w = smallthinker.route(params[1], emb, DIMS)
        idx2, _ = smallthinker.route(other[1], emb, DIMS)
    assert np.array_equal(np.asarray(idx), np.asarray(idx2))
    for name in ("held_slots", "load_max_over_mean", "top1_weight_mean"):
        assert float(sa[1]["moe"][name]) == float(sb[1]["moe"][name])
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=DIMS.n_experts)
    assert float(sa[1]["moe"]["load_max_over_mean"]) == pytest.approx(
        load.max() * DIMS.n_experts / idx.size)
    assert float(sa[1]["moe"]["top1_weight_mean"]) == pytest.approx(
        float(jnp.mean(jnp.max(w, axis=-1))))
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    cfg = ref_config(layers=2)
    sound, _ = reference_loss_and_grads(flat, x, y, cfg)
    late, _ = reference_loss_and_grads(flat, x, y,
                                       dict(cfg, fault="router_late"))
    loss, _, _ = program_loss_and_grads(
        run_config(dataset, "smallthinker_t-l2", False), params, x, y)
    assert loss == pytest.approx(sound, rel=2e-5)
    assert abs(late - sound) > 1e-4 * abs(sound)


def test_a_window_layer_and_a_global_layer_are_two_kinds(dataset,
                                                         monkeypatch):
    """Layer 1 is global: its output does not move when the rotary tables
    do (it applies no rotation), and it sees every earlier key. Layer 2 is
    a window layer: it moves with the tables, and it is not the same layer
    without its window — a token ``window`` or more back reaches it through
    no direct path."""
    x = jax.random.normal(jax.random.key(8), (1, T, DIMS.d_model))
    p = jax.tree.map(lambda a: a * 5.0, smallthinker.block(
        "b", DIMS, (0, DIMS.n_experts), True, "xla").init(
            jax.random.key(9), (T, DIMS.d_model))[0])
    attend = lambda dims, windowed: smallthinker.attention_sublayer(
        p, x, dims, windowed, "xla")
    other_tables = dataclasses.replace(DIMS, rope_theta=1e3)
    no_window = dataclasses.replace(DIMS, window=T)
    with jax.default_matmul_precision("highest"):
        assert np.array_equal(np.asarray(attend(DIMS, False)),
                              np.asarray(attend(other_tables, False)))
        assert float(jnp.max(jnp.abs(attend(DIMS, True)
                                     - attend(other_tables, True)))) > 1e-3
        banded, full = attend(DIMS, True), attend(no_window, True)
        # the first ``window`` queries see every earlier key either way
        np.testing.assert_allclose(np.asarray(banded[:, :DIMS.window]),
                                   np.asarray(full[:, :DIMS.window]),
                                   atol=1e-5)
        assert float(jnp.max(jnp.abs(banded[:, DIMS.window:]
                                     - full[:, DIMS.window:]))) > 1e-3
        # moving token 0 moves a window layer's last output not at all, a
        # global layer's it does
        moved = x.at[:, 0].add(1.0)
        last = lambda windowed, x: smallthinker.attention_sublayer(
            p, x, DIMS, windowed, "xla")[:, -1]
        assert np.array_equal(np.asarray(last(True, x)),
                              np.asarray(last(True, moved)))
        assert not np.array_equal(np.asarray(last(False, x)),
                                  np.asarray(last(False, moved)))
    # the layout decides the kind: global, window, window, window
    kinds = []
    monkeypatch.setattr(
        smallthinker, "attention_sublayer",
        lambda p, x, dims, windowed, backend: kinds.append(windowed) or x)
    model = get_model("smallthinker_t", dataset)
    params, _ = seeded(model, 8)
    _apply(model, params, batch(8)[0])
    assert kinds == [False, True, True, True] * 2


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_moves_the_reference(fault):
    """A fault that moved nothing could set no limit."""
    cfg = ref_config(layers=4, held=4)
    like = jax.eval_shape(lambda k: init_model(smallthinker.build(
        "smallthinker_t-l4-e4", (T,), VOCAB), k)[0], jax.random.key(0))
    names = ["embed", "block1", "block2", "block3", "block4", "lm_head"]
    flat = weights.make_weights(9, weights.flat_specs(like, names),
                                {"matrix": 0.1})
    x, y = batch(9)
    sound, sg = reference_loss_and_grads(flat, x, y, cfg)
    faulty, fg = reference_loss_and_grads(flat, x, y, dict(cfg, fault=fault))
    moved = max(float(jnp.max(jnp.abs(sg[k] - fg[k]))) for k in sg)
    assert moved > 1e-6 or abs(sound - faulty) > 1e-6


def test_arch_strings_carry_the_share():
    dims, layers, held = smallthinker.parse_arch(
        "smallthinker_21b_a3b-l4-e16")
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            dims.expert_ff, dims.n_experts, dims.top_k, dims.window) == \
        (2560, 28, 4, 128, 768, 64, 6, 4096)
    assert dims.layout == (0, 1, 1, 1) * 13 and dims.n_layers == 52
    assert (layers, held) == (4, (0, 16))
    assert smallthinker.parse_arch("smallthinker_21b_a3b-l4-e16r1")[2] == \
        (16, 16)
    assert smallthinker.parse_arch("smallthinker_21b_a3b")[1:] == \
        (52, (0, 64))
    assert smallthinker.parse_arch("zaya1_8b") is None
    assert arch_name("smallthinker_21b_a3b-l4-e16") == \
        "smallthinker_21b_a3b-l4-e16"
    assert "smallthinker_21b_a3b" in MODEL_NAMES
    assert "smallthinker_21b_a3b-l4-e16" in ARCH_HELP
    assert not collects_aux_loss("smallthinker_21b_a3b-l4-e16")
    for bad in ("smallthinker_21b_a3b-e5", "smallthinker_21b_a3b-e16r4",
                "smallthinker_21b_a3b-l53"):
        with pytest.raises(ValueError):
            arch_name(bad)
    # -l<n> keeps the FIRST n layers of the layout: -l4 is one whole period
    model = smallthinker.build("smallthinker_21b_a3b-l4-e16", (16384,), 19072)
    assert [l.name for l in model.layers] == [
        "embed", "block1", "block2", "block3", "block4", "lm_head"]
    assert model.strategies == ("single",)


def test_the_published_share_has_the_parameters_the_issue_reckoned():
    """Shapes alone (no array is made): one layer of the 16-expert share and
    the cell's cut, and the fallback's (8 held)."""
    model = smallthinker.build("smallthinker_21b_a3b-l4-e16", (16384,), 19072)
    like = jax.eval_shape(lambda k: init_model(model, k)[0],
                          jax.random.key(0))
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attention == 20_971_520
    assert param_count(like[1]) == param_count(like[2]) == \
        attention + 163_840 + 5_120 + 16 * 5_898_240 == 115_512_320
    assert param_count(like) == 4 * 115_512_320 + 2 * 19072 * 2560 + 2560 \
        == 559_700_480
    small = smallthinker.build("smallthinker_21b_a3b-l4-e8", (16384,), 19072)
    assert param_count(jax.eval_shape(
        lambda k: init_model(small, k)[0], jax.random.key(0))) == 370_956_800


def test_the_family_rematerializes_its_layers_of_itself(dataset):
    """``LayerModel.remat_layers``: the step lowers to the same text whether
    the run asks for remat_layers or not, and that text checkpoints."""
    assert get_model("smallthinker_t", dataset).remat_layers
    assert not get_model("zaya_t", dataset).remat_layers
    texts = []
    for remat in (False, True):
        s = make_strategy(run_config(dataset, "smallthinker_t-l4-e4", remat))
        state = jax.eval_shape(s.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((BATCH, T), jnp.int32)
        texts.append(s.train_step.lower(
            state, x, x, jax.ShapeDtypeStruct((), jnp.float32)).as_text())
    assert texts[0] == texts[1]
    # jax.checkpoint lowers its recomputation behind a barrier
    assert "optimization_barrier" in texts[0]


@pytest.mark.parametrize("strategy", ["dp", "gpipe", "pipedream", "tp",
                                      "fsdp"])
def test_validate_refuses_strategies_the_model_is_not_brought_up_on(
        dataset, strategy):
    cfg = pcfg.RunConfig(benchmark=dataset, arch="smallthinker_t",
                         strategy=strategy, num_devices=2, batch_size=2)
    with pytest.raises(ValueError, match="brought up on single"):
        cfg.validate()


# ---------------------------------------------------------------------------
# what the shared code left as it was
# ---------------------------------------------------------------------------

# sha256 of zaya's lowered step (StableHLO text, no locations) AT THE PARENT
# of the PR that gave models/dropless.py's gated MLP its activation as an
# argument and the attention paths a window (868a79b): with ``act`` unset and
# ``window`` 0 neither changed an operation of that family's step. kanana2's
# five, recorded before these, stand in tests/test_zaya.py.
# Recorded again when the dropless layer's common buffer came to move its
# rows by gathers alone and its layers to count ``buffer_fill``: both
# changed the step's text. Recorded again when the token sum's empty slots
# came to read a row each of their own in place of row 0
# (``dropless._read_rows``) and the layer's row gathers to clamp their
# indices (``mode="clip"``): both changed the step's text.
STEPS_AT_PARENT = {
    ("zaya_t-e4r1", True, "float32"):
        "4ffd1ecaf54af9b81730c9eeec15d3bbcb04df034ea0626410ed5b33eaa264a2",
    ("zaya_t-e4r1", True, "bfloat16"):
        "b8f80a598b61529d592c81d2a5ab875e4a4bdd14e6f2aaf99e201b09374a5c93",
    ("zaya_t", False, "float32"):
        "e23b9dbfa7dd718c57cdb491e5ccf3e711cd1a5b8d0cd19c774c09f98aa52fc0",
}


def lowered_step(arch, remat, dtype):
    name = "shared-test-64"
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
        return s.train_step.lower(
            state, x, x, jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    finally:
        del pcfg.DATASETS[name]


@pytest.mark.parametrize("case", sorted(STEPS_AT_PARENT),
                         ids=lambda c: f"{c[0]}-{'remat' if c[1] else 'plain'}"
                                       f"-{c[2]}")
def test_zayas_step_is_the_text_it_was(case):
    assert hashlib.sha256(lowered_step(*case).encode()).hexdigest() == \
        STEPS_AT_PARENT[case], (
            "the lowered step differs from the one recorded before dropless "
            "took an activation. If a later change to that model or to jax "
            "is what moved it, record the new hash here.")
