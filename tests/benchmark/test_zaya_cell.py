"""The zaya1-8b configuration and its cell: a CPU rehearsal of the accepted
train driver on the family's test size (tests/benchmark/data/zaya: the
program's ``zaya_t`` with the share of rank 1 of 2, float32,
``remat_layers``), the planted faults and the control through the harness's
own comparison, the counts behind ``train_step_mfu`` and the rooflines, the
file against the catalog's row, and the manifest's proof that the addition
edited nothing. Nothing printed here is a device metric."""

import json
import os
import shutil
import types

import jax
import pytest

from benchmarks import run_cell
from benchmarks.harness import compare, manifest, train_driver, weights
from benchmarks.harness.traffic import SeededBatches

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "zaya")
MAN = manifest.Manifest()
CONFIG = MAN.config("zaya1-8b")
TRAFFIC = MAN.traffic("train-b2-t8192")
REF = MAN.reference(CONFIG)
CELL = "zaya1-ep2-train"


def context(seed=3):
    man = manifest.Manifest()
    man.dir = DATA
    man.index = dict(
        man.index,
        configs=[{"name": "zaya-tiny", "file": os.path.relpath(
            os.path.join(DATA, "configs", "zaya-tiny.json"), man.root)}],
        workloads=[{"name": "tiny", "config": "zaya-tiny",
                    "traffic": "train-zaya-tiny", "chips": 1}])
    args = types.SimpleNamespace(workload="tiny", seed=seed, seconds=0.5,
                                 trace=0)
    rc = run_cell.RunContext(man, args, jax.devices())
    rc.read_memory_peak = lambda: 0
    rc.mark = lambda phase: None
    return rc


def test_the_train_driver_runs_the_cell_at_the_test_size():
    """The accepted driver end to end: the program's strategy with
    remat_layers, the seed's weights laid into its tree by leaf name (the
    tied matrix once, at the embedding), the window, the reference
    following three Adam steps, every number inside float32 round-off."""
    rc = context()
    out = train_driver.run(rc)
    by = {c.name: c.value for c in out["numbers"]}
    assert compare.report(out["numbers"]), by
    assert out["counters"]["steps"] > 0 and rc.window_compiles == 0
    assert out["counters"]["model_flops"] == pytest.approx(
        out["counters"]["samples"]
        * rc.reference.train_flops_per_sample(rc.config, (64,)))


@pytest.fixture(scope="module")
def readings():
    """The sound reference, each planted fault and the control, three Adam
    steps each on one seed's weights and batches."""
    rc = context()
    config, hp = rc.config, train_driver.hyperparameters(
        rc.traffic["run_config"])
    _, strategy = train_driver.build(config, rc.traffic)
    names = [l.name for l in strategy.model.layers]
    shapes = jax.eval_shape(strategy.init, jax.random.key(0)).params
    specs = weights.flat_specs(shapes, names)
    assert [k for k, s in specs.items() if 256 in s] == ["embed/tok"]
    flat = weights.make_weights(3, specs, config["weights"])
    data = SeededBatches(3, "tokens", (64,), config["vocab_size"], 4)
    batches = [data.batch(0, i) for i in range(train_driver.CHECK_STEPS)]

    def numbers(rounding="float32", **planted):
        return train_driver.reference_numbers(
            rc.reference, dict(config, **planted), hp, flat, batches,
            rounding)

    def judged(side, ref):
        side = dict(side, grad_diff=train_driver.gradient_differences(
            side["grad"], ref["grad"]))
        return compare.train_numbers(side, ref, config["limits"])

    return numbers, judged


@pytest.mark.parametrize("what", ["again", "control"] + [
    f"fault:{f}" for f in REF.FAULTS])
def test_a_planted_fault_or_the_control_fails_a_limit(readings, what):
    numbers, judged = readings
    ref = numbers()
    if what == "again":  # the pair: the sound reference passes itself
        assert all(c.ok for c in judged(numbers(), ref))
        return
    side = numbers("float8_e4m3") if what == "control" \
        else numbers(fault=what.split(":")[1])
    failed = [c.name for c in judged(side, ref) if not c.ok]
    assert failed, what


def test_the_file_holds_every_number_of_the_catalog_s_row():
    """The catalog's config (model-configs guide), key for key, but for the
    one key of it that ``reduced`` lists; no width among the reduced."""
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272}
    entry = next(c for c in MAN.index["configs"] if c["name"] == "zaya1-8b")
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert entry["reduced"] == CONFIG["reduced"] == [
        "n_layer", "num_experts_held", "vocab_size"]
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 16, "vocab_size": 262272}
    assert (CONFIG["n_layer"], CONFIG["num_experts_held"],
            CONFIG["vocab_size"], CONFIG["padded_vocab_size"],
            CONFIG["n_positions"]) == (4, 8, 32784, 32896, 8192)
    # the floors of a model_config cut: four layers of period 1, 8 experts,
    # an eighth of the vocabulary
    assert CONFIG["n_layer"] >= 4 and CONFIG["num_experts_held"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert "Two chips" in CONFIG["deployment"]
    assert len(CONFIG["assumed"]) >= 8 and len(CONFIG["departures"]) == 1
    assert CONFIG["precision"]["train"]["router"] == "float32"


def test_the_arch_string_is_the_file_s_cut():
    from ddlbench_tpu.models import zaya

    dims, layers, held = zaya.parse_arch(CONFIG["arch"])
    assert layers == CONFIG["n_layer"]
    assert held == (CONFIG["first_expert_held"], CONFIG["num_experts_held"])
    rope = CONFIG["rope_parameters"]["hybrid"]
    for key, got in (("hidden_size", dims.d_model),
                     ("num_attention_heads", dims.n_heads),
                     ("num_key_value_heads", dims.n_kv_heads),
                     ("head_dim", dims.head_dim),
                     ("cca_time0", dims.conv_taps[0]),
                     ("cca_time1", dims.conv_taps[1]),
                     ("router_hidden_size", dims.router_dim),
                     ("moe_intermediate_size", dims.expert_ff),
                     ("num_experts", dims.n_experts),
                     ("num_hidden_layers", dims.n_layers),
                     ("rms_norm_eps", dims.rms_eps)):
        assert CONFIG[key] == got, key
    assert rope["rope_theta"] == dims.rope_theta
    assert rope["partial_rotary_factor"] * dims.head_dim == dims.rotary
    assert CONFIG["num_experts_per_tok"] == 1
    # the rate of the selection bias's update: the file's, the reference's
    # by the file, the program's by its constant
    assert CONFIG["select_bias_update_rate"] == zaya.BIAS_UPDATE_RATE
    assert CONFIG["select_bias_updates_per_step"] == \
        zaya.BIAS_UPDATES_PER_STEP


def test_train_flops_of_a_step():
    """ISSUE 32's reckoning of a step of 2 x 8192 tokens at 5 layers —
    scores 4.1, projections and convolutions 2.7, router 0.3, held experts
    at balanced routing 3.1, head 6.6 TFLOP — for the 4 layers kept (the
    issue's fallback: the check's reference flow does not fit beside 5)."""
    tokens = 16384
    attn_proj = 2048 * 1024 + 2 * 2048 * 256 + 1024 * 2048
    convs = 2 * 1280 + 2 * 10 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    routed = 0.5 * 3 * 2048 * 2048
    L = CONFIG["n_layer"]
    assert REF.matmul_params_per_token(CONFIG) == pytest.approx(
        L * (attn_proj + convs + router + routed) + 2048 * 32896)
    assert REF.held_slots_balanced(CONFIG, tokens) == 8192
    step = 2 * REF.train_flops_per_sample(CONFIG, (8192,))
    part = lambda per_token: 3 * 2.0 * per_token * tokens
    assert part(5 * (attn_proj + convs)) == pytest.approx(2.7e12, rel=0.03)
    assert part(5 * router) == pytest.approx(0.3e12, rel=0.15)
    assert part(5 * routed) == pytest.approx(3.1e12, rel=0.01)
    assert part(2048 * 32896) == pytest.approx(6.6e12, rel=0.01)
    scores = 3 * L * 8 * 2 * 256 * 8192 * 8193 / 2 * 2
    assert scores * 5 / L == pytest.approx(4.1e12, rel=0.01)
    assert step == pytest.approx(
        part(REF.matmul_params_per_token(CONFIG)) + scores)
    assert step == pytest.approx(14.8e12, rel=0.01)


def test_kernel_shapes_and_work():
    flash, gmm = MAN.kernel("flash_attn"), MAN.kernel("moe_gmm")
    (calls, shape), = REF.kernel_calls("flash_attn", CONFIG, TRAFFIC)
    assert (calls, shape) == (4, dict(B=2, H=8, T=8192, dh=128))
    f, b = flash.work(**shape)
    # FLOPs bound the call, so the K-headed tensors' overcount of bytes
    # moves nothing: the least time is the FLOPs'
    assert f == pytest.approx(6 * 2.0 * 2 * 8 * 8192 * 8192 * 128 / 2)
    assert f / 197e12 > 5 * b / 819e9
    assert REF.kernel_calls("fused_xent", CONFIG, TRAFFIC) == [
        (1, dict(N=16384, D=2048, V=32896))]
    (calls, shape), = REF.kernel_calls("moe_gmm", CONFIG, TRAFFIC)
    assert (calls, shape) == (4, dict(slots=8192.0, D=2048, F=2048, G=8))
    f, b = gmm.work(**shape)
    assert f == 9 * 2.0 * 8192 * 2048 * 2048
    with pytest.raises(KeyError):
        REF.kernel_calls("paged_decode_attn", CONFIG, TRAFFIC)


def test_the_cell_reports_what_the_issue_lists():
    printed = {m["name"] for m in MAN.per_layer_of(CELL)}
    assert printed == {
        "window_compiles.train", "input_stall_share.train", "train_step_mfu",
        "train_peak_hbm_share", "flash_attn_roofline", "fused_xent_roofline",
        "moe_gmm_roofline", "device_idle_share.train",
        "step_forward_ms.train", "step_backward_ms.train",
        "step_optimizer_ms.train", "norm_ms.train", "attn_ms.train",
        "head_loss_ms.train", "unscoped_device_share.train",
        "moe_route_ms.train", "moe_experts_ms.train", "cca_mix_ms.train",
        "moe_router_ms.train"}
    assert {m["name"] for m in MAN.end_to_end_of(CELL)} == {
        "train_samples_per_s_per_chip", "setup_s"}
    cell = MAN.workload(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "zaya1-8b", "train-b2-t8192")
    assert TRAFFIC == dict(MAN.traffic("train-b4-t4096"), run_config=dict(
        MAN.traffic("train-b4-t4096")["run_config"], batch_size=2))
    # the two new parts are read by the accepted part reader, each with all
    # five tokens, so the innermost decides
    from ddlbench_tpu.telemetry import scopes as program

    five = list(program.PARTS) + [program.CCA_MIX, program.ROUTER]
    reader = MAN.reader(MAN.metric_file("cca_mix_ms.train"))
    for name, part in (("cca_mix_ms.train", "cca_mix"),
                       ("moe_router_ms.train", "router")):
        spec = MAN.metric_file(name)
        assert spec["reader"] == "scope_part_ms"
        assert spec["args"] == {"part": part, "parts": five}
        assert spec["source"] == "device_trace"
    tokens = program.KINDS + tuple(five)
    inner = lambda op: reader.innermost(op, tokens)
    assert inner("jit(train_step)/jvp(block2)/attn/cca_mix/mul") == "cca_mix"
    assert inner("jit(train_step)/transpose(jvp(block2))/route/router/"
                 "dot_general") == "router"
    assert inner("jit(train_step)/jvp(block2)/route/sort") == "route"
    assert inner("jit(train_step)/jvp(block2)/route/experts/jit(gmm)/"
                 "pallas_call") == "experts"
    assert inner("jit(train_step)/jvp(block2)/attn/flash_attn_fwd/"
                 "pallas_call") == "attn"
    # the accepted route metric lists three parts: the router counts as route
    three = program.KINDS + program.PARTS
    assert reader.innermost("jit(train_step)/jvp(block2)/route/router/dot",
                            three) == "route"


def test_the_program_s_step_carries_the_new_scopes():
    """The rehearsal's compiled step names both parts, forward and
    backward, each inside the scope it is a part of."""
    from benchmarks.harness import scopes

    rc = context()
    _, strategy = train_driver.build(rc.config, rc.traffic)
    state = jax.eval_shape(strategy.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, 64), "int32")
    # jax's compile cache strips debug info from its key: an entry of a
    # step traced before a scope was added would stand in for this one
    text = scopes.compile_fresh(strategy.train_step.lower(
        state, x, x, jax.ShapeDtypeStruct((), "float32")))
    ops = set(scopes.scope_table(text).values())
    for part in ("cca_mix", "router"):
        outer = {"cca_mix": "attn", "router": "route"}[part]
        inside = f"/{outer}/{part}/"
        # a rematerialized layer's backward has the checkpoint's own name
        # between the instance and the kinds
        for phase in ("jit(train_step)/jvp(block2)/",
                      "jit(train_step)/transpose(jvp(block2))/"):
            assert any(op.startswith(phase) and inside in op
                       for op in ops), (part, phase)


def test_the_rehearsal_configuration_is_an_addition(tmp_path):
    """The way this configuration came in, rehearsed on the committed
    benchmark with the test-size files of data/zaya: new files, new entries,
    the cell's name appended to the rosters it joins — and ``against`` finds
    not one byte changed in a file that was there."""
    root = tmp_path / "repo"
    ix = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in ix["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = manifest.tree_hashes(str(root), ix["paths"])
    for kind, name in (("configs", "zaya-tiny.json"),
                       ("traffic", "train-zaya-tiny.json")):
        shutil.copy(os.path.join(DATA, kind, name),
                    root / "benchmarks" / kind / name)
    ix["configs"].append({
        "name": "zaya-tiny", "source": "tests/benchmark/data/zaya",
        "file": "benchmarks/configs/zaya-tiny.json",
        "reduced": ["n_layer", "num_experts_held", "vocab_size"],
        "why": "the family's test size, the share of rank 1 of 2"})
    ix["workloads"].append({
        "name": "zaya-tiny-train", "config": "zaya-tiny",
        "traffic": "train-zaya-tiny", "chips": 1,
        "why": "4 x 64 tokens a step, float32: the rehearsal of the cell"})
    joined = [m for m in ix["end_to_end"] + ix["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(joined) == 20  # the rate, 17 accepted metrics, this PR's 2
    for m in joined:
        m["workloads"].append("zaya-tiny-train")
    (root / "BENCHMARK.json").write_text(json.dumps(ix))
    man = manifest.Manifest(str(root))
    assert manifest.check(man) == []
    assert manifest.against(man, ROOT) == []
    after = manifest.tree_hashes(str(root), ix["paths"])
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/zaya-tiny.json",
        "benchmarks/traffic/train-zaya-tiny.json"]
    assert {m["name"] for m in man.per_layer_of("zaya-tiny-train")} \
        == {m["name"] for m in MAN.per_layer_of(CELL)}
