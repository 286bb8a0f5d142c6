"""The kanana-2-30b-a3b configuration and its cell: a CPU rehearsal of the
accepted train driver on the family's test size (tests/benchmark/data/
kanana2: the program's ``kanana2_t`` with the share of rank 1 of 4, float32,
``remat_layers``), the planted faults and the control through the harness's
own comparison, the counts behind ``train_step_mfu`` and the rooflines, and
the manifest's proof that the addition edited nothing. Nothing printed here
is a device metric."""

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
                    "kanana2")
MAN = manifest.Manifest()
CONFIG = MAN.config("kanana-2-30b-a3b")
TRAFFIC = MAN.traffic("train-b4-t4096")
REF = MAN.reference(CONFIG)
CELL = "kanana2-ep16-train"


def context(seed=3):
    man = manifest.Manifest()
    man.dir = DATA
    man.index = dict(
        man.index,
        configs=[{"name": "kanana2-tiny", "file": os.path.relpath(
            os.path.join(DATA, "configs", "kanana2-tiny.json"), man.root)}],
        workloads=[{"name": "tiny", "config": "kanana2-tiny",
                    "traffic": "train-kanana2-tiny", "chips": 1}])
    args = types.SimpleNamespace(workload="tiny", seed=seed, seconds=0.5,
                                 trace=0)
    rc = run_cell.RunContext(man, args, jax.devices())
    rc.read_memory_peak = lambda: 0
    rc.mark = lambda phase: None
    return rc


def test_the_train_driver_runs_the_cell_at_the_test_size():
    """The accepted driver end to end: the program's strategy with
    remat_layers, weights laid into its tree by leaf name, the window, the
    reference following three Adam steps, every number inside float32
    round-off."""
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
    flat = weights.make_weights(3, weights.flat_specs(shapes, names),
                                config["weights"])
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


def test_the_file_holds_every_published_width():
    """The catalog's config (model-configs guide), key for key, but for the
    three keys ``reduced`` lists; no width among them."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    entry = next(c for c in MAN.index["configs"]
                 if c["name"] == "kanana-2-30b-a3b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "n_layer", "n_routed_experts_held", "vocab_size"]
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert (CONFIG["n_layer"], CONFIG["n_routed_experts_held"],
            CONFIG["vocab_size"]) == (5, 8, 16032)
    # the floors of a model_config cut: a period + four expert layers, 8
    # experts, an eighth of the vocabulary
    assert CONFIG["n_layer"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert "16 chips" in CONFIG["deployment"] and CONFIG["assumed"]


def test_the_arch_string_is_the_file_s_cut():
    from ddlbench_tpu.models import kanana2

    dims, layers, held = kanana2.parse_arch(CONFIG["arch"])
    assert layers == CONFIG["n_layer"]
    assert held == (CONFIG["first_expert_held"],
                    CONFIG["n_routed_experts_held"])
    for key, got in (("hidden_size", dims.d_model),
                     ("num_attention_heads", dims.n_heads),
                     ("qk_nope_head_dim", dims.qk_nope),
                     ("qk_rope_head_dim", dims.qk_rope),
                     ("v_head_dim", dims.v_head),
                     ("kv_lora_rank", dims.kv_latent),
                     ("intermediate_size", dims.dense_ff),
                     ("moe_intermediate_size", dims.expert_ff),
                     ("n_routed_experts", dims.n_experts),
                     ("n_shared_experts", dims.n_shared),
                     ("num_experts_per_tok", dims.top_k),
                     ("routed_scaling_factor", dims.route_scale),
                     ("num_hidden_layers", dims.n_layers),
                     ("first_k_dense_replace", dims.first_dense),
                     ("rope_theta", dims.rope_theta),
                     ("rms_norm_eps", dims.rms_eps)):
        assert CONFIG[key] == got, key


def test_train_flops_of_a_step():
    """ISSUE 27: 34.7 TFLOP of model work a step of 4 x 4096 tokens, the
    held experts at balanced routing (768 slots an expert)."""
    per_token = REF.matmul_params_per_token(CONFIG)
    mla = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    expert_layer = 2048 * 128 + 3 * 2048 * 1536 + 0.375 * 3 * 2048 * 768
    assert per_token == pytest.approx(
        5 * mla + 3 * 2048 * 6144 + 4 * expert_layer + 2048 * 16128)
    assert REF.held_slots_balanced(CONFIG, 4 * 4096) == 6144
    step = 4 * REF.train_flops_per_sample(CONFIG, (4096,))
    assert step == pytest.approx(34.7e12, rel=2e-3)
    attn = 3 * 5 * 32 * 2 * 320 * 4096 * 4097 / 2 * 4
    assert attn / step == pytest.approx(0.297, abs=0.002)


def test_kernel_shapes_and_work():
    """flash_attn at the mean width 160 is exact for q/k 192, v 128, in
    FLOPs and bytes alike; fused_xent and moe_gmm at the cell's shapes."""
    flash, gmm = MAN.kernel("flash_attn"), MAN.kernel("moe_gmm")
    (calls, shape), = REF.kernel_calls("flash_attn", CONFIG, TRAFFIC)
    assert (calls, shape) == (5, dict(B=4, H=32, T=4096, dh=160.0))
    f, b = flash.work(**shape)
    B, H, T = 4, 32, 4096
    assert f == pytest.approx(2.0 * B * H * T * T / 2 * (3 * 192 + 3 * 128))
    assert b == pytest.approx(2.0 * B * H * T * (6 * 192 + 6 * 128))
    assert REF.kernel_calls("fused_xent", CONFIG, TRAFFIC) == [
        (1, dict(N=16384, D=2048, V=16128))]
    (calls, shape), = REF.kernel_calls("moe_gmm", CONFIG, TRAFFIC)
    assert (calls, shape) == (4, dict(slots=6144.0, D=2048, F=768, G=8))
    f, b = gmm.work(**shape)
    assert f == 9 * 2.0 * 6144 * 2048 * 768
    assert b == 2 * (4 * 6144 * 2048 + 9 * 8 * 2048 * 768)
    with pytest.raises(KeyError):
        REF.kernel_calls("paged_decode_attn", CONFIG, TRAFFIC)

    class Ctx:
        config, traffic, reference = CONFIG, TRAFFIC, REF
        counters = {"steps": 3}

    assert gmm.calls(Ctx) == (f * 12, b * 12)


def test_the_cell_reports_what_the_issue_lists():
    printed = {m["name"] for m in MAN.per_layer_of(CELL)}
    assert {"mla_latent_ms.train", "moe_route_ms.train",
            "moe_experts_ms.train", "moe_gmm_roofline",
            "flash_attn_roofline", "fused_xent_roofline", "train_step_mfu",
            "attn_ms.train", "unscoped_device_share.train"} <= printed
    assert "conv_ms.train" not in printed
    assert {m["name"] for m in MAN.end_to_end_of(CELL)} == {
        "train_samples_per_s_per_chip", "setup_s"}
    assert MAN.workload(CELL)["chips"] == 1
    # the three parts are read by a reader of their own: the kinds are the
    # accepted ten (tests/benchmark/test_scope_readers.py pins them)
    from ddlbench_tpu.telemetry import scopes as program

    reader = MAN.reader(MAN.metric_file("moe_experts_ms.train"))
    tokens = program.KINDS + program.PARTS
    for name, part in (("mla_latent_ms.train", "latent"),
                       ("moe_route_ms.train", "route"),
                       ("moe_experts_ms.train", "experts")):
        spec = MAN.metric_file(name)
        assert spec["reader"] == "scope_part_ms"
        assert spec["args"] == {"part": part, "parts": list(program.PARTS)}
    inner = lambda op: reader.innermost(op, tokens)
    assert inner("jit(train_step)/transpose(jvp(block2))/route/cond/"
                 "branch_0_fun/experts/jit(tgmm)/pallas_call") == "experts"
    assert inner("jit(train_step)/jvp(block2)/route/sort") == "route"
    assert inner("jit(train_step)/jvp(block2)/latent/dot_general") \
        == "latent"
    assert inner("jit(train_step)/jvp(block2)/attn/flash_attn_fwd/"
                 "pallas_call") == "attn"
    assert inner("jit(train_step)/jvp(block2)/add") is None
    assert inner("jit(train_step)/jvp(stem)/conv/conv_general_dilated") \
        == "conv"


def test_the_part_reader_sums_the_innermost_part():
    """scope_part_ms on a recorded-style table: experts inside route goes
    to experts; a program without the part reads nothing."""
    from benchmarks.harness import scopes

    reader = MAN.reader(MAN.metric_file("moe_route_ms.train"))
    table = {
        "fusion.1": "jit(train_step)/jvp(block2)/route/sort",
        "gmm.3": "jit(train_step)/jvp(block2)/route/cond/branch_0_fun/"
                 "experts/jit(gmm)/pallas_call",
        "fusion.2": "jit(train_step)/transpose(jvp(block2))/route/scatter",
        "fusion.3": "jit(train_step)/jvp(block2)/mlp/dot_general",
    }
    text = "\n".join(f'  %{n} = f32[] add(), metadata={{op_name="{op}"}}'
                     for n, op in table.items())

    class Ctx:
        trace_summary = types.SimpleNamespace(op_seconds={
            "fusion.1": 0.010, "gmm.3": 0.200, "fusion.2": 0.030,
            "fusion.3": 0.5, "copy.9": 0.1})
        counters = {"steps": 10}
        _step_hlo = text
        _scope_times = object()

    parts = ["latent", "route", "experts"]
    assert scopes.scope_table(text) == table
    old = scopes.step_hlo
    scopes.step_hlo = lambda rc: rc._step_hlo
    try:
        assert reader.read(Ctx, "route", parts) == pytest.approx(4.0)
        assert reader.read(Ctx, "experts", parts) == pytest.approx(20.0)
        assert reader.read(Ctx, "latent", parts) is None
        Ctx._scope_times = None  # a program without scopes
        assert reader.read(Ctx, "route", parts) is None
    finally:
        scopes.step_hlo = old


def test_the_rehearsal_configuration_is_an_addition(tmp_path):
    """The way this configuration came in, rehearsed on the committed
    benchmark with the test-size files of data/kanana2: new files, new
    entries, the cell's name appended to the rosters it joins — and
    ``against`` finds not one byte changed in a file that was there."""
    root = tmp_path / "repo"
    ix = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in ix["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = manifest.tree_hashes(str(root), ix["paths"])
    for kind, name in (("configs", "kanana2-tiny.json"),
                       ("traffic", "train-kanana2-tiny.json")):
        shutil.copy(os.path.join(DATA, kind, name),
                    root / "benchmarks" / kind / name)
    ix["configs"].append({
        "name": "kanana2-tiny", "source": "tests/benchmark/data/kanana2",
        "file": "benchmarks/configs/kanana2-tiny.json",
        "reduced": ["n_layer", "n_routed_experts_held", "vocab_size"],
        "why": "the family's test size, the share of rank 1 of 4"})
    ix["workloads"].append({
        "name": "kanana2-tiny-train", "config": "kanana2-tiny",
        "traffic": "train-kanana2-tiny", "chips": 1,
        "why": "4 x 64 tokens a step, float32: the rehearsal of the cell"})
    joined = [m for m in ix["end_to_end"] + ix["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(joined) == 20  # the rate, 15 accepted metrics, this PR's 4
    for m in joined:
        m["workloads"].append("kanana2-tiny-train")
    (root / "BENCHMARK.json").write_text(json.dumps(ix))
    man = manifest.Manifest(str(root))
    assert manifest.check(man) == []
    assert manifest.against(man, ROOT) == []
    after = manifest.tree_hashes(str(root), ix["paths"])
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/kanana2-tiny.json",
        "benchmarks/traffic/train-kanana2-tiny.json"]
    assert {m["name"] for m in man.per_layer_of("kanana2-tiny-train")} \
        == {m["name"] for m in MAN.per_layer_of(CELL)}
