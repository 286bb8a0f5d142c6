"""The smallthinker-21b-a3b configuration and its cell: a CPU rehearsal of
the accepted train driver on the family's test size
(tests/benchmark/data/smallthinker: the program's ``smallthinker_t``, one
period of the layout with the share of rank 3 of 4, one sequence a step as
the cell has, float32, ``remat_layers``), the planted faults and the control
through the harness's own comparison, the counts behind ``train_step_mfu``
and the rooflines, the file against the catalog's row, and the manifest's
proof that the addition edited nothing. Nothing printed here is a device
metric."""

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
                    "smallthinker")
MAN = manifest.Manifest()
CONFIG = MAN.config("smallthinker-21b-a3b")
TRAFFIC = MAN.traffic("train-b1-t16384")
REF = MAN.reference(CONFIG)
CELL = "smallthinker-t16k-train"


def context(seed=3):
    man = manifest.Manifest()
    man.dir = DATA
    man.index = dict(
        man.index,
        configs=[{"name": "smallthinker-tiny", "file": os.path.relpath(
            os.path.join(DATA, "configs", "smallthinker-tiny.json"),
            man.root)}],
        workloads=[{"name": "tiny", "config": "smallthinker-tiny",
                    "traffic": "train-smallthinker-tiny", "chips": 1}])
    args = types.SimpleNamespace(workload="tiny", seed=seed, seconds=0.5,
                                 trace=0)
    rc = run_cell.RunContext(man, args, jax.devices())
    rc.read_memory_peak = lambda: 0
    rc.mark = lambda phase: None
    return rc


def test_the_train_driver_runs_the_cell_at_the_test_size():
    """The accepted driver end to end: the program's strategy with
    remat_layers, the seed's weights laid into its tree by leaf name, the
    window, the reference following three Adam steps of one sequence each,
    every number inside float32 round-off."""
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
    # embedding and head are two matrices
    assert sorted(k for k, s in specs.items() if 256 in s) == [
        "embed/tok", "lm_head/head"]
    flat = weights.make_weights(3, specs, config["weights"])
    data = SeededBatches(3, "tokens", (64,), config["vocab_size"], 1)
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
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    entry = next(c for c in MAN.index["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "n_layer", "moe_num_primary_experts_held", "vocab_size"]
    assert not any(manifest.WIDTH.search(k) for k in CONFIG["reduced"])
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert (CONFIG["n_layer"], CONFIG["moe_num_primary_experts_held"],
            CONFIG["first_expert_held"], CONFIG["vocab_size"],
            CONFIG["padded_vocab_size"], CONFIG["n_positions"]) == (
                4, 16, 0, 18992, 19072, 16384)
    # the floors of a model_config cut: one whole period of the layout and
    # four layers, 8 experts, an eighth of the vocabulary; the context whole
    assert CONFIG["n_layer"] >= 4 and CONFIG["n_layer"] % 4 == 0
    assert CONFIG["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert CONFIG["moe_num_primary_experts_held"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["n_positions"] == published["max_position_embeddings"]
    assert "Four chips" in CONFIG["deployment"]
    assert len(CONFIG["assumed"]) >= 8 and len(CONFIG["departures"]) == 2
    assert CONFIG["precision"]["train"]["router"] == "float32"
    assert CONFIG["dataset"]["sample_shape"] == [16384]
    assert CONFIG["dataset"]["num_classes"] == 19072


def test_the_arch_string_is_the_file_s_cut():
    from ddlbench_tpu.models import smallthinker

    dims, layers, held = smallthinker.parse_arch(CONFIG["arch"])
    assert layers == CONFIG["n_layer"]
    assert held == (CONFIG["first_expert_held"],
                    CONFIG["moe_num_primary_experts_held"])
    for key, got in (("hidden_size", dims.d_model),
                     ("num_attention_heads", dims.n_heads),
                     ("num_key_value_heads", dims.n_kv_heads),
                     ("head_dim", dims.head_dim),
                     ("moe_ffn_hidden_size", dims.expert_ff),
                     ("moe_num_primary_experts", dims.n_experts),
                     ("moe_num_active_primary_experts", dims.top_k),
                     ("sliding_window_size", dims.window),
                     ("num_hidden_layers", dims.n_layers),
                     ("rope_theta", dims.rope_theta),
                     ("rms_norm_eps", dims.rms_eps)):
        assert CONFIG[key] == got, key
    assert CONFIG["sliding_window_layout"] == CONFIG["rope_layout"] == \
        list(dims.layout)
    assert [REF.is_window_layer(CONFIG, i) for i in range(1, 5)] == [
        False, True, True, True]


def test_train_flops_of_a_step():
    """ISSUE 35's reckoning of a step of 1 x 16,384 tokens: attention cores
    13.35 TFLOP (the global layer 5.77, each window layer 2.52 — the pairs
    INSIDE the window, not T^2 / 2), projections 8.25, the head 4.8, held
    experts at balanced routing 3.48, the router 0.06: 29.9."""
    T = 16384
    proj = 2 * 2560 * 3584 + 2 * 2560 * 512
    router = 2560 * 64
    routed = 6 * 16 / 64 * 3 * 2560 * 768
    assert REF.matmul_params_per_token(CONFIG) == pytest.approx(
        4 * (proj + router + routed) + 2560 * 19072)
    assert REF.held_slots_balanced(CONFIG, T) == 24576
    assert REF.held_slots_balanced(CONFIG, T) / 16 == 1536  # a held expert
    part = lambda per_token: 3 * 2.0 * per_token * T
    assert part(4 * proj) == pytest.approx(8.25e12, rel=0.01)
    assert part(4 * routed) == pytest.approx(3.48e12, rel=0.01)
    assert part(2560 * 19072) == pytest.approx(4.8e12, rel=0.01)
    assert REF.mask_pairs(T, 0) == T * (T + 1) / 2
    assert REF.mask_pairs(T, 4096) == 4096 * T - 4096 * 4095 / 2 == 58722304
    assert REF.mask_pairs(4096, 4096) == REF.mask_pairs(4096, 0)
    core = lambda pairs: 3 * 28 * 2.0 * 256 * pairs
    assert core(REF.mask_pairs(T, 0)) == pytest.approx(5.77e12, rel=0.01)
    assert core(REF.mask_pairs(T, 4096)) == pytest.approx(2.52e12, rel=0.01)
    scores = core(REF.mask_pairs(T, 0)) + 3 * core(REF.mask_pairs(T, 4096))
    assert scores == pytest.approx(13.35e12, rel=0.01)
    step = REF.train_flops_per_sample(CONFIG, (T,))
    assert step == pytest.approx(
        part(REF.matmul_params_per_token(CONFIG)) + scores)
    assert step == pytest.approx(29.9e12, rel=0.01)
    # at 8,192 a window layer still visits 75% of the causal pairs, at the
    # cell's 16,384 44%
    share = lambda t: REF.mask_pairs(t, 4096) / REF.mask_pairs(t, 0)
    assert share(8192) == pytest.approx(0.75, abs=0.01)
    assert share(T) == pytest.approx(0.44, abs=0.01)


def test_kernel_shapes_and_work():
    banded, gmm = MAN.kernel("flash_attn_banded"), MAN.kernel("moe_gmm")
    glob, window = REF.kernel_calls("flash_attn_banded", CONFIG, TRAFFIC)
    shape = dict(B=1, H=28, T=16384, dh=128)
    assert glob == (1, dict(shape, window=0))
    assert window == (3, dict(shape, window=4096))
    f0, b0 = banded.work(**glob[1])
    f1, b1 = banded.work(**window[1])
    assert f0 == 6 * 2.0 * 28 * 128 * 16384 * 16385 / 2
    assert f1 == 6 * 2.0 * 28 * 128 * 58722304
    assert b0 == b1 == 12.0 * 28 * 16384 * 128 * 2
    # the reference's count of the scores and the kernel's agree: the six
    # products of forward + backward are 3 x the two of the forward
    assert f0 + 3 * f1 == pytest.approx(
        REF.train_flops_per_sample(CONFIG, (16384,))
        - 3 * 2.0 * REF.matmul_params_per_token(CONFIG) * 16384)
    # FLOPs bound both calls, so the K-headed tensors' overcount of bytes
    # moves nothing: the least time is the FLOPs'
    assert f1 / 197e12 > 5 * b1 / 819e9
    # the accepted causal count would credit a window layer 2.3 x its work
    causal, _ = MAN.kernel("flash_attn").work(**shape)
    assert causal / f1 == pytest.approx(2.29, abs=0.01)
    assert banded.EVENTS == MAN.kernel("flash_attn").EVENTS
    assert REF.kernel_calls("fused_xent", CONFIG, TRAFFIC) == [
        (1, dict(N=16384, D=2560, V=19072))]
    (calls, shape), = REF.kernel_calls("moe_gmm", CONFIG, TRAFFIC)
    assert (calls, shape) == (4, dict(slots=24576.0, D=2560, F=768, G=16))
    f, _ = gmm.work(**shape)
    assert f == 9 * 2.0 * 24576 * 2560 * 768
    for absent in ("flash_attn", "paged_decode_attn"):
        with pytest.raises(KeyError):
            REF.kernel_calls(absent, CONFIG, TRAFFIC)


def test_the_cell_reports_what_the_issue_lists():
    """Held as a SUBSET: a later PR's metric may join the cell."""
    printed = {m["name"] for m in MAN.per_layer_of(CELL)}
    assert printed >= {
        "window_compiles.train", "input_stall_share.train", "train_step_mfu",
        "train_peak_hbm_share", "fused_xent_roofline", "moe_gmm_roofline",
        "device_idle_share.train", "step_forward_ms.train",
        "step_backward_ms.train", "step_optimizer_ms.train", "norm_ms.train",
        "attn_ms.train", "head_loss_ms.train", "unscoped_device_share.train",
        "moe_route_ms.train", "moe_experts_ms.train", "moe_router_ms.train",
        "flash_attn_banded_roofline", "attn_window_ms.train"}
    # flash_attn.work counts every causal pair: not this cell's to report
    assert "flash_attn_roofline" not in printed
    assert {m["name"] for m in MAN.end_to_end_of(CELL)} == {
        "train_samples_per_s_per_chip", "setup_s"}
    cell = MAN.workload(CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "smallthinker-21b-a3b", "train-b1-t16384")
    # train-b2-t8192 with one sequence a step — and without its
    # ``remat_layers``: an accepted test (test_recompute_reader.py) holds the
    # waiting step_recompute_ms.train entry to EVERY cell whose mix says so,
    # and neither file is this PR's to edit; the model rematerializes its
    # layers of itself (LayerModel.remat_layers), so the step is the same
    other = dict(MAN.traffic("train-b2-t8192")["run_config"], batch_size=1)
    assert other.pop("remat_layers") is True
    assert TRAFFIC == dict(MAN.traffic("train-b2-t8192"), run_config=other)
    from ddlbench_tpu.models import smallthinker

    assert smallthinker.build(CONFIG["arch"], (16384,), 19072).remat_layers
    from ddlbench_tpu.telemetry import scopes as program

    six = list(program.PARTS) + [program.CCA_MIX, program.ROUTER,
                                 program.WINDOW]
    spec = MAN.metric_file("attn_window_ms.train")
    assert spec["reader"] == "scope_part_ms"
    assert spec["args"] == {"part": "window", "parts": six}
    assert spec["source"] == "device_trace"
    roof = MAN.metric_file("flash_attn_banded_roofline")
    assert (roof["reader"], roof["args"], roof["unit"]) == (
        "kernel_roofline", {"kernel": "flash_attn_banded"}, "%")
    reader = MAN.reader(spec)
    inner = lambda op: reader.innermost(op, program.KINDS + tuple(six))
    assert inner("jit(train_step)/jvp(block2)/attn/window/flash_attn_fwd/"
                 "pallas_call") == "window"
    assert inner("jit(train_step)/jvp(block1)/attn/flash_attn_fwd/"
                 "pallas_call") == "attn"
    assert inner("jit(train_step)/transpose(jvp(block2))/route/router/"
                 "dot_general") == "router"
    # the accepted files list fewer names: there the window counts as attn,
    # the router as route
    assert reader.innermost(
        "jit(train_step)/jvp(block2)/attn/window/mul",
        program.KINDS + program.PARTS) == "attn"
    assert program.WINDOW not in program.PARTS + program.KINDS


def test_the_program_s_step_carries_the_window_and_the_router():
    """The rehearsal's compiled step names the window inside attn on the
    window layers alone, and the router inside route, forward and
    backward."""
    from benchmarks.harness import scopes

    rc = context()
    _, strategy = train_driver.build(rc.config, rc.traffic)
    state = jax.eval_shape(strategy.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 64), "int32")
    # jax's compile cache strips debug info from its key: an entry of a
    # step traced before a scope was added would stand in for this one
    text = scopes.compile_fresh(strategy.train_step.lower(
        state, x, x, jax.ShapeDtypeStruct((), "float32")))
    ops = set(scopes.scope_table(text).values())
    for block, windowed in (("block1", False), ("block2", True),
                            ("block4", True)):
        for phase in (f"jit(train_step)/jvp({block})/",
                      f"jit(train_step)/transpose(jvp({block}))/"):
            mine = [op for op in ops if op.startswith(phase)]
            assert any("/route/router/" in op for op in mine), (block, phase)
            assert any("/route/experts/" in op for op in mine), (block, phase)
            assert any("/attn/" in op for op in mine), (block, phase)
            assert any("/attn/window/" in op for op in mine) == windowed, (
                block, phase)


def test_the_rehearsal_configuration_is_an_addition(tmp_path):
    """The way this configuration came in, rehearsed on the committed
    benchmark with the test-size files of data/smallthinker: new files, new
    entries, the cell's name appended to the rosters it joins — and
    ``against`` finds not one byte changed in a file that was there."""
    root = tmp_path / "repo"
    ix = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in ix["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = manifest.tree_hashes(str(root), ix["paths"])
    for kind, name in (("configs", "smallthinker-tiny.json"),
                       ("traffic", "train-smallthinker-tiny.json")):
        shutil.copy(os.path.join(DATA, kind, name),
                    root / "benchmarks" / kind / name)
    ix["configs"].append({
        "name": "smallthinker-tiny",
        "source": "tests/benchmark/data/smallthinker",
        "file": "benchmarks/configs/smallthinker-tiny.json",
        "reduced": ["n_layer", "moe_num_primary_experts_held", "vocab_size"],
        "why": "the family's test size, the share of rank 3 of 4"})
    ix["workloads"].append({
        "name": "smallthinker-tiny-train", "config": "smallthinker-tiny",
        "traffic": "train-smallthinker-tiny", "chips": 1,
        "why": "1 x 64 tokens a step, float32: the rehearsal of the cell"})
    joined = [m for m in ix["end_to_end"] + ix["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(joined) >= 20  # the rate, 17 accepted metrics, this PR's 2
    for m in joined:
        m["workloads"].append("smallthinker-tiny-train")
    (root / "BENCHMARK.json").write_text(json.dumps(ix))
    man = manifest.Manifest(str(root))
    assert manifest.check(man) == []
    assert manifest.against(man, ROOT) == []
    after = manifest.tree_hashes(str(root), ix["paths"])
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/smallthinker-tiny.json",
        "benchmarks/traffic/train-smallthinker-tiny.json"]
    assert {m["name"] for m in man.per_layer_of("smallthinker-tiny-train")} \
        == {m["name"] for m in MAN.per_layer_of(CELL)}
