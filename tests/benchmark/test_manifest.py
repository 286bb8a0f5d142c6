"""BENCHMARK.json checks itself: the rules a driver refuses a manifest on,
held here so that a refusal is found in the sandbox (PR 22 was refused on the
first of the mutations below)."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest, scopes

ROOT = manifest.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
THIRD = os.path.join(HERE, "data", "third")


def with_proposed(ix, name="gpt2s-serve-closed", bound=0.05):
    """``ix`` with the entries of ``data/proposed/<name>.json`` added,
    as the PR that brings that cell will add them (its bounds, null in the
    file until two full sets are measured on a sourced mix, stand at
    ``bound`` here)."""
    add = json.load(open(os.path.join(HERE, "data", "proposed",
                                      f"{name}.json")))
    ix = copy.deepcopy(ix)
    ix["workloads"] += add["workloads"]
    ix["end_to_end"] += [dict(m, bound=bound) for m in add["end_to_end"]]
    ix["per_layer"] += add["per_layer"]
    return ix


@pytest.fixture()
def man():
    """The committed manifest (train cells on one chip and on four) with the
    serve cell that waits in ``data/proposed/``: train and serve cells side
    by side, which is what most of the rules are about."""
    m = manifest.Manifest()
    m.index = with_proposed(m.index)
    return m


def test_the_committed_manifest_is_sound():
    assert manifest.check(manifest.Manifest()) == []


def test_the_index_alone_says_where_a_metric_is_reported(man):
    """A metric's file says what the metric is; no file repeats a roster.
    Every cell of the index is reached by some metric's roster, and the
    proposed serve cell's entries agree with the files that wait for them:
    it joins the committed cells by entries alone."""
    assert manifest.check(man) == []
    committed = manifest.Manifest().index
    assert len(man.index["workloads"]) == len(committed["workloads"]) + 1
    metrics = os.path.join(man.dir, "metrics")
    for name in os.listdir(metrics):
        if name.endswith(".json"):
            with open(os.path.join(metrics, name)) as f:
                assert "workloads" not in json.load(f), name
    cells = {w["name"] for w in man.index["workloads"]}
    assert {"gpt2s-serve-closed", "resnet50-dp4"} <= cells
    assert "resnet50-dp4" in {w["name"] for w in committed["workloads"]}
    assert cells == {w for m in man.index["per_layer"]
                     for w in m["workloads"]}
    for m in man.per_layer_of("gpt2s-serve-closed"):
        assert man.metric_file(m["name"])["moves"] == m["moves"]
    planted = manifest.Manifest()
    planted.metric_file = lambda name: dict(
        manifest.Manifest().metric_file(name), workloads=["gpt2s-train"])
    assert any("the index alone" in e for e in manifest.check(planted))


def _per_layer(ix, name):
    return next(m for m in ix["per_layer"] if m["name"] == name)


def _e2e(ix, name):
    return next(m for m in ix["end_to_end"] if m["name"] == name)


def m_metric_on_cell_without_its_arrow(ix):
    _per_layer(ix, "window_compiles.train")["workloads"].append(
        "gpt2s-serve-closed")


def m_metric_without_workloads(ix):
    del _per_layer(ix, "train_step_mfu")["workloads"]


def m_moves_nothing(ix):
    _per_layer(ix, "train_step_mfu")["moves"] = "goodput"


def m_fifth_end_to_end(ix):
    for extra in ("serve_itl_p50_s", "serve_itl_p99_s"):
        ix["end_to_end"].append(dict(_e2e(ix, "serve_itl_p95_s"), name=extra))


def m_bad_name(ix):
    _per_layer(ix, "train_step_mfu")["name"] = "train step,mfu"


def m_bad_unit(ix):
    _e2e(ix, "serve_out_tokens_per_s")["unit"] = "tokens per second"


def m_two_chips(ix):
    ix["workloads"][0]["chips"] = 2


def m_second_four_chip_cell(ix):
    for w in ix["workloads"][:2]:
        assert w["chips"] == 1
        w["chips"] = 4


def m_config_without_cell(ix):
    ix["workloads"] = [w for w in ix["workloads"]
                       if w["config"] != "gpt2-small"]
    for m in ix["end_to_end"] + ix["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"]
                              if not w.startswith("gpt2s")]


def m_long_source(ix):
    ix["configs"][0]["source"] = "https://example.org/" + "x" * 200


def m_reduced_width(ix):
    ix["configs"][1]["reduced"] = ["n_embd"]


def m_loose_bound(ix):
    _e2e(ix, "serve_itl_p95_s")["bound"] = 0.2


def m_no_setup(ix):
    ix["end_to_end"] = [m for m in ix["end_to_end"] if m["name"] != "setup_s"]


def m_extra_key(ix):
    _per_layer(ix, "train_step_mfu")["why"] = "because"


def m_roofline_unit(ix):
    _per_layer(ix, "flash_attn_roofline")["unit"] = "share"


def m_command_outside_paths(ix):
    ix["command"] = ["python3", "bench.py"]


def m_index_disagrees_with_file(ix):
    _per_layer(ix, "device_idle_share.serve")["layer"] = "chip"


MUTATIONS = [
    (m_metric_on_cell_without_its_arrow, "which it should move, is not"),
    (m_metric_without_workloads, "lists no workloads"),
    (m_moves_nothing, "no end-to-end metric"),
    (m_fifth_end_to_end, "more than four"),
    (m_bad_name, "name"),
    (m_bad_unit, "unit"),
    (m_two_chips, "chips is not 1 or 4"),
    (m_second_four_chip_cell, "may ask for 4 chips"),
    (m_config_without_cell, "has no cell"),
    (m_long_source, "1-200 characters"),
    (m_reduced_width, "width"),
    (m_loose_bound, "bound"),
    (m_no_setup, "setup_s"),
    (m_extra_key, "has keys"),
    (m_roofline_unit, "roofline share's unit"),
    (m_command_outside_paths, "outside paths"),
    (m_index_disagrees_with_file, "in its file"),
]


@pytest.mark.parametrize("mutate,says", MUTATIONS,
                         ids=[m.__name__[2:] for m, _ in MUTATIONS])
def test_check_refuses(man, mutate, says):
    man.index = copy.deepcopy(man.index)
    mutate(man.index)
    errors = manifest.check(man)
    assert any(says in e for e in errors), errors


def test_unsuffixed_metric_on_train_and_serve_cells_is_refused(man):
    """Even where both cells report the end-to-end metric named, a metric
    listed on a train and a serve cell has to be split by suffix."""
    man.index = copy.deepcopy(man.index)
    _e2e(man.index, "setup_s")  # every cell reports setup_s
    m = _per_layer(man.index, "train_peak_hbm_share")
    m["moves"] = "setup_s"
    m["workloads"] = ["gpt2s-train", "gpt2s-serve-closed"]
    assert any("split it by suffix" in e for e in manifest.check(man))


def test_printed_metrics_must_be_the_declared_set(man):
    cell = "gpt2s-serve-closed"
    e2e = {m["name"]: 1.0 for m in man.end_to_end_of(cell)}
    assert manifest.check_printed(man, cell, 0, e2e) == []
    assert manifest.check_printed(man, cell, 0,
                                  dict(e2e, train_step_mfu=1.0))
    e2e.pop("serve_itl_p95_s")
    assert manifest.check_printed(man, cell, 0, e2e)
    layer = {m["name"]: 1.0 for m in man.per_layer_of(cell)}
    assert manifest.check_printed(man, cell, 1, layer) == []
    # a reader that found nothing leaves its metric out: still sound
    layer.pop("paged_chunk_attn_roofline")
    assert manifest.check_printed(man, cell, 1, layer) == []
    assert manifest.check_printed(man, cell, 1,
                                  dict(layer, **{"window_compiles.train": 0}))


def test_per_cell_sets_follow_the_arrows(man):
    for w in man.index["workloads"]:
        reports = {m["name"] for m in man.end_to_end_of(w["name"])}
        for m in man.per_layer_of(w["name"]):
            assert m["moves"] in reports, (w["name"], m["name"])
    serve = {m["name"] for m in man.per_layer_of("gpt2s-serve-closed")}
    assert "window_compiles.serve" in serve
    assert "window_compiles.train" not in serve


def copy_of_the_benchmark(root):
    """The committed benchmark (every directory of ``paths`` and the index)
    copied under ``root``; returns the index and ``{file: sha256}``."""
    ix = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in ix["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return ix, manifest.tree_hashes(str(root), ix["paths"])


def test_a_fifth_cell_and_a_new_metric_are_files_and_entries_only(tmp_path):
    """A later PR adds a cell, a traffic mix and a per-layer metric with its
    reader by adding files and entries: nothing under benchmarks/harness
    learns a name."""
    root = tmp_path / "repo"
    ix = with_proposed(copy_of_the_benchmark(root)[0])
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "serve-closed96.json")))
    mix.update(prompt=[256, 384, 448], output=[8, 16, 32])
    (root / "benchmarks/traffic/serve-longprompt.json").write_text(
        json.dumps(mix))
    ix["workloads"].append({
        "name": "gpt2s-serve-longprompt", "config": "gpt2-small",
        "traffic": "serve-longprompt", "chips": 1,
        "why": "prompts 256-448, outputs 8-32: prefill-bound"})
    new = {"name": "decode_rows_per_step", "unit": "rows", "better": "higher",
           "source": "program_counter", "layer": "serve engine",
           "moves": "serve_out_tokens_per_s",
           "workloads": ["gpt2s-serve-longprompt"]}
    ix["per_layer"].append(new)
    spec = {k: v for k, v in new.items() if k != "workloads"}
    (root / "benchmarks/metrics/decode_rows_per_step.json").write_text(
        json.dumps(dict(spec, reader="decode_rows")))
    (root / "benchmarks/metrics/readers/decode_rows.py").write_text(
        "def read(ctx):\n"
        "    calls = ctx.counters['decode_calls']\n"
        "    return sum(map(len, calls)) / len(calls) if calls else None\n")
    for m in ix["end_to_end"]:
        if m["name"].startswith("serve_"):
            m["workloads"].append("gpt2s-serve-longprompt")
    (root / "BENCHMARK.json").write_text(json.dumps(ix))
    man = manifest.Manifest(str(root))
    assert manifest.check(man) == []
    assert manifest.against(man, ROOT) == []
    assert man.traffic("serve-longprompt")["prompt"] == [256, 384, 448]
    spec = man.metric_file("decode_rows_per_step")

    class Ctx:
        counters = {"decode_calls": [[1, 2, 3], [4]]}

    assert man.reader(spec).read(Ctx()) == 2.0
    assert [m["name"] for m in man.per_layer_of("gpt2s-serve-longprompt")] \
        == ["decode_rows_per_step"]


JOINED = ("train_step_mfu", "device_idle_share.train",
          "step_forward_ms.train", "head_loss_ms.train")


def add_the_third_configuration(root, ix):
    """What a ``model_config`` PR brings, as that PR may bring it: new files
    (LeNet's configuration, traffic and reference from ``data/third``, the
    reference with a ``kernel_calls`` of its own; a metric file that reads a
    scope kind the vocabulary lacks, and the ``scope_kinds.<name>.json`` that
    makes it one) and new entries and names in the index. Nothing that is
    there is written to."""
    bench = root / "benchmarks"
    shutil.copy(os.path.join(THIRD, "traffic", "train-lenet.json"),
                bench / "traffic" / "train-lenet.json")
    ref = open(os.path.join(THIRD, "reference", "lenet.py")).read()
    (bench / "reference" / "lenet.py").write_text(
        ref + "\n\ndef kernel_calls(kernel, config, traffic):\n"
        "    B = traffic['run_config']['batch_size']\n"
        "    return {'flash_attn': [(2, dict(B=B, H=4, T=128, dh=32))]}"
        "[kernel]\n")
    cfg = json.load(open(os.path.join(THIRD, "configs", "lenet-mnist.json")))
    cfg["reference"] = "benchmarks/reference/lenet.py"
    (bench / "configs" / "lenet-mnist.json").write_text(json.dumps(cfg))
    ix["configs"].append({
        "name": "lenet-mnist", "source": cfg["source"],
        "file": "benchmarks/configs/lenet-mnist.json", "reduced": [],
        "why": "a family the harness has never heard of"})
    ix["workloads"].append({
        "name": "lenet-train", "config": "lenet-mnist",
        "traffic": "train-lenet", "chips": 1,
        "why": "batch 8 of 28x28 digits: a third configuration's train cell"})
    rosters = [m for m in ix["end_to_end"] + ix["per_layer"]
               if m["name"] in JOINED + ("train_samples_per_s_per_chip",)]
    assert len(rosters) == len(JOINED) + 1
    for m in rosters:
        m["workloads"].append("lenet-train")
    new = {"name": "gate_ms.train", "unit": "ms/step", "better": "lower",
           "source": "program_span", "layer": "models",
           "moves": "train_samples_per_s_per_chip"}
    (bench / "metrics" / "gate_ms.train.json").write_text(json.dumps(
        dict(new, reader="scope_ms", args={"kinds": ["gate"]})))
    (bench / "metrics" / "scope_kinds.lenet.json").write_text(json.dumps(
        {"kinds": ["gate"]}))
    ix["per_layer"].append(dict(new, workloads=["lenet-train"]))
    (root / "BENCHMARK.json").write_text(json.dumps(ix))


def test_a_configuration_and_its_cell_are_an_addition(tmp_path):
    """The proof of the repair: a third configuration's train cell joins
    four metrics that exist by gaining a name in their rosters, brings a
    scope kind and its own kernel shapes, and every file that was there is
    byte for byte the same."""
    root = tmp_path / "repo"
    ix, before = copy_of_the_benchmark(root)
    gate = "jit(train_step)/transpose(jvp(conv1))/gate/mul"
    metrics = str(root / "benchmarks" / "metrics")
    assert "gate" not in scopes.vocabulary(metrics)
    add_the_third_configuration(root, ix)
    man = manifest.Manifest(str(root))
    assert manifest.check(man) == []
    assert manifest.against(man, ROOT) == []
    # joined, by index entries alone
    printed = {m["name"] for m in man.per_layer_of("lenet-train")}
    assert printed == set(JOINED) | {"gate_ms.train"}
    for name in JOINED:
        assert callable(man.reader(man.metric_file(name)).read)
    # the new kind is vocabulary there, after the ten, and only there: the
    # copy's own harness classifies by it, this tree's does not
    assert scopes.vocabulary(metrics) == scopes.KINDS + ("gate",)
    assert scopes.classify(gate) == ("backward", None)
    r = subprocess.run(
        [sys.executable, "-c", "from benchmarks.harness import scopes; "
         f"print(scopes.classify({gate!r}))"],
        cwd=root, env=dict(os.environ, PYTHONPATH=str(root)),
        capture_output=True, text=True)
    assert r.stdout.strip() == "('backward', 'gate')", r.stderr
    # a metric file that reads a kind no scope_kinds file lists is refused
    os.rename(os.path.join(metrics, "scope_kinds.lenet.json"),
              root / "kept.json")
    assert any("'gate', which no scope_kinds" in e
               for e in manifest.check(man))
    os.rename(root / "kept.json",
              os.path.join(metrics, "scope_kinds.lenet.json"))
    # its own kernel shapes, through the kernel file that is there
    cfg = man.config("lenet-mnist")

    class Ctx:
        config, traffic = cfg, man.traffic("train-lenet")
        reference = man.reference(cfg)
        counters = {"steps": 5}

    flash = man.kernel("flash_attn")
    f1, b1 = flash.work(B=8, H=4, T=128, dh=32)
    assert flash.calls(Ctx) == (f1 * 10, b1 * 10)
    # and not one byte changed in a file that was there
    after = manifest.tree_hashes(str(root), ix["paths"])
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/lenet-mnist.json",
        "benchmarks/metrics/gate_ms.train.json",
        "benchmarks/metrics/scope_kinds.lenet.json",
        "benchmarks/reference/lenet.py",
        "benchmarks/traffic/train-lenet.json"]


def f_file_changed(root, ix):
    with open(root / "benchmarks/kernels/flash_attn.py", "a") as f:
        f.write("\n# tuned\n")


def f_file_deleted(root, ix):
    os.remove(root / "benchmarks/traffic/train-b16.json")


def f_bound_changed(root, ix):
    ix["end_to_end"][0]["bound"] = 0.02


def f_name_left_a_roster(root, ix):
    _per_layer(ix, "train_step_mfu")["workloads"].remove("gpt2s-train")


def f_entry_removed(root, ix):
    ix["per_layer"] = [m for m in ix["per_layer"]
                       if m["name"] != "fused_xent_roofline"]


def f_why_reworded(root, ix):
    ix["workloads"][0]["why"] += " (reworded)"


def f_run_seconds(root, ix):
    ix["run_seconds"] = 20


FAULTS = [
    (f_file_changed, "flash_attn.py is in the parent and differs"),
    (f_file_deleted, "train-b16.json is in the parent and was deleted"),
    (f_bound_changed, "otherwise than by gaining a name"),
    (f_name_left_a_roster, "otherwise than by gaining a name"),
    (f_entry_removed, "'fused_xent_roofline' of the parent was removed"),
    (f_why_reworded, "otherwise than by gaining a name"),
    (f_run_seconds, "run_seconds changed"),
]


@pytest.mark.parametrize("fault,says", FAULTS,
                         ids=[f.__name__[2:] for f, _ in FAULTS])
def test_against_the_parent_lists_what_an_adding_pr_is_refused_on(
        tmp_path, fault, says):
    """``--check-manifest --against <parent checkout>``: the additions of a
    ``model_config`` PR pass; each planted edit is named."""
    root = tmp_path / "repo"
    ix, _ = copy_of_the_benchmark(root)
    add_the_third_configuration(root, ix)
    fault(root, ix)
    (root / "BENCHMARK.json").write_text(json.dumps(ix))
    errors = manifest.against(manifest.Manifest(str(root)), ROOT)
    assert len(errors) == 1 and says in errors[0], errors


def _run_cell(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run_cell.py"),
         *args], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)


def test_check_manifest_mode_of_the_command():
    r = _run_cell("--check-manifest")
    assert r.returncode == 0, r.stderr
    assert "0 fault(s)" in r.stdout


def test_check_manifest_against_a_parent_checkout(tmp_path):
    """The command's ``--against``: this tree against itself adds nothing
    and edits nothing; against a parent one of whose files differs, the
    file is named and the exit code says so."""
    r = _run_cell("--check-manifest", "--against", ROOT)
    assert r.returncode == 0, r.stderr
    assert "0 fault(s)" in r.stdout
    parent = tmp_path / "parent"
    copy_of_the_benchmark(parent)
    with open(parent / "benchmarks/harness/peaks.py", "a") as f:
        f.write("\n")
    r = _run_cell("--check-manifest", "--against", str(parent))
    assert r.returncode == 1
    assert "benchmarks/harness/peaks.py is in the parent and differs" \
        in r.stderr
    assert "1 fault(s)" in r.stdout


def test_no_tpu_no_result():
    """Off the chip the command exits nonzero and prints nothing that looks
    like a result."""
    r = _run_cell("--workload", "gpt2s-train", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU backend" in r.stderr
