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

from benchmarks.harness import manifest

ROOT = manifest.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))


def with_proposed(ix, name="gpt2s-serve-closed", bound=0.05):
    """``ix`` with the entries of ``data/proposed/<name>.json`` added,
    as the PR that brings that cell will add them (its bounds, null in the
    file until two full sets are measured, stand at ``bound`` here)."""
    add = json.load(open(os.path.join(HERE, "data", "proposed",
                                      f"{name}.json")))
    ix = copy.deepcopy(ix)
    ix["workloads"] += add["workloads"]
    ix["end_to_end"] += [dict(m, bound=bound) for m in add["end_to_end"]]
    ix["per_layer"] += add["per_layer"]
    return ix


@pytest.fixture()
def man():
    """The committed manifest with the serve cell that waits in
    ``data/proposed/``: train and serve cells side by side, which is
    what most of the rules are about."""
    m = manifest.Manifest()
    m.index = with_proposed(m.index)
    return m


def test_the_committed_manifest_is_sound():
    assert manifest.check(manifest.Manifest()) == []


def test_the_committed_manifest_with_the_proposed_serve_cell_is_sound(man):
    assert manifest.check(man) == []
    assert len(man.index["workloads"]) == 3
    # the proposed entries agree with the metric files that wait for them
    for m in man.per_layer_of("gpt2s-serve-closed"):
        assert man.metric_file(m["name"])["moves"] == m["moves"]


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
    ix["workloads"][0]["chips"] = 4
    ix["workloads"][1]["chips"] = 4


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


def test_a_fifth_cell_and_a_new_metric_are_files_and_entries_only(tmp_path):
    """A later PR adds a cell, a traffic mix and a per-layer metric with its
    reader by adding files and entries: nothing under benchmarks/harness
    learns a name."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    ix = with_proposed(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
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
    (root / "benchmarks/metrics/decode_rows_per_step.json").write_text(
        json.dumps(dict(new, reader="decode_rows")))
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
    assert man.traffic("serve-longprompt")["prompt"] == [256, 384, 448]
    spec = man.metric_file("decode_rows_per_step")

    class Ctx:
        counters = {"decode_calls": [[1, 2, 3], [4]]}

    assert man.reader(spec).read(Ctx()) == 2.0
    assert [m["name"] for m in man.per_layer_of("gpt2s-serve-longprompt")] \
        == ["decode_rows_per_step"]


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


def test_no_tpu_no_result():
    """Off the chip the command exits nonzero and prints nothing that looks
    like a result."""
    r = _run_cell("--workload", "gpt2s-train", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU backend" in r.stderr
