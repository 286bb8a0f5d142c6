"""``step_recompute_ms.train`` (PR 33): the device time of what a
``jax.checkpoint`` makes the backward compute again, by jax's own
``rematted_computation`` token on the compiled step's op_names. CPU only:
the device seconds below are made up, and the entry waits in
``data/proposed/`` (its note says which accepted tests hold it out)."""

import copy
import json
import os

import pytest

from benchmarks.harness import manifest, scopes
from test_scope_readers import fake_context, reader

HERE = os.path.dirname(os.path.abspath(__file__))
REMAT = "jit(train_step)/transpose(jvp(block1))/jvp(block1)/checkpoint/"

TABLE = {
    # the forward pass proper, and what the checkpoint's policy kept of it
    "fusion.1": "jit(train_step)/jvp(block1)/attn/dot_general",
    "flash_attn_fwd.3": "jit(train_step)/jvp(block1)/attn/flash_attn_fwd/"
                        "pallas_call",
    # the backward proper of a checkpointed layer: `checkpoint`, no token
    "flash_attn_dq_dkv.4": REMAT + "attn/flash_attn_dq_dkv/pallas_call",
    "fusion.5": "jit(train_step)/transpose(jvp(block1))/jvp(block1)/"
                "checkpoint/mlp/transpose",
    # recomputed: the layer's own, a nested checkpoint's, a fusion's root
    "fusion.6": REMAT + "rematted_computation/ln/mul",
    "gmm.7": REMAT + "rematted_computation/route/cond/branch_1_fun/"
             "checkpoint/rematted_computation/experts/jit(gmm)/pallas_call",
    "convert_fusion.8": REMAT + "rematted_computation/attn/convert",
    # merged instructions list several paths: the first counts
    "fusion.9": REMAT + "rematted_computation/mlp/mul;jit(train_step)/"
                "optimizer/sub",
    "fusion.10": "jit(train_step)/optimizer/sub;" + REMAT
                 + "rematted_computation/mlp/mul",
    # the token inside another name is no token
    "fusion.11": "jit(train_step)/jvp(not_rematted_computation_2)/mlp/mul",
    "copy.12": "",
    "never_ran": REMAT + "rematted_computation/mlp/dot_general",
}
SECONDS = {"fusion.1": 0.040, "flash_attn_fwd.3": 0.020,
           "flash_attn_dq_dkv.4": 0.045, "fusion.5": 0.008,
           "fusion.6": 0.003, "gmm.7": 0.006, "convert_fusion.8": 0.001,
           "fusion.9": 0.002, "fusion.10": 0.016, "fusion.11": 0.032,
           "copy.12": 0.064, "iota.13": 0.128}  # the last: another program's


@pytest.mark.parametrize("op_name,want", [
    (REMAT + "rematted_computation/ln/mul", True),
    (REMAT + "attn/flash_attn_dq_dkv/pallas_call", False),
    ("jit(train_step)/jvp(block1)/attn/flash_attn_fwd/pallas_call", False),
    ("jit(f)/transpose(jvp(rematted_computation))/mul", True),
    ("jit(f)/jvp(my_rematted_computation)/mul", False),
    ("jit(f)/rematted_computation.2/mul", False),
    ("jit(f)/optimizer/sub;" + REMAT + "rematted_computation/mul", False),
    ("", False),
])
def test_recomputed_is_the_token_standing_alone_on_the_first_path(op_name,
                                                                  want):
    assert reader("recompute_ms").recomputed(op_name) is want


def test_recompute_ms_sums_the_rematerialized_instructions():
    read = reader("recompute_ms").read
    ctx = fake_context(table=TABLE, op_seconds=SECONDS, steps=2)
    assert read(ctx) == pytest.approx(
        1000 * (0.003 + 0.006 + 0.001 + 0.002) / 2)
    # what it counts lies inside the backward phase, where it runs
    times = scopes.device_time(ctx)
    assert 1000 * times.by_phase["backward"][0] / 2 >= read(ctx)


def test_a_step_that_rematerializes_nothing_reads_nothing():
    """No instruction matched returns nothing, never 0: a step without a
    checkpoint, and one whose recomputed instructions did not run in the
    window."""
    read = reader("recompute_ms").read
    plain = {n: op for n, op in TABLE.items()
             if "rematted_computation/" not in op}
    assert read(fake_context(table=plain, op_seconds=SECONDS)) is None
    quiet = {n: s for n, s in SECONDS.items() if n in plain}
    assert read(fake_context(table=TABLE, op_seconds=quiet)) is None
    assert read(fake_context()) is None  # test_scope_readers' own table


def test_the_proposed_entry_joins_the_index_by_itself():
    """The metric's file, reader and the entry that waits agree, and the
    manifest with the entry appended is sound and an addition."""
    with open(os.path.join(HERE, "data", "proposed",
                           "step_recompute_ms.train.json")) as f:
        (entry,) = json.load(f)["per_layer"]
    man = manifest.Manifest()
    assert entry["name"] not in {m["name"] for m in man.index["per_layer"]}
    before = copy.deepcopy(man.index)
    man.index["per_layer"].append(entry)
    assert manifest.check(man) == []
    spec = man.metric_file(entry["name"])
    assert spec["reader"] == "recompute_ms" and "args" not in spec
    assert callable(man.reader(spec).read)
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        "device_trace", "models", "train_samples_per_s_per_chip")
    # the cells that rematerialize, and no other: remat_layers in their mix
    remat = {w["name"] for w in before["workloads"]
             if man.traffic(w["traffic"]).get("run_config", {}).get(
                 "remat_layers")}
    assert set(entry["workloads"]) == remat
    for name in entry["workloads"]:
        assert entry["name"] in {m["name"] for m in man.per_layer_of(name)}


def test_the_tiny_rematerialized_step_has_something_to_read():
    """The rehearsal configuration of ``kanana2-ep16-train``
    (``remat_layers``) compiled here: its table holds recomputed
    instructions, every one in the backward phase, and the reader sums
    exactly them."""
    from test_kanana2_cell import context

    rc = context()
    table = scopes.scope_table(scopes.step_hlo(rc))
    module = reader("recompute_ms")
    again = [n for n, op in table.items() if module.recomputed(op)]
    assert len(again) > 50
    assert {scopes.classify(table[n])[0] for n in again} == {"backward"}
    rc.trace_summary = type("S", (), {"op_seconds": {n: 0.001
                                                     for n in table}})
    rc.counters = {"steps": 1}
    assert module.read(rc) == pytest.approx(len(again))
