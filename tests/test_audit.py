"""Compiled-program audit plane (telemetry/audit.py, tools/auditbench.py).

The tentpole pins (PR 17 / ROADMAP observability): for every explicit
shard_map engine the analytic ``comm_stats`` wire-byte formulas tie out
EXACTLY — per collective, per engine — against the ledger walked out of
the optimized HLO the backend actually compiled:

* dp ZeRO-1 bucketed: one RS + one JIT-AG per bucket, wire == the
  physical_* twins, RS in the wire dtype;
* dp int8: scale sidecars are exactly one scalar f32 psum per bucket on
  top of the two metric psums, and their wire is priced;
* gpipe: 2 collective-permutes x (S-1)*dp pairs, conveyor wire == trips
  x per-iteration wire, grad/state rows land in the two padded-row
  payload classes;
* tp-in-stage: every nonscalar all-reduce classifies into a (mesh axes,
  payload) class — activation psums over 'model', sliced/replicated
  gradient rows, padded state rows — nothing unexplained;
* serve: ``pool_page_bytes`` == the compiled programs' actual pool
  buffer bytes per layer and in total, int8 exactly f32/4.

Plus the schema/degradation contract (cost/memory introspection missing
=> fields None, never KeyError), the planner HBM audit recorded in the
partition.json idiom, and the ``auditbench diff`` regression gate
(a doubled collective exits nonzero; a self-diff is clean).
"""

import copy
import json

import jax
import jax.numpy as jnp
import pytest

from ddlbench_tpu.config import RunConfig, ServeConfig
from ddlbench_tpu.telemetry.audit import (AUDIT_SCHEMA_VERSION,
                                          collective_ledger,
                                          diff_manifests, load_manifests,
                                          lower_manifest,
                                          planner_stage_hbm_audit,
                                          program_manifest, reconcile_train,
                                          record_hbm_audit, resolve_axes,
                                          serve_pool_audit, write_manifests)
from tiny_models import TINY_LM, tiny_dense_model, tiny_transformer

pytestmark = pytest.mark.audit


# ---- HLO ledger parsing ----------------------------------------------------


_HLO = """\
HloModule probe
  %ar0 = f32[4,8]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %loss = f32[] all-reduce(%p1), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %row = f32[1,1]{1,0} all-reduce(%p2), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add
  %rs = f32[16]{0} reduce-scatter(%p3), replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%add
  %ag = bf16[64]{0} all-gather(%p4), replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
  %cp = f32[2,8]{1,0} collective-permute(%p5), source_target_pairs={{0,1},{1,2},{2,3}}
  %cps = f32[2,8]{1,0} collective-permute-start(%p5), source_target_pairs={{4,5},{5,6}}
  %cpd = f32[2,8]{1,0} collective-permute-done(%cps)
"""


def test_ledger_parses_kinds_groups_and_wire():
    """Literal + iota replica groups, -start counted once (-done skipped),
    and the ring-model wire conventions per kind."""
    ops = {op.name: op for op in collective_ledger(_HLO)}
    assert set(ops) == {"ar0", "loss", "row", "rs", "ag", "cp", "cps"}

    ar = ops["ar0"]  # 2 groups of 4, payload 4*8*4 = 128B
    assert (ar.n_groups, ar.group_size, ar.payload_bytes) == (2, 4, 128.0)
    assert ar.wire_bytes == 2 * 2.0 * 3 / 4 * 128.0
    assert not ar.scalar

    # rank-0 single element = metric psum; rank>=1 single element (a
    # padded [1,1] state row) is PAYLOAD — the distinction that makes the
    # gpipe/tpp grad+state ties exact
    assert ops["loss"].scalar
    assert not ops["row"].scalar

    rs = ops["rs"]  # iota [2,4]<=[8]: groups {0..3},{4..7}; per-shard out
    assert rs.groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert rs.wire_bytes == 2 * 3 * 64.0

    ag = ops["ag"]  # iota with transpose: {0,4},{1,5},{2,6},{3,7}
    assert ag.groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert ag.wire_bytes == 4 * (1 / 2) * 128.0  # bf16[64] = 128B gathered

    assert ops["cp"].n_pairs == 3
    assert ops["cp"].wire_bytes == 3 * 64.0
    assert ops["cps"].n_pairs == 2  # async start; done not double-counted


_G8 = "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add"


@pytest.mark.parametrize("line,want", [
    # what XLA's combiner makes of a gradient row, a [1,1] state row and two
    # metric psums that share replica groups (gpipe's step on the jax 0.9.0
    # CPU compiler): four operands, two of them scalars
    ("%ar = (f32[1,1040]{1,0}, f32[1,1]{1,0}, f32[], f32[]) "
     "all-reduce(%a, %b, %c, %d), " + _G8,
     [("ar[0]", 4160.0, False), ("ar[1]", 4.0, False),
      ("ar[2]", 4.0, True), ("ar[3]", 4.0, True)]),
    ("%ars = (bf16[8]{0}, f32[]) all-reduce-start(%a, %b), " + _G8,
     [("ars[0]", 16.0, False), ("ars[1]", 4.0, True)]),
    # an async all-gather's shape is (operand, result): the result counts
    ("%ags = (f32[16]{0}, f32[128]{0}) all-gather-start(%a), "
     "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}",
     [("ags", 512.0, False)]),
    ("%agc = ((f32[16]{0}, bf16[8]{0}), (f32[128]{0}, bf16[64]{0})) "
     "all-gather-start(%a, %b), replica_groups={{0,1,2,3,4,5,6,7}}, "
     "dimensions={0}",
     [("agc[0]", 512.0, False), ("agc[1]", 128.0, False)]),
    # (operand, result, two context words)
    ("%cps = (f32[2,8]{1,0}, f32[2,8]{1,0}, u32[], u32[]) "
     "collective-permute-start(%a), source_target_pairs={{0,1},{1,2}}",
     [("cps", 64.0, False)]),
])
def test_ledger_counts_a_combined_collective_per_operand(line, want):
    ops = collective_ledger("HloModule probe\n  " + line + "\n")
    assert [(op.name, op.payload_bytes, op.scalar) for op in ops] == want
    # the wire model is linear in the payload: the operands' wire sums to
    # what the instruction moves
    kind, g, pairs = ops[0].kind, ops[0].group_size, ops[0].n_pairs
    total = sum(op.payload_bytes for op in ops)
    assert sum(op.wire_bytes for op in ops) == pytest.approx({
        "all-reduce": 2.0 * (g - 1) / g * total,
        "all-gather": (g - 1) / g * total,
        "collective-permute": total * pairs}[kind])


def test_resolve_axes_against_mesh_partitions():
    mesh_axes = [("data", 2), ("model", 4)]
    assert resolve_axes([[0, 1, 2, 3], [4, 5, 6, 7]], mesh_axes) == "model"
    assert resolve_axes([[0, 4], [1, 5], [2, 6], [3, 7]],
                        mesh_axes) == "data"
    assert resolve_axes([[0, 1, 2, 3, 4, 5, 6, 7]],
                        mesh_axes) == "data+model"
    assert resolve_axes([[0, 2], [1, 3], [4, 6], [5, 7]], mesh_axes) is None
    assert resolve_axes(None, mesh_axes) is None


# ---- manifest schema + graceful degradation --------------------------------


def test_manifest_schema_on_cpu(devices):
    """A real compiled program on the cpu backend: the pinned key set, with
    cost/memory fields either numeric or None — never missing."""
    fn = jax.jit(lambda x: (x @ x.T).sum())
    man = lower_manifest(fn, (jnp.ones((8, 8)),), "probe/matmul")
    for key in ("audit_schema_version", "name", "jax_version",
                "jaxlib_version", "backend", "mesh_axes", "flops",
                "bytes_accessed", "memory", "hlo_available", "collectives",
                "collective_totals", "scalar_collectives",
                "wire_bytes_total"):
        assert key in man
    assert man["audit_schema_version"] == AUDIT_SCHEMA_VERSION
    assert man["name"] == "probe/matmul"
    assert man["hlo_available"]
    assert man["collectives"] == []  # single-device program
    # cpu's cost_analysis returns flops; the contract is numeric-or-None
    assert man["flops"] is None or man["flops"] > 0


def test_manifest_degrades_to_none_fields():
    """A backend with NO introspection surfaces: every analysis field is
    None / empty, nothing raises (the KeyError-never contract)."""
    class Opaque:
        def cost_analysis(self):
            raise NotImplementedError

        def memory_analysis(self):
            raise NotImplementedError

        def as_text(self):
            raise NotImplementedError

    man = program_manifest(Opaque(), "probe/opaque")
    assert man["flops"] is None
    assert man["bytes_accessed"] is None
    assert man["memory"] is None
    assert not man["hlo_available"]
    assert man["collectives"] == []
    assert man["wire_bytes_total"] == 0.0


def test_partial_cost_dict_yields_none_not_keyerror():
    class Partial:
        def cost_analysis(self):
            return [{"transcendentals": 7.0}]  # no flops/bytes keys

        def memory_analysis(self):
            return None

        def as_text(self):
            return ""

    man = program_manifest(Partial(), "probe/partial")
    assert man["flops"] is None and man["bytes_accessed"] is None
    assert man["memory"] is None


# ---- train-engine tie-outs (the tentpole pins) -----------------------------


def _dp_cfg(**kw):
    base = dict(benchmark="mnist", strategy="dp", num_devices=8,
                compute_dtype="float32", batch_size=2, steps_per_epoch=2,
                momentum=0.5, weight_decay=1e-4)
    base.update(kw)
    cfg = RunConfig(**base)
    cfg.validate()
    return cfg


def _dp_audit(train_factory, cfg):
    from ddlbench_tpu.parallel.dp import DPStrategy

    # same cache namespace as test_dp_shard/test_comm_overlap: identical
    # (model, config) engines compile once per session
    strat = train_factory(("dpshard", "dense", cfg),
                          lambda: DPStrategy(tiny_dense_model(), cfg))
    ts = strat.init(jax.random.key(cfg.seed))
    B = cfg.global_batch()
    x = jax.random.normal(jax.random.key(1), (B, 4, 4, 1))
    y = jax.random.randint(jax.random.key(2), (B,), 0, 4)
    fn = getattr(strat, "_jit_train_step", None) or strat.train_step
    man = lower_manifest(fn, (ts, *strat.shard_batch(x, y),
                              jnp.float32(0.1)),
                         "dp", mesh=getattr(strat, "mesh", None))
    return man, reconcile_train(strat, man), strat


def _assert_tied(rec):
    bad = [c for c in rec["checks"] if not c["ok"]]
    assert rec["tieable"], rec
    assert not bad, bad
    assert not rec["unexplained"], rec["unexplained"]
    assert rec["ok"]


def test_dp_zero1_bucketed_wire_ties_exactly(devices, train_factory):
    """ZeRO-1 bucketed: exactly one reduce-scatter + one f32 all-gather
    per REALIZED bucket (layer alignment can cap the requested count),
    wire bytes == comm_stats' physical twins."""
    man, rec, strat = _dp_audit(
        train_factory, _dp_cfg(dp_shard_update=True, comm_buckets=4))
    _assert_tied(rec)
    nb = int(strat._flat_meta.num_buckets)
    assert nb > 1  # bucketing actually engaged
    by = {c["check"]: c for c in rec["checks"]}
    assert by["rs_op_count"]["actual"] == nb
    assert by["ag_op_count"]["actual"] == nb
    assert man["collective_totals"]["reduce-scatter"]["count"] == nb


def test_dp_int8_scale_sidecars_tie(devices, train_factory):
    """int8 wire: per-bucket RS in s8 plus EXACTLY one scalar f32 absmax
    psum per bucket on top of the two metric psums, scale wire priced."""
    man, rec, strat = _dp_audit(
        train_factory, _dp_cfg(dp_shard_update=True, comm_buckets=3,
                               allreduce_dtype="int8"))
    _assert_tied(rec)
    by = {c["check"]: c for c in rec["checks"]}
    assert by["scalar_f32_psums"]["expected"] == 2 + 3
    assert by["rs_wire_dtype"]["actual"] == 3  # all three RS on s8 wire
    assert by["scale_wire_bytes"]["expected"] == \
        rec["comm_stats"]["scale_bytes"]


def test_dp_replicated_gspmd_is_untieable_by_design(devices, train_factory):
    """The GSPMD pmean engine compiles compiler-chosen collective soup:
    reported tieable False with the manifest still attached — never a
    false 'ok', never a crash."""
    man, rec, _ = _dp_audit(train_factory, _dp_cfg())
    assert rec["tieable"] is False
    assert rec["ok"] is False
    assert man["hlo_available"]


def _gpipe_model():
    from ddlbench_tpu.models.layers import LayerModel, dense, flatten

    layers = [flatten(), dense("g1", 16, relu=True),
              dense("g2", 12, relu=True), dense("g3", 10, relu=True),
              dense("g4", 10)]
    return LayerModel("tinypipe5", layers, (8, 8, 1), 10)


def test_gpipe_conveyor_and_row_classes_tie(devices, train_factory):
    """gpipe S=4 x dp=2: 2 boundary collective-permutes with (S-1)*dp
    pairs each, conveyor wire == (M*V+S-1) trips x per-iteration wire,
    and every gradient/state all-reduce lands in one of the two
    padded-row payload classes."""
    from ddlbench_tpu.parallel.gpipe import GPipeStrategy

    cfg = RunConfig(strategy="gpipe", num_devices=8, num_stages=4,
                    dp_replicas=2, micro_batch_size=4, num_microbatches=4,
                    compute_dtype="float32", momentum=0.0,
                    weight_decay=0.0, steps_per_epoch=2)
    cfg.validate()
    strat = train_factory(
        ("audit", "gpipe5", cfg),
        lambda: GPipeStrategy(_gpipe_model(), cfg,
                              stage_bounds=[0, 2, 3, 4, 5]))
    ts = strat.init(jax.random.key(0))
    B = cfg.global_batch()
    x = jax.random.normal(jax.random.key(1), (B, 8, 8, 1))
    y = jax.random.randint(jax.random.key(2), (B,), 0, 10)
    man = lower_manifest(strat.train_step,
                         (ts, *strat.shard_batch(x, y), jnp.float32(0.1)),
                         "gpipe", mesh=strat.mesh)
    rec = reconcile_train(strat, man)
    _assert_tied(rec)
    by = {c["check"]: c for c in rec["checks"]}
    assert by["cp_op_count"]["actual"] == 2
    cs = rec["comm_stats"]
    cp_wire = man["collective_totals"]["collective-permute"]["wire_bytes"]
    T = cfg.num_microbatches + cfg.num_stages - 1
    assert cs["physical_boundary_bytes"] == T * cp_wire


def test_tpp_payload_classes_tie(devices, train_factory):
    """tp-in-stage (S=2 x tp=2 x dp=2): activation psums classify onto the
    'model' axis at mb x act_shape bytes; sliced/replicated gradient rows
    and padded state rows explain every remaining all-reduce; summed
    grad+state wire == comm_stats' physical_allreduce_bytes exactly."""
    from ddlbench_tpu.parallel.tpp import TPGPipeStrategy

    cfg = RunConfig(strategy="gpipe", benchmark="synthtext",
                    arch="transformer_t", num_devices=8, num_stages=2,
                    tp_size=2, dp_replicas=2, micro_batch_size=4,
                    num_microbatches=4, compute_dtype="float32",
                    momentum=0.0, weight_decay=0.0, steps_per_epoch=2)
    cfg.validate()
    strat = train_factory(
        ("audit", "tpp-tiny", cfg),
        lambda: TPGPipeStrategy(tiny_transformer(), cfg))
    ts = strat.init(jax.random.key(0))
    B = cfg.global_batch()
    x = jax.random.randint(jax.random.key(1), (B, 32), 0,
                           TINY_LM.num_classes)
    y = jax.random.randint(jax.random.key(2), (B, 32), 0,
                           TINY_LM.num_classes)
    man = lower_manifest(strat.train_step,
                         (ts, *strat.shard_batch(x, y), jnp.float32(0.1)),
                         "tpp", mesh=strat.mesh)
    rec = reconcile_train(strat, man)
    _assert_tied(rec)
    # the Megatron psums are present and resolved onto the 'model' axis
    assert rec["tp_psum_ops"] >= 1
    cs = rec["comm_stats"]
    assert cs["tp_psum_payload_bytes"] == \
        cfg.micro_batch_size * 32 * 32 * 4  # mb x [T, d_model] f32


# ---- serve KV-pool tie-out -------------------------------------------------


def _serve_cfg(**kw):
    base = dict(max_batch=4, pool_pages=20, page=4, max_len=16,
                prefill_chunk=4)
    base.update(kw)
    cfg = ServeConfig(**base)
    cfg.validate()
    return cfg


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_serve_pool_page_bytes_tie(serve_factory, kv_dtype):
    """pool_page_bytes x pool_pages == the actual pool_k/pool_v buffer
    bytes the compiled programs take as donated arguments, per layer and
    in total; int8 pages are exactly f32/4; sidecars split out."""
    eng = serve_factory(_serve_cfg(kv_dtype=kv_dtype))
    pa = serve_pool_audit(eng)
    assert pa["ok"], [c for c in pa["checks"] if not c["ok"]]
    assert pa["pool_page_bytes"] == float(eng.bytes_per_page)
    if kv_dtype == "int8":
        assert pa["sidecar_bytes"] > 0  # absmax scale planes
        by = {c["check"]: c for c in pa["checks"]}
        assert by["int8_page_is_f32_quarter"]["ok"]


def test_serve_program_manifests_cover_the_jit_surface(serve_factory):
    """audit_programs() exposes (name, jitfn, args) for every compiled
    serve program; each lowers to a manifest at the engine's shapes."""
    eng = serve_factory(_serve_cfg())
    progs = dict((name, (fn, args))
                 for name, fn, args in eng.audit_programs())
    assert {"decode", "prefill", "cow"} <= set(progs)
    fn, args = progs["decode"]
    man = lower_manifest(fn, args, "serve/decode",
                         mesh=getattr(eng, "_mesh", None))
    assert man["hlo_available"]
    assert man["memory"] is None or man["memory"]["argument_bytes"] > 0


# ---- planner HBM audit + partition.json record -----------------------------


def test_planner_stage_hbm_audit_signed_error():
    man = {"memory": {"peak_bytes": 8 * 1000.0}}
    rec = {"stage_mem": [900.0, 1100.0]}
    hbm = planner_stage_hbm_audit(rec, man, world=8)
    assert hbm["measured_chip_bytes"] == 1000.0
    assert [s["err_bytes"] for s in hbm["stages"]] == [-100.0, 100.0]
    assert hbm["stages"][0]["err_frac"] == -0.1
    assert hbm["predicted_peak_bytes"] == 1100.0
    # degradation: no memory_analysis, or no per-stage predictions -> None
    assert planner_stage_hbm_audit(rec, {"memory": None}, 8) is None
    assert planner_stage_hbm_audit({"stage_mem": None}, man, 8) is None


def test_record_hbm_audit_lands_in_partition_json(tmp_path):
    """The audit merges under plan_auto.hbm_audit in the run's
    partition.json (atomic tmp+replace), preserving the decision record."""
    from ddlbench_tpu.parallel.api import _plan_path

    cfg = RunConfig(benchmark="mnist", strategy="dp", num_devices=8,
                    checkpoint_dir=str(tmp_path))
    path = _plan_path(cfg)
    doc = {"plan_auto": {"fingerprint": "f" * 8,
                         "winner": {"pp": 2, "stage_mem": [1.0, 2.0]}}}
    with open(path, "w") as f:
        json.dump(doc, f)
    hbm = {"world": 8, "stages": []}
    assert record_hbm_audit(cfg, hbm) == path
    with open(path) as f:
        out = json.load(f)
    assert out["plan_auto"]["hbm_audit"] == hbm
    assert out["plan_auto"]["fingerprint"] == "f" * 8  # record preserved
    # no persisted plan -> None, not a crash
    cfg2 = RunConfig(benchmark="mnist", strategy="dp", num_devices=8)
    assert record_hbm_audit(cfg2, hbm) is None


# ---- ledger IO + the diff regression gate ----------------------------------


def _tiny_ledger():
    return {
        "audit_schema_version": AUDIT_SCHEMA_VERSION,
        "programs": [{
            "name": "train/dp", "flops": 1000.0, "bytes_accessed": 4000.0,
            "memory": {"peak_bytes": 2000.0},
            "wire_bytes_total": 980.0,
            "collective_totals": {
                "reduce-scatter": {"count": 3, "payload_bytes": 140.0,
                                   "wire_bytes": 490.0},
                "all-gather": {"count": 3, "payload_bytes": 560.0,
                               "wire_bytes": 490.0},
            },
        }],
    }


def test_write_load_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.json")
    write_manifests(path, _tiny_ledger()["programs"],
                    header={"tool": "test", "schema_version": 1})
    doc = load_manifests(path)
    assert doc["audit_schema_version"] == AUDIT_SCHEMA_VERSION
    assert doc["tool"] == "test"
    assert doc["programs"][0]["name"] == "train/dp"


def test_diff_catches_doubled_collective(tmp_path):
    """The deliberate-regression fixture: doubling one collective's count
    and wire must flag (and auditbench diff must exit nonzero); the
    self-diff is clean (rc 0)."""
    old = _tiny_ledger()
    new = copy.deepcopy(old)
    rs = new["programs"][0]["collective_totals"]["reduce-scatter"]
    rs["count"] *= 2
    rs["wire_bytes"] *= 2
    new["programs"][0]["wire_bytes_total"] += 490.0

    report = diff_manifests(old, new)
    assert not report["ok"]
    flagged = {r["metric"] for r in report["regressions"]}
    assert "collectives[reduce-scatter].count" in flagged
    assert "collectives[reduce-scatter].wire_bytes" in flagged
    assert "wire_bytes_total" in flagged
    assert diff_manifests(old, copy.deepcopy(old))["ok"]

    # the CLI gate inherits the verdicts as exit codes
    from ddlbench_tpu.tools.auditbench import run_diff

    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    write_manifests(pa, old["programs"])
    write_manifests(pb, new["programs"])
    assert run_diff(pa, pb, tolerance=0.01, quiet=True) == 1
    assert run_diff(pa, pa, tolerance=0.01, quiet=True) == 0


def test_diff_tolerance_and_removal():
    old = _tiny_ledger()
    drift = copy.deepcopy(old)
    drift["programs"][0]["flops"] *= 1.005  # assembler burp < tolerance
    assert diff_manifests(old, drift)["ok"]

    gone = copy.deepcopy(old)
    gone["programs"] = []
    report = diff_manifests(old, gone)
    assert not report["ok"]
    assert report["removed"] == ["train/dp"]

    added = copy.deepcopy(old)
    added["programs"].append({"name": "train/new", "flops": 1.0})
    report = diff_manifests(old, added)
    assert report["ok"]  # additions are reported, not failures
    assert report["added"] == ["train/new"]
