"""Comm/compute overlap for the dp ZeRO-1 engine (ISSUE 6).

Acceptance pins, all tier-1-fast on the 8-virtual-device CPU mesh:

* the f32 BUCKETED sharded update (--comm-buckets K, incl. the fully
  overlapped engine with just-in-time all-gather) is BITWISE-identical to
  the monolithic PR 3 path — params AND per-step losses, 16+ steps,
  grad-accum and Adam included. Bucketing only moves pad zeros between
  leaves, never a reduction order within a bucket, so this is exact by
  construction and pinned here against regression;
* --comm-buckets 1 reproduces the pre-bucketing FlatMeta layout exactly;
* every bucket's gradient collective carries the grad_sync/bucket<b>
  named scope in the compiled step, and the step's one host span
  (dp_explicit_update) carries the EXACT wire-byte accounting (int8 = 1/4
  the f32 gradient bytes, also pinned through comm_stats);
* the int8 wire's stochastic rounding is unbiased, seed-deterministic
  (bitwise run replay), and absmax round-trip exact;
* the overlapped engine's flat sharded params survive eval, checkpoint
  round-trip, and materialize_params.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.layers import LayerModel, dense, flatten
from ddlbench_tpu.parallel.common import (FlatMeta, bucket_slice, flat_meta,
                                          from_device_major, pack_flat,
                                          quantize_int8,
                                          shard_bucket_slice,
                                          stochastic_round_int8,
                                          sum_safe_qmax, to_device_major,
                                          unpack_buckets, unpack_flat)
from ddlbench_tpu.parallel.dp import DPStrategy
from ddlbench_tpu.train.comm_stats import comm_stats

pytestmark = pytest.mark.comm


from tiny_models import tiny_dense_model as _dense_model  # noqa: E402
# (one home for the model the two dp suites' shared train_factory cache
# keys compile — see tests/tiny_models.py)


def _cfg(**kw):
    base = dict(benchmark="mnist", strategy="dp", num_devices=8,
                compute_dtype="float32", batch_size=2, steps_per_epoch=2,
                momentum=0.5, weight_decay=1e-4)
    base.update(kw)
    cfg = RunConfig(**base)
    cfg.validate()
    return cfg


def _batch(B, step, num_classes=4, shape=(4, 4, 1)):
    kx, ky = jax.random.split(jax.random.key(100 + step))
    return (jax.random.normal(kx, (B, *shape)),
            jax.random.randint(ky, (B,), 0, num_classes))


def _run(factory, cfg, steps, lr=0.2):
    # session-shared compiled-strategy cache (conftest train_factory);
    # the key namespace matches test_dp_shard's, so the engines the two
    # suites share (same tiny model, same config base) compile ONCE
    strat = factory(("dpshard", "dense", cfg),
                    lambda: DPStrategy(_dense_model(), cfg))
    model = strat.model
    ts = strat.init(jax.random.key(cfg.seed))
    losses = []
    for s in range(steps):
        x, y = _batch(cfg.global_batch(), s, model.num_classes,
                      model.in_shape)
        ts, m = strat.train_step(ts, *strat.shard_batch(x, y),
                                 jnp.float32(lr))
        losses.append(float(m["loss"]))
    return np.array(losses), ts, strat


def _flat_params(strat, ts):
    p = (strat.materialize_params(ts)
         if hasattr(strat, "materialize_params") else ts.params)
    return np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree.leaves(p)])


# ---- FlatMeta bucketing ----------------------------------------------------


def _abs_params(model, world=8):
    from ddlbench_tpu.models.layers import init_model

    return jax.eval_shape(lambda k: init_model(model, k)[0],
                          jax.random.key(0))


def test_single_bucket_is_the_legacy_layout():
    """--comm-buckets 1 must reproduce the pre-bucketing FlatMeta exactly:
    one bucket spanning every leaf, one tail pad."""
    p = _abs_params(_dense_model())
    m1 = flat_meta(p, 8)
    mk = flat_meta(p, 8, buckets=1,
                   leaf_groups=[len(jax.tree.leaves(l)) for l in p])
    assert m1.padded == -(-m1.length // 8) * 8
    for m in (m1, mk):
        assert m.num_buckets == 1
        assert m.bucket_leaves == ((0, len(jax.tree.leaves(p))),)
        assert m.bucket_offsets == (0,)
        assert m.bucket_padded == (m.padded,)
    assert m1.padded == mk.padded


def test_buckets_are_contiguous_layer_aligned_and_world_padded():
    p = _abs_params(_dense_model())
    groups = [len(jax.tree.leaves(l)) for l in p]
    leaf_starts = np.cumsum([0] + groups)
    m = flat_meta(p, 8, buckets=3, leaf_groups=groups)
    assert 1 < m.num_buckets <= 3
    # contiguous leaf coverage, boundaries on layer starts, world-padded
    prev_stop = 0
    off = 0
    for (l0, l1), bp, bo in zip(m.bucket_leaves, m.bucket_padded,
                                m.bucket_offsets):
        assert l0 == prev_stop
        assert l0 in leaf_starts and l1 in leaf_starts
        assert bp % 8 == 0 and bp >= sum(m.sizes[l0:l1])
        assert bo == off
        prev_stop, off = l1, off + bp
    assert prev_stop == len(jax.tree.leaves(p))
    assert m.padded == sum(m.bucket_padded)


def test_bucket_bounds_balance():
    """The greedy split must balance element counts via CUMULATIVE
    fair-share targets — a per-bucket accumulator drifts (one oversized
    bucket inflates every later threshold), regression: equal groups
    split [3, 6, 1, 2] instead of [3, 3, 3, 3]."""
    from ddlbench_tpu.parallel.common import _bucket_bounds

    def bucket_sizes(gs, buckets):
        bd = _bucket_bounds(gs, buckets)
        return [sum(gs[bd[i]:bd[i + 1]]) for i in range(len(bd) - 1)]

    assert bucket_sizes([1] * 12, 4) == [3, 3, 3, 3]
    assert bucket_sizes([1] * 8, 4) == [2, 2, 2, 2]
    # heterogeneous: every bucket within one max-group of the fair share
    gs = [5, 3, 8, 2, 7, 1, 4, 6]
    for buckets in (2, 3, 4):
        sizes = bucket_sizes(gs, buckets)
        assert len(sizes) == buckets
        assert max(sizes) <= sum(gs) / buckets + max(gs)


def test_pack_unpack_roundtrip_with_buckets():
    model = _dense_model()
    from ddlbench_tpu.models.layers import init_model

    params, _, _ = init_model(model, jax.random.key(3))
    groups = [len(jax.tree.leaves(l)) for l in params]
    for buckets in (1, 2, 3, 16):
        m = flat_meta(params, 8, buckets=buckets, leaf_groups=groups)
        flat = pack_flat(params, m)
        assert flat.shape == (m.padded,)
        back = unpack_flat(flat, m)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # per-bucket unpack (the overlapped forward's dataflow)
        stretches = [bucket_slice(flat, m, b) for b in range(m.num_buckets)]
        back2 = unpack_buckets(stretches, m)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_major_layout_roundtrip():
    """to/from_device_major invert each other, agree with the per-bucket
    shard slices, and are the identity permutation for one bucket."""
    p = _abs_params(_dense_model())
    groups = [len(jax.tree.leaves(l)) for l in p]
    for buckets in (1, 3):
        m = flat_meta(p, 8, buckets=buckets, leaf_groups=groups)
        flat = jnp.arange(m.padded, dtype=jnp.float32)
        dm = to_device_major(flat, m, 8)
        np.testing.assert_array_equal(np.asarray(from_device_major(dm, m, 8)),
                                      np.asarray(flat))
        if buckets == 1:
            np.testing.assert_array_equal(np.asarray(dm), np.asarray(flat))
        # device d's shard, bucket b slice == bucket b's d-th 1/world slice
        shard_len = m.padded // 8
        for d in range(8):
            shard = dm[d * shard_len:(d + 1) * shard_len]
            for b in range(m.num_buckets):
                bl = m.bucket_padded[b] // 8
                want = bucket_slice(flat, m, b)[d * bl:(d + 1) * bl]
                got = shard_bucket_slice(shard, m, 8, b)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))


# ---- acceptance: f32 bucketed/overlapped pinned bitwise vs monolithic ------


def test_overlapped_bitwise_trajectory_16_steps(devices, train_factory):
    """The fully overlapped engine (bucketed RS + just-in-time AG, params
    sharded between steps) must reproduce the monolithic PR 3 sharded
    update BITWISE over >= 16 steps: per-step losses AND final params."""
    la, tsa, sa = _run(train_factory, _cfg(dp_shard_update=True), steps=16)
    lb, tsb, sb = _run(train_factory, _cfg(dp_shard_update=True, comm_buckets=4),
                       steps=16)
    assert sb._overlap and not sa._overlap
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(_flat_params(sa, tsa),
                                  _flat_params(sb, tsb))


@pytest.mark.parametrize("kw", [dict(optimizer="adam"),
                                dict(grad_accum_steps=2),
                                dict(comm_buckets=8)])
def test_overlapped_bitwise_variants(devices, train_factory, kw):
    """Bitwise parity holds across Adam, gradient accumulation (per-bucket
    RS inside the micro-step scan), and deeper bucketing."""
    kw = dict(kw)
    buckets = kw.pop("comm_buckets", 4)
    la, tsa, sa = _run(train_factory, _cfg(dp_shard_update=True, **kw), steps=4)
    lb, tsb, sb = _run(train_factory, _cfg(dp_shard_update=True,
                                   comm_buckets=buckets, **kw), steps=4)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(_flat_params(sa, tsa),
                                  _flat_params(sb, tsb))


def test_bucketed_replicated_update_bitwise(devices, train_factory):
    """Buckets WITHOUT the sharded update (replicated explicit engine,
    per-bucket psum in the wire dtype): the f32-equivalent check uses bf16
    wire on both sides so only bucketing varies."""
    la, tsa, sa = _run(train_factory, _cfg(allreduce_dtype="bf16"), steps=4)
    lb, tsb, sb = _run(train_factory, _cfg(allreduce_dtype="bf16", comm_buckets=3),
                       steps=4)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(_flat_params(sa, tsa),
                                  _flat_params(sb, tsb))


def test_standalone_f32_buckets_bitwise_vs_gspmd_dp(devices, train_factory):
    """--comm-buckets alone (f32, no sharded update) is a valid dp knob:
    it routes through the explicit replicated engine (one psum per
    bucket) and stays BITWISE on the GSPMD dp trajectory."""
    la, tsa, sa = _run(train_factory, _cfg(), steps=4)  # GSPMD dp
    cfg = _cfg(comm_buckets=3)
    assert cfg.dp_explicit_collectives() and not cfg.dp_overlap_engine()
    lb, tsb, sb = _run(train_factory, cfg, steps=4)
    assert sb._flat_meta.num_buckets > 1
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(_flat_params(sa, tsa),
                                  _flat_params(sb, tsb))


def test_comm_buckets_1_routes_to_monolithic_engine(devices, train_factory):
    """--comm-buckets 1 must not even enter the overlapped engine: params
    stay the replicated pytree and the meta is the single-bucket layout."""
    _, ts, strat = _run(train_factory, _cfg(dp_shard_update=True, comm_buckets=1),
                        steps=1)
    assert not strat._overlap
    assert strat._flat_meta.num_buckets == 1
    assert isinstance(ts.params, list)  # per-layer pytree, not a flat array


# ---- overlapped-engine state: eval / checkpoint / materialize --------------


def test_overlapped_eval_and_materialize_match_monolithic(devices, train_factory):
    _, tsa, sa = _run(train_factory, _cfg(dp_shard_update=True), steps=3)
    _, tsb, sb = _run(train_factory, _cfg(dp_shard_update=True, comm_buckets=4),
                      steps=3)
    assert tsb.params.ndim == 1  # flat sharded vector between steps
    np.testing.assert_array_equal(_flat_params(sa, tsa),
                                  _flat_params(sb, tsb))
    x, y = _batch(16, 77)
    ma = sa.eval_step(tsa, *sa.shard_batch(x, y))
    mb = sb.eval_step(tsb, *sb.shard_batch(x, y))
    for k in ma:
        np.testing.assert_array_equal(np.asarray(ma[k]), np.asarray(mb[k]))


def test_overlapped_checkpoint_roundtrip(devices, train_factory, tmp_path):
    from ddlbench_tpu.train.checkpoint import (restore_checkpoint,
                                               save_checkpoint)

    _, ts, strat = _run(train_factory, _cfg(dp_shard_update=True, comm_buckets=4),
                        steps=2)
    save_checkpoint(str(tmp_path), 1, ts, seed=1)
    target = strat.init(jax.random.key(1))
    _, restored = restore_checkpoint(str(tmp_path), target)
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- per-bucket spans + wire-byte accounting -------------------------------


def _traced_run(train_factory, cfg, steps):
    from ddlbench_tpu.telemetry import Tracer, get_tracer, set_tracer

    prev = get_tracer()
    tracer = set_tracer(Tracer())
    tracer.enable()
    try:
        _, ts, strat = _run(train_factory, cfg, steps=steps)
    finally:
        tracer.disable()
        set_tracer(prev)
    return tracer, ts, strat


def test_bucket_scopes_and_exact_wire_bytes(devices, train_factory):
    """Each bucket's gradient collective sits under grad_sync/bucket<b> in
    the compiled step (what a device trace times), and the one host span of
    the step carries wire-byte args that sum EXACTLY to comm_stats'
    physical accounting."""
    import re

    cfg = _cfg(dp_shard_update=True, comm_buckets=4)
    tracer, ts, strat = _traced_run(train_factory, cfg, steps=2)
    K = strat._flat_meta.num_buckets
    assert K > 1
    spans = [e for e in tracer.events() if e[1] == "dp_explicit_update"]
    assert len(spans) == 2  # one a step, no zero-work marker beside it
    assert not [e for e in tracer.events() if e[1].endswith("_bucket")]
    cs = comm_stats(strat)
    for e in spans:
        assert e[6]["buckets"] == K and e[6]["mode"] == "overlapped"
        np.testing.assert_allclose(e[6]["grad_wire_bytes"],
                                   cs["physical_reduce_scatter_bytes"],
                                   rtol=1e-12)
        np.testing.assert_allclose(e[6]["param_wire_bytes"],
                                   cs["physical_all_gather_bytes"],
                                   rtol=1e-12)
    x, y = _batch(cfg.global_batch(), 0, strat.model.num_classes,
                  strat.model.in_shape)
    text = strat._jit_train_step.lower(
        ts, *strat.shard_batch(x, y), jnp.float32(0.2)).compile().as_text()
    found = set()
    for line in text.splitlines():
        if re.search(r"= \S+ (reduce-scatter|all-reduce)(-start)?\(", line):
            m = re.search(r'op_name="[^"]*grad_sync/bucket(\d+)/', line)
            if m:
                found.add(int(m.group(1)))
    assert found == set(range(K))


def _dp_stats(**kw):
    from ddlbench_tpu.parallel.api import make_strategy

    cfg = _cfg(arch="lenet", **kw)
    return comm_stats(make_strategy(cfg))


def test_comm_stats_int8_quarters_gradient_wire(devices):
    """int8 = exactly 1/4 the f32 gradient wire bytes (logical AND
    physical), sharded and replicated; the param all-gather stays f32."""
    sh = _dp_stats(dp_shard_update=True)
    q = _dp_stats(dp_shard_update=True, allreduce_dtype="int8")
    np.testing.assert_allclose(q["reduce_scatter_bytes"],
                               sh["reduce_scatter_bytes"] / 4, rtol=1e-12)
    np.testing.assert_allclose(q["physical_reduce_scatter_bytes"],
                               sh["physical_reduce_scatter_bytes"] / 4,
                               rtol=1e-12)
    np.testing.assert_allclose(q["all_gather_bytes"], sh["all_gather_bytes"],
                               rtol=1e-12)
    assert q["wire_dtype"] == "int8" and q["scale_bytes"] > 0
    rep = _dp_stats()
    qr = _dp_stats(allreduce_dtype="int8")
    np.testing.assert_allclose(qr["allreduce_bytes"],
                               rep["allreduce_bytes"] / 4, rtol=1e-12)


def test_comm_stats_buckets_conserve_totals(devices):
    """Bucketing repartitions the padded vector; totals must not move."""
    mono = _dp_stats(dp_shard_update=True)
    buck = _dp_stats(dp_shard_update=True, comm_buckets=4)
    assert buck["comm_buckets"] > 1.0
    np.testing.assert_allclose(buck["reduce_scatter_bytes"],
                               mono["reduce_scatter_bytes"], rtol=1e-12)
    # physical bytes may grow by the extra per-bucket pads, never shrink
    assert (buck["physical_reduce_scatter_bytes"]
            >= mono["physical_reduce_scatter_bytes"])


# ---- int8 stochastic rounding ----------------------------------------------


def test_stochastic_rounding_is_unbiased():
    """E[round(v)] == v: the empirical mean over many independent draws
    converges to the real value (the property that keeps the quantized
    gradient sum an unbiased estimate)."""
    v = jnp.array([0.25, -1.75, 3.5, 0.0, 126.99, -126.99, 7.0])
    draws = jnp.stack([
        stochastic_round_int8(v, jax.random.key(i)).astype(jnp.float32)
        for i in range(4000)])
    np.testing.assert_allclose(np.asarray(draws.mean(0)), np.asarray(v),
                               atol=0.05)
    # integers round exactly, every draw
    assert np.all(np.asarray(draws[:, 6]) == 7.0)
    assert np.all(np.asarray(draws[:, 3]) == 0.0)


def test_stochastic_rounding_deterministic_under_key():
    v = jax.random.normal(jax.random.key(5), (512,)) * 40.0
    a = stochastic_round_int8(v, jax.random.key(9))
    b = stochastic_round_int8(v, jax.random.key(9))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = stochastic_round_int8(v, jax.random.key(10))
    assert np.any(np.asarray(a) != np.asarray(c))


def test_quantize_absmax_roundtrip():
    """Values that are integer multiples of the scale dequantize EXACTLY;
    the absmax element maps to +-qmax with zero rounding error."""
    for qmax in (127, 15):
        # integer grid x a power-of-two scale: every value is an exact
        # integer multiple of the resulting absmax/qmax scale, so the
        # stochastic rounding sees zero fraction and the round trip is
        # bit-exact (the general property: exact for multiples of scale)
        q_true = np.array([-qmax, -3, 0, 1, 5, qmax], dtype=np.float32)
        scale_src = jnp.asarray(q_true * 0.25)
        q, scale = quantize_int8(scale_src, jax.random.key(0), qmax=qmax)
        np.testing.assert_allclose(float(scale), 0.25, rtol=0)
        np.testing.assert_array_equal(
            np.asarray(q.astype(jnp.float32) * scale), np.asarray(scale_src))
        assert int(np.max(np.abs(np.asarray(q)))) == qmax
    # all-zero block: scale 1, everything stays finite and zero
    qz, sz = quantize_int8(jnp.zeros((4,)), jax.random.key(0))
    assert float(sz) == 1.0 and np.all(np.asarray(qz) == 0)


def test_quantized_values_respect_sum_safe_qmax():
    """No quantized magnitude may exceed 127 // world — the bound that
    keeps the IN-int8 collective sum from overflowing."""
    assert sum_safe_qmax(8) == 15 and sum_safe_qmax(2) == 63
    with pytest.raises(ValueError, match="127"):
        sum_safe_qmax(128)
    v = jax.random.normal(jax.random.key(1), (2048,)) * 100.0
    q, _ = quantize_int8(v, jax.random.key(2), qmax=15)
    assert int(np.max(np.abs(np.asarray(q)))) <= 15
    assert 8 * 15 <= 127  # the sum bound itself


def test_int8_trains_and_replays_bitwise(devices, train_factory):
    """End-to-end: the int8 wire trains (losses finite, loosely tracking
    f32 — the range loss is the accuracy gate's business, accparity
    dp-int8), and two runs under the same seed replay BITWISE."""
    lref, _, _ = _run(train_factory, _cfg(dp_shard_update=True), steps=4)
    l1, ts1, s1 = _run(train_factory, _cfg(dp_shard_update=True,
                                   allreduce_dtype="int8", comm_buckets=2),
                       steps=4)
    l2, ts2, s2 = _run(train_factory, _cfg(dp_shard_update=True,
                                   allreduce_dtype="int8", comm_buckets=2),
                       steps=4)
    assert np.all(np.isfinite(l1))
    np.testing.assert_allclose(l1, lref, rtol=0.05)
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_array_equal(_flat_params(s1, ts1),
                                  _flat_params(s2, ts2))
    # the rounding-noise stream advanced: the qstep counter ticked
    assert int(np.asarray(ts1.opt["qstep"])) == 4


def test_int8_replicated_update_trains(devices, train_factory):
    lq, ts, strat = _run(train_factory, _cfg(allreduce_dtype="int8"), steps=3)
    assert np.all(np.isfinite(lq))
    assert int(np.asarray(ts.opt["qstep"])) == 3


# ---- overlap-fraction reducer ----------------------------------------------


def test_overlap_fraction_interval_math():
    from ddlbench_tpu.telemetry.overlap import overlap_fraction

    ev = [
        {"ph": "X", "name": "reduce-scatter.1", "ts": 0, "dur": 10,
         "args": {"wire_bytes": 100.0}},
        {"ph": "X", "name": "reduce-scatter.1", "ts": 20, "dur": 10,
         "args": {"wire_bytes": 50.0}},
        {"ph": "X", "name": "fusion.7", "ts": 5, "dur": 20},
        # containers must not count as compute-under-comm
        {"ph": "X", "name": "dp_explicit_update", "ts": 0, "dur": 1000},
        {"ph": "X", "name": "train_step", "ts": 0, "dur": 1000},
        # non-complete events are ignored
        {"ph": "i", "name": "reduce-scatter.1", "ts": 3},
    ]
    r = overlap_fraction(ev)
    assert r["comm_spans"] == 2 and r["compute_spans"] == 1
    np.testing.assert_allclose(r["overlap_fraction"], 0.5)
    assert r["wire_bytes"] == {"reduce-scatter.1": 150.0}
    # no comm spans -> fraction 0, not a division error
    assert overlap_fraction([])["overlap_fraction"] == 0.0
    # explicit compute prefixes override the default complement rule
    r2 = overlap_fraction(ev, compute_prefixes=("nothing-matches",))
    assert r2["overlap_fraction"] == 0.0


def test_overlap_cli_on_exported_trace(devices, train_factory, tmp_path):
    """--trace output -> export -> CLI reducer: a host trace holds no
    communication span any more (the engine's one span is a container, its
    bytes are arguments), so the reducer reports none — overlap is read
    from a device trace, where the collectives are."""
    from ddlbench_tpu.telemetry import export_chrome_trace
    from ddlbench_tpu.telemetry.overlap import main as overlap_main

    tracer, _, _ = _traced_run(
        train_factory, _cfg(dp_shard_update=True, comm_buckets=2), steps=1)
    path = str(tmp_path / "trace.json")
    export_chrome_trace(tracer, path)
    doc = json.load(open(path))
    (span,) = [e for e in doc["traceEvents"]
               if e.get("name") == "dp_explicit_update"]
    assert span["args"]["buckets"] == 2
    assert span["args"]["grad_wire_bytes"] > 0
    assert span["args"]["param_wire_bytes"] > 0
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert overlap_main([path]) == 0
    out = json.loads(buf.getvalue())
    assert out["comm_spans"] == 0 and out["wire_bytes"] == {}
    assert out["overlap_fraction"] == 0.0


# ---- config gates ----------------------------------------------------------


def test_comm_bucket_config_gates():
    with pytest.raises(ValueError, match="comm_buckets"):
        _cfg(comm_buckets=0)
    with pytest.raises(ValueError, match="dp strategy"):
        _cfg(strategy="single", num_devices=1, comm_buckets=4)
    # buckets alone route dp through the explicit replicated engine, the
    # same way a non-f32 wire dtype does — no sharded update required
    cfg_buckets = _cfg(comm_buckets=4)
    assert cfg_buckets.dp_explicit_collectives()
    assert not cfg_buckets.dp_overlap_engine()
    assert _cfg(dp_shard_update=True, comm_buckets=4).dp_overlap_engine()
    assert not _cfg(dp_shard_update=True).dp_overlap_engine()
    assert not _cfg(allreduce_dtype="bf16",
                    comm_buckets=4).dp_overlap_engine()


def test_comm_flags_helper(monkeypatch):
    """distributed.comm_flags: one authoritative flag string of flags
    libtpu 0.0.34 knows, carried in LIBTPU_INIT_ARGS (jaxlib's XLA_FLAGS
    parser aborts on a tpu-prefixed flag); apply is idempotent and skips
    runs pinned to another platform."""
    import os

    from jax._src import xla_bridge

    from ddlbench_tpu.distributed import apply_comm_flags, comm_flags

    flags = comm_flags()
    assert "--xla_tpu_enable_async_collective_fusion=true" in flags
    # not a libtpu 0.0.34 flag: fatal at backend init (PERF.md, PR 21)
    assert "windowed_einsum" not in flags
    assert not apply_comm_flags("cpu")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--marker=1")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    assert apply_comm_flags("tpu")
    once = os.environ["LIBTPU_INIT_ARGS"]
    assert "--marker=1" in once and "async_collective_fusion" in once
    assert "XLA_FLAGS" not in os.environ
    assert apply_comm_flags("tpu")  # idempotent
    assert os.environ["LIBTPU_INIT_ARGS"] == once


def test_comm_flags_refuse_after_backend_init(monkeypatch):
    """libtpu reads LIBTPU_INIT_ARGS once, at backend init: a call that
    would have to add a flag after that raises instead of returning with
    the overlap flags silently not in effect; with every flag already in
    the environment it is a no-op."""
    import jax

    from ddlbench_tpu.distributed import apply_comm_flags, comm_flags

    jax.devices()  # the test session's backend is up
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    with pytest.raises(RuntimeError, match="already initialized"):
        apply_comm_flags()
    monkeypatch.setenv("LIBTPU_INIT_ARGS", comm_flags())
    assert apply_comm_flags()
