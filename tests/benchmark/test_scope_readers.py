"""The scope readers: the parse of HLO text, the classification of an
op_name, the join onto a reduced trace, and the two facts about jax's compile
cache that the join rests on (``harness/scopes.py``). CPU only: the device
seconds below are synthetic or were recorded on the chip."""

import contextlib
import gzip
import json
import os
import re
import shutil
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run_cell
from benchmarks.harness import manifest, scopes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmarks", "data")

HLO = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.7 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/transpose(jvp(group1_block3))/bn/mul" stack_frame_id=3}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> (f32[8], f32[8]) {
  %Arg_0.1 = f32[8]{0:T(1024)} parameter(0), metadata={op_name="ts.params[0][\\'w\\']"}
  %multiply_reduce_fusion.2 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(group1_block3))/bn/reduce_sum" stack_frame_id=3}
  %flash_attn_fwd.12 = (bf16[16,12,1024,64]{3,2,1,0}, f32[16,12,1024]{2,1,0}) custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(block7)/attn/flash_attn_fwd/pallas_call" stack_frame_id=9}
  %copy-start.3 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%Arg_0.1)
  copy-done.3 = f32[8]{0:S(1)} copy-done(%copy-start.3)
  ROOT %tuple.4 = (f32[8]{0}, f32[8]{0}) tuple(%multiply_reduce_fusion.2, %copy-done.3)
}
'''


def test_scope_table_reads_every_computation():
    table = scopes.scope_table(HLO)
    assert table["multiply_reduce_fusion.2"] == (
        "jit(train_step)/transpose(jvp(group1_block3))/bn/reduce_sum")
    assert table["multiply.7"].endswith("/bn/mul")  # inside the fusion
    assert table["flash_attn_fwd.12"].endswith("flash_attn_fwd/pallas_call")
    assert table["copy-start.3"] == "" and table["copy-done.3"] == ""
    assert table["Arg_0.1"] == "ts.params[0][\\'w\\']"
    assert "main.9" not in table and "fused_computation.1" not in table


@pytest.mark.parametrize("op_name,want", [
    # wrappers: jvp and transpose unwrap to the scope they wrap
    ("jit(train_step)/jvp(stem)/conv/conv_general_dilated",
     ("forward", "conv")),
    ("jit(train_step)/transpose(jvp(group1_block3))/bn/reduce_sum",
     ("backward", "bn")),
    ("jit(train_step)/optimizer/mul", ("optimizer", None)),
    # a ReLU or a residual add sits under its instance, in no kind
    ("jit(train_step)/jvp(group1_block1)/jit(relu)/max", ("forward", None)),
    ("jit(train_step)/transpose(jvp(group2_block1))/select_n",
     ("backward", None)),
    # remat: the recomputed forward runs in the backward pass
    ("jit(train_step)/transpose(jvp(block1))/jvp(block1)/checkpoint/"
     "rematted_computation/ln/mul", ("backward", "ln")),
    # the innermost kind wins: the head's LayerNorm inside the fused loss
    ("jit(train_step)/jvp(lm_head)/loss/ln/rsqrt", ("forward", "ln")),
    ("jit(train_step)/transpose(jvp(lm_head))/loss/fused_xent_dw/"
     "pallas_call", ("backward", "loss")),
    ("jit(train_step)/jvp(block7)/attn/flash_attn_fwd/pallas_call",
     ("forward", "attn")),
    # an einsum's equation is a path token with commas and an arrow
    ("jit(train_step)/transpose(jvp(block2))/attn/bhqd,bhkd->bhqk/"
     "dot_general", ("backward", "attn")),
    # control flow of the fused loss's row chunks
    ("jit(train_step)/jvp(lm_head)/loss/while/body/closed_call/dot_general",
     ("forward", "loss")),
    # step phases: a dp engine's collective, per bucket
    ("jit(step)/shard_map/grad_sync/bucket3/psum_invariant",
     ("grad_sync", None)),
    # jit(f) names a function, not a scope: a function called `loss` or
    # `conv` is no kind
    ("jit(train_step)/jit(loss)/add", ("unscoped", None)),
    # differentiated outside every scope (the parameters' cast to bfloat16;
    # all of a program that has no scopes): a wrapper around nothing marks
    # no phase
    ("jit(train_step)/jvp()/convert_element_type", ("unscoped", None)),
    ("jit(train_step)/transpose(jvp())/dot_general", ("unscoped", None)),
    # nothing of the program's: XLA's own names, arguments, no metadata
    ("jit(train_step)/max", ("unscoped", None)),
    ("ts.params[3][\\'conv1\\']", ("unscoped", None)),
    ("reduce_sum", ("unscoped", None)),
    ("", ("unscoped", None)),
    # merged instructions list several paths: the first counts
    ("jit(f)/transpose(jvp(fc))/fc/mul;jit(f)/optimizer/sub",
     ("backward", "fc")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


# ---- the readers on a synthetic trace --------------------------------------

TABLE = {
    "fusion.1": "jit(train_step)/jvp(stem)/conv/conv_general_dilated",
    "fusion.2": "jit(train_step)/transpose(jvp(stem))/conv/"
                "conv_general_dilated",
    "multiply_reduce_fusion": "jit(train_step)/transpose(jvp(stem))/bn/"
                              "reduce_sum",
    "add_fusion": "jit(train_step)/jvp(group1_block1)/add",
    "subtract_fusion": "jit(train_step)/optimizer/sub",
    "copy.1": "",
    "never_ran": "jit(train_step)/jvp(fc)/fc/dot_general",
}
OP_SECONDS = {"fusion.1": 0.030, "fusion.2": 0.050,
              "multiply_reduce_fusion": 0.010, "add_fusion": 0.004,
              "subtract_fusion": 0.002, "copy.1": 0.003,
              "iota.9": 0.001}  # another program's: not in the table


def fake_context(table=TABLE, op_seconds=OP_SECONDS, steps=2):
    hlo = "".join(f'  %{n} = f32[] add(), metadata={{op_name="{op}"}}\n'
                  if op else f"  %{n} = f32[] copy()\n"
                  for n, op in table.items())
    return types.SimpleNamespace(
        _step_hlo=hlo, counters={"steps": steps},
        trace_summary=types.SimpleNamespace(op_seconds=op_seconds))


def reader(name):
    return manifest.load_module(os.path.join(
        ROOT, "benchmarks", "metrics", "readers", f"{name}.py"))


def test_scope_ms_by_phase_and_by_kind():
    read = reader("scope_ms").read
    ctx = fake_context()
    assert read(ctx, phase="forward") == pytest.approx(1000 * 0.034 / 2)
    assert read(ctx, phase="backward") == pytest.approx(1000 * 0.060 / 2)
    assert read(ctx, phase="optimizer") == pytest.approx(1000 * 0.002 / 2)
    assert read(ctx, kinds=["conv"]) == pytest.approx(1000 * 0.080 / 2)
    assert read(ctx, kinds=["bn", "ln"]) == pytest.approx(1000 * 0.010 / 2)
    # the phases and the unscoped rest sum to all the device time
    times = scopes.device_time(ctx)
    assert sum(v[0] for v in times.by_phase.values()) == pytest.approx(
        sum(OP_SECONDS.values()))


def test_nothing_matched_returns_nothing():
    read = reader("scope_ms").read
    ctx = fake_context()
    assert read(ctx, kinds=["attn"]) is None        # a kind the model lacks
    assert read(ctx, kinds=["fc"]) is None          # in the table, never ran
    assert read(ctx, phase="grad_sync") is None


def test_unscoped_share_counts_unnamed_and_unknown_instructions():
    value = reader("unscoped_share").read(fake_context())
    assert value == pytest.approx(100 * (0.003 + 0.001) / 0.100)


def test_a_program_without_scopes_reads_nothing(capsys):
    """What the readers meet on the parent of the PR that added the scopes
    (and on an executable served from a stale cache): op_names, none of the
    vocabulary. Every scope metric is left out; nothing raises."""
    plain = {n: re.sub(r"(conv|bn|fc|optimizer)/", "",
                       re.sub(r"jvp\(\w+\)", "jvp()", op))
             for n, op in TABLE.items()}
    assert plain["fusion.2"] == (
        "jit(train_step)/transpose(jvp())/conv_general_dilated")
    # jax's own scopes (an einsum names its equation) make no program scoped
    plain["fusion.1"] = "jit(train_step)/jvp(bhqd,bhkd->bhqk)/dot_general"
    assert scopes.classify(plain["fusion.1"]) == ("forward", None)
    ctx = fake_context(table=plain)
    assert reader("scope_ms").read(ctx, phase="forward") is None
    assert reader("scope_ms").read(ctx, kinds=["conv"]) is None
    assert reader("unscoped_share").read(ctx) is None
    assert "none of the program's named scopes" in capsys.readouterr().err


def test_manifest_holds_the_nine_scope_metrics():
    """The rule, not the roster: the cells each metric was accepted on are
    AMONG its roster (a later cell joins by gaining a name there), and a
    kind a model lacks is not printed for it."""
    man = manifest.Manifest()
    assert manifest.check(man) == []
    both = {"resnet50-single", "gpt2s-train"}
    accepted = {
        "step_forward_ms.train": both, "step_backward_ms.train": both,
        "step_optimizer_ms.train": both, "norm_ms.train": both,
        "head_loss_ms.train": both, "unscoped_device_share.train": both,
        "conv_ms.train": {"resnet50-single"},
        "attn_ms.train": {"gpt2s-train"}, "mlp_ms.train": {"gpt2s-train"}}
    got = {m["name"]: set(m["workloads"]) for m in man.index["per_layer"]
           if m["source"] == "program_span"}
    assert set(accepted) <= set(got)
    for name, cells in accepted.items():
        assert cells <= got[name], name
        spec = man.metric_file(name)
        assert spec["moves"] == "train_samples_per_s_per_chip"
        assert spec["better"] == "lower"
        assert callable(man.reader(spec).read)
    for name in got:  # every scope metric reads the one reader
        assert man.metric_file(name)["reader"] in ("scope_ms",
                                                   "unscoped_share"), name
    printed = {m["name"] for m in man.per_layer_of("gpt2s-train")}
    assert "conv_ms.train" not in printed and "attn_ms.train" in printed


# ---- the vocabulary is data -------------------------------------------------


def test_the_vocabulary_is_what_the_scope_kinds_files_list(tmp_path):
    """The kinds are the ``kinds`` lists of the ``scope_kinds*.json`` files
    beside the metric files, in the order of the files' names; the committed
    file lists the ten kinds the accepted cells were read with, so what
    counts as scoped (``unscoped_device_share.train``) is what it was, and
    every kind a metric file reads is one of them."""
    ten = ("conv", "bn", "pool", "fc", "embed", "ln", "attn", "mlp", "head",
           "loss")
    assert scopes.KINDS == ten == scopes.vocabulary()
    man = manifest.Manifest()
    read = {k for m in man.index["per_layer"]
            for k in man.metric_file(m["name"]).get("args", {}).get(
                "kinds", ())}
    assert read == set(ten) - {"pool", "embed"}  # read by no metric yet
    # a later PR's file comes after, adds what is new, repeats nothing
    shutil.copy(os.path.join(scopes.METRICS_DIR, "scope_kinds.json"),
                tmp_path / "scope_kinds.json")
    (tmp_path / "scope_kinds.moe.json").write_text(json.dumps(
        {"kinds": ["router", "mlp", "expert"]}))
    (tmp_path / "router_ms.train.json").write_text(json.dumps(
        {"name": "router_ms.train", "reader": "scope_ms",
         "args": {"kinds": ["gate"]}}))  # a metric file makes no kind
    assert scopes.vocabulary(str(tmp_path)) == ten + ("router", "expert")
    op = "jit(train_step)/jvp(block3)/mlp/router/dot_general"
    assert scopes.classify(op) == ("forward", "mlp")
    times = scopes.reduce_scopes({"fusion.1": 0.5}, {"fusion.1": op})
    assert times.by_kind == {"mlp": [0.5, 1]}


def test_the_benchmark_s_vocabulary_is_the_program_s():
    """The benchmark's copy against the program's own tuple, order and all
    (``tests/test_scopes.py`` holds the same from the program's side)."""
    from ddlbench_tpu.telemetry import scopes as program

    assert scopes.KINDS == program.KINDS
    assert isinstance(scopes.KINDS, tuple)
    assert scopes.STEP_PHASES == program.PHASES


# ---- jax's compile cache: the two halves of the stale-executable hazard ----


@contextlib.contextmanager
def program_cache(directory):
    """The program's compile-cache settings over a directory of the test's
    own, every program kept as ``run_cell.cached_jax`` keeps them, with
    jax's cache events counted; everything restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from ddlbench_tpu.distributed import enable_compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    events = []
    live = [True]

    def listen(event, **_):
        if live[0] and event.startswith("/jax/compilation_cache/"):
            events.append(event.rsplit("/", 1)[1])

    jax.monitoring.register_event_listener(listen)
    try:
        enable_compilation_cache()
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(directory))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        yield events
    finally:
        live[0] = False
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def entries(directory):
    return sorted(f for f in os.listdir(directory) if f.endswith("-cache"))


def test_no_scope_less_entry_stands_in_for_the_readers_compile(tmp_path):
    """Half (a). jax strips metadata from its cache key, so the executable
    of a program compiled before a scope was added is served, scope-less,
    to the program that has it: the hazard, pinned first. ``compile_fresh``
    (what ``step_hlo`` compiles with) is not served it: its text has the
    scope, and it leaves the cache as it found it."""
    def plain(w, x):
        return jnp.tanh(x @ w).sum()

    def scoped():  # traced anew each time, as a freshly built strategy is
        def f(w, x):
            with jax.named_scope("block1"), jax.named_scope("fc"):
                return jnp.tanh(x @ w).sum()
        return f

    # the same name and the same operations: only the metadata differs
    plain.__name__ = plain.__qualname__ = "f"
    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    with program_cache(tmp_path) as events:
        jax.jit(plain).lower(w, x).compile()
        assert events.count("cache_misses") == 1
        kept = entries(tmp_path)
        del events[:]
        stale = jax.jit(scoped()).lower(w, x).compile().as_text()
        assert events.count("cache_hits") == 1
        assert "block1/fc" not in stale
        del events[:]
        text = scopes.compile_fresh(jax.jit(scoped()).lower(w, x))
        assert events.count("compile_requests_use_cache") == 1
        assert events.count("cache_hits") == 0
    assert "block1/fc/dot_general" in text
    assert entries(tmp_path) == kept
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False


def test_step_hlo_compiles_once_a_program_and_costs_the_runs_nothing(
        tmp_path):
    """Half (b): the first ``step_hlo`` of a program compiles the step and
    keeps the TEXT beside the compile cache; the next one (another process:
    a freshly built strategy, another call stack) reads that file and asks
    for no compile. The entries the window's own call wrote, which the
    untraced runs share with the parent, are neither read nor added to."""
    from test_rehearsal import context

    from benchmarks.harness import train_driver
    from benchmarks.harness.traffic import SeededBatches

    rc = context("train-tiny")
    with program_cache(tmp_path) as events:
        cfg, strategy = train_driver.build(rc.config, rc.traffic)
        ds = rc.config["dataset"]
        data = SeededBatches(rc.seed, ds["kind"], tuple(ds["sample_shape"]),
                             rc.config.get("vocab_size", ds["num_classes"]),
                             cfg.global_batch())
        ts, _, _ = train_driver.seeded_state(strategy, rc.seed,
                                             rc.config["weights"])
        batch = strategy.shard_batch(*data.batch(0, 0))
        ts, m = strategy.train_step(
            ts, *batch, jnp.float32(rc.traffic["run_config"]["lr"]))
        jax.block_until_ready(m["loss"])
        kept = entries(tmp_path)
        assert any(f.startswith("jit_train_step-") for f in kept)
        del events[:], ts, strategy
        text = scopes.step_hlo(rc)
        assert events.count("cache_hits") == 0, events
        assert entries(tmp_path) == kept
        (kept_text,) = [f for f in os.listdir(tmp_path)
                        if f.startswith("step_hlo-")]
        assert scopes.step_hlo(rc) is text  # memoised: nine metrics, one
        del events[:], rc._step_hlo
        again = (lambda: scopes.step_hlo(rc))()  # one frame deeper
        assert "compile_requests_use_cache" not in events, events
    assert again == text
    assert entries(tmp_path) == kept
    kinds = {scopes.classify(op)[1] for op in scopes.scope_table(text).values()}
    assert {"embed", "ln", "attn", "mlp", "loss"} <= kinds


# ---- the pair recorded on the chip -----------------------------------------

KERNELS = ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv",
           "fused_xent_fwd", "fused_xent_dh", "fused_xent_dw")


@pytest.fixture(scope="module")
def recorded():
    """Two steps of THIS program's gpt2s-train on the chip (PR 24,
    ``benchmarks/keep_scopes.py``): the event list and the scope table of
    the same compile, which no later compile can change."""
    def load(name):
        with gzip.open(os.path.join(DATA, name), "rt") as f:
            return json.load(f)

    events = load("gpt2s-train.2steps.scoped.events.json.gz")
    table = load("gpt2s-train.2steps.scope_table.json.gz")
    return trace.reduce(events), table


def test_recorded_pair_is_scoped_and_sums_to_the_busy_time(recorded):
    summary, table = recorded
    times = scopes.reduce_scopes(summary.op_seconds, table)
    unscoped_s = sum(s for _, s in times.unscoped)
    assert unscoped_s < 0.05 * times.total_s
    # forward + backward + optimizer + unscoped is all the device did, and
    # on one core nothing overlaps: it is the busy time
    assert set(times.by_phase) == {"forward", "backward", "optimizer",
                                   "unscoped"}
    assert sum(v[0] for v in times.by_phase.values()) == pytest.approx(
        summary.busy_s, rel=1e-6)
    fwd, bwd = times.by_phase["forward"][0], times.by_phase["backward"][0]
    assert 1.7 < bwd / fwd < 2.4
    assert times.by_phase["optimizer"][0] < 0.05 * times.total_s


def test_recorded_pair_puts_the_kernels_in_their_kinds(recorded):
    summary, table = recorded
    ops = summary.op_seconds
    for kernel in KERNELS:
        names = [n for n in ops if kernel in n]
        assert names, kernel
        kind = "attn" if kernel.startswith("flash") else "loss"
        phase = "forward" if kernel.endswith("fwd") else "backward"
        assert {scopes.classify(table[n]) for n in names} == {(phase, kind)}
    times = scopes.reduce_scopes(ops, table)
    xent = summary.kernel_seconds(["fused_xent"])
    flash = summary.kernel_seconds(["flash_attn"])
    head_loss, _ = times.seconds(kinds=["fc", "head", "loss"])
    attn, _ = times.seconds(kinds=["attn"])
    steps = 2
    assert xent <= head_loss <= xent + 0.005 * steps
    assert attn >= flash
    assert times.seconds(kinds=["mlp"])[0] > 0
    assert times.seconds(kinds=["conv"]) == (0, 0)


def test_keep_scopes_cuts_whole_steps(recorded):
    """``keep_scopes.cut`` on the recorded two steps asked for one: half the
    events, a window that brackets exactly them."""
    from benchmarks import keep_scopes

    with gzip.open(os.path.join(
            DATA, "gpt2s-train.2steps.scoped.events.json.gz"), "rt") as f:
        events = json.load(f)
    _, table = recorded
    (evs,) = events["devices"].values()
    one = keep_scopes.cut(events, table, steps_traced=2, steps=1)
    (kept,) = one["devices"].values()
    step_events = [e for e in evs if e[0] in table]
    assert len([e for e in kept if e[0] in table]) * 2 == len(step_events)
    assert one["host"][0][0] == trace.WINDOW_SPAN
    assert one["host"][0][2] == pytest.approx(max(e[2] for e in kept))
    assert trace.reduce(one).busy_s == pytest.approx(
        trace.reduce(events).busy_s / 2, rel=0.02)


# ---- across chips ------------------------------------------------------------


def test_step_hlo_across_chips_takes_the_window_s_own_shardings(tmp_path):
    """A two-chip data-parallel cell: the driver keeps how the window's own
    arguments lay over the chips, ``step_hlo`` partitions the step it lowers
    by them, and the partitioner's all-reduces carry the path of the ops
    they were made from (GSPMD opens no ``grad_sync`` scope)."""
    from test_rehearsal import context

    from benchmarks.harness import train_driver

    rc = context("train-tiny-dp2", chips=2)
    with program_cache(tmp_path):
        train_driver.run(rc)
        state, x, y = rc.step_shardings
        assert len(x.device_set) == 2 and not x.is_fully_replicated
        assert all(s.is_fully_replicated for s in jax.tree.leaves(state))
        text = scopes.step_hlo(rc)
    table = scopes.scope_table(text)
    reduces = [op for name, op in table.items()
               if name.startswith("all-reduce")]
    assert reduces, "the partitioned step holds no all-reduce"
    phases = {scopes.classify(op)[0] for op in reduces}
    assert "backward" in phases and "grad_sync" not in phases
    kinds = {scopes.classify(op)[1] for op in table.values()}
    assert {"embed", "ln", "attn", "mlp", "loss"} <= kinds
    # a trace of that step: nothing reads a grad_sync phase, the rest reads
    ops = {name: 0.001 for name in table}
    ctx = types.SimpleNamespace(
        _step_hlo=text, counters={"steps": 1},
        trace_summary=types.SimpleNamespace(op_seconds=ops))
    assert reader("scope_ms").read(ctx, phase="grad_sync") is None
    assert reader("scope_ms").read(ctx, phase="backward") > 0
    # an explicit engine names its collectives, and the same reader reads
    # them (the metric comes with the cell that runs such an engine)
    ctx = fake_context(table=dict(TABLE, **{
        "all-reduce.3": "jit(step)/shard_map/grad_sync/bucket0/psum"}),
        op_seconds=dict(OP_SECONDS, **{"all-reduce.3": 0.006}))
    assert reader("scope_ms").read(ctx, phase="grad_sync") == pytest.approx(
        3.0)
