"""The program names its own device work (ddlbench_tpu/telemetry/scopes.py).

Compiled here on the CPU backend, so nothing below is a device number: the
tests read the optimized HLO's ``op_name`` metadata, which is what a device
trace's instruction names are joined to (benchmarks/harness/scopes.py — the
yardstick's own parser is used here, so the program is held to what the
benchmark reads).
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import scopes as bench_scopes
from ddlbench_tpu import config as pcfg
from ddlbench_tpu.parallel import make_strategy
from ddlbench_tpu.telemetry import scopes

pytestmark = pytest.mark.telemetry

BATCH = 2
# instructions that move no data and take no device time
FREE = re.compile(r"= \S+ (parameter|constant|tuple|get-tuple-element|"
                  r"bitcast)\(")

CASES = {
    "resnet18": dict(benchmark="cifar10", arch="resnet18"),
    "transformer_fused": dict(benchmark="synthtext", arch="transformer_t",
                              fused_head_loss=True),
    "transformer_remat": dict(benchmark="synthtext", arch="transformer_t",
                              fused_head_loss=False, remat_layers=True),
}
# every kind the model's step should carry, on its instance scopes
WANT = {
    "resnet18": ({"conv", "bn", "pool", "fc", "loss"},
                 {"stem", "group1_block1", "group4_block2", "gap", "fc"}),
    "transformer_fused": ({"embed", "ln", "attn", "mlp", "loss"},
                          {"embed", "block1", "block2", "lm_head"}),
    "transformer_remat": ({"embed", "ln", "attn", "mlp", "head", "loss"},
                          {"embed", "block1", "block2", "lm_head"}),
}
# sha256 of the step's lowered StableHLO text (no locations, so no scope
# name is in it) AT THE PARENT OF THE PR THAT ADDED THE SCOPES (a52236e):
# the scopes changed no operation of the program. The text is made from
# shapes alone and does not depend on the machine.
LOWERED_AT_PARENT = {
    "resnet18":
        "e5bacbafcea419a7b1219547b40140a0bd51dd775b220c3311af88b28610b9a8",
    "transformer_fused":
        "58f3a4f43b220bd50b0284cd5c3f1fbc056889610efed46b316b7713d55f4508",
    "transformer_remat":
        "6430b106d852a3bb6b747f386e0ae1d5f2810c4ba2c55eca955c359d1babf2f7",
}


def _lowered(case):
    cfg = pcfg.RunConfig(strategy="single", compute_dtype="float32",
                         batch_size=BATCH, **CASES[case])
    cfg.validate()
    strategy = make_strategy(cfg)
    ds = pcfg.DATASETS[cfg.benchmark]
    state = jax.eval_shape(strategy.init, jax.random.key(0))
    if ds.kind == "tokens":
        x = y = jax.ShapeDtypeStruct((BATCH, *ds.image_size), jnp.int32)
    else:
        x = jax.ShapeDtypeStruct((BATCH, *ds.image_size), jnp.float32)
        y = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    return strategy.train_step.lower(
        state, x, y, jax.ShapeDtypeStruct((), jnp.float32))


def _entry(text):
    """{instruction: (line, op_name)} of the ENTRY computation's
    instructions that do work."""
    body = re.search(r"^ENTRY .*?\{\n(.*?)^\}", text, re.M | re.S).group(1)
    table = bench_scopes.scope_table(body)
    out = {}
    for line in body.splitlines():
        m = bench_scopes._INSTRUCTION.match(line)
        if m and not FREE.search(line):
            out[m.group(1)] = (line, table[m.group(1)])
    return out


@pytest.fixture(scope="module", params=sorted(CASES))
def compiled(request):
    lowered = _lowered(request.param)
    return request.param, lowered, lowered.compile().as_text()


def test_every_scope_the_model_should_produce_is_in_the_step(compiled):
    case, _, text = compiled
    kinds, instances = WANT[case]
    paths = set(bench_scopes.scope_table(text).values())
    tokens = set()
    for p in paths:
        for t in bench_scopes._tokens(p):
            tokens.add(re.sub(r"^(?:\w+\()+|\)+$", "", t))
    assert kinds | instances | {"optimizer"} <= tokens
    assert kinds <= set(scopes.KINDS)


def test_forward_backward_and_optimizer_instructions_exist(compiled):
    _, _, text = compiled
    phases = {}
    for name, (_, op) in _entry(text).items():
        phases.setdefault(bench_scopes.classify(op)[0], []).append(name)
    assert {"forward", "backward", "optimizer"} <= set(phases)


def test_what_the_program_traced_is_scoped(compiled):
    """The guard of the instrumentation, counted in instructions (on the
    chip it is device time: ``unscoped_device_share.train``). Of every
    instruction that carries an op_name at all — that comes from an
    operation the program traced — under 5% lie outside the program's
    scopes. Instructions the compiler made itself (layout copies, the CPU
    backend's rewritten convolutions) have no metadata to carry one: 10-16%
    of the instructions here, not the program's to name."""
    _, _, text = compiled
    named = unscoped = 0
    for line in text.splitlines():
        if bench_scopes._INSTRUCTION.match(line) and not FREE.search(line):
            op = bench_scopes._OP_NAME.search(line)
            if op is None:
                continue
            named += 1
            unscoped += bench_scopes.classify(op.group(1))[0] == "unscoped"
    assert named > 1000
    assert unscoped < 0.05 * named, (unscoped, named)


def test_remat_recomputation_counts_as_backward():
    text = _lowered("transformer_remat").compile().as_text()
    ops = [op for op in bench_scopes.scope_table(text).values()
           if "rematted_computation" in op]
    assert ops
    assert {bench_scopes.classify(op)[0] for op in ops} == {"backward"}


def test_the_scopes_changed_no_operation_of_the_step(compiled):
    case, lowered, _ = compiled
    got = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert got == LOWERED_AT_PARENT[case], (
        "the lowered step differs from the one recorded before the named "
        "scopes were added. If a later change to the model or to jax is "
        "what moved it, record the new hash here; a scope must never be "
        "what does.")


def test_vocabulary_is_what_the_benchmark_reads():
    assert scopes.KINDS == bench_scopes.KINDS
    assert scopes.PHASES == bench_scopes.STEP_PHASES
    assert scopes.VOCABULARY == scopes.KINDS + scopes.PHASES


# ---- dp: the gradient collectives -----------------------------------------


def _dp_step_text(devices, **kw):
    from tiny_models import tiny_dense_model

    from ddlbench_tpu.parallel.dp import DPStrategy

    cfg = pcfg.RunConfig(benchmark="mnist", strategy="dp", num_devices=8,
                         compute_dtype="float32", batch_size=2,
                         steps_per_epoch=2, momentum=0.5, **kw)
    cfg.validate()
    strat = DPStrategy(tiny_dense_model(), cfg)
    ts = strat.init(jax.random.key(0))
    x = jnp.zeros((cfg.global_batch(), *strat.model.in_shape), jnp.float32)
    y = jnp.zeros((cfg.global_batch(),), jnp.int32)
    step = getattr(strat, "_jit_train_step", strat.train_step)
    return step.lower(ts, *strat.shard_batch(x, y),
                      jnp.float32(0.1)).compile().as_text()


def _collectives(text):
    """op_names of the all-reduce / reduce-scatter instructions (XLA may
    combine several into one with a tuple result, under the first's name)."""
    return [bench_scopes._OP_NAME.search(line).group(1)
            for line in text.splitlines()
            if re.search(r" (all-reduce|reduce-scatter)(-start)?\(", line)
            and bench_scopes._INSTRUCTION.match(line)
            and bench_scopes._OP_NAME.search(line)]


@pytest.mark.parametrize("kw,buckets", [
    (dict(dp_shard_update=True), {0}),                      # monolithic
    (dict(comm_buckets=2), {0, 1}),                         # replicated
    (dict(dp_shard_update=True, allreduce_dtype="int8"), {0}),
])
def test_explicit_engines_put_gradient_collectives_under_grad_sync(
        devices, kw, buckets):
    text = _dp_step_text(devices, **kw)
    synced = [op for op in _collectives(text) if "grad_sync/" in op]
    assert synced
    assert {bench_scopes.classify(op)[0] for op in synced} == {"grad_sync"}
    # every bucket is named in the step (on the collective itself or, where
    # XLA combined the buckets' collectives, on the part it hands back)
    assert {int(b) for b in re.findall(r"grad_sync/bucket(\d+)/", text)} \
        == buckets


def test_gspmd_engine_has_no_collective_of_its_own_to_name(devices):
    """Under GSPMD the partitioner makes the gradient all-reduces out of
    the backward operations themselves: they carry that operation's path
    (a layer's backward), and dp.py has no call of its own to scope."""
    ops = _collectives(_dp_step_text(devices))
    assert ops
    grads = [op for op in ops if "transpose(jvp(" in op]
    assert grads and not any("grad_sync" in op for op in ops)
    assert {bench_scopes.classify(op)[0] for op in grads} == {"backward"}
