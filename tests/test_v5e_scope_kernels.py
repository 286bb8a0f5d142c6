"""The named scopes must not hide the Pallas kernels from the benchmark.

A trace event of a Pallas kernel is named after its HLO instruction, and the
instruction's name is built from the name stack it was traced under: inside
the program's scopes ``jvp_flash_attn_fwd_.12`` became ``flash_attn_fwd.12``.
``benchmarks/kernels/*.py`` find a kernel by a substring of that name, so a
kernel's name has to hold one of the six substrings wherever a scope is put
(the one-pass backwards ``flash_attn_dq_dkv`` and ``fused_xent_dh_dw`` hold
``flash_attn_dq`` and ``fused_xent_dh``).
Checked here by compiling the program's own attention sublayer and fused head
loss, forward and backward, inside their scopes, for a described v5e chip at
gpt2-small's widths (about two seconds each; nothing runs, no number is a
device's).

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library (the on-chip-measurement guide, section 2).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, T, D, H, V = 16, 1024, 768, 12, 50304


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The kernels' dispatch asks the default backend, which is the CPU
    here: steer it in the test (it reads nothing else a test could leave
    set), quiet the compile cache (an executable for a described chip cannot
    be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from ddlbench_tpu import distributed

    monkeypatch.setattr(distributed, "is_tpu_backend", lambda: True)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _mosaic_calls(fn, chip, *shapes):
    """{instruction name: op_name} of the Mosaic calls ``fn`` compiles to."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    out = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line).group(1)
            out[name] = re.search(r'op_name="([^"]*)"', line).group(1)
    return out


def _assert_kernels(found, instance, kind, kernels):
    for kernel, wrapper in kernels:
        names = [n for n in found if kernel in n]
        assert len(names) == 1, (kernel, sorted(found))
        assert (f"{wrapper.format(instance)}/{kind}/{kernel}/pallas_call"
                in found[names[0]])


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_flash_attention_keeps_its_names_inside_the_scopes(one_chip,
                                                           as_on_tpu, remat):
    from ddlbench_tpu.models.layers import apply_slice
    from ddlbench_tpu.models.transformer import transformer_block

    block = transformer_block("block3", D, H, attention_backend="auto")
    params = jax.eval_shape(lambda k: block.init(k, (T, D))[0],
                            jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)

    def loss(x, *flat):
        p = jax.tree.unflatten(tree, flat)
        y, _ = apply_slice([block], [p], [{}], x, True, remat)
        return y.astype(jnp.float32).sum()

    found = _mosaic_calls(
        jax.grad(loss), one_chip, ((B, T, D), jnp.bfloat16),
        *[(a.shape, jnp.bfloat16) for a in leaves])
    # the sublayer holds exactly the forward and the one-pass backward: a
    # rematerialized layer keeps the forward's o and lse (apply_slice) and
    # compiles to no second forward kernel
    assert sorted(n.split(".")[0] for n in found) == [
        "flash_attn_dq_dkv", "flash_attn_fwd"], sorted(found)
    _assert_kernels(found, "block3", "attn", (
        ("flash_attn_fwd", "jvp({})"),
        ("flash_attn_dq_dkv", "transpose(jvp({0}))/jvp({0})/checkpoint"
         if remat else "transpose(jvp({}))")))
    # benchmarks/kernels/flash_attn.py finds a kernel's events by substring:
    # each name has to hold one of its three, or its time leaves the roofline
    from benchmarks.kernels.flash_attn import EVENTS

    assert all(any(e in n for e in EVENTS) for n in found)


# (B, H, T, q/k width), v width: what the shape rule picks has to pass Mosaic
# (scoped VMEM above all: the resident kernels ask for what they hold and a
# quarter more) at a realistic batch*heads — a refusal found without a chip
# call
ONE_PASS = ["flash_attn_dq_dkv", "flash_attn_fwd"]
PAIR = ["flash_attn_dkv", "flash_attn_dq", "flash_attn_fwd"]


@pytest.mark.parametrize("shape,dv,kernels", [
    ((2, 12, 8192, 64), 64, ONE_PASS),
    ((4, 12, 4096, 64), 64, ONE_PASS),
    ((4, 32, 4096, 192), 128, ONE_PASS),   # kanana2-ep16-train
    ((1, 8, 16384, 192), 128, ONE_PASS),   # 58.1 MiB held: near the budget
    ((1, 12, 32768, 64), 64, ONE_PASS),    # 61.2 MiB
    ((1, 8, 32768, 192), 128, PAIR),       # resident forward (51.5 MiB)
    ((1, 4, 65536, 64), 64, PAIR),
])
def test_long_sequences_compile_with_the_kernels_the_rule_picks(
        one_chip, as_on_tpu, shape, dv, kernels):
    from ddlbench_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    found = _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                          *[(shape, jnp.bfloat16)] * 2,
                          (shape[:3] + (dv,), jnp.bfloat16))
    # outside the program's scopes the instruction is jvp_<name>_.N; the
    # op_name ends [transpose(]jvp(<name>)[)]/pallas_call
    assert sorted(re.search(r"(\w+)\)*/pallas_call$", op).group(1)
                  for op in found.values()) == kernels


def _head_kernels(one_chip, d, v):
    """The Mosaic calls of the program's LM head + fused loss, forward and
    backward, at width ``d`` and vocabulary ``v``."""
    from ddlbench_tpu.models.transformer import lm_head
    from ddlbench_tpu.parallel.common import fused_slice_loss_sums

    head = lm_head("lm_head", v)

    def loss(h, w, scale, bias, labels):
        p = {"ln_f": {"scale": scale, "bias": bias}, "head": w}
        return fused_slice_loss_sums([head], [p], [{}], h, labels, 0.0)[0]

    return _mosaic_calls(
        jax.grad(loss, argnums=(0, 1)), one_chip,
        ((B, T, d), jnp.bfloat16), ((d, v), jnp.bfloat16),
        ((d,), jnp.bfloat16), ((d,), jnp.bfloat16), ((B, T), jnp.int32))


def test_fused_xent_keeps_its_names_inside_the_scopes(one_chip, as_on_tpu):
    found = _head_kernels(one_chip, D, V)
    # the head holds exactly the forward and the one-pass backward
    assert sorted(n.split(".")[0] for n in found) == [
        "fused_xent_dh_dw", "fused_xent_fwd"], sorted(found)
    _assert_kernels(found, "lm_head", "loss", (
        ("fused_xent_fwd", "jvp({})"),
        ("fused_xent_dh_dw", "transpose(jvp({}))")))


@pytest.mark.parametrize("d,v", [(D, V), (2048, 16128)])  # the two LM cells
def test_every_head_kernel_is_one_event_of_the_roofline(one_chip, as_on_tpu,
                                                        d, v):
    """benchmarks/kernels/fused_xent.py sums the device time of the events
    whose name holds one of its EVENTS (read here, not edited) against the
    work of three products: a Mosaic call of the head that no entry finds
    would leave the roofline, one that two entries find would count twice
    (``fused_xent_dh_dw`` holds ``fused_xent_dh`` and not ``fused_xent_dw``)."""
    from benchmarks.kernels.fused_xent import EVENTS

    found = _head_kernels(one_chip, d, v)
    assert len(found) == 2
    for name in found:
        assert [e for e in EVENTS if e in name] in (
            ["fused_xent_fwd"], ["fused_xent_dh"]), name


@pytest.mark.parametrize("kernel,n,d,v,dtype", [
    ("fwd", 16384, 768, 50304, jnp.bfloat16),    # gpt2s-train
    ("bwd", 16384, 768, 50304, jnp.bfloat16),
    ("fwd", 16384, 2048, 16128, jnp.bfloat16),   # kanana2-ep16-train
    ("bwd", 16384, 2048, 16128, jnp.bfloat16),
    ("bwd", 16384, 512, 32768, jnp.float32),
    ("bwd", 16384, 4096, 32768, jnp.bfloat16),   # rows and columns shrunk
])
def test_fused_xent_accounting_against_mosaic(one_chip, as_on_tpu,
                                              monkeypatch, kernel, n, d, v,
                                              dtype):
    """What ``_held_vmem_bytes`` sums against what Mosaic itself needs,
    which shows only by refusal: the kernel compiles under a limit of the
    sum alone (the 25% the launch adds is margin, not need) and is refused
    under half of it (the sum is no more than twice Mosaic's number; the
    searched ratios, 1.03-1.22, are in the function's docstring)."""
    from ddlbench_tpu.ops import fused_xent as fx

    isz = jnp.dtype(dtype).itemsize
    held = fx._held_vmem_bytes(kernel, *fx._blocks(kernel, n, d, v, isz,
                                                   False), d, isz)
    shapes = [((n, d), dtype), ((d, v), dtype), ((n,), jnp.int32)]
    if kernel == "fwd":
        def call(h, w, labels):
            return fx._fxent_fwd_pallas(h, w, labels, 0.0, False)[0]
    else:
        shapes.append(((n,), jnp.float32))

        def call(h, w, labels, lse):
            one = jnp.float32(1.0)
            return fx._fxent_bwd_pallas(h, w, labels, lse, one, one, 0.0,
                                        False)

    def compile_under(limit):  # a new function each time: jit caches traces
        monkeypatch.setattr(fx, "vmem_limit_bytes", lambda _: limit)
        return _mosaic_calls(lambda *a: call(*a), one_chip, *shapes)

    assert len(compile_under(held)) == 1
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        compile_under(held // 2)


def test_the_latent_attention_block_compiles_with_named_kernels(one_chip,
                                                                as_on_tpu):
    """kanana-2-30b-a3b's expert block at its published widths and the
    cell's batch (4 x 4096): the flash forward resident and the backward
    the one-pass kernel at q/k 192, v 128, both under the block's
    ``attn``; the grouped products' Pallas kernels under ``experts``, with
    names the benchmark's moe_gmm.EVENTS find."""
    from ddlbench_tpu.models import kanana2
    from ddlbench_tpu.models.layers import apply_slice

    dims = kanana2.FAMILY["kanana2_30b_a3b"]
    block = kanana2.expert_block("block2", dims, (0, 8), "auto")
    params, state = jax.eval_shape(
        lambda k: block.init(k, (4096, dims.d_model))[:2], jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)

    def loss(x, *flat):
        p = jax.tree.unflatten(tree, flat)
        y, _ = apply_slice([block], [p], [state], x, True)
        return y.astype(jnp.float32).sum()

    found = _mosaic_calls(
        jax.grad(loss), one_chip, ((4, 4096, dims.d_model), jnp.bfloat16),
        *[(a.shape, jnp.float32 if a.ndim == 1 and a.shape[0] == 128
           or a.shape == (dims.d_model, 128) else jnp.bfloat16)
          for a in leaves])
    flash = sorted(n.split(".")[0] for n in found if "flash" in n)
    assert flash == ["flash_attn_dq_dkv", "flash_attn_fwd"]
    for n, op in found.items():
        if "flash" in n:
            assert "(block2)" in op and "/attn/" in op, op
    from benchmarks.kernels.moe_gmm import EVENTS

    products = [n for n in found if "flash" not in n]
    # two branches (the common buffer and the one with room for every
    # slot) x (3 forward + 3 for the rows' gradient; the weights' tgmm are
    # not asked for: the gradient here is the input's)
    assert len(products) == 2 * 6
    assert all(any(e in n for e in EVENTS) for n in products), products
    assert all("/route/" in found[n] and "/experts/" in found[n]
               for n in products)


def test_the_compressed_attention_block_compiles_with_named_kernels(
        one_chip, as_on_tpu):
    """zaya1-8b's hybrid block at its published widths and the cell's batch
    (2 x 8192): 8 query heads over 2 key/value heads in the flash kernels —
    the forward resident, the backward the one-pass kernel, both under the
    block's ``attn`` and neither under ``cca_mix`` —; the grouped products'
    Pallas kernels under ``route`` / ``experts`` at this family's tiling,
    with names the benchmark's moe_gmm.EVENTS find."""
    from ddlbench_tpu.models import zaya
    from ddlbench_tpu.models.layers import apply_slice

    dims = zaya.FAMILY["zaya1_8b"]
    block = zaya.hybrid_block("block2", dims, (0, 8), "auto", first=False,
                              last=False)
    T = 8192
    params, state = jax.eval_shape(
        lambda k: block.init(k, ((T, dims.d_model), (T, dims.router_dim)))[:2],
        jax.random.key(0))
    # the block reads its state (the selection bias): values, not shapes
    state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    router, rest = params["router"], {k: v for k, v in params.items()
                                      if k != "router"}
    leaves, tree = jax.tree.flatten(rest)
    r_leaves, r_tree = jax.tree.flatten(router)

    def loss(x, r, *flat):
        p = dict(jax.tree.unflatten(tree, flat[:len(leaves)]),
                 router=jax.tree.unflatten(r_tree, flat[len(leaves):]))
        (y, r2), _ = apply_slice([block], [p], [state], (x, r), True)
        return y.astype(jnp.float32).sum() + r2.sum()

    found = _mosaic_calls(
        jax.grad(loss), one_chip, ((2, T, dims.d_model), jnp.bfloat16),
        ((2, T, dims.router_dim), jnp.float32),
        *[(a.shape, jnp.bfloat16) for a in leaves],
        *[(a.shape, jnp.float32) for a in r_leaves])
    flash = sorted(n.split(".")[0] for n in found if "flash" in n)
    assert flash == ["flash_attn_dq_dkv", "flash_attn_fwd"]
    for n, op in found.items():
        if "flash" in n:
            assert "(block2)" in op and "/attn/" in op, op
            assert "cca_mix" not in op, op
    from benchmarks.kernels.moe_gmm import EVENTS

    products = [n for n in found if "flash" not in n]
    # the common buffer has room for every slot at 8 of 16 experts held
    # (2 x the balanced 8,192 = all 16,384): one branch, 3 forward + 3 for
    # the rows' gradient
    assert len(products) == 6
    assert all(any(e in n for e in EVENTS) for n in products), products
    assert all("/route/" in found[n] and "/experts/" in found[n]
               and "/router/" not in found[n] for n in products)


@pytest.mark.parametrize("windowed", [False, True], ids=["global", "window"])
def test_the_window_and_global_blocks_compile_with_named_kernels(
        one_chip, as_on_tpu, windowed):
    """smallthinker-21b-a3b's two kinds of layer at their published widths
    and the cell's batch (1 x 16384): 28 query heads over 4 key/value heads
    in the flash kernels — the forward resident, the backward the one-pass
    kernel, with a window of 4,096 or none —, under the block's ``attn`` and,
    on a window layer alone, under ``window``; the grouped products' Pallas
    kernels under ``route`` / ``experts``, not under ``router``."""
    from ddlbench_tpu.models import smallthinker
    from ddlbench_tpu.models.layers import apply_slice

    dims = smallthinker.FAMILY["smallthinker_21b_a3b"]
    block = smallthinker.block("block2", dims, (0, 16), windowed, "auto")
    T = 16384
    params, state = jax.eval_shape(
        lambda k: block.init(k, (T, dims.d_model))[:2], jax.random.key(0))
    state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    leaves, tree = jax.tree.flatten(params)
    router = [path[0].key == "router" for path, _ in
              jax.tree_util.tree_flatten_with_path(params)[0]]

    def loss(x, *flat):
        y, _ = apply_slice([block], [jax.tree.unflatten(tree, flat)],
                           [state], x, True)
        return y.astype(jnp.float32).sum()

    found = _mosaic_calls(
        jax.grad(loss), one_chip, ((1, T, dims.d_model), jnp.bfloat16),
        *[(a.shape, jnp.float32 if r else jnp.bfloat16)
          for a, r in zip(leaves, router)])
    flash = sorted(n.split(".")[0] for n in found if "flash" in n)
    assert flash == ["flash_attn_dq_dkv", "flash_attn_fwd"]
    for n, op in found.items():
        if "flash" in n:
            assert "(block2)" in op and "/attn/" in op, op
            assert ("/attn/window/" in op) == windowed, op
    from benchmarks.kernels.flash_attn_banded import EVENTS as FLASH
    from benchmarks.kernels.moe_gmm import EVENTS

    assert all(any(e in n for e in FLASH) for n in found if "flash" in n)
    products = [n for n in found if "flash" not in n]
    # the common buffer (2 x the balanced 24,576 slots) and the cond's
    # second one, each 3 forward + 3 for the rows' gradient
    assert len(products) == 2 * 6
    assert all(any(e in n for e in EVENTS) for n in products), products
    assert all("/route/" in found[n] and "/experts/" in found[n]
               and "/router/" not in found[n] for n in products)


def _called(line: str):
    """The computations an HLO instruction calls."""
    names = re.findall(r"(?:calls|to_apply|body|condition|true_computation|"
                       r"false_computation)=%?([\w.\-]+)", line)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
        names += re.findall(r"%?([\w.\-]+)", group)
    return names


def test_the_common_buffer_scatters_nothing_on_the_chip(one_chip, as_on_tpu):
    """kanana2_t-e4r1's whole train step as XLA:TPU compiles it, 8 x 64
    tokens, bf16, remat: 1,536 token-slots and a common buffer of 1,024
    rows, so each expert layer has the cond whose taken branch runs the
    slots past the buffer. Under ``route``, a scatter (XLA:TPU's slow way
    to sum rows) is left only in what that branch calls: the common
    buffer's rows move by gathers, forward and backward, and the branch
    keeps the gather and scatter-add around its grouped products."""
    from ddlbench_tpu import config as pcfg
    from ddlbench_tpu.models import dropless
    from ddlbench_tpu.parallel import make_strategy

    assert dropless.buffer_rows(8 * 64 * 3, 16, 4) == 1024
    name = "kanana2-v5e-64"
    pcfg.DATASETS[name] = pcfg.DatasetSpec(name, (64,), 128, 1 << 20, 1 << 10,
                                           kind="tokens")
    try:
        cfg = pcfg.RunConfig(benchmark=name, arch="kanana2_t-e4r1",
                             strategy="single", num_devices=1, batch_size=8,
                             compute_dtype="bfloat16", remat_layers=True,
                             # the fused head's blocks want a width of 128
                             fused_head_loss=False, optimizer="adam",
                             lr=1e-3)
        cfg.validate()
        s = make_strategy(cfg)
        on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=one_chip)
        state = jax.tree.map(on_chip, jax.eval_shape(s.init,
                                                     jax.random.key(0)))
        x = on_chip(jax.ShapeDtypeStruct((8, 64), jnp.int32))
        text = s.train_step.lower(
            state, x, x, on_chip(jax.ShapeDtypeStruct((), jnp.float32))
        ).compile().as_text()
    finally:
        del pcfg.DATASETS[name]
    comps, current = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            current = head.group(1)
            comps[current] = []
        elif current is not None:
            comps[current].append(line)
    # everything the conditionals' branches reach
    todo = [c for lines in comps.values() for line in lines
            if " conditional(" in line for c in _called(line)]
    assert todo, "the step has no conditional"
    branch = set()
    while todo:
        c = todo.pop()
        if c not in branch:
            branch.add(c)
            todo += [d for line in comps.get(c, ()) for d in _called(line)]

    def route(kind, inside):
        return [line for c, lines in comps.items() if (c in branch) == inside
                for line in lines if f" {kind}(" in line and "/route/" in line]

    assert route("scatter", True), "the branch keeps its scatter-add"
    assert not route("scatter", False), route("scatter", False)[:3]
