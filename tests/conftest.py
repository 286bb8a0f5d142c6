"""Test harness: force an 8-virtual-device CPU backend.

The reference has no pytest/CI harness at all (SURVEY.md §4); its only
"fake backend" is launching gloo ranks as localhost processes
(pipedream-fork/runtime/tests/communication/README.md:3-16). Here every
distributed strategy is testable in-process on a virtual CPU mesh.

The platform is pinned through jax.config before the first backend touch, so
the suite runs on the virtual CPU mesh whether or not JAX_PLATFORMS is set and
whether or not the machine has an accelerator.
"""

import os

# The suite's persistent compile cache lives OUTSIDE the checkout (where it
# was before PR 21 moved the program's default to <repo>/.jax_cache), so a
# fresh checkout on a machine that has run the suite before does not
# recompile ~250 programs (~7 of tier-1's minutes). Set before jax is
# imported; tests/test_chip_bringup.py checks the program's own default
# with the variable removed.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/ddlbench_xla_cache")

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from ddlbench_tpu.models import kanana2, smallthinker, zaya  # noqa: E402

# The test size of the kanana2 family (tests/test_kanana2.py, the rehearsal
# configuration under tests/benchmark/data/kanana2): every code path, 1-core
# CPU compiles. The program's own table holds published sizes only.
kanana2.FAMILY["kanana2_t"] = kanana2.Dims(
    d_model=64, n_heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_latent=32,
    dense_ff=96, expert_ff=24, n_experts=16, n_shared=2, top_k=3,
    route_scale=2.448, n_layers=3)

# The test size of the zaya family (tests/test_zaya.py, the rehearsal
# configuration under tests/benchmark/data/zaya): 4 query heads over 2
# key/value heads, both convolutions, the carried router state, 8 experts.
zaya.FAMILY["zaya_t"] = zaya.Dims(
    d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, conv_taps=(2, 2),
    rotary=8, router_dim=32, expert_ff=48, n_experts=8, n_layers=3)

# The test size of the smallthinker family (tests/test_smallthinker.py, the
# rehearsal configuration under tests/benchmark/data/smallthinker): two
# periods of the layout (global, window, window, window), a window shorter
# than the tests' 64 tokens, 4 query heads over 2 key/value heads, 8 experts,
# 3 a token.
smallthinker.FAMILY["smallthinker_t"] = smallthinker.Dims(
    d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, expert_ff=24,
    n_experts=8, top_k=3, window=24, layout=(0, 1, 1, 1) * 2)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (compile-heavy integration tests; "
             "the default set is the <5-min commit gate)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow (use --runslow for the full suite)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def train_factory():
    """Session-shared TRAIN-strategy cache (tier-1 budget, ROADMAP item 5
    — the training-side sibling of ``serve_factory``): strategies carry
    their compiled train/eval steps, so two tests (or two phases of one
    resume test) that need the same (model, config) engine should reuse
    ONE instance instead of paying the trace+compile again. Strategies
    are stateless between runs — ``init()`` returns a fresh TrainState —
    which is what makes the sharing sound.

    Call it with a hashable key and a zero-arg builder::

        strat = train_factory(("dpshard", "dense", cfg),
                              lambda: DPStrategy(_dense_model(), cfg))

    Frozen RunConfigs are hashable and belong in the key: anything that
    changes the compiled program must change the key.
    """
    cache = {}

    def make(key, builder):
        if key not in cache:
            cache[key] = builder()
        return cache[key]

    make.cache = cache
    return make


@pytest.fixture(scope="session")
def serve_factory():
    """Session-shared serving fixture (tier-1 budget, ROADMAP item 5):
    ONE tiny LM plus a jitted-callable cache keyed by (page, sampling,
    kv_dtype, speculative, tp) — the things the engine's traced programs
    close over — so
    every serve test that builds an engine at the same page size reuses
    the compiled decode/prefill/COW programs instead of re-tracing them
    per test (``shared_fns``, the same mechanism servebench's policy rows
    already use).

    Call it with a ServeConfig to get a ServeEngine; pass ``server=True``
    for a ReplicatedServer (make_server). ``.model``/``.params``/
    ``.state`` expose the underlying LM for standalone-decode oracles.
    """
    from tiny_models import tiny_transformer

    from ddlbench_tpu.models.layers import init_model

    model = tiny_transformer()
    params, state, _ = init_model(model, jax.random.key(0))
    fns = {}

    def make(cfg, *, server=False, **kw):
        from ddlbench_tpu.serve.engine import ServeEngine, make_server

        # kv_dtype changes the pool layout every program closes over, the
        # speculative draft width K sets the verify program's span shape,
        # and tp rebuilds every program as a shard_map over the model
        # mesh — all belong in the shared-callable key
        key = (cfg.page, cfg.temperature > 0.0, cfg.kv_dtype,
               cfg.speculative, cfg.tp)
        shared = fns.get(key)
        if server:
            out = make_server(model, params, state, cfg,
                              shared_fns=shared, **kw)
            fns.setdefault(key, out.engines[0].jit_fns())
        else:
            out = ServeEngine(model, params, state, cfg,
                              shared_fns=shared, **kw)
            fns.setdefault(key, out.jit_fns())
        return out

    make.model, make.params, make.state = model, params, state
    return make
