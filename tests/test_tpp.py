"""Composed tensor x pipeline parallelism (parallel/tpp.py).

Oracle: the plain gpipe pipeline on the same model/init/batch. Megatron
slicing is exact math — local head groups + column/row-parallel MLP with a
psum — so the composed engine must reproduce the unsliced pipeline's loss
trajectory to float tolerance, including the shared-leaf (LN/bias/embed)
gradient all-reduce over the 'model' axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.transformer import (_VARIANTS, build_transformer,
                                             tp_split_layer_params)


def _merge(shard, repl):
    return {**repl, **shard}


def test_tp_split_reconstructs_block_params():
    """Shard slices re-concatenate to the full block matrices, with wqkv's
    q|k|v block layout preserved."""
    from ddlbench_tpu.models.layers import init_model

    _VARIANTS.setdefault("transformer_t", dict(d_model=32, n_layers=2,
                                               n_heads=4))
    model = build_transformer("transformer_t", (16,), 64)
    params, _, _ = init_model(model, jax.random.key(0))
    block = params[1]  # layer 0 is the embedding
    n = 2
    shards, repl = tp_split_layer_params(block, n)
    assert set(repl) == {"ln1", "ln2", "b2"}
    d = block["wo"].shape[1]
    dl = d // n
    # wo/w2 rows and w1/b1 columns concatenate back exactly
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s["wo"]) for s in shards], 0),
        np.asarray(block["wo"]))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s["w1"]) for s in shards], 1),
        np.asarray(block["w1"]))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s["b1"]) for s in shards], 0),
        np.asarray(block["b1"]))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s["w2"]) for s in shards], 0),
        np.asarray(block["w2"]))
    # wqkv: shard s's columns are the s-th head-group slice of EACH of q|k|v
    full = np.asarray(block["wqkv"]).reshape(d, 3, d)
    for s, sh in enumerate(shards):
        np.testing.assert_array_equal(
            np.asarray(sh["wqkv"]).reshape(d, 3, dl),
            full[:, :, s * dl:(s + 1) * dl])


def test_tp_split_replicates_non_block_layers():
    embed_p = {"tok": jnp.ones((8, 4)), "pos": jnp.ones((16, 4))}
    shards, repl = tp_split_layer_params(embed_p, 4)
    assert all(s == {} for s in shards)
    assert repl is embed_p


def test_tp_size_config_validation():
    cfg = RunConfig(strategy="gpipe", benchmark="synthtext",
                    arch="transformer_t", num_devices=4, tp_size=2,
                    num_stages=2, micro_batch_size=2, num_microbatches=2)
    cfg.validate()
    with pytest.raises(ValueError, match="tp_size"):
        RunConfig(strategy="pipedream", benchmark="synthtext",
                  arch="transformer_t", num_devices=4, tp_size=2,
                  num_stages=2).validate()
    with pytest.raises(ValueError, match="token or seq2seq"):
        RunConfig(strategy="gpipe", benchmark="mnist", arch="resnet18",
                  num_devices=4, tp_size=2, num_stages=2).validate()
    with pytest.raises(ValueError, match="must equal"):
        RunConfig(strategy="gpipe", benchmark="synthtext",
                  arch="transformer_t", num_devices=4, tp_size=2,
                  num_stages=4).validate()


@pytest.mark.pipesched
def test_tpp_matches_gpipe_loss_trajectory(monkeypatch):
    """2 stages x 2 TP shards == 2-stage plain gpipe, same init/batches:
    the loss trajectories must agree to f32 tolerance over several steps
    (this exercises the sliced-matmul math, the row-parallel psums, AND the
    replicated-leaf gradient all-reduce — a missing LN-grad psum diverges
    the trajectory within a step or two).

    Tier-1 since ISSUE 7 (no slow mark): now that tpp rides the schedule
    runtime's timetable the integration must stay green in the commit
    gate, not hidden behind --runslow. Runs on the suite's shared
    TINY_LM shapes (T=32, vocab 64): the sliced-matmul/psum math this
    pins is shape-independent, and the synthtext T=1024 variant cost
    ~95 s of the tier-1 wall (ROADMAP item 5) — the full-size shapes
    stay covered by the --runslow 3-D/MoE/eval variants below."""
    import ddlbench_tpu.config as config
    from ddlbench_tpu.parallel.api import make_strategy
    from tests.tiny_models import TINY_LM  # registers transformer_t

    monkeypatch.setitem(config.DATASETS, "tinylm", TINY_LM)
    base = dict(benchmark="tinylm", arch="transformer_t",
                strategy="gpipe", micro_batch_size=2, num_microbatches=2,
                compute_dtype="float32", fused_head_loss=False,
                steps_per_epoch=2, attention_backend="xla")
    cfg_ref = RunConfig(num_devices=2, num_stages=2, **base)
    cfg_tpp = RunConfig(num_devices=4, num_stages=2, tp_size=2, **base)

    ref = make_strategy(cfg_ref)
    tpp = make_strategy(cfg_tpp)
    from ddlbench_tpu.parallel.tpp import TPGPipeStrategy

    assert isinstance(tpp, TPGPipeStrategy)

    spec = cfg_ref.dataset()
    T = spec.seq_len
    ts_r = ref.init(jax.random.key(0))
    ts_t = tpp.init(jax.random.key(0))
    losses_r, losses_t = [], []
    # 2 steps, not more: a missing psum diverges the trajectory within a
    # step or two, so step 2 already discriminates; the 3-step/3-D
    # variants stay under --runslow
    for step in range(2):
        x = jax.random.randint(jax.random.key(10 + step),
                               (cfg_ref.global_batch(), T), 0,
                               spec.num_classes, jnp.int32)
        y = jax.random.randint(jax.random.key(50 + step),
                               (cfg_ref.global_batch(), T), 0,
                               spec.num_classes, jnp.int32)
        ts_r, m_r = ref.train_step(ts_r, *ref.shard_batch(x, y),
                                   jnp.float32(0.05))
        ts_t, m_t = tpp.train_step(ts_t, *tpp.shard_batch(x, y),
                                   jnp.float32(0.05))
        losses_r.append(float(m_r["loss"]))
        losses_t.append(float(m_t["loss"]))
    np.testing.assert_allclose(losses_t, losses_r, rtol=2e-4, atol=2e-5)
    # the trajectory moved (the comparison is not vacuous)
    assert losses_r[0] != losses_r[-1]


@pytest.mark.slow
def test_tpp_3d_matches_hybrid_gpipe():
    """Full 3-D parallelism: dp=2 x stages=2 x tp=2 (8 devices) must match
    the hybrid dp=2 x stages=2 gpipe (4 devices) on the same global batch —
    the DP gradient all-reduce composes onto both packed matrices via the
    same pcast transpose."""
    from ddlbench_tpu.parallel.api import make_strategy

    _VARIANTS.setdefault("transformer_t", dict(d_model=32, n_layers=2,
                                               n_heads=4))
    base = dict(benchmark="synthtext", arch="transformer_t",
                strategy="gpipe", micro_batch_size=2, num_microbatches=2,
                dp_replicas=2, compute_dtype="float32",
                fused_head_loss=False, steps_per_epoch=2,
                attention_backend="xla")
    cfg_ref = RunConfig(num_devices=4, num_stages=2, **base)
    cfg_tpp = RunConfig(num_devices=8, num_stages=2, tp_size=2, **base)
    ref = make_strategy(cfg_ref)
    tpp = make_strategy(cfg_tpp)
    assert cfg_ref.global_batch() == cfg_tpp.global_batch() == 8
    spec = cfg_ref.dataset()
    ts_r = ref.init(jax.random.key(0))
    ts_t = tpp.init(jax.random.key(0))
    for step in range(2):
        x = jax.random.randint(jax.random.key(20 + step),
                               (cfg_ref.global_batch(), spec.seq_len), 0,
                               spec.num_classes, jnp.int32)
        y = jax.random.randint(jax.random.key(40 + step),
                               (cfg_ref.global_batch(), spec.seq_len), 0,
                               spec.num_classes, jnp.int32)
        ts_r, m_r = ref.train_step(ts_r, *ref.shard_batch(x, y),
                                   jnp.float32(0.05))
        ts_t, m_t = tpp.train_step(ts_t, *tpp.shard_batch(x, y),
                                   jnp.float32(0.05))
        np.testing.assert_allclose(float(m_t["loss"]), float(m_r["loss"]),
                                   rtol=2e-4)
        # accuracy is an integer argmax count over 8192 random-init tokens:
        # TP's sliced matmuls re-associate the f32 reductions, so a handful
        # of near-tied logits may flip argmax — tolerate a few tokens, not
        # a trajectory-level divergence
        np.testing.assert_allclose(float(m_t["accuracy"]),
                                   float(m_r["accuracy"]), atol=5e-4)


@pytest.mark.slow
def test_tpp_moe_replicated_blocks_run_and_match():
    """MoE archs under tp_size>1: the splitter replicates MoE blocks whole
    (expert FFN is not Megatron-sliced), so the apply side must run them
    full-width WITHOUT psum — regression for the head-slicing crash and the
    psum-times-tp bug on replicated-under-tp layers."""
    import ddlbench_tpu.models.moe as moe
    from ddlbench_tpu.parallel.api import make_strategy

    moe._VARIANTS.setdefault("transformer_moe_t",
                             dict(d_model=32, n_layers=2, n_heads=4,
                                  n_experts=4))
    base = dict(benchmark="synthtext", arch="transformer_moe_t",
                strategy="gpipe", micro_batch_size=2, num_microbatches=2,
                compute_dtype="float32", fused_head_loss=False,
                steps_per_epoch=2, attention_backend="xla")
    ref = make_strategy(RunConfig(num_devices=2, num_stages=2, **base))
    tpp = make_strategy(RunConfig(num_devices=4, num_stages=2, tp_size=2,
                                  **base))
    spec = ref.cfg.dataset()
    ts_r = ref.init(jax.random.key(0))
    ts_t = tpp.init(jax.random.key(0))
    # TWO steps: step 2's loss reflects step 1's parameter update, so a
    # gradient-scaling bug on replicated-under-tp leaves (tp-times or 1/tp
    # grads from a wrong psum) diverges the comparison — one step would
    # only compare forwards from identical inits
    for step in range(2):
        xs = jax.random.randint(jax.random.key(7 + step),
                                (ref.cfg.global_batch(), spec.seq_len), 0,
                                spec.num_classes, jnp.int32)
        ys = jax.random.randint(jax.random.key(9 + step),
                                (ref.cfg.global_batch(), spec.seq_len), 0,
                                spec.num_classes, jnp.int32)
        ts_r, m_r = ref.train_step(ts_r, *ref.shard_batch(xs, ys),
                                   jnp.float32(0.05))
        ts_t, m_t = tpp.train_step(ts_t, *tpp.shard_batch(xs, ys),
                                   jnp.float32(0.05))
        np.testing.assert_allclose(float(m_t["loss"]), float(m_r["loss"]),
                                   rtol=2e-4)


@pytest.mark.slow
def test_tpp_eval_matches_gpipe():
    from ddlbench_tpu.parallel.api import make_strategy

    _VARIANTS.setdefault("transformer_t", dict(d_model=32, n_layers=2,
                                               n_heads=4))
    base = dict(benchmark="synthtext", arch="transformer_t",
                strategy="gpipe", micro_batch_size=2, num_microbatches=2,
                compute_dtype="float32", fused_head_loss=False,
                steps_per_epoch=2, attention_backend="xla")
    cfg_ref = RunConfig(num_devices=2, num_stages=2, **base)
    cfg_tpp = RunConfig(num_devices=4, num_stages=2, tp_size=2, **base)
    ref = make_strategy(cfg_ref)
    tpp = make_strategy(cfg_tpp)
    spec = cfg_ref.dataset()
    ts_r = ref.init(jax.random.key(0))
    ts_t = tpp.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(3),
                           (cfg_ref.global_batch(), spec.seq_len), 0,
                           spec.num_classes, jnp.int32)
    y = jax.random.randint(jax.random.key(4),
                           (cfg_ref.global_batch(), spec.seq_len), 0,
                           spec.num_classes, jnp.int32)
    m_r = ref.eval_step(ts_r, *ref.shard_batch(x, y))
    m_t = tpp.eval_step(ts_t, *tpp.shard_batch(x, y))
    np.testing.assert_allclose(float(m_t["loss"]), float(m_r["loss"]),
                               rtol=2e-4)
    assert int(m_t["correct"]) == int(m_r["correct"])
    assert int(m_t["correct5"]) == int(m_r["correct5"])
    assert int(m_t["count"]) == int(m_r["count"])
