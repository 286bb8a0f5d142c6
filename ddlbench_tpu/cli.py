"""CLI mirroring the reference harness flags.

``run/run/run.sh -b benchmark -f framework -g gpus -n nodes -m model -q queue
-p loginterval -s`` (run.sh:16-47) becomes::

    python -m ddlbench_tpu.cli -b cifar10 -f dp -g 8 -m resnet50 -p 25

plus explicit overrides for batch/microbatch/epochs that the reference passes
through env vars (run_template.sh:70-73). Constraint checks (multi-device only
for dp/gpipe/pipedream — run.sh:51-54) live in RunConfig.validate().
"""

from __future__ import annotations

import argparse
import json
import sys

from ddlbench_tpu.config import (
    ATTENTION_BACKENDS,
    DATASETS,
    HardwareModel,
    RunConfig,
    STRATEGIES,
)
from ddlbench_tpu.models.zoo import ARCH_HELP, arch_name


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="ddlbench_tpu", description=__doc__)
    p.add_argument("-b", "--benchmark", default="mnist", choices=sorted(DATASETS))
    p.add_argument("-f", "--framework", default="single", choices=STRATEGIES,
                   help="parallelization strategy (reference: pytorch|horovod|gpipe|pipedream)")
    p.add_argument("-g", "--devices", type=int, default=1,
                   help="total number of chips (reference: gpus x nodes)")
    p.add_argument("-m", "--model", "--arch", default="resnet18",
                   type=arch_name, metavar="ARCH", help=ARCH_HELP)
    p.add_argument("-p", "--log-interval", type=int, default=25)
    p.add_argument("-s", "--real-data", action="store_true",
                   help="use on-disk data via the native loader (reference -s flag, inverted)")
    p.add_argument("--data-dir", default=None, help="on-disk dataset root (-s mode)")
    p.add_argument("--no-augment", action="store_true",
                   help="disable train-time augmentation in -s mode "
                        "(crop/flip per the reference transforms)")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="async input pipeline depth: batches prepared (incl. "
                        "device placement) ahead of the train loop by a "
                        "background thread (data/prefetch.py); 0 = "
                        "synchronous")
    p.add_argument("--no-prefetch", action="store_true",
                   help="shorthand for --prefetch-depth 0 (fully synchronous "
                        "input pipeline)")
    p.add_argument("-e", "--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--micro-batch-size", type=int, default=None)
    p.add_argument("--num-microbatches", type=int, default=None)
    p.add_argument("--stages", type=int, default=None)
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="interleaved schedule (gpipe or pipedream): model "
                        "chunks per device (cuts the pipeline bubble by "
                        "this factor)")
    from ddlbench_tpu.partition.schedule import PIPE_SCHEDULES

    p.add_argument("--pipe-schedule", default="fill-drain",
                   choices=PIPE_SCHEDULES,
                   help="pipeline timetable for -f gpipe, executed by the "
                        "schedule-programmable runtime "
                        "(parallel/pipeline_rt.py): fill-drain = GPipe "
                        "flush (default), 1f1b = synchronous "
                        "one-forward-one-backward, interleaved = 1F1B over "
                        "stages x --virtual-stages chunks, zero-bubble = "
                        "ZB-H1 split backward (weight-grad events fill the "
                        "drain bubble; composes with --virtual-stages), "
                        "zero-bubble-h2 = ZB-H2 (--zb-h2-stash extra "
                        "in-flight microbatches + trailing W deferred past "
                        "the step boundary; steady bubble -> 0), searched "
                        "= budgeted local search seeded by both heuristics "
                        "(partition/schedule_search.py; never worse than "
                        "1f1b/zero-bubble at their activation memory). "
                        "pipedream remains the ASYNC 1F1B engine (weight "
                        "stashing)")
    p.add_argument("--zb-h2-stash", type=int, default=1,
                   help="zero-bubble-h2's extra in-flight activation stash "
                        "(microbatches per chunk): more hides more warmup "
                        "idle, costs that many extra stashed boundary "
                        "activations in the planner's memory term")
    p.add_argument("--sched-search-budget", type=int, default=256,
                   help="searched-schedule move-evaluation budget; same "
                        "budget + --sched-search-seed reproduce the table "
                        "bitwise")
    p.add_argument("--sched-search-seed", type=int, default=0,
                   help="rng seed for the searched schedule's shift moves")
    p.add_argument("--pipe-costs", default="unit", choices=("unit", "profile"),
                   help="timetable cost model for the event schedules: "
                        "unit = F=B=W half-ticks (the classic grids); "
                        "profile = per-chunk F/B/W cost vectors summed "
                        "from the --auto-partition profile over the chosen "
                        "bounds, so uneven stage splits execute on "
                        "cost-weighted timetables (partition/schedule.py)")
    p.add_argument("--schedule-trace", default=None, metavar="PATH",
                   help="a prior run's --trace JSON: --auto-partition's "
                        "schedule advisor folds the MEASURED bubble "
                        "fraction reduced from its pipe_tick spans into "
                        "the ranking (telemetry/bubble.py), outranking "
                        "the analytic value for that schedule")
    p.add_argument("--dp-replicas", type=int, default=1)
    p.add_argument("--tp-size", type=int, default=1,
                   help="composed tensor x pipeline parallelism (gpipe + "
                        "transformer archs): Megatron-slice each stage this "
                        "many ways; -g = dp_replicas x tp_size x stages "
                        "(parallel/tpp.py; add --dp-replicas for 3-D)")
    p.add_argument("--stage-replication", default=None,
                   help="uneven hybrid PPxDP: comma list of per-stage "
                        "replication factors summing to -g, e.g. 1,3 "
                        "(parallel/hetero.py; the reference optimizer's "
                        "heterogeneous plans)")
    p.add_argument("--update-interval", type=int, default=1,
                   help="pipedream macrobatch: accumulate grads over K "
                        "microbatches per optimizer step (reference "
                        "runtime/optimizer.py update_interval)")
    p.add_argument("--steps-per-epoch", type=int, default=None)
    p.add_argument("--grad-accum-steps", type=int, default=1,
                   help="gradient-accumulation micro-steps per update "
                        "(Horovod backward_passes_per_step parity)")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--optimizer", default=None, choices=("sgd", "adam"),
                   help="default: adam for seq2seq benchmarks (reference "
                        "translation parity), sgd otherwise")
    p.add_argument("--shard-opt-state", action="store_true",
                   help="ZeRO-1 on dp: shard optimizer state over the data "
                        "axis (params stay replicated)")
    p.add_argument("--dp-shard-update", action="store_true",
                   help="explicit sharded weight update (ZeRO-1): on -f dp, "
                        "reduce-scatter grads and update a 1/world slice of "
                        "packed params + optimizer state per chip; on "
                        "-f gpipe, the hybrid PP x ZeRO-1 engine — each "
                        "stage's packed rows + optimizer state shard across "
                        "the pipe mesh's 'data' axis (memory/dp, grad wire "
                        "halved, per-bucket JIT all-gather in the forward)")
    p.add_argument("--allreduce-dtype", default="f32",
                   choices=("f32", "float32", "bf16", "bfloat16", "int8"),
                   help="wire dtype for dp's gradient collectives "
                        "(bf16 = EQuARX-style compressed allreduce, half "
                        "the gradient wire bytes; int8 = per-bucket absmax "
                        "scaling + stochastic rounding, quarter the bytes, "
                        "deterministic under --seed)")
    p.add_argument("--comm-buckets", type=int, default=1, metavar="K",
                   help="dp comm/compute overlap: split the packed flat "
                        "gradient into K layer-aligned buckets, each riding "
                        "its own reduce-scatter as the backward unwinds; "
                        "with --dp-shard-update the params stay sharded "
                        "between steps and the forward all-gathers each "
                        "bucket just-in-time (parallel/dp.py overlapped "
                        "engine). 1 = the monolithic collective program")
    p.add_argument("--warmup-epochs", type=int, default=0,
                   help="gradual lr warmup epochs (Horovod ImageNet parity: "
                        "base lr -> base*world over this many epochs)")
    p.add_argument("--moe-aux-weight", type=float, default=0.01,
                   help="MoE router load-balance loss weight (MoE archs)")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="MoE expert capacity = ceil(cf * tokens / experts)")
    p.add_argument("--label-smoothing", type=float, default=None,
                   help="training-objective label smoothing (default: 0.1 for "
                        "seq2seq benchmarks — GNMT parity — else 0)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--attention-backend", default="auto",
                   choices=ATTENTION_BACKENDS,
                   help="auto = Pallas flash-attention kernel on TPU")
    p.add_argument("--no-fused-head-loss", action="store_true",
                   help="disable the fused LM-head projection+cross-entropy "
                        "(materialize full logits instead)")
    p.add_argument("--remat-layers", action="store_true",
                   help="jax.checkpoint every layer in the one-apply "
                        "strategies (recompute activations in the backward; "
                        "fits XLA-attention long-context on one chip)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--jsonl", default=None, help="also write structured metrics JSONL here")
    p.add_argument("--auto-partition", action="store_true",
                   help="profile + hierarchical partitioner choose stage bounds")
    p.add_argument("--plan", default="manual", choices=("manual", "auto"),
                   help="auto = solve the FULL dp/pp/tp mix + stage split "
                        "+ schedule from the profile under the per-chip "
                        "HBM cap (partition/planner.py) and run the "
                        "winner on the existing engines (dp ZeRO-1, "
                        "gpipe/pipeline_rt with --dp-shard-update, tp); "
                        "pass -f gpipe and leave the mix flags unset — "
                        "the decision (all candidates, predicted step "
                        "time, peak bytes/chip, why the winner won) is "
                        "recorded in partition.json")
    p.add_argument("--plan-bounds", default=None, metavar="0,K,...,L",
                   help="explicit per-stage layer bounds for the pipeline "
                        "strategies (stages x virtual-stages + 1 comma "
                        "ints from 0) — execute exactly the split a "
                        "--plan auto run chose")
    p.add_argument("--hbm-gb", type=float, default=None, metavar="G",
                   help="per-chip HBM budget in GiB for the planner / "
                        "auto-partition feasibility gates (default: the "
                        "HardwareModel's 16 GiB v5e constant) — a tight "
                        "cap provably flips --plan auto toward pp>1")
    p.add_argument("--profile-mode", default="flops", choices=("flops", "time"))
    p.add_argument("--trace-dir", default=None,
                   help="write a jax.profiler trace of the run here")
    p.add_argument("--xla-trace-steps", default=None, metavar="A:B",
                   help="capture the jax.profiler trace only for global "
                        "train steps [A, B) instead of the whole run "
                        "(requires --trace-dir; keeps device profiles "
                        "openable on long runs)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write the host-side span trace (train loop, "
                        "prefetch producer, sync/checkpoint phases) as "
                        "Chrome trace-event JSON here — load in Perfetto "
                        "(ui.perfetto.dev) or chrome://tracing")
    p.add_argument("--trace-capacity", type=int, default=200_000,
                   help="span ring-buffer bound; the newest events win "
                        "when a run outlives it")
    p.add_argument("--audit", default=None, metavar="PATH",
                   help="write the compiled train step's audit manifest "
                        "here (telemetry/audit.py: flops, HBM components, "
                        "per-collective ledger from the optimized HLO, "
                        "comm_stats wire-byte tie-out) — AOT introspection "
                        "only, the run itself is untouched")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save a checkpoint per epoch here (orbax, atomic "
                        "commit protocol)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest VALID checkpoint in "
                        "--checkpoint-dir (torn/corrupt ones are skipped); "
                        "an empty dir warns and starts fresh")
    p.add_argument("--checkpoint-every-steps", type=int, default=None,
                   metavar="K",
                   help="also commit a mid-epoch checkpoint every K steps "
                        "(full resume state: bitwise mid-epoch resume)")
    p.add_argument("--keep-checkpoints", type=int, default=None, metavar="N",
                   help="retain only the newest N committed checkpoints "
                        "(older ones + stale .tmp dirs are GC'd)")
    p.add_argument("--elastic-resume", action="store_true",
                   help="topology-portable resume (train/reshard.py): when "
                        "the checkpoint's recorded world shape mismatches "
                        "the current mesh, reshard the ZeRO-1 flat state "
                        "between world sizes (pure permutation, f32 "
                        "bitwise) instead of raising CheckpointShapeError; "
                        "lr world-scaling stays pinned to the launch world")
    p.add_argument("--elastic-slices", type=int, default=None, metavar="E",
                   help="world-invariant reduction order for -f dp "
                        "--dp-shard-update: gradients computed in E fixed "
                        "slices of the global batch and reduced over a "
                        "canonical balanced tree (+ butterfly allreduce), "
                        "so a run checkpointed at world N resumes at world "
                        "M with BITWISE-identical f32 trajectories (E a "
                        "power of two divisible by every world it runs on)")
    p.add_argument("--inject", action="append", default=[],
                   metavar="KIND@EPOCH:STEP",
                   help="deterministic fault injection (repeatable): kill | "
                        "preempt | shrink | grow | ckpt-corrupt | "
                        "prefetch-die | nan-loss | nan-grad | grad-spike | "
                        "slow-host at the given 1-based epoch / 0-based "
                        "step (ddlbench_tpu/faults/; shrink/grow = the "
                        "graceful-checkpoint half of a chaosbench world "
                        "reshape — the supervisor restarts at the new -g)")
    from ddlbench_tpu.guard.policy import ANOMALY_POLICIES
    from ddlbench_tpu.train.watchdog import NAN_POLICIES

    p.add_argument("--anomaly-policy", default=None,
                   choices=ANOMALY_POLICIES,
                   help="stability guard (ddlbench_tpu/guard/): arms "
                        "on-device (finite, grad-norm) detection in the "
                        "train step plus a host EWMA spike detector; skip "
                        "drops anomalous updates in-step (params/opt state "
                        "bitwise untouched), rewind restores the last "
                        "committed checkpoint and replays")
    p.add_argument("--anomaly-budget", type=int, default=3, metavar="K",
                   help="consecutive anomalies (or rewinds for the same "
                        "step) tolerated before the run fails")
    p.add_argument("--loss-scale", default=None, metavar="dynamic|FLOAT",
                   help="loss scaling for bf16 paths: 'dynamic' "
                        "(on-device growth/backoff, overflowed updates "
                        "dropped) or a fixed scale; power-of-two dynamic "
                        "scales keep f32 runs bitwise")
    p.add_argument("--grad-spike-factor", type=float, default=10.0,
                   help="grad-norm spike threshold: factor x EWMA")
    p.add_argument("--nan-policy", default=None, choices=NAN_POLICIES,
                   help="DEPRECATED alias for --anomaly-policy (loss-only "
                        "detection, no on-device guard)")
    p.add_argument("--hang-timeout-s", type=float, default=None,
                   help="abort (with a stack dump) if any step takes longer "
                        "than this; forces a per-step host sync while armed")
    p.add_argument("--log-activations-dir", default=None,
                   help="dump per-layer activations + gradients as npz here "
                        "(torchlogger analog)")
    p.add_argument("--log-activations-freq", type=int, default=1,
                   help="log every N epochs (with --log-activations-dir)")
    p.add_argument("--log-activations-steps", type=int, default=1,
                   help="minibatches to log per logged epoch")
    from ddlbench_tpu.distributed import add_platform_arg

    add_platform_arg(p)
    return p


def _parse_step_window(spec):
    """'A:B' -> (A, B); bounds validated by RunConfig.validate()."""
    if spec is None:
        return None
    try:
        a, b = spec.split(":")
        return int(a), int(b)
    except ValueError:
        raise SystemExit(
            f"--xla-trace-steps expects A:B (two integers); got {spec!r}")


def config_from_args(args) -> RunConfig:
    return RunConfig(
        benchmark=args.benchmark,
        strategy=args.framework,
        arch=args.model,
        num_devices=args.devices,
        synthetic=not args.real_data,
        data_dir=args.data_dir,
        augment=not args.no_augment,
        prefetch_depth=0 if args.no_prefetch else args.prefetch_depth,
        epochs=args.epochs,
        log_interval=args.log_interval,
        batch_size=args.batch_size,
        micro_batch_size=args.micro_batch_size,
        num_microbatches=args.num_microbatches,
        num_stages=args.stages,
        virtual_stages=args.virtual_stages,
        pipe_schedule=args.pipe_schedule,
        zb_h2_stash=args.zb_h2_stash,
        sched_search_budget=args.sched_search_budget,
        sched_search_seed=args.sched_search_seed,
        pipe_costs=args.pipe_costs,
        schedule_trace=args.schedule_trace,
        dp_replicas=args.dp_replicas,
        tp_size=args.tp_size,
        stage_replication=(tuple(int(r) for r in
                                 args.stage_replication.split(","))
                           if args.stage_replication else None),
        update_interval=args.update_interval,
        steps_per_epoch=args.steps_per_epoch,
        grad_accum_steps=args.grad_accum_steps,
        lr=args.lr,
        optimizer=args.optimizer,
        shard_opt_state=args.shard_opt_state,
        dp_shard_update=args.dp_shard_update,
        allreduce_dtype=args.allreduce_dtype,
        comm_buckets=args.comm_buckets,
        warmup_epochs=args.warmup_epochs,
        moe_aux_weight=args.moe_aux_weight,
        moe_capacity_factor=args.moe_capacity_factor,
        label_smoothing=args.label_smoothing,
        compute_dtype=args.dtype,
        attention_backend=args.attention_backend,
        fused_head_loss=not args.no_fused_head_loss,
        remat_layers=args.remat_layers,
        seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        resume=args.resume,
        checkpoint_every_steps=args.checkpoint_every_steps,
        keep_checkpoints=args.keep_checkpoints,
        elastic_resume=args.elastic_resume,
        elastic_slices=args.elastic_slices,
        inject=tuple(args.inject),
        nan_policy=args.nan_policy if args.nan_policy is not None else "abort",
        anomaly_policy=args.anomaly_policy,
        anomaly_budget=args.anomaly_budget,
        loss_scale=args.loss_scale,
        grad_spike_factor=args.grad_spike_factor,
        hang_timeout_s=args.hang_timeout_s,
        auto_partition=args.auto_partition,
        plan=args.plan,
        plan_bounds=(tuple(int(b) for b in args.plan_bounds.split(","))
                     if args.plan_bounds else None),
        profile_mode=args.profile_mode,
        hardware=(HardwareModel(hbm_bytes=args.hbm_gb * 1024**3)
                  if args.hbm_gb is not None else HardwareModel()),
        trace=args.trace,
        trace_capacity=args.trace_capacity,
        audit=args.audit,
        trace_dir=args.trace_dir,
        xla_trace_steps=_parse_step_window(args.xla_trace_steps),
        activation_log_dir=args.log_activations_dir,
        activation_log_freq=args.log_activations_freq,
        activation_log_steps=args.log_activations_steps,
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from ddlbench_tpu.distributed import (apply_platform,
                                          backend_provenance,
                                          enable_compilation_cache,
                                          initialize)

    if args.nan_policy is not None:
        # deprecated alias for the unified guard surface (warn once per run)
        tail = (" (--anomaly-policy wins; the alias is ignored)"
                if args.anomaly_policy is not None else "")
        print(f"WARNING: --nan-policy is deprecated; use --anomaly-policy "
              f"{args.nan_policy}{tail}", file=sys.stderr, flush=True)

    apply_platform(args.platform)
    enable_compilation_cache()
    if args.comm_buckets > 1:
        # async-collective overlap flags must land in LIBTPU_INIT_ARGS
        # before the first backend touch; no-op on cpu-pinned runs
        from ddlbench_tpu.distributed import apply_comm_flags

        apply_comm_flags(args.platform)

    if args.inject:
        # armed BEFORE initialize() so slow-host can hit the multihost init
        # path; run_benchmark re-arms the same specs (fired state persists)
        from ddlbench_tpu import faults

        faults.arm(args.inject)

    initialize()  # no-op unless DDLB_* multi-host env is set
    # a run that found no accelerator and was not asked for cpu stops here
    backend_provenance(args.platform, "train")
    cfg = config_from_args(args)
    cfg.validate()

    from ddlbench_tpu.train.loop import run_benchmark
    from ddlbench_tpu.train.metrics import MetricLogger

    # Run manifest (info.txt parity, run.sh:88-96).
    manifest = {k: v for k, v in vars(args).items()}
    print("run manifest: " + json.dumps(manifest), flush=True)

    from ddlbench_tpu.guard import PREEMPT_EXIT_CODE, GracefulPreemption

    logger = MetricLogger(cfg.epochs, cfg.log_interval, jsonl_path=args.jsonl)
    try:
        if args.trace_dir and cfg.xla_trace_steps is None:
            # Whole-run jax.profiler trace — the TPU-native replacement for
            # the reference's hook-based torchprofiler (SURVEY.md §5.1).
            # With --xla-trace-steps the loop opens/closes the capture
            # window itself (train/loop.py _XlaWindow).
            import jax

            with jax.profiler.trace(args.trace_dir):
                result = run_benchmark(cfg, logger=logger)
        else:
            result = run_benchmark(cfg, logger=logger)
    except GracefulPreemption as e:
        # the loop already committed the step-granular checkpoint; the
        # distinct exit code tells supervisors "evicted cleanly, resume me"
        print(f"preempted: {e} (exit {PREEMPT_EXIT_CODE})", flush=True)
        return PREEMPT_EXIT_CODE
    finally:
        # flush + close the --jsonl stream even when a run dies mid-epoch:
        # the structured log is most valuable for exactly those runs
        logger.close()
    result.pop("train_state", None)
    print("result: " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
