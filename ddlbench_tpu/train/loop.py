"""The benchmark train/eval loop — shared by all strategies.

Parity with the reference's per-driver loops (benchmark/mnist/mnist_pytorch.py:
train_epoch :52-99, test_epoch :102-133, summary :222-226): `epochs` training
epochs, per-LOGINTER throughput/memory lines, one validation epoch per training
epoch, and a final averaged summary. The loop is strategy-agnostic; all
device-side work lives in the strategy's jitted steps.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ddlbench_tpu import faults
from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.data.prefetch import Prefetcher
from ddlbench_tpu.data.synthetic import make_synthetic
from ddlbench_tpu.guard import (GracefulPreemption, GuardRewind,
                                PreemptionHandler, StabilityGuard)
from ddlbench_tpu.parallel.api import make_strategy
from ddlbench_tpu.telemetry import (StepLatencyStats, Tracer, compiles,
                                    export_chrome_trace, get_tracer,
                                    set_tracer)
from ddlbench_tpu.train.metrics import MetricLogger
from ddlbench_tpu.train.watchdog import (HangWatchdog, TrainingFailure,
                                         check_finite)
from ddlbench_tpu.parallel.common import step_decay_lr

_NULL_CTX = contextlib.nullcontext()


class _XlaWindow:
    """Windowed jax.profiler capture: ``--xla-trace-steps A:B`` profiles
    global train steps [A, B) into ``trace_dir`` (device timelines stay
    small enough to open; the host trace covers the whole run). With no
    window configured every call is a no-op."""

    def __init__(self, cfg: RunConfig):
        self.window = cfg.xla_trace_steps
        self.trace_dir = cfg.trace_dir
        self.active = False
        self.done = False

    def step(self, gstep: int, sync) -> None:
        """Called before dispatching global step ``gstep``; ``sync()`` must
        block until the device drained (used to close the window)."""
        if self.window is None or self.done:
            return
        start, stop = self.window
        if not self.active and start <= gstep < stop:
            jax.profiler.start_trace(self.trace_dir)
            self.active = True
        elif self.active and gstep >= stop:
            sync()
            self.close()

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            print(f"xla profile (steps {self.window[0]}:{self.window[1]}) "
                  f"written to {self.trace_dir}", flush=True)


def _write_audit(cfg: RunConfig, strategy) -> None:
    """--audit PATH: AOT-lower the train step at the run's exact shapes,
    extract the compiled-program manifest (flops, HBM components, the
    per-collective ledger out of the optimized HLO), tie it to comm_stats,
    and write the ledger next to the run's JSON. Lowering with a synthetic
    shape-double never executes and never consumes the real data stream;
    with `--plan auto` + a persisted plan the planner's per-stage HBM
    error also lands in partition.json (plan_auto.hbm_audit)."""
    from ddlbench_tpu.telemetry.audit import (lower_manifest,
                                              planner_stage_hbm_audit,
                                              reconcile_train,
                                              record_hbm_audit,
                                              write_manifests)
    from ddlbench_tpu.distributed import record_provenance

    prov = record_provenance(None, "train --audit")
    probe = make_synthetic(cfg.dataset(), cfg.global_batch(),
                           steps_per_epoch=1)
    ts0 = strategy.init(jax.random.key(cfg.seed))
    x0, y0 = probe.batch(0, 0)
    jit_step = getattr(strategy, "_jit_train_step", None) \
        or strategy.train_step
    man = lower_manifest(
        jit_step, (ts0, *strategy.shard_batch(x0, y0),
                   jnp.float32(cfg.resolved_lr())),
        name=f"train/{cfg.strategy}/{cfg.arch}@{cfg.num_devices}",
        mesh=getattr(strategy, "mesh", None))
    man["reconcile"] = reconcile_train(strategy, man)
    if cfg.plan == "auto" and cfg.checkpoint_dir:
        # the resolved plan is persisted; grade its HBM model against
        # memory_analysis() and record the signed per-stage error there
        import json as _json

        from ddlbench_tpu.parallel.api import _plan_path

        path = _plan_path(cfg)
        if path and os.path.exists(path):
            with open(path) as f:
                winner = _json.load(f).get("plan_auto", {}).get("winner")
            if winner:
                hbm = planner_stage_hbm_audit(winner, man,
                                              cfg.num_devices)
                man["hbm_audit"] = hbm
                if hbm is not None:
                    record_hbm_audit(cfg, hbm)
    write_manifests(cfg.audit, [man],
                    header={**prov, "tool": "train"})
    rec = man["reconcile"]
    print(f"audit: manifest -> {cfg.audit} "
          f"(tieable={rec['tieable']} ok={rec.get('ok')})", flush=True)


def run_benchmark(cfg: RunConfig, strategy=None, logger: Optional[MetricLogger] = None,
                  warmup_steps: int = 1) -> Dict[str, Any]:
    """Run the full 3-epoch benchmark protocol; returns the summary dict."""
    cfg.validate()
    if cfg.plan == "auto" and strategy is None:
        # --plan auto resolves BEFORE anything reads the config: the
        # rewritten strategy shapes the data stream's global batch, the
        # lr world-scaling, and the checkpoint metadata exactly as the
        # explicitly-flagged equivalent run would (the bitwise contract).
        from ddlbench_tpu.partition.planner import resolve_auto_plan

        def _probe_input_ms(cfg=cfg):
            # real data: price the host loader into the solve exactly as
            # --auto-partition prices it into stage 0 (fold_input_node).
            # A throwaway probe stream keeps the real one unconsumed; the
            # pre-plan global batch equals the post-plan one (the rewrite
            # preserves it), so the per-microbatch scaling is exact. Only
            # evaluated on a plan-cache MISS (resolve_auto_plan).
            from ddlbench_tpu.profiler.profile import measure_input_ms

            probe = _make_data(cfg)
            try:
                global_ms = measure_input_ms(probe)
            finally:
                getattr(probe, "close", lambda: None)()
            mb_pre, _ = cfg.resolved_batches()
            ms = global_ms * mb_pre / cfg.global_batch()
            print(f"plan auto: measured input cost "
                  f"{global_ms:.2f} ms/global-batch "
                  f"({ms:.3f} ms/microbatch)", flush=True)
            return ms

        cfg = resolve_auto_plan(
            cfg, input_time_ms=0.0 if cfg.synthetic else _probe_input_ms)
    data = _make_data(cfg)
    if strategy is None:
        input_ms = 0.0
        if (cfg.auto_partition and not cfg.synthetic
                and cfg.strategy in ("gpipe", "pipedream")):
            # Input-node cost for the partitioner (reference parity:
            # profiler main.py:388-407): measure the on-disk loader's fetch
            # cost so --auto-partition prices host-side data loading into
            # stage 0. A throwaway loader instance keeps the real training
            # stream unconsumed, and the per-GLOBAL-batch measurement is
            # scaled to the per-MICROBATCH units of the profile graph.
            from ddlbench_tpu.profiler.profile import measure_input_ms

            # sequential streams (the native on-disk loader) need a
            # throwaway instance so the training stream stays unconsumed;
            # random-access sources (translation corpus) are probed directly
            if getattr(data, "stateful_stream", False):
                probe = _make_data(cfg)
                try:
                    global_ms = measure_input_ms(probe)
                finally:
                    probe.close()
            else:
                global_ms = measure_input_ms(data)
            mb_, _ = cfg.resolved_batches()
            input_ms = global_ms * mb_ / cfg.global_batch()
            print(f"auto-partition: measured input cost "
                  f"{global_ms:.2f} ms/global-batch "
                  f"({input_ms:.3f} ms/microbatch)", flush=True)
        strategy = make_strategy(cfg, input_time_ms=input_ms)
    if cfg.audit:
        _write_audit(cfg, strategy)
    logger = logger or MetricLogger(cfg.epochs, cfg.log_interval)

    # Step-level telemetry (ddlbench_tpu/telemetry/): a fresh bounded
    # tracer per run when --trace is set, exported (Perfetto-loadable) in
    # the finally so a run that dies mid-epoch still leaves its trace.
    # With tracing off the global tracer stays disabled and every span
    # site below is a no-op check.
    tracer, prev_tracer = None, None
    if cfg.trace:
        # fail fast on an unwritable path — the export happens at run END,
        # and discovering a bad --trace there would waste the whole run
        with open(cfg.trace, "a"):
            pass
        prev_tracer = get_tracer()
        tracer = set_tracer(Tracer(cfg.trace_capacity)).enable()

    # Failure detection (SURVEY.md §5.3): the watchdog is kicked at every
    # host sync point below; non-finite losses go through cfg.nan_policy.
    # Started only after warmup so the first deadline excludes XLA compile
    # (tens of seconds); with warmup_steps=0 the first step's compile counts.
    wd = HangWatchdog(cfg.hang_timeout_s) if cfg.hang_timeout_s else None
    xla_window = _XlaWindow(cfg)
    # Stability guard (ddlbench_tpu/guard/): the ONE policy surface for
    # every anomaly — on-device (finite, grad_norm) flags from the guarded
    # engines, non-finite losses at the legacy check sites, EWMA grad-norm
    # spikes — plus graceful preemption. With neither --anomaly-policy nor
    # --loss-scale set, the guard only mirrors the legacy nan_policy checks.
    guard = StabilityGuard(cfg)
    preempt = None
    if cfg.checkpoint_dir:
        # SIGTERM/SIGINT -> flag -> step-boundary checkpoint -> distinct
        # exit code. Only armed when there is somewhere to commit to.
        preempt = PreemptionHandler().install()
    # Deterministic fault injection (ddlbench_tpu/faults/): armed for the
    # run, disarmed in the finally. With cfg.inject empty this arms nothing
    # and every hook below is a single falsy check.
    faults.arm(cfg.inject)
    if any(s.kind == "grad-spike" for s in faults.armed_specs()):
        from ddlbench_tpu.guard.policy import GUARD_UNWIRED_STRATEGIES

        # grad-spike is consumed by the guard's device-metric window; with
        # the guard disarmed — or a strategy whose engine carries no guard
        # wiring and so emits no device metrics — the spec would silently
        # never fire. Surface it instead of breaking the deterministic-
        # firing contract quietly.
        if not guard.device_armed:
            print("WARNING: --inject grad-spike has no effect without "
                  "--anomaly-policy/--loss-scale (the guard's grad-norm "
                  "detector is what consumes it)", file=sys.stderr,
                  flush=True)
        elif cfg.strategy in GUARD_UNWIRED_STRATEGIES:
            print(f"WARNING: --inject grad-spike has no effect with "
                  f"-f {cfg.strategy} (its engine has no device-guard "
                  f"wiring, so no grad-norm stream feeds the detector)",
                  file=sys.stderr, flush=True)
    if preempt is None and \
            any(s.kind in ("preempt", "shrink", "grow")
                for s in faults.armed_specs()):
        # the graceful path needs somewhere to commit; without it the
        # injected SIGTERM is just an uncheckpointed death (rc -15) —
        # and for shrink/grow there is then no checkpoint to reshape from
        print("WARNING: --inject preempt/shrink/grow without "
              "--checkpoint-dir kills the run uncheckpointed (the graceful "
              "path needs a commit target)", file=sys.stderr, flush=True)
    try:
        while True:
            try:
                return _run_benchmark(cfg, strategy, data, logger,
                                      warmup_steps, wd, xla_window, guard,
                                      preempt)
            except GuardRewind as rw:
                # --anomaly-policy rewind: restore the last committed
                # checkpoint through the existing latest_valid resume path;
                # the (epoch, step)-addressed data stream fast-forwards
                # deterministically, so the replay is bitwise. The guard
                # bounds repeated rewinds for the same step by the budget.
                from ddlbench_tpu.train.checkpoint import latest_valid

                if latest_valid(cfg.checkpoint_dir) is None:
                    # no committed checkpoint yet: re-entering would fall
                    # through the empty-dir resume path and silently restart
                    # with FRESH params (not a rewind) while the logger keeps
                    # the abandoned attempt's records — escalate instead
                    raise TrainingFailure(
                        f"guard: rewind requested but no committed "
                        f"checkpoint exists in {cfg.checkpoint_dir} ({rw}); "
                        f"use --checkpoint-every-steps to bound the window "
                        f"before the first epoch-end commit") from rw
                print(f"guard: rewinding to the last valid checkpoint "
                      f"({rw})", flush=True)
                get_tracer().complete("guard_rewind",
                                      time.perf_counter_ns(),
                                      time.perf_counter_ns())
                guard.reset_window()  # drop the abandoned interval's flags
                cfg = cfg.replace(resume=True)
    finally:
        faults.disarm()
        if preempt is not None:
            preempt.uninstall()
        if wd:
            wd.stop()
        # an exception mid-window must still stop + flush the device
        # profile (and leave jax.profiler usable for the next run)
        xla_window.close()
        if tracer is not None:
            tracer.disable()
            set_tracer(prev_tracer)  # drop the ring; untraced runs follow
            try:
                # mesh shape + run identity: joins this trace to the
                # run's audit manifest (same fields in the ledger header)
                mesh = getattr(strategy, "mesh", None)
                n = export_chrome_trace(tracer, cfg.trace, extra_metadata={
                    "train": {"strategy": cfg.strategy, "arch": cfg.arch,
                              "num_devices": cfg.num_devices,
                              "mesh_shape": (dict(mesh.shape)
                                             if mesh is not None else None)}})
            except OSError as e:  # never mask the run's own exception
                print(f"telemetry: trace export to {cfg.trace} failed: {e}",
                      flush=True)
            else:
                print(f"telemetry: {n} trace events written to {cfg.trace}"
                      + (f" ({tracer.dropped_events} dropped: ring full)"
                         if tracer.dropped_events else ""), flush=True)


def _make_data(cfg: RunConfig):
    global_batch = cfg.global_batch()
    spec = cfg.dataset()
    if cfg.synthetic:
        return make_synthetic(
            spec, global_batch, seed=cfg.seed, steps_per_epoch=cfg.steps_per_epoch
        )
    if spec.kind == "seq2seq" and cfg.data_dir:
        # Real translation corpus (train.src/train.tgt parallel line files):
        # BPE-tokenized fixed-shape prefix-LM streams with padding-efficiency
        # accounting (data/translation.py).
        from ddlbench_tpu.data.translation import (
            TranslationData, find_parallel_corpus)

        if find_parallel_corpus(cfg.data_dir, "train"):
            data = TranslationData(cfg.data_dir, spec, global_batch,
                                   seed=cfg.seed,
                                   steps_per_epoch=cfg.steps_per_epoch)
            rep = data.bucketing_report()
            print(
                f"translation data: vocab {data.tokenizer.vocab_size}, "
                f"padding efficiency {rep['fixed_efficiency']:.3f} fixed vs "
                f"{rep['bucketed_efficiency']:.3f} bucketed "
                f"({rep['num_compiles_bucketed']} bucket compiles)",
                flush=True,
            )
            return data
    if spec.kind == "tokens" and cfg.data_dir:
        # Real text corpus (train.txt): BPE-tokenized document-packed causal
        # LM windows (data/textcorpus.py) — the raw-bytes placeholder below
        # stays synthetic-only.
        from ddlbench_tpu.data.textcorpus import (
            TextCorpusData, find_text_corpus)

        if find_text_corpus(cfg.data_dir, "train"):
            data = TextCorpusData(cfg.data_dir, spec, global_batch,
                                  seed=cfg.seed,
                                  steps_per_epoch=cfg.steps_per_epoch)
            print(
                f"text corpus: {data.num_tokens} tokens, vocab "
                f"{data.tokenizer.vocab_size}, "
                f"{data.steps_per_epoch()} steps/epoch", flush=True)
            return data
    from ddlbench_tpu.data.ondisk import OnDiskData

    train_count = (cfg.steps_per_epoch or 0) * global_batch or None
    test_count = max(global_batch, (train_count or 0) // 5) if train_count else None
    return OnDiskData(
        cfg.data_dir or "./data", spec, global_batch, seed=cfg.seed,
        train_count=train_count, test_count=test_count,
        augment=cfg.augment, prefetch_depth=cfg.prefetch_depth,
    )


def _run_benchmark(cfg: RunConfig, strategy, data, logger: MetricLogger,
                   warmup_steps: int, wd: Optional[HangWatchdog],
                   xla_window: Optional[_XlaWindow] = None,
                   guard: Optional[StabilityGuard] = None,
                   preempt: Optional[PreemptionHandler] = None
                   ) -> Dict[str, Any]:

    guard = guard or StabilityGuard(cfg)
    mb, chunks = cfg.resolved_batches()
    global_batch = cfg.global_batch()

    def _scaled_lr(lr_world: int):
        lr = cfg.resolved_lr()
        # The gradual warmup ramps away exactly the world-scaling factor
        # (imagenet_horovod.py:258-275), so it only does something where
        # that scaling is applied — warmup_world stays 1 elsewhere and
        # gradual_warmup_lr is then the identity.
        w = 1
        if (cfg.strategy == "dp" and cfg.scale_lr_by_world
                and cfg.resolved_optimizer() == "sgd"):
            # Horovod parity: lr scaled by world size (mnist_horovod.py:226)
            # and by the accumulation count (lr * batches_per_allreduce *
            # hvd.size(), imagenet_horovod.py:131). SGD only — linear
            # scaling is the SGD heuristic; the reference never scales its
            # Adam (translation) lr by replica count. ``lr_world`` is
            # normally the mesh world, but an ELASTIC resume pins it to
            # the LAUNCH world recorded in the checkpoint — shrinking a
            # fleet must never silently change the learning rate.
            lr = lr * lr_world * cfg.grad_accum_steps
            w = lr_world
        return lr, w

    lr_world = getattr(strategy, "world_size", cfg.num_devices)
    base_lr, warmup_world = _scaled_lr(lr_world)

    # Step-latency accounting (telemetry/stats.py): every loop iteration's
    # wall time is recorded (two monotonic clock reads — stays on even with
    # tracing off) and aggregated to p50/p95/p99/max per epoch for the
    # epoch lines / JSONL / summary. The tracer is only consulted through
    # its `enabled` flag on the hot path.
    stats = StepLatencyStats()
    tracer = get_tracer()

    # Warmup: trigger compilation outside the timed region (first XLA compile is
    # tens of seconds; the reference's closest analog is cudnn.benchmark=True,
    # imagenet_pytorch.py:58-66). Runs on a throwaway state so the measured run
    # starts from pristine params/momentum/BN stats. The wall time is kept
    # as the run's explicit warmup/compile accounting — never mixed into
    # the step-latency distribution. jax's compile tally
    # (telemetry/compiles.py) splits it into trace + lower, compile or cache
    # load, and the programs the persistent cache lacked.
    if warmup_steps > 0:
        t_warm = time.perf_counter_ns()
        with tracer.span("warmup_compile"):
            ts_warm = strategy.init(jax.random.key(cfg.seed))
            batch = strategy.shard_batch(*data.batch(epoch=0, step=0))
            for _ in range(warmup_steps):
                ts_warm, m = strategy.train_step(ts_warm, *batch,
                                                 jnp.float32(base_lr))
            jax.block_until_ready(m)
            if wd:
                # also compile eval_step now, so the watchdog deadline
                # (armed below) never spans a first-eval XLA compile
                jax.block_until_ready(strategy.eval_step(ts_warm, *batch))
            del ts_warm
        t_warm_end = time.perf_counter_ns()
        stats.set_warmup((t_warm_end - t_warm) / 1e9,
                         compiles.between(t_warm, t_warm_end))

    ts = strategy.init(jax.random.key(cfg.seed))

    # Comm-volume accounting (RuntimeStats parity, SURVEY.md §5.5).
    from ddlbench_tpu.train.comm_stats import comm_stats

    try:
        cs = comm_stats(strategy)
    except NotImplementedError as e:  # sp/tp/fsdp/ep: no analytic model
        print(f"comm volume/step: not modelled ({e})", flush=True)
    else:
        parts = [f"boundaries {cs['boundary_bytes'] / 1e6:.2f} MB",
                 f"allreduce {cs['allreduce_bytes'] / 1e6:.2f} MB"]
        if cs.get("reduce_scatter_bytes") or cs.get("all_gather_bytes"):
            # explicit sharded weight update: the allreduce decomposes
            parts.append(f"reduce-scatter "
                         f"{cs['reduce_scatter_bytes'] / 1e6:.2f} MB")
            parts.append(f"all-gather "
                         f"{cs['all_gather_bytes'] / 1e6:.2f} MB")
        print(f"comm volume/step: {cs['total_bytes'] / 1e6:.2f} MB "
              f"({', '.join(parts)})", flush=True)

    # Asynchronous input pipeline (data/prefetch.py): batch production AND
    # shard_batch/device_put run a bounded prefetch_depth ahead of the
    # consuming loop on a producer thread, so step N's H2D transfer overlaps
    # step N-1's compute. depth 0 (--no-prefetch) is the synchronous
    # fallback through the same interface; both paths feed the loop the same
    # (epoch, step)-addressed batches, so losses are bitwise identical.
    prefetch = Prefetcher(data, strategy.shard_batch,
                          depth=cfg.prefetch_depth, watchdog=wd)

    # Retention pin: the path of the checkpoint the run would currently
    # rewind/resume to — gc never drops it (train/checkpoint.py), so a
    # newer post-commit-corrupted checkpoint cannot crowd the only known-
    # restorable state out of a tight --keep-checkpoints window. Updated to
    # every newly committed checkpoint (which then IS the rewind target).
    ckpt_pin: Optional[str] = None
    start_epoch, resume_step, global_step = 1, 0, 0
    if cfg.checkpoint_dir and cfg.resume:
        from ddlbench_tpu.train import reshard
        from ddlbench_tpu.train.checkpoint import (latest_valid,
                                                   load_logical,
                                                   restore_info)

        info = latest_valid(cfg.checkpoint_dir)
        if wd:
            # on a rewind re-entry the watchdog thread is already running;
            # the restore below gets a full deadline
            wd.kick()
        if info is None:
            # A restarted-from-scratch supervisor loop (tools/chaosbench.py)
            # passes --resume unconditionally; an empty/missing checkpoint
            # dir must start fresh, not crash.
            print(f"resume: no valid checkpoint under {cfg.checkpoint_dir}; "
                  f"starting fresh", flush=True)
        else:
            # Topology check BEFORE touching orbax: a world-shape mismatch
            # either routes through the reshard pass (--elastic-resume) or
            # raises the named CheckpointShapeError instead of dying on a
            # cryptic orbax shape assert (train/reshard.py).
            saved_logical = load_logical(info.path)
            cur_logical = reshard.logical_meta(strategy, cfg, ts, lr_world)
            decision = reshard.compare(saved_logical, cur_logical,
                                       cfg.elastic_resume)
            with tracer.span("checkpoint_restore",
                             reshard=decision == "reshard"):
                if decision == "reshard":
                    print(f"elastic resume: resharding checkpoint from "
                          f"world {saved_logical['world']} to "
                          f"{cur_logical['world']} "
                          f"(buckets {saved_logical.get('buckets')} -> "
                          f"{cur_logical.get('buckets')})", flush=True)
                    ts = reshard.elastic_restore(info, ts, saved_logical,
                                                 strategy, cfg)
                else:
                    ts = restore_info(info, ts)
            if saved_logical is not None:
                if saved_logical.get("global_batch") != cfg.global_batch():
                    print(f"resume: WARNING checkpoint was written at "
                          f"global batch {saved_logical.get('global_batch')}"
                          f", run uses {cfg.global_batch()} — the "
                          f"(epoch, step)-addressed data streams will not "
                          f"match the original trajectory", flush=True)
                saved_lr_world = saved_logical.get("lr_world")
                if saved_lr_world and saved_lr_world != lr_world:
                    # pin the lr world-scaling to the LAUNCH world: the
                    # run's hyperparameters were fixed at launch, and a
                    # reshaped fleet must replay the same schedule
                    lr_world = saved_lr_world
                    base_lr, warmup_world = _scaled_lr(lr_world)
                    print(f"elastic resume: lr world-scaling pinned to the "
                          f"launch world ({lr_world})", flush=True)
                if saved_logical.get("elastic_slices") != \
                        cfg.elastic_slices:
                    print(f"resume: WARNING checkpoint recorded "
                          f"--elastic-slices "
                          f"{saved_logical.get('elastic_slices')}, run "
                          f"uses {cfg.elastic_slices} — reduction orders "
                          f"differ, the trajectory will not be bitwise",
                          flush=True)
            ckpt_pin = info.path
            meta = info.meta
            if meta.get("seed") is not None and meta["seed"] != cfg.seed:
                print(f"resume: WARNING checkpoint was written with seed "
                      f"{meta['seed']}, run uses seed {cfg.seed} — the "
                      f"(epoch, step)-addressed data/RNG streams will not "
                      f"match the original trajectory", flush=True)
            if meta.get("logger"):
                logger.load_state_dict(meta["logger"])
            steps_ = data.steps_per_epoch(train=True)
            if info.mid_epoch:
                # step-granular checkpoint: resume INSIDE the epoch at the
                # next step — the data iterator position IS the step index
                # (every source is (epoch, step)-addressed) and per-step
                # RNG streams are pure (seed, epoch, step) fold-ins, so the
                # replayed trajectory is bitwise
                start_epoch, resume_step = info.epoch, info.step + 1
                if resume_step >= steps_:  # epoch actually completed
                    start_epoch, resume_step = info.epoch + 1, 0
                print(f"resumed from {cfg.checkpoint_dir} epoch "
                      f"{info.epoch} step {info.step} (mid-epoch)",
                      flush=True)
            else:
                start_epoch = info.epoch + 1
                print(f"resumed from {cfg.checkpoint_dir} epoch "
                      f"{info.epoch}", flush=True)
            global_step = (meta.get("global_step")
                           if meta.get("global_step") is not None
                           else (start_epoch - 1) * steps_ + resume_step)
            if not info.mid_epoch:
                # post-resume validation BEFORE training continues
                # (reference semantics: main_with_runtime.py:374-376 re-runs
                # validate() right after restoring) — confirms the restored
                # state is the one that was saved, not merely loadable.
                # Mid-epoch resumes skip it: the epoch is not finished, and
                # its epoch-end validation will run at the normal point.
                ev = evaluate(cfg, strategy, ts, data, info.epoch, wd,
                              prefetcher=prefetch, guard=guard)
                logger.valid_epoch(info.epoch, ev["loss"], ev["accuracy"],
                                   top5=ev.get("top5"))

    # Topology-portable metadata written beside every commit from here on:
    # the recorded shape is what lets the NEXT resume detect a world-size
    # mismatch and reshard instead of crashing (train/reshard.py).
    ckpt_logical = None
    if cfg.checkpoint_dir:
        from ddlbench_tpu.train import reshard as _reshard

        ckpt_logical = _reshard.logical_meta(strategy, cfg, ts, lr_world)

    # Activation/gradient deep-dive logging (torchlogger analog, §5.5).
    # Works on the flat per-layer param structure; pipeline strategies pack
    # params per stage, so those log from the model definition is not wired —
    # documented in profiler/actlog.py.
    actlog = None
    if cfg.activation_log_dir:
        from ddlbench_tpu.profiler.actlog import ActivationLogger

        # Structure check once here: the logger needs the flat per-layer param
        # list; pipeline strategies pack params per stage (ts structure is
        # fixed by strategy.init, so this cannot change mid-run).
        model = getattr(strategy, "model", None)
        params = getattr(ts, "params", None)
        if (model is not None and isinstance(params, list)
                and len(params) == len(model.layers)):
            actlog = ActivationLogger(
                cfg.activation_log_dir, model, jnp.dtype(cfg.compute_dtype),
                cfg.activation_log_freq, cfg.activation_log_steps,
                moe_aux_weight=cfg.moe_aux_weight,
                label_smoothing=cfg.resolved_label_smoothing(),
            )
        else:
            print("activation logging unsupported for this strategy "
                  "(packed or absent per-layer params); skipped", flush=True)

    if wd:
        wd.kick()
        wd.start()

    # Host/device trace alignment: when a jax.profiler capture is on (whole
    # run via cli.py's --trace-dir, or the [A, B) window below), every step
    # dispatch is wrapped in a StepTraceAnnotation carrying the global step
    # number, so device timelines line up with the host spans' step args.
    annotate_steps = cfg.trace_dir is not None
    if xla_window is None:
        xla_window = _XlaWindow(cfg)

    summary_acc = (logger.valid_history[-1]["accuracy"]
                   if logger.valid_history else 0.0)
    for epoch in range(start_epoch, cfg.epochs + 1):
        # mid-epoch resume: only the first epoch starts at an interior step
        ep_start = resume_step if epoch == start_epoch else 0
        lr = step_decay_lr(base_lr, epoch - 1, cfg.lr_step_epochs, cfg.lr_step_gamma)
        steps = data.steps_per_epoch(train=True)
        tick = time.perf_counter()
        interval_tick, interval_samples = tick, 0
        # On-device metric accumulation: step losses are summed as lazy
        # jax.Arrays and transferred ONCE per log interval (the logged loss
        # is the interval mean), so the host never blocks the dispatch queue
        # between intervals. The watchdog path below keeps its opt-in
        # per-step sync — and since every loss already lands on the host
        # there, it accumulates the plain floats instead of paying a
        # second device-side sum and interval transfer.
        loss_sum, host_loss_sum, interval_steps = None, 0.0, 0
        metrics = None
        stream = prefetch.stream(epoch, train=True,
                                 keep_raw=actlog is not None,
                                 start_step=ep_start)
        try:
            for step, fetched in enumerate(stream, start=ep_start):
                if actlog is not None and actlog.should_log(epoch, step):
                    bx, by = fetched.raw
                    try:
                        # overlapped dp keeps params as a flat sharded
                        # vector between steps; ask the strategy for the
                        # per-layer pytree instead of touching ts.params
                        p_log = (strategy.materialize_params(ts)
                                 if hasattr(strategy, "materialize_params")
                                 else ts.params)
                        path = actlog.log(epoch, step, p_log,
                                          ts.model_state, bx, by)
                    except RuntimeError as e:  # e.g. non-addressable sharded params
                        print(f"activation logging failed ({e}); disabled",
                              flush=True)
                        actlog, path = None, None
                    if path:
                        print(f"activations logged: {path}", flush=True)
                step_lr = lr
                if cfg.warmup_epochs and epoch - 1 < cfg.warmup_epochs:
                    from ddlbench_tpu.parallel.common import gradual_warmup_lr

                    step_lr = gradual_warmup_lr(
                        lr, warmup_world, epoch - 1, step, steps,
                        cfg.warmup_epochs)
                # Step wall time = this loop body (dispatch + any sync the
                # body performs); the ring wait on input is accounted
                # separately as stall (data/prefetch.py), so the two
                # decompose the epoch instead of double-counting it.
                t_step = time.perf_counter_ns()
                # the whole loop body as one host span: ddl/train_step on the
                # profiler's clock, train_step in the ring when --trace is on
                with tracer.span("train_step", epoch=epoch, step=step,
                                 global_step=global_step):
                    # fault hook: `kill` SIGKILLs / `preempt` SIGTERMs at this
                    # step boundary — before the dispatch, so the last committed
                    # checkpoint is what a resume must recover from
                    faults.step_boundary(epoch, step)
                    if preempt is not None and preempt.requested:
                        # graceful preemption: commit the state as of the LAST
                        # COMPLETED step through the atomic protocol, then exit
                        # with the distinct code (cli.py). The guard flushes
                        # first so an anomalous pending step cannot be the
                        # state that gets committed.
                        guard.flush(epoch, step)
                        _commit_preemption(cfg, ts, epoch, step, global_step,
                                           logger, tracer, wd, ckpt_pin,
                                           ckpt_logical)
                    if faults.poison_grad(epoch, step):
                        # `nan-grad`: a NaN lr rides into the backward through
                        # the guard-armed engines' objective multiplier
                        # (lr*0+1), poisoning the device-side gradients — the
                        # on-device detection/skip path is what gets exercised.
                        # Disarmed engines have no multiplier: the NaN scales
                        # the update directly and params stay NaN, which is
                        # exactly what a real NaN gradient does without a guard
                        # (nan_policy then sees it at the next loss sync)
                        step_lr = float("nan")
                    xla_window.step(global_step, lambda: (
                        float(metrics["loss"]) if metrics is not None else None))
                    ann = (jax.profiler.StepTraceAnnotation(
                        "train", step_num=global_step)
                        if annotate_steps else _NULL_CTX)
                    with ann:
                        ts, metrics = strategy.train_step(ts, *fetched.batch,
                                                          jnp.float32(step_lr))
                    if faults.poison_loss(epoch, step):
                        # `nan-loss`: poison this step's HOST-side loss (device
                        # state untouched) — drives the --nan-policy path
                        metrics = dict(metrics)
                        metrics["loss"] = jnp.float32(float("nan"))
                    global_step += 1
                    interval_samples += global_batch
                    interval_steps += 1
                    # With the watchdog armed, sync every step so the deadline
                    # really is per-step (a small pipelining cost, only when
                    # opted in); otherwise the loop transfers one accumulated
                    # scalar per log interval.
                    log_step = (step + 1) % cfg.log_interval == 0 or step == steps - 1
                    if wd:
                        with tracer.span("step_sync"):
                            step_loss = float(metrics["loss"])  # transfer = sync
                        # per-step health first: a dropped/rewound update is the
                        # step's primary event, the loss value its symptom
                        guard.step_health(epoch, step + 1, metrics)
                        guard.check_loss(step_loss, epoch, step + 1)
                        wd.kick()
                        host_loss_sum += step_loss
                    else:
                        loss_sum = (metrics["loss"] if loss_sum is None
                                    else loss_sum + metrics["loss"])
                        # guard: chain (finite, grad_norm) lazily on device —
                        # synced with the same interval transfer below
                        guard.accumulate(metrics)
                    if log_step:
                        if wd:
                            # per-step syncs already landed (and checked) every
                            # loss; the interval mean is free host math
                            loss = host_loss_sum / interval_steps
                        else:
                            # one transfer = sync; the sum chains every step in
                            # the interval, so non-finite losses propagate into
                            # it (the interval mean cannot pin the offending
                            # step — only the watchdog's per-step sync can)
                            with tracer.span("interval_sync"):
                                loss = float(loss_sum) / interval_steps
                            guard.check_loss(loss, epoch, step + 1,
                                             where=f"in epoch {epoch} interval "
                                                   f"ending step {step + 1}")
                            guard.flush(epoch, step + 1)
                        loss_sum, host_loss_sum, interval_steps = None, 0.0, 0
                        if tracer.enabled:
                            # an expert model's routing counters of this
                            # step (the interval's sync has just landed)
                            for key in ("moe_held_slots",
                                        "moe_buffer_fill",
                                        "moe_load_max_over_mean",
                                        "moe_top1_weight_mean"):
                                if key in metrics:
                                    tracer.counter("moe/" + key[4:],
                                                   float(metrics[key]))
                        now = time.perf_counter()
                        logger.train_interval(
                            epoch,
                            100.0 * (step + 1) / steps,
                            interval_samples / max(1e-9, now - interval_tick),
                            loss,
                        )
                        interval_tick, interval_samples = now, 0
                t_step_end = time.perf_counter_ns()
                stats.record_step(epoch, (t_step_end - t_step) / 1e9)
                if tracer.enabled and step == ep_start and \
                        getattr(strategy, "timetable", None) is not None:
                    # pipeline runtimes: project the schedule timetable
                    # onto this step's window as per-stage pipe_tick marker
                    # spans — telemetry/bubble.py's food. Once per epoch:
                    # the projection is identical every step (the schedule
                    # is static), so more would only fill the ring.
                    from ddlbench_tpu.telemetry.bubble import emit_tick_spans

                    emit_tick_spans(tracer, strategy.timetable, t_step,
                                    t_step_end, step=global_step - 1)
                if (cfg.checkpoint_every_steps
                        and (step + 1) % cfg.checkpoint_every_steps == 0
                        and step != steps - 1):  # epoch-end save covers last
                    from ddlbench_tpu.train.checkpoint import save_checkpoint

                    # a pending anomaly must apply its policy BEFORE the
                    # commit — under rewind/abort the live state may be
                    # poisoned, and a poisoned commit would become the
                    # rewind target itself
                    guard.flush(epoch, step + 1)
                    if wd:
                        wd.kick()  # the save gets a full deadline
                    with tracer.span("checkpoint_save", epoch=epoch,
                                     step=step):
                        ckpt_pin = save_checkpoint(
                            cfg.checkpoint_dir, epoch, ts, step=step,
                            global_step=global_step,
                            logger_state=logger.state_dict(), seed=cfg.seed,
                            keep=cfg.keep_checkpoints, pin=ckpt_pin,
                            logical=ckpt_logical)
                    if wd:
                        wd.kick()
        finally:
            stream.close()
        # the final step is always a log_step, so the loop already synced on
        # the full ts chain before the clock stops here
        epoch_time = time.perf_counter() - tick
        logger.epoch_done(epoch,
                          (steps - ep_start) * global_batch / epoch_time,
                          epoch_time,
                          input_stall_ms=stream.stall_ms,
                          step_ms=stats.epoch_summary(epoch))

        # Validation epoch (test_epoch parity, mnist_pytorch.py:102-133).
        with tracer.span("eval_epoch", epoch=epoch):
            val = evaluate(cfg, strategy, ts, data, epoch, wd,
                           prefetcher=prefetch, guard=guard)
        logger.valid_epoch(epoch, val["loss"], val["accuracy"],
                           top5=val.get("top5"))
        summary_acc = val["accuracy"]

        if cfg.checkpoint_dir:
            from ddlbench_tpu.train.checkpoint import save_checkpoint

            if wd:
                wd.kick()  # the save itself gets a full deadline
            with tracer.span("checkpoint_save", epoch=epoch):
                ckpt_pin = save_checkpoint(
                    cfg.checkpoint_dir, epoch, ts,
                    global_step=global_step,
                    logger_state=logger.state_dict(),
                    seed=cfg.seed, keep=cfg.keep_checkpoints, pin=ckpt_pin,
                    logical=ckpt_logical)
            if wd:
                wd.kick()

    xla_window.close()  # a window that outlived the run still gets flushed
    result = logger.summary(summary_acc, step_time=stats.run_summary())
    if guard.active:
        # anomalies absorbed / skipped / rewound / backed off — the
        # robustness half of the benchmark result (chaosbench aggregates
        # the per-event "guard:" lines across attempts too)
        result["guard"] = guard.summary()
    result["device"] = _device_record(ts)
    result["train_state"] = ts
    return result


def _device_record(ts) -> Dict[str, Any]:
    """The device the run executed on, as jax reports it, and where the
    work lives while the train state ``ts`` is still alive: how many
    devices hold a shard of it, and every local device's live bytes (0 on
    the CPU backend, which keeps no memory statistics). Supervisors
    (chaosbench, chip_smoke.py) read this from the result line instead of
    touching a backend themselves."""
    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
        "state_devices": len(set().union(*(
            leaf.sharding.device_set for leaf in jax.tree.leaves(ts)
            if isinstance(leaf, jax.Array)))),
        "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use", 0)
                         for d in jax.local_devices()],
    }


def _commit_preemption(cfg: RunConfig, ts, epoch: int, step: int,
                       global_step: int, logger: MetricLogger, tracer, wd,
                       pin: Optional[str],
                       logical: Optional[Dict[str, Any]] = None) -> None:
    """Graceful preemption at the (epoch, step) boundary: commit the state
    as of the last COMPLETED step through the atomic protocol, then raise
    :class:`GracefulPreemption` (cli.py maps it to PREEMPT_EXIT_CODE)."""
    from ddlbench_tpu.train.checkpoint import checkpoint_name, save_checkpoint

    # state at this boundary = end of step-1 (or the previous epoch's end
    # when preempted before the epoch's first dispatch)
    ck_epoch, ck_step = (epoch, step - 1) if step > 0 else (epoch - 1, None)
    if pin and os.path.basename(pin) == checkpoint_name(ck_epoch, ck_step) \
            and os.path.isdir(pin):
        # zero steps completed since the pinned commit (preempted right
        # after a periodic save, or at the first boundary after a resume):
        # re-saving would rmtree-and-rewrite the only restorable state —
        # a second signal mid-save would destroy it for nothing
        where = (f"epoch {ck_epoch} step {ck_step}" if ck_step is not None
                 else f"epoch {ck_epoch}")
        # prefix must stay "preempt: checkpoint committed" — the chaosbench
        # supervisor matches it to classify the exit as graceful
        print(f"preempt: checkpoint committed at {where} (reusing the "
              f"existing commit)", flush=True)
        raise GracefulPreemption(
            f"preemption checkpoint committed at {where}",
            checkpoint_path=pin)
    if wd:
        wd.kick()  # the save gets a full deadline
    span_args = {"epoch": ck_epoch}
    if ck_step is not None:
        span_args["step"] = ck_step
    with tracer.span("checkpoint_save", **span_args):
        path = save_checkpoint(
            cfg.checkpoint_dir, ck_epoch, ts, step=ck_step,
            global_step=global_step, logger_state=logger.state_dict(),
            seed=cfg.seed, keep=cfg.keep_checkpoints, pin=pin,
            logical=logical)
    where = (f"epoch {ck_epoch} step {ck_step}" if ck_step is not None
             else f"epoch {ck_epoch}")
    print(f"preempt: checkpoint committed at {where}", flush=True)
    raise GracefulPreemption(
        f"preemption checkpoint committed at {where}", checkpoint_path=path)


def evaluate(cfg: RunConfig, strategy, ts, data, epoch: int,
             wd: Optional[HangWatchdog] = None,
             prefetcher: Optional[Prefetcher] = None,
             guard: Optional[StabilityGuard] = None) -> Dict[str, float]:
    """One validation epoch with on-device metric accumulation.

    loss*count / correct / correct5 / count are summed as lazy jax.Arrays —
    ONE device->host transfer per epoch instead of a blocking ``float()``
    per step, so eval steps pipeline like train steps. With a watchdog
    ARMED, eval keeps the per-step sync (train-path parity,
    train/watchdog.py semantics: the deadline must bound DEVICE progress,
    which the prefetcher heartbeat — host input progress — cannot prove);
    the heartbeat additionally covers gaps where slow input production is
    the bottleneck."""
    pf = prefetcher or Prefetcher(data, strategy.shard_batch,
                                  depth=cfg.prefetch_depth, watchdog=wd)
    loss_sum = correct_sum = correct5_sum = count_sum = None
    saw_correct5 = True
    steps = 0

    def acc(total, v):
        return v if total is None else total + v

    tracer = get_tracer()
    stream = pf.stream(epoch, train=False)
    try:
        for fetched in stream:
            with tracer.span("eval_step"):
                m = strategy.eval_step(ts, *fetched.batch)
            steps += 1
            if wd is not None:
                # armed watchdog: per-step transfer = sync, so a device hang
                # mid-eval dies within one deadline (and a non-finite eval
                # loss is attributed to its actual step)
                step_loss = float(m["loss"])
                if guard is not None:  # unified policy surface
                    guard.check_loss(step_loss, epoch, steps, train=False)
                else:
                    check_finite(step_loss, epoch, steps, cfg.nan_policy)
                wd.kick()
            loss_sum = acc(loss_sum, m["loss"] * m["count"])
            correct_sum = acc(correct_sum, m["correct"])
            count_sum = acc(count_sum, m["count"])
            if "correct5" in m:
                correct5_sum = acc(correct5_sum, m["correct5"])
            else:  # strategy without prec@5 support: report None, never 0.0
                saw_correct5 = False
    finally:
        stream.close()
    if steps:  # ONE device->host transfer for all accumulators = epoch sync
        with tracer.span("eval_epoch_sync"):
            loss_sum, correct_sum, correct5_sum, count_sum = jax.device_get(
                (loss_sum, correct_sum,
                 correct5_sum if saw_correct5 else 0, count_sum))
    total_count = int(count_sum) if steps else 0
    loss = float(loss_sum) / max(1, total_count) if steps else 0.0
    # detection happens at the one epoch-end transfer, so no specific step
    # can honestly be blamed. The guard is the one policy surface for this
    # site too (skip/rewind degrade to warn: eval has no update to drop).
    if guard is not None:
        guard.check_loss(loss, epoch, steps, train=False,
                         where=f"in validation epoch {epoch} "
                               f"(epoch-end check)")
    else:
        check_finite(loss, epoch, steps, cfg.nan_policy,
                     where=f"in validation epoch {epoch} (epoch-end check)")
    if wd:
        wd.kick()  # the epoch-end transfer above proved device progress
    return {
        "loss": loss,
        "accuracy": int(correct_sum) / max(1, total_count) if steps else 0.0,
        # prec@5 (PipeDream eval parity, main_with_runtime.py:639-653);
        # None when unsupported by the strategy or when no eval step ran
        "top5": (int(correct5_sum) / total_count
                 if saw_correct5 and steps and total_count else None),
    }
