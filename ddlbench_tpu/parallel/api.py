"""Strategy factory: one entry point for the four parallelization engines.

The reference binds workloads to engines by having nine separate driver
scripts (SURVEY.md §1 L4); here ``make_strategy(cfg)`` returns an object with
a uniform interface consumed by one train loop (ddlbench_tpu/train/loop.py):

* ``init(key) -> train_state`` (device-placed/sharded)
* ``shard_batch(x, y) -> batch_args`` — place a global batch onto the
  strategy's mesh. The result is an OPAQUE tuple of the data arguments the
  step functions expect; callers always splat it
  (``train_step(ts, *batch_args, lr)``). Most strategies return (x, y); the
  hetero engines return per-device row shards plus a per-microbatch
  valid-count vector. CONTRACT: ``shard_batch`` must be callable off the
  main thread — the async input pipeline (data/prefetch.py) runs it on a
  producer thread so device placement overlaps compute. Implementations
  must therefore be pure placement (device_put / reshape of their
  arguments + immutable self state), never mutate per-call host state, and
  never assume main-thread-only facilities (signal handlers, thread-local
  tracing contexts).
* ``train_step(train_state, *batch_args, lr) -> (train_state, metrics)``
  (jitted)
* ``eval_step(train_state, *batch_args) -> {loss, correct, count[,
  correct5]}`` (jitted; ``correct5`` is the optional prec@5 numerator — the
  loop reports top5 only when a strategy provides it)
* ``world_size``
"""

from __future__ import annotations

import json
import os
from typing import Optional, Sequence

import jax

from ddlbench_tpu.config import RunConfig
from ddlbench_tpu.models.zoo import get_model



# Persisted auto-partition plan (reference parity: the optimizer's output
# outlives the process as gpus=N.txt + generated stage code,
# optimizer_graph_hierarchical.py:334-346 / run_template.sh:436-498). Here
# the plan is data: the graph-level stage bounds plus the cfg fields the
# plan rewrote. Persisting it next to the checkpoints makes --resume
# independent of profiling noise — a time-mode re-profile could otherwise
# pick different bounds and fail the restore on shape mismatch.
_PLAN_FILE = "partition.json"


def _plan_path(cfg: RunConfig):
    return (os.path.join(cfg.checkpoint_dir, _PLAN_FILE)
            if cfg.checkpoint_dir else None)


def _load_plan(cfg: RunConfig, key: dict):
    """Returns (plan_or_None, keep_existing): ``keep_existing`` marks a
    readable plan whose key mismatched — it belongs to a DIFFERENT run
    configuration (possibly a flag typo) and must not be overwritten by
    this run's re-profile."""
    path = _plan_path(cfg)
    if not (cfg.resume and path and os.path.exists(path)):
        return None, False
    try:
        with open(path) as f:
            plan = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        print(f"auto-partition: ignoring unreadable plan {path} ({e}); "
              f"re-profiling", flush=True)
        return None, False
    pkey = plan.get("key")
    if _stale_pre_plan_key(pkey, key):
        # migration shim: a stale pre-plan-mode partition.json (written
        # before _plan_key carried the "plan" field) that otherwise
        # matches this run must invalidate LOUDLY and re-solve — never
        # KeyError on the missing field, and never count as a foreign
        # config (keep_existing stays False so the re-solve overwrites it)
        print(f"auto-partition: persisted plan {path} predates the "
              f"--plan mode field; invalidating (re-profiling and "
              f"re-writing)", flush=True)
        return None, False
    if pkey != key:
        print(f"auto-partition: persisted plan {path} was computed for "
              f"{plan.get('key')}, run is {key}; re-profiling (the "
              f"existing plan file is kept)", flush=True)
        return None, True
    if plan.get("fingerprint") != _plan_fingerprint(cfg):
        # same run identity but the COST MODEL changed (--hbm-gb /
        # --profile-mode): the persisted bounds were solved under other
        # feasibility gates — re-profile in place (missing fingerprint =
        # a pre-fingerprint file, invalidated the same way)
        print(f"auto-partition: persisted plan {path} was solved under a "
              f"different cost model ({plan.get('fingerprint')}); "
              f"re-profiling and re-writing", flush=True)
        return None, False
    return plan, False


def _plan_key(cfg: RunConfig) -> dict:
    """The fields a persisted plan must match to be reusable: a plan from a
    different model/topology would mis-shard or trip shape asserts, and one
    from different batch/virtual-stage flags would silently override what
    the user asked for. ``pipe_schedule`` and the cost-model mode are part
    of the key too — a plan solved (and whose cost vectors were extracted)
    under one schedule/cost model must never be silently reused by another
    run's timetable. Must be computed from the PRE-rewrite cfg (plans
    rewrite micro_batch_size etc.), so callers capture it up front."""
    mb, chunks = cfg.resolved_batches()
    return {"arch": cfg.arch, "benchmark": cfg.benchmark,
            "strategy": cfg.strategy, "num_devices": cfg.num_devices,
            "num_hosts": cfg.num_hosts, "micro_batch_size": mb,
            "num_microbatches": chunks, "virtual_stages": cfg.virtual_stages,
            "pipe_schedule": cfg.pipe_schedule,
            "pipe_costs": cfg.pipe_costs,
            # the plan MODE is part of the identity: an --auto-partition
            # bounds plan and a --plan auto full-mix plan live in the same
            # file but mean different things (pre-plan-mode files are
            # invalidated loudly by the migration shim in _load_plan /
            # planner._load_cached, never KeyError'd)
            "plan": cfg.plan}


def _plan_fingerprint(cfg: RunConfig) -> dict:
    """The cost-model half of a persisted plan's identity: the key names
    WHAT was planned (model, topology, batch grammar, plan mode); a plan
    additionally depends on HOW costs and feasibility were priced, so the
    fingerprint pins the profile mode and the hardware constants
    (--hbm-gb rides cfg.hardware). Shared by the --auto-partition bounds
    plan here and the --plan auto record (partition/planner.py)."""
    import dataclasses

    return {"profile_mode": cfg.profile_mode,
            "hardware": dataclasses.asdict(cfg.hardware)}


def _stale_pre_plan_key(old_key, key: dict) -> bool:
    """The migration shim's ONE match rule: ``old_key`` predates the
    plan-mode field (no "plan" entry) but otherwise names exactly this
    run's configuration — whatever mode is now looking at it. Shared by
    the loader and writer here; planner._load_cached deliberately uses a
    BROADER rule (any pre-plan-mode file invalidates a --plan auto read,
    matching or not, since the old schema carries no plan_auto record)."""
    return (isinstance(old_key, dict) and "plan" not in old_key
            and {**old_key, "plan": key.get("plan")} == key)


def _backup_foreign_plan(path: str, key: dict) -> None:
    """A fresh (non-resume) run pointed at a checkpoint_dir holding a
    DIFFERENT configuration's plan — e.g. a flag typo — must not silently
    clobber it next to that run's checkpoints (ADVICE r3): keep a backup.
    Shared by the --auto-partition bounds writer below and the --plan auto
    full-mix writer (partition/planner.py). A stale pre-plan-mode file of
    the SAME configuration is not foreign — the migration shim already
    invalidated it, so the re-solve overwrites in place."""
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            old_key = json.load(f).get("key")
    except (json.JSONDecodeError, OSError):
        old_key = None
    if _stale_pre_plan_key(old_key, key):
        # pre-plan-mode file of this very config (whichever mode is now
        # re-solving it): the migration shim already invalidated it
        # loudly, so the re-solve overwrites in place
        return
    if old_key != key:
        bak = path + ".bak"
        n = 1
        while os.path.exists(bak):  # never clobber an earlier backup
            bak = f"{path}.bak{n}"
            n += 1
        os.replace(path, bak)
        print(f"auto-partition: existing plan {path} belongs to a "
              f"different configuration ({old_key}); backed up to {bak}",
              flush=True)


def _save_plan(key: dict, cfg: RunConfig, graph_bounds) -> None:
    path = _plan_path(cfg)
    if path is None:
        return
    os.makedirs(cfg.checkpoint_dir, exist_ok=True)
    _backup_foreign_plan(path, key)
    repl = cfg.stage_replication
    payload = {
        "key": key,
        "fingerprint": _plan_fingerprint(cfg),
        "graph_bounds": [int(b) for b in graph_bounds],
        "num_stages": cfg.num_stages,
        "dp_replicas": cfg.dp_replicas,
        "stage_replication": list(repl) if repl else None,
        "micro_batch_size": cfg.micro_batch_size,
        "num_microbatches": cfg.num_microbatches,
        "virtual_stages": cfg.virtual_stages,
        # schedule/cost provenance: which timetable and cost model the
        # plan was solved under, plus the resolved per-chunk (f, b, w)
        # half-tick vectors so a --resume reuses the exact weighted
        # timetable without re-profiling
        "pipe_schedule": cfg.pipe_schedule,
        "pipe_costs": cfg.pipe_costs,
        "pipe_cost_vectors": ([list(v) for v in cfg.pipe_cost_vectors]
                              if cfg.pipe_cost_vectors else None),
    }
    # atomic: the window-catching harness SIGKILLs overdue runs, and a
    # truncated plan file would break every later --resume
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def _measured_bubbles(cfg: RunConfig):
    """{schedule: measured bubble fraction} reduced from the trace JSON a
    prior run left under ``--trace`` (``--schedule-trace PATH``), via the
    telemetry/bubble.py reducer — the advisor then ranks that schedule by
    what it actually did on this machine instead of the analytic model.
    None (advice stays analytic) when no trace is supplied, it is
    unreadable, or it carries no pipe_tick projections."""
    if not cfg.schedule_trace:
        return None
    from ddlbench_tpu.telemetry.bubble import bubble_fraction

    try:
        with open(cfg.schedule_trace) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"schedule advisor: unreadable --schedule-trace "
              f"{cfg.schedule_trace} ({e}); using analytic bubbles",
              flush=True)
        return None
    got = bubble_fraction(doc)
    if not got["tick_spans"] or not got.get("schedule"):
        print(f"schedule advisor: {cfg.schedule_trace} carries no "
              f"pipe_tick projections; using analytic bubbles", flush=True)
        return None
    print(f"schedule advisor: measured bubble "
          f"{got['bubble_fraction']:.4f} for {got['schedule']} "
          f"({got['tick_spans']} tick spans, {got['stages']} stages, "
          f"{cfg.schedule_trace})", flush=True)
    return {got["schedule"]: got["bubble_fraction"]}


def make_strategy(cfg: RunConfig, devices: Optional[Sequence[jax.Device]] = None,
                  input_time_ms: float = 0.0):
    """Build the configured strategy. ``input_time_ms``: measured
    per-MICROBATCH data-loading cost (profiler.measure_input_ms scaled by
    the caller) — with --auto-partition it becomes the profile graph's Input
    node, folded into layer 0's stage for the partitioning DP
    (profiler.fold_input_node; train/loop.py supplies it for the -s path)."""
    if cfg.plan == "auto":
        # normally already resolved at run start (train/loop.py), where the
        # rewritten strategy also shapes the data stream and lr scaling;
        # direct callers (tools, tests) get the same rewrite here
        from ddlbench_tpu.partition.planner import resolve_auto_plan

        cfg = resolve_auto_plan(cfg, input_time_ms=input_time_ms)
    cfg.validate()
    model = get_model(cfg.arch, cfg.benchmark,
                      moe_capacity_factor=cfg.moe_capacity_factor,
                      attention_backend=cfg.attention_backend)

    stage_bounds = None
    if cfg.auto_partition and cfg.strategy in ("gpipe", "pipedream"):
        # profile -> partition -> EXECUTE the plan: the reference's PipeDream
        # phases 1-3 (profiler main.py -> optimizer_graph_hierarchical.py ->
        # convert_graph_to_model.py), whose output actually configures its
        # runtime (run_template.sh:436-498). The plan's stage bounds and
        # per-stage replication factors drive the mesh: uniform plans run on
        # the 2-D ('data','stage') mesh, uneven plans on parallel/hetero.py's
        # flat 'pipe' axis.
        from ddlbench_tpu.partition.optimizer import (
            partition_hierarchical,
            stage_bounds_from_graph,
        )
        from ddlbench_tpu.profiler.profile import profile_model

        mb, chunks = cfg.resolved_batches()
        from ddlbench_tpu.models.branchy import get_dag

        spec = cfg.dataset()
        dag = get_dag(cfg.arch, spec.image_size, spec.num_classes)
        dag_shapes = None
        plan_key = _plan_key(cfg)  # pre-rewrite flags; plans rewrite cfg
        persisted, keep_existing = _load_plan(cfg, plan_key)
        applied = False
        if persisted is not None:
            cfg_before = cfg
            try:
                stage_bounds = [int(b) for b in persisted["graph_bounds"]]
                repl_p = persisted.get("stage_replication")
                cv_p = persisted.get("pipe_cost_vectors")
                cfg = cfg.replace(
                    num_stages=persisted["num_stages"],
                    dp_replicas=persisted["dp_replicas"],
                    stage_replication=tuple(repl_p) if repl_p else None,
                    micro_batch_size=persisted["micro_batch_size"],
                    num_microbatches=persisted["num_microbatches"],
                    virtual_stages=persisted.get("virtual_stages", 1),
                    pipe_cost_vectors=(tuple(tuple(int(x) for x in v)
                                             for v in cv_p)
                                       if cv_p else None))
                cfg.validate()
                applied = True
                print(f"auto-partition: reusing persisted plan "
                      f"({_plan_path(cfg)}, bounds={stage_bounds})",
                      flush=True)
            except (KeyError, TypeError, ValueError) as e:
                # schema drift / hand edit / no-longer-valid combination:
                # fall back to re-profiling, same as no plan at all
                cfg = cfg_before
                print(f"auto-partition: persisted plan not applicable "
                      f"({e!r}); re-profiling", flush=True)
        if not applied:
            if dag is not None:
                # branchy arch: profile the REAL dataflow DAG (the reference
                # traces these with TensorWrapper, graph_creator.py:55-195),
                # then chainize it at NODE granularity with packed-crossing
                # boundary sizes — the partitioner may cut at any position
                # (incl. non-articulation cuts where several tensors cross,
                # e.g. between nasnet cells) and the chosen cuts are executed
                # via branchy.to_packed_chain below
                from ddlbench_tpu.profiler.profile import (packed_chain_graph,
                                                           profile_dag)

                cdtype = jax.numpy.dtype(cfg.compute_dtype)
                dag_graph, dag_shapes = profile_dag(
                    dag, mb, mode=cfg.profile_mode, dtype=cdtype,
                    hw=cfg.hardware, return_shapes=True)
                # one itemsize everywhere: the profile's activation sizes and
                # the input-crossing bytes below must share units for the DP's
                # cut comparison to be meaningful
                graph = packed_chain_graph(dag_graph, dag, mb,
                                           itemsize=cdtype.itemsize)
                if input_time_ms > 0.0:
                    # fold_input_node semantics: data loading prices into the
                    # stage hosting block 0
                    graph.topological_sort()[0].forward_compute_time += (
                        input_time_ms)
            else:
                graph = profile_model(model, mb, mode=cfg.profile_mode,
                                      hw=cfg.hardware,
                                      input_time_ms=input_time_ms)
                # DP view: the Input node folds into layer 0's stage — the
                # reference co-locates its DataLoader with stage 0's ranks, and
                # a chip cannot run "just data loading", so Input must never
                # form its own stage.
                from ddlbench_tpu.profiler.profile import fold_input_node

                graph = fold_input_node(graph)

            if cfg.virtual_stages > 1:
                # interleaved runtimes live on the 2-D grid, whose plans are
                # uniform by construction — search ONLY that executable family
                # (partition_interleaved) and execute the winner, rather than
                # emitting a hetero plan the V>1 runtime would have to drop
                from ddlbench_tpu.partition.optimizer import partition_interleaved

                iplan = partition_interleaved(
                    graph, cfg.num_devices, cfg.virtual_stages, cfg.hardware,
                    num_hosts=cfg.num_hosts, num_microbatches=chunks,
                    micro_batch=mb)
                stage_bounds = list(iplan.bounds)
                # replicas split each microbatch's rows — the caller's global
                # batch M*mb is unchanged (same convention as the uniform-plan
                # rewrite below)
                cfg = cfg.replace(
                    num_stages=iplan.num_stages, dp_replicas=iplan.replication,
                    stage_replication=None,
                    micro_batch_size=mb // iplan.replication,
                    num_microbatches=chunks)
                print(
                    f"auto-partition (interleaved): executing "
                    f"S={iplan.num_stages} x V={iplan.virtual_stages} "
                    f"(replication={iplan.replication}, bounds={stage_bounds}, "
                    f"bottleneck {iplan.pipeline_time_ms:.3f} ms)",
                    flush=True,
                )
                plan = None
            else:
                plan = partition_hierarchical(
                    graph, cfg.num_devices, cfg.hardware, num_hosts=cfg.num_hosts
                )
                repl = tuple(s.replication for s in plan.stages)
            if plan is not None:
                if repl and len(set(repl)) == 1 and mb % repl[0] == 0:
                    # uniform plan: normalize straight to the 2-D-mesh
                    # form (the same rewrite the strategy dispatch below
                    # applies) so event schedules / the hybrid engine —
                    # which reject hetero stage_replication tuples — can
                    # still execute the plan's bounds instead of falling
                    # back to balanced ones
                    cfg_planned = cfg.replace(
                        num_stages=len(repl), dp_replicas=repl[0],
                        stage_replication=None,
                        micro_batch_size=mb // repl[0],
                        num_microbatches=chunks)
                else:
                    cfg_planned = cfg.replace(
                        num_stages=None, dp_replicas=1,
                        stage_replication=repl)
                try:
                    cfg_planned.validate()
                    stage_bounds = plan.stage_bounds()
                    cfg = cfg_planned
                    print(
                        f"auto-partition: executing plan "
                        f"{[(s.start, s.end, s.replication) for s in plan.stages]} "
                        f"(bounds={stage_bounds}, replication={repl}, "
                        f"bottleneck {plan.pipeline_time_ms:.3f} ms)",
                        flush=True,
                    )
                except ValueError as e:
                    # e.g. micro-batch not divisible by a replication factor:
                    # keep the profiled balanced split rather than fail the run
                    stage_bounds = stage_bounds_from_graph(
                        graph, cfg.resolved_stages())
                    print(
                        f"auto-partition: plan {repl} not executable ({e}); "
                        f"falling back to balanced bounds {stage_bounds}",
                        flush=True,
                    )
            if cfg.pipe_costs == "profile":
                # cost-weighted timetables: sum the profile graph's
                # per-node times over the CHOSEN chunk bounds and
                # quantize onto the half-tick grid — the event runtime
                # then executes a table packed for the plan's genuinely
                # uneven chunks instead of the F=B=W unit fiction
                from ddlbench_tpu.partition.schedule import (
                    quantize_cost_vectors_clipped)
                from ddlbench_tpu.profiler.profile import chunk_cost_ms

                f_ms, b_ms = chunk_cost_ms(graph, stage_bounds)
                # the searched packer needs to SEE the real unevenness:
                # an 8-half-tick cap flattens extreme profiles into the
                # same grid the heuristics already pack (no-silent-caps)
                max_units = 64 if cfg.pipe_schedule == "searched" else 8
                vectors, clipped = quantize_cost_vectors_clipped(
                    f_ms, b_ms, max_units=max_units)
                cfg = cfg.replace(pipe_cost_vectors=vectors)
                print(f"auto-partition: cost-weighted timetable vectors "
                      f"(f/b/w half-ticks per chunk) {vectors}", flush=True)
                if clipped:
                    print(f"auto-partition: WARNING {clipped} event cost(s) "
                          f"clipped at the {max_units}-half-tick "
                          f"quantization cap — the timetable underweights "
                          f"the most expensive chunks (profile is more "
                          f"uneven than the grid can express)", flush=True)
            if not keep_existing:
                _save_plan(plan_key, cfg, stage_bounds)
        if dag is not None:
            # execute the chosen node-position cuts: one packed composite
            # span per chunk, boundaries carry every crossing tensor in one
            # flat buffer (branchy.to_packed_chain docstring)
            from ddlbench_tpu.models.branchy import to_packed_chain

            model = to_packed_chain(dag, stage_bounds[1:-1],
                                    out_shapes=dag_shapes)
            stage_bounds = list(range(len(model.layers) + 1))
            print(f"auto-partition: packed-boundary chain, "
                  f"{len(model.layers)} spans", flush=True)
        if cfg.strategy == "gpipe":
            from ddlbench_tpu.partition.schedule import (
                recommend_schedule, recommend_virtual_stages)

            _, chunks = cfg.resolved_batches()
            table = recommend_virtual_stages(
                cfg.resolved_stages(), chunks, len(model.layers))
            print(f"schedule advisor (S={cfg.resolved_stages()}, M={chunks}): "
                  f"{table}", flush=True)
            # schedules are data now: advise the best TIMETABLE at the
            # chosen V, not just the best V — ranked by the cost-weighted
            # bubble when the plan carries cost vectors, and by the
            # MEASURED bubble for any schedule a --schedule-trace covers
            # (reality outranks the model, ROADMAP item 2c)
            measured = _measured_bubbles(cfg)
            sched = recommend_schedule(cfg.resolved_stages(), chunks,
                                       cfg.virtual_stages,
                                       costs=cfg.pipe_cost_vectors,
                                       measured=measured)
            best = sched[0]
            tail = ("" if best["schedule"] == cfg.pipe_schedule else
                    f" (run has --pipe-schedule {cfg.pipe_schedule})")
            basis = ("measured" if "bubble_measured" in best
                     else "weighted" if cfg.pipe_cost_vectors else "analytic")
            print(f"schedule advisor: best schedule at V="
                  f"{cfg.virtual_stages} is {best['schedule']} "
                  f"({basis} bubble "
                  f"{best.get('bubble_measured', best['bubble'])})"
                  f"{tail}: {sched}", flush=True)
    if stage_bounds is None and cfg.plan_bounds is not None and \
            cfg.strategy in ("gpipe", "pipedream"):
        # Explicit stage bounds (--plan-bounds, or a solved --plan auto
        # rewrite): the engine executes exactly this split instead of its
        # balanced default — the end of the profile -> graph -> plan loop.
        # config.validate could not know the layer count; check it here
        # (a named error, not the engine's bare assert)
        if cfg.plan_bounds[-1] != len(model.layers):
            raise ValueError(
                f"--plan-bounds {list(cfg.plan_bounds)} must end at the "
                f"model's layer count ({cfg.arch} has "
                f"{len(model.layers)} layers)")
        stage_bounds = [int(b) for b in cfg.plan_bounds]
    if (stage_bounds is None and cfg.strategy in ("gpipe", "pipedream")):
        # Manual (non-auto-partition) pipeline run on a branchy arch: the
        # articulation chain is hopeless to balance (nasnet's whole cell
        # stack is ONE block — two tensors cross every cell boundary), so
        # split at NODE granularity over packed boundaries instead; the
        # engines' balanced default split then has n positions to choose
        # from, like any chain model.
        from ddlbench_tpu.models.branchy import get_dag, to_packed_chain

        spec_b = cfg.dataset()
        dag_b = get_dag(cfg.arch, spec_b.image_size, spec_b.num_classes)
        if dag_b is not None:
            model = to_packed_chain(
                dag_b, range(1, len(dag_b.layers)))
            print(f"branchy arch: node-granular packed chain "
                  f"({len(model.layers)} layers) for the stage split",
                  flush=True)
    if cfg.strategy == "single":
        from ddlbench_tpu.parallel.single import SingleStrategy

        return SingleStrategy(model, cfg)
    if cfg.strategy == "dp":
        from ddlbench_tpu.parallel.dp import DPStrategy, make_data_mesh

        mesh = make_data_mesh(cfg.num_devices, devices)
        return DPStrategy(model, cfg, mesh)
    repl = tuple(cfg.stage_replication or ())
    if repl and len(set(repl)) == 1:
        # Uniform plan: the regular 2-D ('data','stage') mesh executes it
        # (cheaper than the flat-axis conveyor). stage_replication semantics
        # are "replicas split each microbatch's rows", so the per-replica
        # micro-batch becomes mb/r — the global batch stays M*mb, matching
        # cfg.global_batch()'s stage_replication accounting for the caller.
        mb_, chunks_ = cfg.resolved_batches()
        cfg = cfg.replace(stage_replication=None, dp_replicas=repl[0],
                          num_stages=len(repl),
                          micro_batch_size=mb_ // repl[0],
                          num_microbatches=chunks_)
        repl = ()
    if cfg.strategy == "gpipe":
        if repl:
            from ddlbench_tpu.parallel.hetero import HeteroGPipeStrategy

            return HeteroGPipeStrategy(model, cfg, devices=devices,
                                       stage_bounds=stage_bounds)
        if cfg.tp_size > 1:
            from ddlbench_tpu.parallel.tpp import TPGPipeStrategy

            return TPGPipeStrategy(model, cfg, devices=devices,
                                   stage_bounds=stage_bounds)
        if cfg.pipe_schedule != "fill-drain":
            # schedule-programmable runtime: 1f1b / interleaved /
            # zero-bubble are TIMETABLES compiled by one event-mode engine
            # (parallel/pipeline_rt.py), not engines of their own
            from ddlbench_tpu.parallel.pipeline_rt import (
                ScheduledPipelineStrategy)

            return ScheduledPipelineStrategy(model, cfg, devices=devices,
                                             stage_bounds=stage_bounds)
        from ddlbench_tpu.parallel.gpipe import GPipeStrategy

        return GPipeStrategy(model, cfg, devices=devices, stage_bounds=stage_bounds)
    if cfg.strategy == "pipedream":
        if repl:
            from ddlbench_tpu.parallel.hetero import HeteroPipeDreamStrategy

            return HeteroPipeDreamStrategy(model, cfg, devices=devices,
                                           stage_bounds=stage_bounds)
        from ddlbench_tpu.parallel.pipedream import PipeDreamStrategy

        return PipeDreamStrategy(model, cfg, devices=devices, stage_bounds=stage_bounds)
    if cfg.strategy == "sp":
        from ddlbench_tpu.parallel.sp import SPStrategy

        return SPStrategy(model, cfg, devices=devices)
    if cfg.strategy == "tp":
        from ddlbench_tpu.parallel.sharded import TPStrategy

        return TPStrategy(model, cfg, devices=devices)
    if cfg.strategy == "fsdp":
        from ddlbench_tpu.parallel.sharded import FSDPStrategy

        return FSDPStrategy(model, cfg, devices=devices)
    if cfg.strategy == "ep":
        from ddlbench_tpu.parallel.ep import EPStrategy

        return EPStrategy(model, cfg, devices=devices)
    raise ValueError(cfg.strategy)
