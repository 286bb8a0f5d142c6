"""Typed configuration for the whole framework.

The reference stacks four config mechanisms (bash getopts flags, env vars as a
cross-process bus, per-script argparse, and PipeDream's generated JSON confs —
see reference run/run/run.sh:16-47, run/run/run_template.sh:70-73,
benchmark/mnist/mnist_pytorch.py:157-160, optimizer/templates/conf.json.template).
Here there is exactly one: a frozen dataclass, constructible from CLI flags
(see ddlbench_tpu/cli.py) or from a dict.

Hardware cost-model constants (the reference inlines NETWORK_BANDWIDTH=5e9,
PCIE_BANDWIDTH=32e9, MEMORY_SIZE=11e9|24e9 in bash, run_template.sh:414-420)
live in :class:`HardwareModel`, defaulted to TPU v5e numbers, and feed the
pipeline partitioner (ddlbench_tpu/partition/optimizer.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    """Shape/size blueprint of one benchmark dataset.

    Mirrors the synthetic-data factory specs in the reference
    (benchmark/generate_synthetic_data.py:75-107). ``kind`` distinguishes image
    workloads (NHWC float input, one label per sample) from token workloads
    (int sequence input, next-token labels) — the sequence-length benchmark
    axis the reference approximates spatially with "highres" (SURVEY.md §5.7).
    """

    name: str
    image_size: Tuple[int, ...]  # (H, W, C) for images; (T,) for tokens
    num_classes: int  # classes, or vocab size for tokens
    train_size: int
    test_size: int
    kind: str = "image"  # "image" | "tokens" | "seq2seq"
    # seq2seq only: length of the source segment within the T-token stream
    # (positions < src_len are the source; loss is masked there).
    src_len: Optional[int] = None

    def __post_init__(self) -> None:
        if self.kind == "seq2seq":
            if self.src_len is None:
                raise ValueError("kind='seq2seq' requires src_len")
            if not 0 < self.src_len < self.image_size[0]:
                raise ValueError(
                    f"src_len {self.src_len} must be inside the "
                    f"{self.image_size[0]}-token stream"
                )

    @property
    def seq_len(self) -> int:
        assert self.kind in ("tokens", "seq2seq")
        return self.image_size[0]


DATASETS: Mapping[str, DatasetSpec] = {
    "mnist": DatasetSpec("mnist", (28, 28, 1), 10, 60_000, 10_000),
    "cifar10": DatasetSpec("cifar10", (32, 32, 3), 10, 50_000, 10_000),
    "imagenet": DatasetSpec("imagenet", (224, 224, 3), 1000, 1_281_167, 50_000),
    # "highres" is the reference's activation-memory stressor
    # (generate_synthetic_data.py:100-107): 512x512x3, 1000 classes.
    "highres": DatasetSpec("highres", (512, 512, 3), 1000, 50_000, 10_000),
    # Token workloads (new first-class axis, not reference parity): a standard
    # LM context and a long-context stressor for sequence parallelism.
    "synthtext": DatasetSpec("synthtext", (1024,), 32_768, 100_000, 10_000, kind="tokens"),
    "longctx": DatasetSpec("longctx", (8192,), 32_768, 20_000, 2_000, kind="tokens"),
    # 32k context: single-chip-trainable ONLY via the flash kernels
    # (ops/flash_attention.py) + fused head — XLA attention
    # would need a 2 GB score matrix per layer per 8k, and at 32k a single
    # layer's matrix alone exceeds one chip's HBM even under remat.
    "longctx32k": DatasetSpec("longctx32k", (32_768,), 32_768, 5_000, 500,
                              kind="tokens"),
    # Synthetic translation: the seq2seq workload (reference GNMT analog,
    # SURVEY.md §2 C13) as a prefix-LM stream — 128 source + 128 target tokens
    # (reference GNMT trains at max seq length 50-75 per side; see
    # models/seq2seq.py for the re-design rationale).
    "synthmt": DatasetSpec("synthmt", (256,), 32_768, 200_000, 20_000,
                           kind="seq2seq", src_len=128),
}

STRATEGIES = ("single", "dp", "gpipe", "pipedream", "sp", "tp", "fsdp", "ep")

# "auto" = the Pallas flash-attention kernel where ops/flash_attention.
# flash_dispatch says it applies, the einsum elsewhere; "flash"/"xla" force
# one. The CLI choices and validate() read this; make_strategy hands
# RunConfig.attention_backend to zoo.get_model, whose builders pass it down.
ATTENTION_BACKENDS = ("auto", "flash", "xla")

# Per-framework default batch sizes from the reference harness
# (run_template.sh:186-266,377-394; see BASELINE.md). For gpipe the tuple is
# (micro_batch_size, num_microbatches) and the effective global batch is the
# product (benchmark/mnist/mnist_gpipe.py:37-41). For pipedream the number is
# the global batch.
DEFAULT_BATCH: Mapping[str, Mapping[str, Any]] = {
    "single": {"mnist": 128, "cifar10": 64, "imagenet": 32, "highres": 32,
               "synthtext": 16, "longctx": 2, "longctx32k": 1, "synthmt": 64},
    "dp": {"mnist": 128, "cifar10": 64, "imagenet": 32, "highres": 32,
           "synthtext": 16, "longctx": 2, "longctx32k": 1, "synthmt": 64},
    "gpipe": {
        "mnist": (128, 24),
        "cifar10": (64, 32),
        "imagenet": (24, 12),
        "highres": (4, 12),
        "synthtext": (4, 8),
        "longctx": (1, 8),
        "longctx32k": (1, 4),
        "synthmt": (16, 8),
    },
    "pipedream": {"mnist": 512, "cifar10": 256, "imagenet": 128, "highres": 64,
                  "synthtext": 64, "longctx": 8, "longctx32k": 4, "synthmt": 128},
    "sp": {"mnist": 128, "cifar10": 64, "imagenet": 32, "highres": 32,
           "synthtext": 16, "longctx": 2, "longctx32k": 1, "synthmt": 32},
    # ep: per-device batch (batch and experts both shard the one mesh axis)
    "ep": {"synthtext": 8, "longctx": 1, "longctx32k": 1},
}


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Bandwidth/memory constants feeding the partitioner cost model.

    Defaults describe one TPU v5e chip and its interconnect; the reference's
    equivalents (Ethernet 5 GB/s, PCIe 32 GB/s, 11/24 GB HBM) are inlined in
    bash at run_template.sh:414-420.
    """

    # Per-link ICI bandwidth (bytes/s). v5e: ~45 GB/s per direction per link.
    ici_bandwidth: float = 4.5e10
    # DCN (inter-host) bandwidth per host (bytes/s).
    dcn_bandwidth: float = 2.5e10
    # HBM per chip (bytes). v5e: 16 GiB.
    hbm_bytes: float = 16 * 1024**3
    # Peak bf16 matmul throughput per chip (FLOP/s). v5e: ~197 TFLOP/s.
    peak_flops: float = 1.97e14
    # Peak HBM bandwidth per chip (bytes/s). v5e: ~819 GB/s.
    hbm_bandwidth: float = 8.19e11

    def levels(self, num_hosts: int, chips_per_host: int):
        """Hierarchical (bandwidth, machines-per-group) levels, fastest first.

        The reference's hierarchical partitioner solves intra-node (PCIe) then
        inter-node (Ethernet) (optimizer_graph_hierarchical.py:282-297); on TPU
        the analogous levels are ICI within a pod slice and DCN across hosts.
        """
        levels = [(self.ici_bandwidth, chips_per_host)]
        if num_hosts > 1:
            levels.append((self.dcn_bandwidth, num_hosts))
        return levels


# Published peaks of the chip a run was MEASURED on, keyed by the exact
# ``jax.devices()[0].device_kind`` string. Reported utilizations (mfu,
# hbm_util) divide by these and nothing else: a device that is not in the
# table is an error, not a default.
#   "TPU v5 lite" is what a TPU v5e reports through jax 0.9.0 / libtpu
#   0.0.34 (my chip run, PR 21); 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB —
#   Google Cloud documentation, "TPU v5e" (the HardwareModel defaults).
DEVICE_PEAKS = {"TPU v5 lite": HardwareModel()}


def device_peaks(device_kind: str) -> HardwareModel:
    """The published peaks for ``device_kind``; raises on a device the
    table does not hold."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to config.DEVICE_PEAKS with "
            f"its source before reporting mfu/hbm_util") from None


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """One benchmark run = dataset x strategy x model x topology.

    CLI surface mirrors the reference's ``run.sh -b -f -g -n -m -q -p -s``
    (run/run/run.sh:16-47).
    """

    benchmark: str = "mnist"  # mnist | cifar10 | imagenet | highres
    strategy: str = "single"  # single | dp | gpipe | pipedream
    arch: str = "resnet18"
    num_devices: int = 1  # total chips (reference: gpus x nodes)
    num_hosts: int = 1
    synthetic: bool = True
    data_dir: Optional[str] = None
    # Asynchronous input pipeline (data/prefetch.py): the producer thread
    # runs batch production + shard_batch/device_put this many steps ahead
    # of the consuming loop through a bounded ring, overlapping host input
    # work and H2D transfers with device compute. 0 = synchronous
    # (--no-prefetch); batches are (epoch, step)-addressed, so losses are
    # bitwise identical either way.
    prefetch_depth: int = 2
    # Train-time augmentation for the on-disk (-s) image path, mirroring the
    # reference drivers' torchvision transforms (see data/ondisk.py).
    augment: bool = True

    # Training protocol (reference: EPOCHS=3, LOGINTER=25;
    # run_template.sh:71, run.sh:6).
    epochs: int = 3
    log_interval: int = 25
    batch_size: Optional[int] = None  # per-device for single/dp; global for pipedream
    micro_batch_size: Optional[int] = None  # gpipe/pipedream microbatch size
    num_microbatches: Optional[int] = None
    steps_per_epoch: Optional[int] = None  # override dataset-size-derived count

    # Optimizer (reference defaults: mnist/cifar lr .01 momentum .5;
    # imagenet .1/.9 + wd 1e-4, step decay /10 every 30 epochs —
    # mnist_pytorch.py:153-156, imagenet_pytorch.py:44-50,225-229).
    # None = per-workload default: "adam" for seq2seq benchmarks (the
    # reference translation runtime trains with AdamWithWeightStashing,
    # runtime/adam.py + translation/main_with_runtime.py:251-256), else "sgd".
    optimizer: Optional[str] = None  # sgd | adam
    adam_beta1: float = 0.9  # reference betas=(0.9, 0.999)
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    lr: Optional[float] = None
    momentum: Optional[float] = None
    weight_decay: Optional[float] = None
    lr_step_epochs: int = 30
    lr_step_gamma: float = 0.1
    # Goyal-et-al gradual warmup (imagenet_horovod.py:258-275): ramp lr from
    # base to base*world over this many leading epochs, per-batch
    # granularity. 0 disables (the reference enables it only in the Horovod
    # ImageNet driver, warmup_epochs=5).
    warmup_epochs: int = 0
    scale_lr_by_world: bool = True  # Horovod parity: lr x world (mnist_horovod.py:226)
    # ZeRO-1 for dp: shard the optimizer state (momentum, adam m/v) over the
    # 'data' axis while params stay replicated — placement-only, XLA shards
    # the update and all-gathers the delta. No reference analog (its DP
    # replicates everything).
    shard_opt_state: bool = False
    # Explicit sharded weight update for dp (ZeRO-1 via shard_map, not
    # GSPMD placement): gradients reduce-scatter over 'data', the packed
    # flat-vector optimizer state and the weight update live 1/world per
    # chip (contiguous slice), updated params all-gather back. Same wire
    # bytes as the replicated ring allreduce (RS + AG = 2(r-1)/r x P), but
    # optimizer memory and update FLOPs drop ~world x. See
    # parallel/dp.py DPShardedEngine.
    dp_shard_update: bool = False
    # Wire dtype for dp's explicit gradient collectives (EQuARX-style
    # compressed allreduce): "float32" (exact; the default), "bfloat16"
    # (halves gradient wire bytes), or "int8" (quarter wire bytes:
    # per-bucket absmax scaling + stochastic rounding on the gradient
    # partials, deterministic under the run seed; accuracy parity gated by
    # the digits matrix — tools/accparity.py dp-bf16/dp-int8 engines).
    # Values "f32"/"bf16" normalize. Any non-f32 setting routes dp through
    # the explicit shard_map collective engine even without dp_shard_update.
    allreduce_dtype: str = "float32"
    # Comm/compute overlap for the explicit dp engine: split the packed
    # flat gradient into this many contiguous, layer-aligned buckets, each
    # riding its own reduce-scatter as the backward unwinds, and (with
    # --dp-shard-update) keep the parameters SHARDED between steps so the
    # forward all-gathers each bucket just-in-time before the first layer
    # that consumes it — earlier layers' compute hides later buckets' wire
    # time under XLA's latency-hiding scheduler (distributed.comm_flags()).
    # 1 (the default) compiles the exact monolithic-collective program.
    comm_buckets: int = 1
    # Gradient accumulation: K micro-steps between optimizer updates, grads
    # averaged (Horovod backward_passes_per_step / batches_per_allreduce
    # parity, imagenet_horovod.py:131-139; dp with SGD also scales lr by K —
    # the linear-scaling heuristic is gated to SGD in train/loop.py). The
    # per-step batch becomes K x the configured batch. single/dp/tp/fsdp.
    grad_accum_steps: int = 1

    # Pipeline topology.
    num_stages: Optional[int] = None  # defaults to num_devices // dp_replicas
    dp_replicas: int = 1  # hybrid PPxDP: replicas per stage (uniform)
    # Uneven hybrid PPxDP: per-stage replication factors, e.g. (1, 3) — the
    # reference optimizer's heterogeneous plans (run_template.sh:436-498).
    # Executed by parallel/hetero.py over a flat 'pipe' mesh axis; mutually
    # exclusive with dp_replicas > 1. Uniform tuples route to the regular
    # 2-D-mesh strategies.
    stage_replication: Optional[Tuple[int, ...]] = None
    # Interleaved schedule (gpipe only): each device owns this many model
    # chunks, cutting the synchronous-pipeline bubble by the same factor at
    # the cost of more (cheap, ICI-neighbor) rotations. Requires
    # num_microbatches % stages == 0 when > 1.
    virtual_stages: int = 1
    # Pipeline schedule for the gpipe-family strategies — a TIMETABLE the
    # schedule-programmable runtime executes (partition/schedule.py data,
    # parallel/pipeline_rt.py engine), not a separate engine per schedule:
    # * "fill-drain"  — GPipe flush (the autodiff scan; the default, and
    #                   bitwise the legacy gpipe program),
    # * "1f1b"        — synchronous 1F1B (same weights every microbatch,
    #                   one update per step; bubble 2(S-1)/(3M+2(S-1))),
    # * "interleaved" — interleaved 1F1B over S x virtual_stages chunks,
    # * "zero-bubble" — ZB-H1-style split backward: weight-grad events
    #                   fill the drain bubble ((S-1)/(3M+S-1)); composes
    #                   with virtual_stages > 1,
    # * "zero-bubble-h2" — ZB-H2-style: zb_h2_stash extra in-flight
    #                   microbatches per chunk and the trailing W events
    #                   deferred past the step boundary (steady-state
    #                   bubble -> 0 at the price of the extra stash),
    # * "searched"    — partition/schedule_search.py: deterministic
    #                   budgeted local search seeded by both heuristics;
    #                   never packs worse than 1f1b/zero-bubble, keeps
    #                   their 1F1B activation memory.
    # pipedream keeps its own ASYNC 1F1B engine (weight stashing).
    pipe_schedule: str = "fill-drain"
    # zero-bubble-h2's extra in-flight activation stash, microbatches per
    # chunk. More stash hides more warmup idle (steady bubble ~
    # max(0, S-1-stash)/(3M+S-1-stash)) but costs that many extra stashed
    # boundary activations per chunk in the planner's memory term.
    zb_h2_stash: int = 1
    # The searched packer's move-evaluation budget and rng seed
    # (partition/schedule_search.py). Same (budget, seed) -> bitwise the
    # same table; the planner prices searched candidates at exactly these
    # values so the priced table is the one the runtime executes.
    sched_search_budget: int = 256
    sched_search_seed: int = 0
    # Cost model for the pipeline timetable (partition/schedule.py):
    # * "unit"    — the F=B=W unit-cost grids (the PR 7 tables, bitwise);
    # * "profile" — per-chunk F/B/W cost vectors summed from the
    #   --auto-partition profile graph over the chosen stage bounds
    #   (quantize_cost_vectors), so uneven stage splits execute on
    #   timetables packed for their true costs. Event schedules only
    #   (the fill-drain autodiff scan is lockstep by construction).
    pipe_costs: str = "unit"
    # Resolved per-chunk (f, b, w) half-tick cost vectors — normally
    # written by the auto-partition path (or restored from a persisted
    # plan), but settable directly for tests/tools.
    pipe_cost_vectors: Optional[Tuple[Tuple[int, ...], Tuple[int, ...],
                                      Tuple[int, ...]]] = None
    # A prior run's --trace JSON: --auto-partition's schedule advisor
    # folds the MEASURED bubble fraction reduced from it
    # (telemetry/bubble.py) into its ranking, outranking the analytic
    # value for the schedule the trace recorded (ROADMAP item 2c).
    schedule_trace: Optional[str] = None
    # Composed tensor x pipeline parallelism (gpipe + transformer archs):
    # each pipeline stage's blocks are Megatron-sliced this many ways over a
    # 'model' mesh axis inside the stage (parallel/tpp.py). num_devices =
    # tp_size x stages. No reference analog (its engines compose PP with DP
    # only); the TPU-native composition rides intra-stage ICI neighbors.
    tp_size: int = 1
    # PipeDream macrobatch mode (runtime/optimizer.py:36-52,119-164):
    # accumulate gradients across update_interval microbatches inside the
    # 1F1B schedule and step once per interval (grads averaged /K). The
    # reference caps weight stashing at 2 versions here and accepts version
    # staleness; our stash ring keeps exact per-microbatch forward weights
    # (documented deviation in parallel/pipedream.py).
    update_interval: int = 1

    # Auto-parallelism: profile the model and choose stage bounds with the
    # hierarchical partitioner before building the pipeline strategies
    # (reference: the whole PipeDream phase 1-3 pipeline).
    auto_partition: bool = False
    profile_mode: str = "flops"  # "flops" (device-free) | "time" (measured)
    # `--plan auto` (partition/planner.py): solve the FULL dp/pp/tp mix +
    # stage split + schedule from the profile under the per-chip HBM cap,
    # then rewrite this config onto the winning engines (dp ZeRO-1,
    # gpipe/pipeline_rt with --dp-shard-update, tp) before anything runs.
    # Resolved at run start (train/loop.py / parallel/api.py) via
    # planner.resolve_auto_plan; the pre-plan config must leave every
    # mix-shaping flag at its default — the planner owns them. "manual"
    # (default) = the flags mean what they say.
    plan: str = "manual"
    # Explicit per-chunk stage bounds over the model's layer chain for the
    # pipeline strategies (len = stages * virtual_stages + 1, starting at
    # 0) — how a solved plan's split reaches the engine, and settable
    # directly (--plan-bounds) so an explicitly-flagged run can execute
    # the exact same split a --plan auto run chose (the bitwise pin).
    plan_bounds: Optional[Tuple[int, ...]] = None

    # MoE (transformer_moe_* archs): Switch router load-balance loss weight
    # and static per-expert capacity = ceil(cf * tokens / experts).
    moe_aux_weight: float = 0.01
    moe_capacity_factor: float = 1.25

    # Label smoothing for the training objective (GNMT parity: the reference
    # translation workload trains with smoothing 0.1,
    # runtime/translation seq2seq label-smoothing module). None = per-workload
    # default (0.1 for seq2seq benchmarks, 0 otherwise).
    label_smoothing: Optional[float] = None

    # Numerics.
    compute_dtype: str = "bfloat16"  # MXU-native; tests use float32
    # "auto" = Pallas flash-attention kernel on TPU, jnp elsewhere.
    attention_backend: str = "auto"  # auto | flash | xla
    # Fused LM-head projection+cross-entropy on the training path
    # (ops/fused_xent.py): the [tokens, vocab] logits never hit HBM. Applies
    # to models whose head supports it (the token/seq2seq workloads).
    fused_head_loss: bool = True
    param_dtype: str = "float32"
    # jax.checkpoint each (microbatch, stage) in pipeline modes — parity with
    # torchgpipe's default activation checkpointing.
    remat_stages: bool = True
    # jax.checkpoint each LAYER in the one-apply strategies (single/dp/tp/
    # fsdp): the backward recomputes layers instead of saving interiors,
    # capping live activations at one layer's working set. Kept besides a
    # layer's input: what its kernels name for keeping (ops/flash_attention.
    # REMAT_KEPT_NAMES: the flash forward's output and row logsumexp, H*dv/d
    # of the input), so the forward kernel is not run again. Off by default
    # (XLA's fusion usually wins); required for XLA-attention long-context
    # training on one chip, where each layer otherwise keeps a [B, H, T, T]
    # score matrix alive into the backward. Incompatible with the Switch
    # MoE archs (collects_aux_loss): their router aux losses are collected
    # through a trace-time side channel (models/moe.py collect_aux_losses)
    # that cannot escape a checkpointed trace.
    remat_layers: bool = False
    seed: int = 1  # reference seeds torch.manual_seed(1) (imagenet_pytorch.py:58-66)

    # Checkpoint/resume (reference: per-stage checkpoint.{stage}.pth.tar per
    # epoch, main_with_runtime.py:580-584; resume :241-262). Saves go through
    # the atomic commit protocol in train/checkpoint.py (tmp -> fsync ->
    # COMMIT marker -> rename); resume picks the newest checkpoint that
    # VERIFIES against its manifest, falling back past torn or corrupt ones.
    checkpoint_dir: Optional[str] = None
    resume: bool = False
    # Step-granular checkpoints: also commit a mid-epoch checkpoint every K
    # completed steps (epoch_N_step_S), carrying the full resume state
    # (global step, interior data-iterator position, metric-logger counters,
    # seed) so a kill mid-epoch resumes bit-for-bit. None = per-epoch only.
    checkpoint_every_steps: Optional[int] = None
    # Retention: keep only the newest N committed checkpoints (older ones
    # and stale .tmp dirs are GC'd after each commit). None = keep all.
    keep_checkpoints: Optional[int] = None
    # Elastic world-size resume (train/reshard.py): when the checkpoint's
    # recorded world shape mismatches the current mesh, reshard the ZeRO-1
    # flat state between world sizes (a pure permutation — f32 bitwise)
    # instead of raising CheckpointShapeError. The lr world-scaling factor
    # stays pinned to the LAUNCH world recorded in the checkpoint, and the
    # global batch must be preserved across the reshape for the
    # (epoch, step)-addressed data streams to line up.
    elastic_resume: bool = False
    # World-invariant reduction order for the dp ZeRO-1 engine: compute
    # gradients in E fixed slices of the GLOBAL batch and reduce them over
    # a canonical balanced binary tree (local fold over each device's
    # contiguous slices + butterfly allreduce across devices) instead of
    # local-sum + psum_scatter. The reduction tree is then a function of E
    # alone, so an elastic run checkpointed at world N and resumed at
    # world M (both dividing E, powers of two) replays the SAME f32 bits —
    # the numerical contract behind chaosbench's shrink/grow
    # trajectory_match. Costs log2(world) full-vector exchange rounds vs
    # the ring reduce-scatter's (world-1)/world. None = off (the default
    # wire path, bitwise-pinned vs GSPMD at a fixed world).
    elastic_slices: Optional[int] = None
    # Deterministic fault injection (ddlbench_tpu/faults/): repeatable
    # KIND@EPOCH:STEP specs, e.g. ("kill@2:5", "nan-loss@1:3"). Empty =
    # disarmed; the hooks then cost one falsy check each.
    inject: Tuple[str, ...] = ()

    # Failure detection (reference has none beyond a 120-min process-group
    # timeout, SURVEY.md §5.3): abort/warn/ignore on non-finite loss, and an
    # optional per-sync hang deadline that stack-dumps and kills the process.
    # DEPRECATED flag surface: superseded by anomaly_policy below (kept as a
    # working alias — resolved_anomaly_policy() falls back to it).
    nan_policy: str = "abort"  # abort | warn | ignore
    hang_timeout_s: Optional[float] = None

    # Stability guard (ddlbench_tpu/guard/). Setting anomaly_policy (or
    # loss_scale) ARMS on-device anomaly detection in the guarded engines
    # (single, dp incl. the explicit shard_map engine, gpipe, tpp,
    # pipedream): each train step folds a fused (loss_finite & grad_finite,
    # global_grad_norm) pair into its metrics, synced on the existing
    # interval path. Policies beyond the legacy abort/warn/ignore:
    # * "skip"   — drop an anomalous update IN-STEP (lax select): params and
    #              optimizer state stay bitwise untouched, including ZeRO-1
    #              sharded slices.
    # * "rewind" — restore the last committed checkpoint via the
    #              latest_valid resume path and replay (the (epoch, step)-
    #              addressed data stream fast-forwards deterministically);
    #              requires checkpoint_dir.
    # None leaves the guard disarmed: engines compile their pre-guard
    # programs and non-finite losses follow nan_policy as before.
    anomaly_policy: Optional[str] = None
    # Consecutive anomalies (skipped steps, backoffs, spikes — or rewinds
    # for the same step) tolerated before escalating to TrainingFailure.
    anomaly_budget: int = 3
    # Loss scaling for the bf16 compute/wire paths: "dynamic" (growth x2
    # after a clean streak, backoff x1/2 on overflow, overflowed updates
    # dropped in-step) or a fixed positive float. Power-of-two dynamic
    # scales keep f32 runs bitwise identical to unscaled ones. None = off.
    loss_scale: Optional[Any] = None
    # Host-side EWMA spike detector: a window whose mean grad norm exceeds
    # factor x EWMA is an anomaly (the diverged-but-finite case).
    grad_spike_factor: float = 10.0

    # Step-level telemetry (ddlbench_tpu/telemetry/): host-side span tracing
    # into a bounded ring buffer, exported as a Chrome-trace-event JSON
    # (Perfetto-loadable) at `trace`. None disables tracing entirely — the
    # hot loop then pays one no-op check per span site and nothing else.
    trace: Optional[str] = None
    trace_capacity: int = 200_000  # ring-buffer bound (events)
    # Whole-run device/XLA profile directory (jax.profiler.trace), and an
    # optional [start, stop) global-step window for the capture — a short
    # window keeps the profile small enough to open while the host trace
    # above covers the whole run. Steps are counted over the whole run
    # (epoch boundaries do not reset the counter; warmup is excluded).
    trace_dir: Optional[str] = None
    xla_trace_steps: Optional[Tuple[int, int]] = None
    # Compiled-program audit manifest (telemetry/audit.py): AOT-lower the
    # train step once before the run, extract flops / HBM components / the
    # per-collective ledger out of the optimized HLO, cross-check the
    # comm_stats wire-byte formulas, and write the ledger JSON here. One
    # extra trace of the already-compiled program shapes; never executes.
    audit: Optional[str] = None

    # Activation/gradient deep-dive logging (torchlogger analog, SURVEY.md
    # §5.5; reference profiler main.py:543-582): every activation_log_freq
    # epochs, dump per-layer activations + dLoss/d(activation) for the first
    # activation_log_steps minibatches as npz files under activation_log_dir.
    activation_log_dir: Optional[str] = None
    activation_log_freq: int = 1
    activation_log_steps: int = 1

    hardware: HardwareModel = dataclasses.field(default_factory=HardwareModel)

    # ---- derived ----

    def dataset(self) -> DatasetSpec:
        return DATASETS[self.benchmark]

    def collects_aux_loss(self) -> bool:
        """The arch's router collects an auxiliary loss over the routed
        batch (models/zoo.collects_aux_loss)."""
        from ddlbench_tpu.models.zoo import collects_aux_loss

        return collects_aux_loss(self.arch)

    def model_strategies(self):
        """The strategies the arch's model is brought up on
        (LayerModel.strategies), None for no such list — and for an arch
        the benchmark's dataset cannot build: make_strategy raises that
        where the model is made, as it always did."""
        from ddlbench_tpu.models.zoo import get_model

        try:
            return get_model(self.arch, self.dataset()).strategies
        except (KeyError, ValueError):  # unknown arch, or not this dataset's
            return None

    def resolved_optimizer(self) -> str:
        if self.optimizer is not None:
            return self.optimizer
        return "adam" if self.dataset().kind == "seq2seq" else "sgd"

    def resolved_lr(self) -> float:
        if self.lr is not None:
            return self.lr
        if self.resolved_optimizer() == "adam":
            return 1e-3  # typical Adam scale (reference passes lr via flag)
        if self.dataset().kind in ("tokens", "seq2seq"):
            return 0.01
        return 0.1 if self.benchmark in ("imagenet", "highres") else 0.01

    def resolved_allreduce_dtype(self) -> str:
        """Canonical allreduce_dtype: 'float32', 'bfloat16', or 'int8'."""
        alias = {"f32": "float32", "float32": "float32",
                 "bf16": "bfloat16", "bfloat16": "bfloat16",
                 "int8": "int8"}
        try:
            return alias[self.allreduce_dtype]
        except KeyError:
            raise ValueError(
                f"unknown allreduce_dtype {self.allreduce_dtype!r} "
                f"(choose f32/float32, bf16/bfloat16, or int8)")

    def dp_overlap_engine(self) -> bool:
        """True when dp runs the OVERLAPPED sharded-update engine: params
        stay sharded between steps (just-in-time bucketed all-gather in the
        forward) and the backward reduce-scatters per bucket. Requires both
        the sharded update and more than one comm bucket; with one bucket
        the engine compiles the exact monolithic (PR 3) program."""
        return (self.dp_explicit_collectives() and self.dp_shard_update
                and self.comm_buckets > 1)

    def dp_explicit_collectives(self) -> bool:
        """True when dp runs the explicit shard_map collective engine
        (sharded weight update, compressed gradient collectives, and/or
        bucketed collectives) instead of leaving the gradient allreduce to
        GSPMD. comm_buckets > 1 routes here like a non-f32 wire dtype
        does: an f32 bucketed run is the replicated engine with one psum
        per bucket (bitwise vs GSPMD dp for non-BN models)."""
        return self.strategy == "dp" and (
            self.dp_shard_update
            or self.comm_buckets > 1
            or self.resolved_allreduce_dtype() != "float32")

    def pipe_shard_engine(self) -> bool:
        """True when the gpipe-family pipeline runtime composes with the
        ZeRO-1 shard axis (hybrid PP x ZeRO-1, ISSUE 8): each stage's
        packed parameter row and optimizer state stay flat and SHARDED
        across the pipe mesh's 'data' axis between steps, the forward
        all-gathers each bucket just-in-time, and the post-scan gradient
        pmean becomes a bucketed reduce-scatter feeding one sharded
        update per step. Selected by --dp-shard-update on -f gpipe
        (same flag as dp's ZeRO-1 engine; validate() scopes it to the
        2-D data x stage mesh — no tp, no hetero replication)."""
        return self.strategy == "gpipe" and self.dp_shard_update

    def resolved_label_smoothing(self) -> float:
        if self.label_smoothing is not None:
            return self.label_smoothing
        return 0.1 if self.dataset().kind == "seq2seq" else 0.0

    def resolved_anomaly_policy(self) -> str:
        """The ONE anomaly-policy surface: anomaly_policy when set, else the
        legacy nan_policy alias (whose values are a subset)."""
        return (self.anomaly_policy if self.anomaly_policy is not None
                else self.nan_policy)

    def resolved_loss_scale(self):
        """None (off), "dynamic", or a fixed positive float."""
        if self.loss_scale is None:
            return None
        if isinstance(self.loss_scale, str):
            if self.loss_scale == "dynamic":
                return "dynamic"
            try:
                v = float(self.loss_scale)
            except ValueError:
                raise ValueError(
                    f"loss_scale must be 'dynamic' or a positive float; "
                    f"got {self.loss_scale!r}")
        else:
            v = float(self.loss_scale)
        import math

        if not math.isfinite(v) or v <= 0:
            raise ValueError(
                f"loss_scale must be 'dynamic' or a positive float; "
                f"got {self.loss_scale!r}")
        return v

    def guard_armed(self) -> bool:
        """True when the engines should compile on-device anomaly
        detection (and loss scaling) into their train steps."""
        return self.anomaly_policy is not None or self.loss_scale is not None

    def resolved_momentum(self) -> float:
        if self.momentum is not None:
            return self.momentum
        return 0.9 if self.benchmark in ("imagenet", "highres") else 0.5

    def resolved_weight_decay(self) -> float:
        if self.weight_decay is not None:
            return self.weight_decay
        return 1e-4 if self.benchmark in ("imagenet", "highres") else 0.0

    def resolved_stages(self) -> int:
        if self.stage_replication:
            return len(self.stage_replication)
        if self.num_stages is not None:
            return self.num_stages
        return max(1, self.num_devices
                   // (max(1, self.dp_replicas) * max(1, self.tp_size)))

    def resolved_batches(self) -> Tuple[int, int]:
        """Return (micro_batch_size, num_microbatches).

        For single/dp, num_microbatches == 1 and micro_batch_size is the
        per-device batch. Defaults follow the reference matrix (BASELINE.md).
        """
        if self.strategy in ("single", "dp", "sp", "tp", "fsdp", "ep"):
            key = self.strategy if self.strategy in DEFAULT_BATCH else "dp"
            b = self.batch_size or DEFAULT_BATCH[key][self.benchmark]
            return int(b), 1
        if self.strategy == "gpipe":
            if self.micro_batch_size and self.num_microbatches:
                # fully explicit grammar: the default matrix is not
                # consulted (benchmarks outside it work with both flags)
                return int(self.micro_batch_size), int(self.num_microbatches)
            mb, chunks = DEFAULT_BATCH["gpipe"][self.benchmark]
            mb = self.micro_batch_size or mb
            if self.num_microbatches:
                chunks = self.num_microbatches
            elif self.batch_size:
                # interpret batch_size as the effective global batch
                chunks = max(1, self.batch_size // mb)
            return int(mb), int(chunks)
        # pipedream: global batch split into microbatches of micro_batch_size.
        global_b = self.batch_size or DEFAULT_BATCH["pipedream"][self.benchmark]
        mb = self.micro_batch_size or max(1, global_b // (2 * self.resolved_stages()))
        chunks = self.num_microbatches or max(1, global_b // mb)
        return int(mb), int(chunks)

    def global_batch(self) -> int:
        mb, chunks = self.resolved_batches()
        accum = self.grad_accum_steps if self.strategy in (
            "single", "dp", "tp", "fsdp") else 1
        if self.strategy in ("single", "sp", "tp"):
            return mb * accum  # sp/tp shard sequence/features, not the batch
        if self.strategy in ("dp", "fsdp", "ep"):
            return mb * self.num_devices * accum
        if self.stage_replication:
            # hetero pipeline: replicas split each microbatch's rows, so the
            # global batch carries no replication factor
            return mb * chunks
        return mb * chunks * max(1, self.dp_replicas)

    def validate(self) -> None:
        if self.benchmark not in DATASETS:
            raise ValueError(f"unknown benchmark {self.benchmark!r}")
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.strategy == "single" and self.num_devices != 1:
            raise ValueError("single strategy uses exactly 1 device")
        from ddlbench_tpu.train.watchdog import NAN_POLICIES

        if self.nan_policy not in NAN_POLICIES:
            raise ValueError(f"unknown nan_policy {self.nan_policy!r}")
        if self.anomaly_policy is not None:
            from ddlbench_tpu.guard.policy import ANOMALY_POLICIES

            if self.anomaly_policy not in ANOMALY_POLICIES:
                raise ValueError(
                    f"unknown anomaly_policy {self.anomaly_policy!r} "
                    f"(choose from {', '.join(ANOMALY_POLICIES)})")
            if self.anomaly_policy == "rewind" and self.checkpoint_dir is None:
                raise ValueError(
                    "anomaly_policy='rewind' needs --checkpoint-dir (the "
                    "rewind target is the last committed checkpoint)")
            from ddlbench_tpu.guard.policy import GUARD_UNWIRED_STRATEGIES

            if self.anomaly_policy == "skip" and \
                    self.strategy in GUARD_UNWIRED_STRATEGIES:
                raise ValueError(
                    f"anomaly_policy='skip' (in-step update drop) needs "
                    f"device-guard wiring, which the {self.strategy!r} "
                    f"engine lacks; use abort/warn/rewind there")
        if self.anomaly_budget < 1:
            raise ValueError("anomaly_budget must be >= 1")
        self.resolved_loss_scale()  # raises on malformed values
        if self.loss_scale is not None and self.strategy == "pipedream":
            raise ValueError(
                "loss_scale is wired into the one-update-per-step train "
                "steps (single/dp/gpipe incl. tp_size > 1, sp/tp/fsdp/ep); "
                "pipedream's per-microbatch updates would need per-event "
                "unscaling and run unscaled")
        if self.grad_spike_factor <= 1.0:
            raise ValueError("grad_spike_factor must be > 1")
        if self.attention_backend not in ATTENTION_BACKENDS:
            raise ValueError(
                f"unknown attention_backend {self.attention_backend!r}"
            )
        if self.hang_timeout_s is not None and self.hang_timeout_s <= 0:
            raise ValueError("hang_timeout_s must be positive")
        if self.checkpoint_every_steps is not None:
            if self.checkpoint_every_steps < 1:
                raise ValueError("checkpoint_every_steps must be >= 1")
            if self.checkpoint_dir is None:
                raise ValueError(
                    "checkpoint_every_steps needs --checkpoint-dir for the "
                    "checkpoint location")
        if self.keep_checkpoints is not None and self.keep_checkpoints < 1:
            raise ValueError(
                "keep_checkpoints must be >= 1 (the newest checkpoint is "
                "never dropped)")
        if self.elastic_resume and self.checkpoint_dir is None:
            raise ValueError(
                "elastic_resume resharding needs --checkpoint-dir (there "
                "is no checkpoint to reshard without one)")
        if self.elastic_slices is not None:
            E = self.elastic_slices
            if E < 1 or (E & (E - 1)):
                raise ValueError(
                    f"elastic_slices must be a positive power of two (the "
                    f"canonical balanced reduction tree over E leaves must "
                    f"decompose at any world cut); got {E}")
            if self.strategy != "dp" or not self.dp_shard_update:
                raise ValueError(
                    "elastic_slices (world-invariant reduction order) runs "
                    "on the dp ZeRO-1 engine (-f dp --dp-shard-update)")
            w = self.num_devices
            if w & (w - 1) or E % w:
                raise ValueError(
                    f"elastic_slices ({E}) needs a power-of-two device "
                    f"count dividing it (got {w}): device boundaries must "
                    f"align with subtrees of the canonical reduction tree")
            if self.global_batch() % E:
                raise ValueError(
                    f"global batch ({self.global_batch()}) must divide "
                    f"into elastic_slices ({E}) equal slices")
            if self.grad_accum_steps > 1:
                raise ValueError(
                    "elastic_slices already slices the global batch; "
                    "grad_accum_steps > 1 is not composed with it")
            if self.resolved_allreduce_dtype() != "float32":
                raise ValueError(
                    "elastic_slices is the exact-replay mode: quantized "
                    "wire dtypes fold device indices into their rounding "
                    "streams and can never be world-invariant (use f32)")
        if self.inject:
            from ddlbench_tpu.faults import parse_injections

            parse_injections(self.inject)  # raises on bad grammar/kind
        if self.prefetch_depth < 0:
            raise ValueError("prefetch_depth must be >= 0 (0 = synchronous)")
        if self.trace_capacity < 1:
            raise ValueError("trace_capacity must be >= 1")
        if self.xla_trace_steps is not None:
            a, b = self.xla_trace_steps
            if a < 0 or b <= a:
                raise ValueError(
                    f"xla_trace_steps must be a [start, stop) window with "
                    f"0 <= start < stop; got {self.xla_trace_steps}")
            if self.trace_dir is None:
                raise ValueError(
                    "xla_trace_steps needs --trace-dir for the profile "
                    "output location")
        if self.label_smoothing is not None and not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError("label_smoothing must be in [0, 1)")
        if self.strategy == "sp" and self.dataset().kind not in ("tokens", "seq2seq"):
            raise ValueError(
                "sp (sequence parallelism) requires a token or seq2seq benchmark")
        if self.strategy == "ep":
            if self.dataset().kind != "tokens":
                raise ValueError("ep (expert parallelism) requires a token benchmark")
            if not self.collects_aux_loss():
                raise ValueError(
                    "ep (expert parallelism) requires a Switch-routed MoE "
                    "arch (transformer_moe_*): parallel/ep.py shards that "
                    "layer's expert axis")
        brought_up = self.model_strategies()
        if brought_up is not None and self.strategy not in brought_up:
            raise ValueError(
                f"{self.arch} is brought up on {', '.join(brought_up)} only "
                f"(its model says which strategies: LayerModel.strategies); "
                f"got {self.strategy!r}")
        if self.remat_layers and self.collects_aux_loss():
            raise ValueError(
                "remat_layers is incompatible with archs whose router "
                "collects an auxiliary loss (transformer_moe_*: it cannot "
                "escape a checkpointed trace); use remat_stages via a "
                "pipeline strategy instead")
        if self.remat_layers and self.strategy not in ("single", "dp", "tp",
                                                       "fsdp"):
            raise ValueError(
                f"remat_layers applies to the one-apply strategies "
                f"(single/dp/tp/fsdp), not {self.strategy!r} — the pipeline "
                f"strategies checkpoint per (microbatch, stage) via "
                f"remat_stages, and sp/ep bound activation memory by "
                f"sharding the sequence/experts instead")
        if self.stage_replication is not None:
            repl = tuple(self.stage_replication)
            if self.strategy not in ("gpipe", "pipedream"):
                raise ValueError(
                    "stage_replication applies to the pipeline strategies")
            if not repl or any(r < 1 for r in repl):
                raise ValueError("stage_replication factors must be >= 1")
            if self.dp_replicas > 1:
                raise ValueError(
                    "stage_replication and dp_replicas are mutually "
                    "exclusive (the tuple already encodes replication)")
            if sum(repl) != self.num_devices:
                raise ValueError(
                    f"stage_replication {repl} sums to {sum(repl)}; "
                    f"num_devices is {self.num_devices}")
            if self.num_stages is not None and self.num_stages != len(repl):
                raise ValueError(
                    f"num_stages ({self.num_stages}) != "
                    f"len(stage_replication) ({len(repl)})")
            mb, _ = self.resolved_batches()
            bad = [s for s, r in enumerate(repl) if mb % r]
            if bad:
                raise ValueError(
                    f"micro-batch {mb} must be divisible by every "
                    f"replication factor; stages {bad} of {repl} are not")
            if self.virtual_stages > 1:
                raise ValueError(
                    "stage_replication and virtual_stages (interleaved "
                    "schedule) are mutually exclusive")
        elif self.strategy in ("gpipe", "pipedream"):
            s = self.resolved_stages()
            if s * max(1, self.dp_replicas) * max(1, self.tp_size) \
                    != self.num_devices:
                raise ValueError(
                    f"stages ({s}) x dp_replicas ({self.dp_replicas}) x "
                    f"tp_size ({self.tp_size}) must equal "
                    f"num_devices ({self.num_devices})"
                )
        if self.tp_size < 1:
            raise ValueError("tp_size must be >= 1")
        if self.tp_size > 1:
            if self.strategy != "gpipe":
                raise ValueError(
                    "tp_size > 1 (composed tensor x pipeline parallelism) "
                    "runs on the gpipe strategy (parallel/tpp.py)")
            if self.dataset().kind not in ("tokens", "seq2seq"):
                raise ValueError(
                    "tp_size > 1 requires a token or seq2seq benchmark "
                    "(transformer blocks are what gets Megatron-sliced)")
            if self.stage_replication is not None:
                raise ValueError(
                    "tp_size > 1 composes with uniform pipeline stages "
                    "(plus dp_replicas for 3-D parallelism); "
                    "stage_replication must stay default")
            if self.virtual_stages > 1:
                raise ValueError(
                    "tp_size > 1 with the interleaved schedule is not "
                    "supported")
        if self.virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        from ddlbench_tpu.partition.schedule import PIPE_SCHEDULES

        if self.pipe_schedule not in PIPE_SCHEDULES:
            raise ValueError(
                f"unknown pipe_schedule {self.pipe_schedule!r} "
                f"(choose from {', '.join(PIPE_SCHEDULES)})")
        if self.pipe_schedule != "fill-drain":
            if self.strategy != "gpipe":
                raise ValueError(
                    f"pipe_schedule={self.pipe_schedule!r} runs on the "
                    f"gpipe strategy's schedule runtime "
                    f"(parallel/pipeline_rt.py); pipedream is the ASYNC "
                    f"1F1B engine and {self.strategy!r} has no pipeline")
            if self.tp_size > 1:
                raise ValueError(
                    "tp_size > 1 composes with the fill-drain schedule "
                    "(parallel/tpp.py); event-mode schedules are scoped "
                    "to the 2-D data x stage mesh")
            if self.stage_replication is not None:
                raise ValueError(
                    "stage_replication (hetero pipeline) executes the "
                    "fill-drain schedule only")
            # 1f1b/zero-bubble at virtual_stages > 1 are the COMPOSED
            # schedules (the interleaved / W-deferring interleaved tables)
            # since PR 18 — no V gate here; the M % S grammar below holds
            # for the whole event family.
        if self.zb_h2_stash < 0:
            raise ValueError("zb_h2_stash must be >= 0")
        if self.sched_search_budget < 0:
            raise ValueError("sched_search_budget must be >= 0")
        if self.update_interval < 1:
            raise ValueError("update_interval must be >= 1")
        if self.update_interval > 1:
            # uniform stage_replication tuples normalize to dp_replicas in
            # make_strategy and ARE macrobatch-compatible; only genuinely
            # uneven plans conflict
            uneven = (self.stage_replication
                      and len(set(self.stage_replication)) > 1)
            if self.strategy != "pipedream" or uneven:
                raise ValueError(
                    "update_interval > 1 (PipeDream macrobatch) requires the "
                    "uniform pipedream strategy")
            _, chunks = self.resolved_batches()
            if chunks % self.update_interval:
                raise ValueError(
                    f"num_microbatches ({chunks}) must be divisible by "
                    f"update_interval ({self.update_interval})")
        if self.grad_accum_steps < 1:
            raise ValueError("grad_accum_steps must be >= 1")
        if self.optimizer is not None and self.optimizer not in ("sgd", "adam"):
            raise ValueError(f"unknown optimizer {self.optimizer!r}")
        if self.grad_accum_steps > 1 and self.strategy not in (
                "single", "dp", "tp", "fsdp"):
            raise ValueError(
                "grad_accum_steps > 1 is supported on single/dp/tp/fsdp "
                "(pipeline strategies already micro-batch)")
        if self.shard_opt_state and self.strategy != "dp":
            raise ValueError(
                "shard_opt_state (ZeRO-1) applies to the dp strategy "
                "(fsdp already shards everything)")
        self.resolved_allreduce_dtype()  # raises on unknown values
        if self.comm_buckets < 1:
            raise ValueError("comm_buckets must be >= 1")
        if self.comm_buckets > 1 and self.strategy != "dp" and \
                not self.pipe_shard_engine():
            raise ValueError(
                "comm_buckets > 1 (bucketed gradient collectives) applies "
                "to the dp strategy's explicit collective engine (-f dp; "
                "combine with --dp-shard-update for the fully overlapped "
                "just-in-time all-gather) or to -f gpipe with "
                "--dp-shard-update (hybrid PP x ZeRO-1 bucket count)")
        if self.dp_shard_update and self.strategy not in ("dp", "gpipe"):
            raise ValueError(
                "dp_shard_update (sharded weight update) applies to the dp "
                "strategy or to -f gpipe (hybrid PP x ZeRO-1 over the pipe "
                "mesh's 'data' axis; fsdp already shards everything)")
        if self.pipe_shard_engine():
            if self.tp_size > 1:
                raise ValueError(
                    "dp_shard_update on gpipe (hybrid PP x ZeRO-1) is "
                    "scoped to the 2-D data x stage mesh; tp_size > 1 "
                    "keeps the replicated update")
            if self.stage_replication is not None:
                raise ValueError(
                    "dp_shard_update on gpipe needs the uniform 2-D mesh; "
                    "stage_replication (hetero pipeline) keeps the "
                    "replicated update")
        if self.plan not in ("manual", "auto"):
            raise ValueError(
                f"unknown plan mode {self.plan!r} (choose manual or auto)")
        if self.plan == "auto":
            if self.strategy != "gpipe":
                raise ValueError(
                    "--plan auto solves the dp/pp/tp mix from the gpipe "
                    "batch grammar (micro-batch x microbatches = the "
                    "global batch the plan preserves); pass -f gpipe — "
                    "the winner may rewrite the strategy to dp/tp/single")
            if self.auto_partition:
                raise ValueError(
                    "--plan auto supersedes --auto-partition (it solves "
                    "the stage split AND the mix); drop one")
            owned = (
                ("--stages", self.num_stages, None),
                ("--dp-replicas", self.dp_replicas, 1),
                ("--tp-size", self.tp_size, 1),
                ("--stage-replication", self.stage_replication, None),
                ("--virtual-stages", self.virtual_stages, 1),
                ("--pipe-schedule", self.pipe_schedule, "fill-drain"),
                ("--pipe-costs", self.pipe_costs, "unit"),
                ("pipe_cost_vectors", self.pipe_cost_vectors, None),
                ("--plan-bounds", self.plan_bounds, None),
                ("--dp-shard-update", self.dp_shard_update, False),
                ("--update-interval", self.update_interval, 1),
            )
            clash = [name for name, val, dflt in owned if val != dflt]
            if clash:
                raise ValueError(
                    f"--plan auto owns the parallelism mix; leave "
                    f"{', '.join(clash)} unset (the planner chooses and "
                    f"records them in partition.json)")
        if self.plan_bounds is not None:
            if self.strategy not in ("gpipe", "pipedream"):
                raise ValueError(
                    "plan_bounds (explicit stage bounds) applies to the "
                    "pipeline strategies")
            if self.auto_partition:
                raise ValueError(
                    "--auto-partition solves the stage bounds; "
                    "--plan-bounds pins them — pick one")
            pb = tuple(int(x) for x in self.plan_bounds)
            chunks_n = self.resolved_stages() * max(1, self.virtual_stages)
            if len(pb) != chunks_n + 1:
                raise ValueError(
                    f"plan_bounds needs stages x virtual_stages + 1 = "
                    f"{chunks_n + 1} entries; got {len(pb)}")
            if pb[0] != 0 or any(a >= b for a, b in zip(pb, pb[1:])):
                raise ValueError(
                    f"plan_bounds must strictly increase from 0; got {pb}")
        if self.pipe_costs not in ("unit", "profile"):
            raise ValueError(
                f"unknown pipe_costs {self.pipe_costs!r} (choose unit or "
                f"profile)")
        if self.pipe_costs == "profile":
            if self.strategy != "gpipe":
                raise ValueError(
                    "pipe_costs='profile' (cost-weighted timetables) "
                    "applies to -f gpipe's schedule runtime")
            if not self.auto_partition:
                raise ValueError(
                    "pipe_costs='profile' needs --auto-partition (the "
                    "profile graph is where the per-chunk costs come from)")
            if self.pipe_schedule == "fill-drain":
                raise ValueError(
                    "pipe_costs='profile' needs an event schedule "
                    "(--pipe-schedule 1f1b/interleaved/zero-bubble/"
                    "zero-bubble-h2/searched); the fill-drain autodiff "
                    "scan executes the unit timetable by construction")
        if self.schedule_trace is not None:
            if self.strategy != "gpipe" or not self.auto_partition:
                raise ValueError(
                    "schedule_trace (measured-bubble schedule advice) "
                    "feeds -f gpipe's --auto-partition advisor; without "
                    "auto-partition there is no advice to fold it into")
        if self.pipe_cost_vectors is not None:
            if self.strategy != "gpipe":
                raise ValueError(
                    "pipe_cost_vectors applies to -f gpipe's schedule "
                    "runtime")
            if self.pipe_schedule == "fill-drain":
                raise ValueError(
                    "cost-weighted timetables execute on the EVENT "
                    "schedules (1f1b/interleaved/zero-bubble/"
                    "zero-bubble-h2/searched); the fill-drain autodiff "
                    "scan is lockstep by construction")
            from ddlbench_tpu.partition.schedule import normalize_costs

            normalize_costs(  # raises on malformed vectors
                self.pipe_cost_vectors,
                self.resolved_stages() * self.virtual_stages)
        if self.dp_shard_update and self.shard_opt_state:
            raise ValueError(
                "dp_shard_update supersedes shard_opt_state: the explicit "
                "engine already shards the optimizer state (pick one)")
        if self.shard_opt_state and self.strategy == "dp" and \
                self.resolved_allreduce_dtype() != "float32":
            raise ValueError(
                "shard_opt_state is a GSPMD placement knob; the compressed-"
                "allreduce engine pins the optimizer state replicated — "
                "use dp_shard_update for sharded state with bf16 wire")
        if self.resolved_allreduce_dtype() != "float32" and \
                self.strategy != "dp":
            raise ValueError(
                "allreduce_dtype applies to the dp strategy's gradient "
                "collectives")
        if self.dp_explicit_collectives():
            if self.collects_aux_loss():
                raise ValueError(
                    "dp_shard_update / compressed allreduce run the train "
                    "step under shard_map, where MoE router statistics "
                    "would become per-shard (replicated dp routes over the "
                    "global batch); use replicated dp for MoE archs")
            if self.remat_layers:
                raise ValueError(
                    "remat_layers is incompatible with the explicit dp "
                    "collective engine (checkpointed traces cannot carry "
                    "the shard_map axis context); use replicated dp")
        if self.virtual_stages > 1:
            if self.strategy not in ("gpipe", "pipedream"):
                raise ValueError(
                    "virtual_stages (interleaved schedule) requires a "
                    "pipeline strategy (gpipe or pipedream)")
            s = self.resolved_stages()
            _, chunks = self.resolved_batches()
            if chunks % s:
                # gpipe's interleaved timetable groups microbatches by S;
                # pipedream's async variant inherits the constraint through
                # its synchronous interleaved eval pipeline
                raise ValueError(
                    f"interleaved schedule needs num_microbatches ({chunks}) "
                    f"divisible by stages ({s})")

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Shape/policy configuration of the continuous-batching serving engine
    (serve/engine.py). Frozen + validated like :class:`RunConfig` — one
    config surface, constructible from servebench flags or a dict.

    The static-shape contract: every decode step is a [max_batch, 1] model
    call and every prefill chunk a [1, prefill_chunk] call, so the jit
    cache holds at most ``max_len / page`` variants of each (one per live
    page count) regardless of traffic.
    """

    max_batch: int = 8  # engine rows = concurrent requests per replica
    pool_pages: int = 64  # shared KV pool slots (slot 0 = scratch)
    page: int = 16  # positions per page (ops/paged_decode.py PAGE analog)
    max_len: int = 256  # per-request stream capacity (prompt + output)
    # tokens a step may process: active decode rows count 1 each, the
    # remainder is packed with prefill chunks. 0 = max_batch + 2 chunks.
    token_budget: int = 0
    # tokens per prefill call (page multiple); 0 = whole prompt in ONE
    # padded call ("unchunked admission" — one compile, more padding)
    prefill_chunk: int = 16
    policy: str = "continuous"  # "continuous" | "static" (the A/B baseline)
    replicas: int = 1  # data-parallel serving replicas (mesh 'data' axis)
    # tensor-parallel width of ONE replica (mesh 'model' axis): the serve
    # jitted programs shard Megatron-style over tp devices — each holds
    # its contiguous head group of every layer (tp_split_layer_params)
    # and its slice of the KV pool, sharing ONE page table — so a model
    # larger than one chip's HBM serves at all. tp=1 is the bitwise-
    # pinned single-chip path (the programs are literally unchanged).
    tp: int = 1
    # cross-request prefix cache (serve/prefix.py): admissions bind the
    # already-resident immutable KV pages of their longest cached prefix
    # and chunk-prefill only the uncached tail. Continuous policy only —
    # the static baseline measures cache-off scheduling by definition.
    prefix_cache: bool = False
    # sampling (0.0 = greedy argmax, the default — all greedy pins are
    # bitwise untouched). temperature > 0 samples from softmax(logits / T)
    # on the host with counter-based per-request seeds (fold sample_seed +
    # request id + token index), so streams are bitwise-reproducible per
    # seed and eviction/recompute regenerates identical tokens.
    temperature: float = 0.0
    top_k: int = 0  # 0 = full vocab; > 0 restricts sampling to the k best
    sample_seed: int = 0
    # request-lifecycle tracing (telemetry/): when True the engine emits
    # submit/queue_wait/admit/prefill_chunk/first_token/decode/evict/
    # recompute/finish events (one Chrome-trace track per request per
    # replica) plus per-step counter tracks into the process-global
    # tracer, stamped in VIRTUAL model-pass units. Metrics-neutral by
    # construction on AND off: tracing only records what the scheduler
    # already decided — token streams and virtual-time JSON are bitwise
    # identical either way (pinned, tests/test_serve_trace.py).
    trace: bool = False
    # flight recorder: ring of the most recent per-step engine states
    # (occupancy, queue depth, packer fill, ...) kept for
    # ``ServeEngine.snapshot()`` — the live-debug window into a serving
    # replica. 0 disables the ring; snapshot() still reports live state.
    flight_recorder: int = 64
    # SLOs in virtual time units, used by snapshot()'s
    # attainment-so-far (telemetry/stats.request_slo_ok). 0 = no SLO.
    # Scheduling NEVER reads these — they are observability-only.
    slo_ttft: float = 0.0
    slo_itl: float = 0.0
    # serve-side heartbeat (ISSUE 15): a replica that HOLDS WORK but makes
    # no scheduling progress for more than this many virtual time units is
    # declared a straggler by ReplicatedServer and drained — its in-flight
    # requests evict onto the recompute path and redistribute least-loaded
    # over the survivors, exactly like a scale-down (train/watchdog.py's
    # no-progress detector, re-used clockless via ProgressMonitor).
    # 0 disables detection (the default — single-replica engines and all
    # pre-chaos callers are bitwise unaffected).
    heartbeat: float = 0.0
    # KV-pool storage dtype (ops/paged_decode.py serve pool). "float32" is
    # the bitwise-pinned default; "bfloat16" halves pool bytes; "int8"
    # quarters them — pages quantize at the write boundary with a stored
    # per-page scale sidecar (unbiased stochastic rounding, counter-based
    # seeds, PR 6's EQuARX-lite machinery) and dequantization is fused
    # into the attention kernels/references. Output quality is pinned by
    # an accparity-style digits gate (tests/test_serve_quant.py).
    kv_dtype: str = "float32"
    # silent-data-corruption defense (serve/integrity.py): when True the
    # engine keeps a host-side crc32c ledger over every pool page's
    # payload + sidecar rows, stamped at the pool-write boundary and
    # verified at every trust boundary (handoff export/import, COW
    # source pages, prefix-hit binds, eviction-recompute). A mismatch
    # quarantines the slot (excluded from allocation for the rest of
    # the run) and recovers every referencing request through the
    # existing re-prefill path, which regenerates pages byte-identically
    # — so detected corruption never reaches a token stream. Off (the
    # default) is bitwise the pre-SDC engine: no ledger, no checks.
    integrity: bool = False
    # background scrub budget: verify up to this many resident stamped
    # pages per step (round-robin cursor), catching latent corruption on
    # cold prefix pages before a full-hit serves them. 0 disables the
    # scrubber; > 0 requires integrity (there is no ledger to check
    # against otherwise).
    scrub: int = 0
    # self-drafting speculative decoding: "none" (every decode pass emits
    # one token per row) or "ngram:N:K" — a host-side N-gram drafter
    # proposes up to K tokens per decode row from the row's own emitted
    # prefix, and ONE verify pass (a K+1-wide chunk call at per-row
    # starts) scores them all; the longest prefix matching greedy argmax
    # is accepted, rejected tail pages roll back like eviction. Greedy
    # only (acceptance compares argmaxes); spec-on greedy streams are
    # pinned BITWISE identical to spec-off (tests/test_serve_spec.py).
    speculative: str = "none"

    def npg_max(self) -> int:
        return -(-self.max_len // self.page)

    def spec_params(self) -> Optional[tuple]:
        """(ngram_n, draft_k) when speculative decoding is on, else None.
        ``validate`` rejects malformed specs; this parses a valid one."""
        if self.speculative == "none":
            return None
        _, n, k = self.speculative.split(":")
        return int(n), int(k)

    def resolved_token_budget(self) -> int:
        if self.token_budget:
            return self.token_budget
        return self.max_batch + 2 * self.resolved_prefill_chunk()

    def resolved_prefill_chunk(self) -> int:
        if self.prefill_chunk:
            return self.prefill_chunk
        return self.npg_max() * self.page  # whole-stream padded chunk

    def validate(self) -> None:
        if self.policy not in ("continuous", "static"):
            raise ValueError(
                f"policy must be continuous|static, got {self.policy!r}")
        if min(self.max_batch, self.page, self.max_len, self.replicas,
               self.tp) < 1:
            raise ValueError(
                "max_batch, page, max_len, replicas, and tp must be "
                "positive")
        if self.prefill_chunk < 0 or self.token_budget < 0:
            # 0 means "resolve a default" for both; negatives would pass
            # the modulo/starvation checks and crash the engine mid-run
            raise ValueError(
                "prefill_chunk and token_budget must be >= 0")
        if self.prefill_chunk and self.prefill_chunk % self.page:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must be a multiple of "
                f"the page size {self.page} (chunks are page-aligned)")
        if self.pool_pages < self.npg_max() + 1:
            raise ValueError(
                f"pool_pages {self.pool_pages} cannot hold one max-length "
                f"request ({self.npg_max()} pages) plus the scratch slot — "
                "a request that can never fit would evict itself forever")
        if self.resolved_token_budget() < self.resolved_prefill_chunk():
            raise ValueError(
                "token_budget below one prefill chunk starves admission "
                f"({self.resolved_token_budget()} < "
                f"{self.resolved_prefill_chunk()})")
        if self.prefix_cache and self.policy != "continuous":
            raise ValueError(
                "prefix_cache requires the continuous policy — the static "
                "baseline measures cache-off scheduling (run it cache-off)")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = full vocab), got "
                             f"{self.top_k}")
        if self.top_k and self.temperature == 0.0:
            raise ValueError(
                "top_k without temperature has no sampling to restrict "
                "(greedy already takes the argmax)")
        if self.flight_recorder < 0:
            raise ValueError(
                f"flight_recorder must be >= 0 (0 disables the ring), "
                f"got {self.flight_recorder}")
        if self.slo_ttft < 0 or self.slo_itl < 0:
            raise ValueError(
                "slo_ttft and slo_itl must be >= 0 (0 = no SLO)")
        if self.heartbeat < 0:
            raise ValueError(
                f"heartbeat must be >= 0 time units (0 disables straggler "
                f"detection), got {self.heartbeat}")
        if self.scrub < 0:
            raise ValueError(
                f"scrub must be >= 0 pages/step (0 disables the "
                f"scrubber), got {self.scrub}")
        if self.scrub and not self.integrity:
            raise ValueError(
                "scrub without integrity has no checksum ledger to "
                "verify against — enable integrity or drop scrub")
        if self.kv_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"kv_dtype must be float32|bfloat16|int8, got "
                f"{self.kv_dtype!r}")
        if self.speculative != "none":
            parts = self.speculative.split(":")
            if len(parts) != 3 or parts[0] != "ngram":
                raise ValueError(
                    f"speculative must be 'none' or 'ngram:N:K', got "
                    f"{self.speculative!r}")
            try:
                n, k = int(parts[1]), int(parts[2])
            except ValueError:
                raise ValueError(
                    f"speculative ngram wants integer N:K, got "
                    f"{self.speculative!r}") from None
            if n < 1 or k < 1:
                raise ValueError(
                    f"speculative ngram needs N >= 1 and K >= 1, got "
                    f"N={n} K={k}")
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (acceptance "
                    "compares draft tokens against greedy argmax); drop "
                    "temperature or speculative")
            if k + 1 > self.max_len:
                raise ValueError(
                    f"speculative draft width K+1 ({k + 1}) exceeds "
                    f"max_len {self.max_len}")

    def replace(self, **kw: Any) -> "ServeConfig":
        return dataclasses.replace(self, **kw)
