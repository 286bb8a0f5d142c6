"""Per-step communication-volume accounting.

Parity with the reference's RuntimeStats, which counts send/recv bytes per
minibatch inside the receive/send helpers (pipedream-fork/runtime/
runtime_utilities.py:4-27, incremented at runtime.py:423-425,444-446,462-464).

Under XLA the collectives are compiled into the program, so instead of runtime
counters we compute the exact analytic volume per train step from the strategy
topology — same numbers, no instrumentation overhead:

* dp: ring all-reduce of all gradients, 2 (r-1)/r * param_bytes per step.
  With the explicit sharded weight update (--dp-shard-update, ZeRO-1) the
  pattern decomposes into its two halves and is reported as such:
  reduce-scatter of the gradients ((r-1)/r * grad_wire_bytes, where the
  wire dtype follows --allreduce-dtype) plus all-gather of the updated
  params ((r-1)/r * param_bytes, always f32 — the master weights). The
  physical_* twins price the PADDED packed flat vector the engine actually
  ships (the pad aligns the per-device shard; logical payload excludes it).
  A bf16 --allreduce-dtype without the sharded update is an explicit bf16
  ring all-reduce: half the gradient wire bytes, same pattern.
* gpipe: every microbatch crosses every interior stage boundary twice
  (activation forward, gradient backward) + one per-step gradient all-reduce
  across each stage's 'data' replicas. The physical_* twins price what the
  compiled scan actually ships: the conveyor ppermutes the full packed
  activation buffer over every (stage, replica) link on every one of the
  T = M*V + S - 1 ticks (forward + its autodiff transpose), and the
  replicated gradient sync rings the PADDED packed stage rows.
* pipedream: same boundary traffic, but the intra-stage replica all-reduce
  happens once per microbatch (per-microbatch updates).
* tpp (TPGPipeStrategy): the Megatron activation psums inside every stage
  are priced PER COLLECTIVE (``tp_psum_payload_bytes`` — the audit plane
  ties every 'model'-axis all-reduce in the optimized HLO to one of the
  analytic payload classes reported here), plus the conveyor boundary and
  the packed-row gradient/state syncs over 'data' and 'data x model'.

Every byte figure here is cross-checked against the per-collective ledger
the audit plane (telemetry/audit.py) walks out of the compiled HLO — the
exact tie-outs are pinned in tests/test_audit.py.
"""

from __future__ import annotations

import math
from typing import Dict


def _ring_allreduce_bytes(payload: float, r: int) -> float:
    return 2.0 * (r - 1) / r * payload if r > 1 else 0.0


def comm_stats(strategy) -> Dict[str, float]:
    """Analytic communication bytes per train step for a built strategy."""
    from ddlbench_tpu.models.layers import param_bytes as pb

    name = type(strategy).__name__
    out: Dict[str, float] = {
        "boundary_bytes": 0.0,
        "allreduce_bytes": 0.0,
        "reduce_scatter_bytes": 0.0,
        "all_gather_bytes": 0.0,
    }
    if name == "SingleStrategy":
        pass
    elif name == "DPStrategy":
        import numpy as np

        params, _, _ = _model_params(strategy)
        r = strategy.world_size
        pbytes = float(pb(params))
        wire_dtype = np.dtype(getattr(strategy, "wire_dtype", "float32"))
        wire_itemsize = wire_dtype.itemsize
        # gradient elements ride the wire in the (possibly narrowed)
        # --allreduce-dtype (int8 = quarter f32 bytes); params are f32
        # (pb already prices them)
        grad_wire = pbytes / 4.0 * wire_itemsize
        meta = getattr(strategy, "_flat_meta", None)
        if meta is not None:
            # bucketed collectives (--comm-buckets) change neither the
            # logical nor the physical totals — the buckets partition the
            # same padded vector (per-bucket pads are already in
            # meta.padded) — only WHEN the bytes move; the per-bucket
            # split is reported for the span/overlap tooling.
            out["comm_buckets"] = float(meta.num_buckets)
            out["wire_dtype"] = str(wire_dtype)
        if getattr(strategy, "shard_update", False):
            out["reduce_scatter_bytes"] = (r - 1) / r * grad_wire
            out["all_gather_bytes"] = (r - 1) / r * pbytes
            # physical: the engine ships the PADDED packed flat vector
            out["physical_reduce_scatter_bytes"] = (
                (r - 1) / r * meta.padded * wire_itemsize)
            out["physical_all_gather_bytes"] = (r - 1) / r * meta.padded * 4.0
            if wire_dtype == np.dtype(np.int8):
                # int8 adds one psum'd f32 scale per bucket (the shared
                # absmax) — priced so the accounting stays EXACT
                out["scale_bytes"] = _ring_allreduce_bytes(
                    4.0 * meta.num_buckets, r)
        else:
            out["allreduce_bytes"] = _ring_allreduce_bytes(grad_wire, r)
            if meta is not None:  # explicit wire engine, replicated update
                out["physical_allreduce_bytes"] = _ring_allreduce_bytes(
                    float(meta.padded * wire_itemsize), r)
                if wire_dtype == np.dtype(np.int8):
                    out["scale_bytes"] = _ring_allreduce_bytes(
                        4.0 * meta.num_buckets, r)
    elif name in ("HeteroGPipeStrategy", "HeteroPipeDreamStrategy"):
        # Uneven hybrid PPxDP (parallel/hetero.py). boundary/allreduce are
        # LOGICAL payload bytes (reference RuntimeStats parity,
        # runtime_utilities.py:4-27): each activation crosses its boundary
        # once fwd + once bwd, each replica group reduces its gradient once
        # per sync. The flat-axis implementation's WIRE traffic is a large
        # multiple — the conveyor ships a full max-interior-activation
        # buffer over every chain link for R rounds per tick, and the async
        # engine runs the gradient ring every tick with masked payloads —
        # reported separately as physical_* (ADVICE r2).
        itemsize = strategy.compute_dtype.itemsize
        M, mb = strategy.num_microbatches, strategy.mb
        bounds, shapes = strategy.bounds, strategy.shapes
        S = strategy.num_stages
        boundary = 0.0
        for s in range(1, S):
            act = mb * math.prod(shapes[bounds[s]]) * itemsize
            boundary += 2.0 * M * act
        out["boundary_bytes"] = boundary
        per_sync = sum(
            _ring_allreduce_bytes(4.0 * strategy._p_lens[s], r)
            for s, r in enumerate(strategy.repl))
        asynch = name == "HeteroPipeDreamStrategy"
        out["allreduce_bytes"] = per_sync * (M if asynch else 1)
        # physical wire estimate: links x rounds x ticks x buffer size
        N, R = strategy.N, strategy._R
        links = N - 1
        buf = float(strategy._act_size) * itemsize
        Lmax = 4.0 * max(strategy._p_lens)  # packed f32 param row
        Rg = max(strategy.repl) - 1
        # singleton stages' ring edges are self-permutes (local copy, no
        # wire): only devices in groups of >1 replicas transmit
        n_ring = sum(r for r in strategy.repl if r > 1)
        if asynch:
            ticks = 2 * M + 2 * S - 2
            conveyors = 2.0  # fwd chain + bwd chain every tick
            ring_ticks = ticks
        else:
            ticks = M + S - 1
            conveyors = 2.0  # jax.grad transposes the fwd conveyor
            ring_ticks = 1
        out["physical_conveyor_bytes"] = conveyors * ticks * R * links * buf
        out["physical_allreduce_bytes"] = float(Rg * ring_ticks * n_ring) * Lmax
    elif name == "TPGPipeStrategy":
        # Megatron-in-stage pipeline (parallel/tpp.py). boundary/allreduce
        # stay LOGICAL (reference RuntimeStats parity); every physical
        # payload class the compiled program ships is priced separately so
        # the audit plane can classify each HLO collective exactly:
        #   * 'model'-axis activation psums: one [mb, seq, d_model] block
        #     output per row-parallel projection (count is XLA's business —
        #     CSE merges some — so we pin the PAYLOAD, not the count)
        #   * 'data'-axis grad sync of the padded sliced rows (S*tp groups)
        #   * 'data x model' grad sync of the padded replicated rows (S)
        #   * state rows pmean'd over 'data' then 'model'
        #   * the stage conveyor: 2 ppermutes x T ticks x (S-1)*dp*tp pairs
        itemsize = strategy.compute_dtype.itemsize
        M, mb = strategy.num_microbatches, strategy.mb
        dp, tp, S = strategy.dp, strategy.tp, strategy.num_stages
        bounds, shapes = strategy.bounds, strategy.shapes
        boundary = 0.0
        for s in range(1, S):
            act = mb * math.prod(shapes[bounds[s]]) * itemsize
            boundary += 2.0 * M * act
        out["boundary_bytes"] = boundary * dp
        out["allreduce_bytes"] = sum(
            tp * _ring_allreduce_bytes(4.0 * strategy._sl_lens[c], dp)
            + _ring_allreduce_bytes(4.0 * strategy._rp_lens[c], dp * tp)
            for c in range(S))
        L_sl = max(max(strategy._sl_lens), 1)
        L_rp = max(max(strategy._rp_lens), 1)
        L_st = max(max(strategy._st_lens), 1)
        out["tp_psum_payload_bytes"] = (
            float(mb) * math.prod(shapes[1]) * itemsize)
        out["tp_grad_sliced_row_bytes"] = 4.0 * L_sl
        out["tp_grad_repl_row_bytes"] = 4.0 * L_rp
        out["tp_state_row_bytes"] = 4.0 * L_st
        out["physical_allreduce_bytes"] = (
            S * tp * _ring_allreduce_bytes(4.0 * L_sl, dp)
            + S * _ring_allreduce_bytes(4.0 * L_rp, dp * tp)
            + S * tp * _ring_allreduce_bytes(4.0 * L_st, dp)
            + S * dp * _ring_allreduce_bytes(4.0 * L_st, tp))
        T = M + S - 1
        out["physical_boundary_bytes"] = (
            2.0 * T * (S - 1) * dp * tp * strategy._act_size * itemsize)
    elif name in ("GPipeStrategy", "PipeDreamStrategy",
                  "ScheduledPipelineStrategy"):
        itemsize = strategy.compute_dtype.itemsize
        M, mb, dp = strategy.num_microbatches, strategy.mb, strategy.dp
        bounds, shapes = strategy.bounds, strategy.shapes
        S = strategy.num_stages
        boundary = 0.0
        for s in range(1, S):
            act = mb * math.prod(shapes[bounds[s]]) * itemsize
            boundary += 2.0 * M * act  # activation fwd + gradient bwd
        out["boundary_bytes"] = boundary * dp  # per replica column
        if name == "GPipeStrategy":
            # physical conveyor: the compiled scan ppermutes the full
            # packed activation buffer (fwd + the autodiff transpose) over
            # every interior link of every replica column on every one of
            # the T = M*V + S - 1 ticks
            V = strategy.num_chunks // S
            T = M * V + S - 1
            out["physical_boundary_bytes"] = (
                2.0 * T * (S - 1) * dp * strategy._act_size * itemsize)
        if dp > 1:
            grad_bytes = sum(
                4.0 * strategy._p_lens[c]
                for c in range(len(strategy._p_lens))
            )  # f32 packed grads (all chunks)
            if getattr(strategy, "pipe_shard", False):
                # hybrid PP x ZeRO-1 (--dp-shard-update on gpipe): the
                # per-step gradient pmean decomposes into its RS half —
                # gradient wire HALVES vs the replicated ring allreduce —
                # plus the params' just-in-time per-bucket all-gather at
                # the next forward (f32 master weights). physical_* twins
                # price the PADDED device-major rows actually shipped.
                meta = strategy._row_meta
                C = strategy.num_chunks
                out["reduce_scatter_bytes"] = (dp - 1) / dp * grad_bytes
                out["all_gather_bytes"] = (dp - 1) / dp * grad_bytes
                out["physical_reduce_scatter_bytes"] = (
                    (dp - 1) / dp * C * meta.padded * 4.0)
                out["physical_all_gather_bytes"] = (
                    (dp - 1) / dp * C * meta.padded * 4.0)
                out["comm_buckets"] = float(meta.num_buckets)
            else:
                per_sync = _ring_allreduce_bytes(grad_bytes, dp)
                syncs = M if name == "PipeDreamStrategy" else 1
                out["allreduce_bytes"] = per_sync * syncs
                if name == "GPipeStrategy":
                    # physical grad/state sync: one ring per stage group
                    # over the PADDED [V, Lmax] device rows
                    V = strategy.num_chunks // S
                    Lp = max(max(strategy._p_lens), 1)
                    Ls = max(max(strategy._s_lens), 1)
                    out["gp_grad_row_bytes"] = 4.0 * V * Lp
                    out["gp_state_row_bytes"] = 4.0 * V * Ls
                    out["physical_allreduce_bytes"] = S * (
                        _ring_allreduce_bytes(4.0 * V * Lp, dp)
                        + _ring_allreduce_bytes(4.0 * V * Ls, dp))
    else:
        raise NotImplementedError(
            f"comm_stats has no analytic model for {name}")
    out["total_bytes"] = (out["boundary_bytes"] + out["allreduce_bytes"]
                          + out["reduce_scatter_bytes"]
                          + out["all_gather_bytes"])
    return out


def _model_params(strategy):
    import jax

    from ddlbench_tpu.models.layers import init_model

    return init_model(strategy.model, jax.random.key(0))
