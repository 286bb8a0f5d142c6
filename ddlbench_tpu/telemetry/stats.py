"""Step-latency aggregation: per-epoch percentiles + warmup accounting.

Wall-clock percentiles over per-step host times answer the first
observability question — is the step-time distribution tight (compute
bound, healthy pipeline) or heavy-tailed (input stalls, periodic syncs,
recompiles)? The loop records EVERY step's wall time (two monotonic clock
reads — cheap enough to stay on even when tracing is off), and the
per-epoch p50/p95/p99/max land on the epoch log line, in JSONL, in
``summary()``, and in ``bench.py`` JSON.

Interpretation note (documented in ARCHITECTURE.md): with async dispatch
and on-device metric accumulation the host loop runs ahead of the device,
so most steps measure DISPATCH cost and the interval-boundary steps absorb
the accumulated device time — a tight p50 with a p95 near
``log_interval x`` the true step time is the signature of a healthy
pipelined loop, not a stutter. The armed watchdog (per-step sync) makes
every sample a true device-step latency.

Warmup/compile accounting is explicit: XLA's first-compile seconds are
clocked separately (``warmup_compile_s``) and NEVER mixed into the step
distribution, so percentiles describe steady-state only. Where jax's compile
tally is installed (``telemetry/compiles.py``) the block is split too:
``warmup_trace_lower_s``, ``warmup_backend_s`` (compile or cache load),
``warmup_cache_misses`` and ``warmup_cache_hits``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional


def percentile(samples: List[float], q: float) -> float:
    """q-th percentile (0..100) with linear interpolation (numpy default).

    Pure-Python on sorted copies — sample counts here are steps/epoch
    (thousands at most), far below where numpy would matter.
    """
    if not samples:
        return 0.0
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    s = sorted(samples)
    k = (len(s) - 1) * (q / 100.0)
    lo = math.floor(k)
    hi = math.ceil(k)
    if lo == hi:
        return s[int(k)]
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def latency_summary(samples_s: List[float]) -> Dict[str, float]:
    """p50/p95/p99/max (milliseconds) + count for one sample set."""
    ms = [t * 1e3 for t in samples_s]
    return {
        "p50_ms": percentile(ms, 50.0),
        "p95_ms": percentile(ms, 95.0),
        "p99_ms": percentile(ms, 99.0),
        "max_ms": max(ms) if ms else 0.0,
        "steps": len(ms),
    }


def request_slo_ok(rec: Dict, slo_ttft: Optional[float] = None,
                   slo_itl: Optional[float] = None) -> bool:
    """One finished record's SLO verdict: TTFT <= slo_ttft AND mean ITL
    (TPOT) <= slo_itl; an omitted SLO always passes. One home for the
    predicate — serve_summary's goodput, the engine's
    ``snapshot()['slo_attainment']``, and serveview's windowed attainment
    must never disagree on what "met the SLO" means. ``arrival`` may be
    None (a request submitted without a stamp — the engine treats that as
    time 0 everywhere else, so the predicate does too)."""
    arrival = rec["arrival"]
    ttft = rec["first_token_t"] - (arrival if arrival is not None else 0.0)
    times = rec["token_times"]
    gaps = [b - a for a, b in zip(times, times[1:])]
    tpot = sum(gaps) / len(gaps) if gaps else 0.0
    return ((slo_ttft is None or ttft <= slo_ttft)
            and (slo_itl is None or tpot <= slo_itl))


def serve_summary(records: List[Dict], *, duration: float,
                  slo_ttft: Optional[float] = None,
                  slo_itl: Optional[float] = None,
                  per_tier: bool = False) -> Dict[str, float]:
    """Serving-side latency/goodput aggregation over completed requests.

    ``records`` are the engine's ``finished`` entries
    (serve/engine.py: arrival, first_token_t, token_times, n_tokens).
    Times are in whatever unit the caller measured — the engine's virtual
    model-pass units by default — and the SLOs are in the same unit.

    Reported through the same percentile machinery as the training step
    stats: TTFT (arrival -> first token) and ITL (gap between consecutive
    tokens of one request, pooled over all requests) p50/p95/p99, plus the
    serving headline — **goodput under SLO**: output tokens per time unit
    counting ONLY requests that met BOTH SLOs (:func:`request_slo_ok`).
    Throughput counts every completed token; the goodput/throughput gap
    is the capacity wasted on requests served too late to matter.

    Degenerate inputs are schema-stable by contract: zero finished
    requests and/or zero duration (a run that admitted nothing, a
    snapshot taken at t=0) return the SAME key set with all-zero values —
    never a ZeroDivisionError, never a dropped field (consumers scrape
    these keys; tests/test_telemetry.py pins the edge paths).

    ``per_tier=True`` (the SLO-tier split, ISSUE 15) additionally reports
    ``{interactive,batch}_{completed, output_tokens, ttft_p50, ttft_p95,
    itl_p50, slo_attainment, goodput_tokens_per_unit}`` — the same
    definitions restricted to each tier's records (a record without a
    ``tier`` field counts as interactive, the engine's default). The keys
    are FLAG-GATED by this parameter so plain callers keep the pinned
    schema; both tiers always appear (zeroes for an absent tier) so the
    flagged schema is stable too.
    """
    ttfts, itls, good_tokens, total_tokens, n_ok = [], [], 0, 0, 0
    # per-tier buckets fill in the SAME pass so the metric definitions
    # (ttft, gap, SLO verdict, goodput) exist exactly once
    by_tier = {t: {"ttft": [], "itl": [], "completed": 0, "tokens": 0,
                   "ok": 0, "good": 0} for t in ("interactive", "batch")}
    for r in records:
        arrival = r["arrival"]
        ttft = r["first_token_t"] - (arrival if arrival is not None
                                     else 0.0)
        times = r["token_times"]
        gaps = [b - a for a, b in zip(times, times[1:])]
        ok = request_slo_ok(r, slo_ttft, slo_itl)
        ttfts.append(ttft)
        itls.extend(gaps)
        total_tokens += r["n_tokens"]
        if ok:
            n_ok += 1
            good_tokens += r["n_tokens"]
        if per_tier:
            b = by_tier.get(r.get("tier", "interactive"))
            if b is not None:  # unknown tier labels fall in no bucket
                b["ttft"].append(ttft)
                b["itl"].extend(gaps)
                b["completed"] += 1
                b["tokens"] += r["n_tokens"]
                if ok:
                    b["ok"] += 1
                    b["good"] += r["n_tokens"]
    out = {
        "completed": len(records),
        "output_tokens": total_tokens,
        "duration": duration,
        # zero-duration guard: rates are 0, not a divide blow-up
        "throughput_tokens_per_unit": (total_tokens / duration
                                       if duration > 0 else 0.0),
        "goodput_tokens_per_unit": (good_tokens / duration
                                    if duration > 0 else 0.0),
        "slo_attainment": n_ok / len(records) if records else 0.0,
        # prompt tokens served from the cross-request prefix cache
        # (serve/prefix.py) over all completed requests — 0 with the cache
        # off or on the static baseline, keeping the schema stable
        "prefix_cached_tokens": sum(
            r.get("cached_tokens", 0) for r in records),
    }
    for name, samples in (("ttft", ttfts), ("itl", itls)):
        for q in (50.0, 95.0, 99.0):
            out[f"{name}_p{q:.0f}"] = percentile(samples, q)
    if slo_ttft is not None:
        out["slo_ttft"] = slo_ttft
    if slo_itl is not None:
        out["slo_itl"] = slo_itl
    if per_tier:
        for tier, b in by_tier.items():
            out[f"{tier}_completed"] = b["completed"]
            out[f"{tier}_output_tokens"] = b["tokens"]
            out[f"{tier}_ttft_p50"] = percentile(b["ttft"], 50.0)
            out[f"{tier}_ttft_p95"] = percentile(b["ttft"], 95.0)
            out[f"{tier}_itl_p50"] = percentile(b["itl"], 50.0)
            out[f"{tier}_slo_attainment"] = (
                b["ok"] / b["completed"] if b["completed"] else 0.0)
            out[f"{tier}_goodput_tokens_per_unit"] = (
                b["good"] / duration if duration > 0 else 0.0)
    return out


class StepLatencyStats:
    """Per-epoch step-duration collector for one run (single-threaded:
    only the train loop records)."""

    def __init__(self) -> None:
        self._epochs: Dict[int, List[float]] = {}
        self.warmup_compile_s: Optional[float] = None
        self.warmup_split: Dict[str, float] = {}

    def record_step(self, epoch: int, seconds: float) -> None:
        self._epochs.setdefault(epoch, []).append(seconds)

    def set_warmup(self, seconds: float,
                   split: Optional[Dict[str, float]] = None) -> None:
        """Clock the out-of-band warmup/compile block (train/loop.py runs
        it on a throwaway state before the measured epochs). ``split``:
        what jax's compile tally says of the block
        (``telemetry.compiles.between``), reported as ``warmup_<key>``."""
        self.warmup_compile_s = seconds
        self.warmup_split = dict(split or {})

    def epoch_summary(self, epoch: int) -> Optional[Dict[str, float]]:
        samples = self._epochs.get(epoch)
        if not samples:
            return None
        return latency_summary(samples)

    def run_summary(self) -> Optional[Dict[str, float]]:
        """Percentiles over ALL recorded steps (not a mean of per-epoch
        percentiles), plus the warmup/compile accounting."""
        samples = [t for ep in sorted(self._epochs) for t in self._epochs[ep]]
        if not samples:
            return None
        out = latency_summary(samples)
        if self.warmup_compile_s is not None:
            out["warmup_compile_s"] = self.warmup_compile_s
            out.update({f"warmup_{k}": v
                        for k, v in self.warmup_split.items()})
        return out
