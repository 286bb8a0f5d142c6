"""Serving benchmark: continuous batching vs static batching under load.

Drives the continuous-batching engine (serve/engine.py) with a seeded
open- or closed-loop workload (serve/workload.py) and reports the serving
metrics that matter for "heavy traffic from millions of users": TTFT and
inter-token-latency p50/p95/p99 plus **goodput under SLO** — output tokens
per time unit counting only requests whose TTFT and mean ITL met their
SLOs (telemetry/stats.serve_summary). One JSON line per configuration,
like every other tool:

    {"tool": "servebench", "policy": "continuous", "arrival": "poisson",
     "goodput_tokens_per_unit": G, "ttft_p95": T, ...}

Time is VIRTUAL by default: one unit = one model pass (a [max_batch, 1]
decode step or one prefill chunk — the engine's cost model, under which
batch parallelism is free and wasted passes are what scheduling policies
differ on). That makes every reported number bitwise-reproducible under a
fixed seed — the same repro discipline as every other tool — while
``--wall-clock`` adds real elapsed seconds for on-chip runs.

The default sweep runs each requested policy (continuous, then the
static whole-batch baseline) over the SAME workload at the SAME pool
size, so the goodput delta is pure scheduling effect.

Usage:
    python -m ddlbench_tpu.tools.servebench [-m transformer_s]
        [-b synthtext] [--arrival poisson|bursty|closed] [--rate 0.5]
        [--requests 64] [--max-batch 8] [--pool-pages 64] [--page 16]
        [--max-len 256] [--slo-ttft 16] [--slo-itl 2.0]
        [--shared-prefix 4:64] [--prefix-cache]
        [--sample temperature:0.8,top-k:40] [--kv-dtype int8]
        [--speculative ngram:3:4] [--deadline-slack 64] [--retry 2:8]
        [--tier-mix 0.5] [--heartbeat 16] [--platform cpu]

Deadlines + SLO tiers (ISSUE 15): ``--deadline-slack S`` stamps every
request with a completion deadline (arrival + S) — hopeless requests are
SHED at admission (the driver retries with bounded backoff under
``--retry N:B``, then rejects) and expired ones cancel into the named
``timeout`` terminal state; ``--tier-mix F`` draws that fraction into
the preemptible ``batch`` tier (interactive admits ahead, batch evicts
first) with the per-tier TTFT/ITL/goodput split in the row. All the new
counters are flag-gated; plain rows keep the pinned schema.
tools/servechaos.py composes the same load with replica kill/stall
injection.

Raw-speed levers (ISSUE 13): ``--kv-dtype`` stores the shared KV pool in
bf16 (half the f32 bytes) or int8 (a quarter — quantize-at-write with
per-page scales, dequant fused in-kernel; the row's ``pool_bytes`` makes
the capacity claim a number), and ``--speculative ngram:N:K`` turns the
decode step into a drafted verify pass (token streams bitwise identical
to greedy; ``spec_accept_rate``/``tokens_per_pass`` report whether the
traffic's self-similarity paid for it).

Self-healing autoscaler (ISSUE 19): ``--autoscale LO:HI`` puts a
FleetController (serve/autoscaler.py) in the loop — per-window SLO
attainment/goodput + shed/timeout/queue signals drive live ``resize()``
within [LO, HI] clamps (hysteresis, per-direction cooldowns, bounded
actuation budget), and a dead or heartbeat-drained replica is
auto-repaired through the factory spawn. ``--shape diurnal|ramp|spike``
grows the matching traffic curves (prompts bitwise-identical across
shapes), so the headline A/B is ``--shape diurnal --autoscale 1:N`` vs a
static ``--replicas N`` fleet: equal goodput, strictly fewer
replica-hours. The tool exits nonzero if an autoscaled run loses a
request.

The prefix-cache A/B: ``--shared-prefix G:P`` synthesizes G groups of
requests sharing a P-token prompt head, and ``--prefix-cache`` lets the
continuous engine serve cached heads from resident KV pages — compare the
``prefill_tokens`` / ``ttft_p50`` / ``prefix_*`` fields against the same
invocation without the flag (identical token streams, pinned).

Observability (PR 11): ``--trace PATH`` records the request-lifecycle
trace (serve/engine.py events in virtual time, one Chrome-trace track per
request per replica plus counter tracks) and writes it Perfetto-loadable
to PATH (``PATH.<policy>`` when several policies run) with the SLOs
embedded in the metadata. Tracing is metrics-neutral: the JSON line and
the token streams are bitwise identical with or without it (pinned).
``--timeline`` additionally reduces the trace in-process
(telemetry/serveview.py) and embeds the per-window SLO/goodput table +
TTFT/ITL component breakdowns in the JSON line (``--window`` sets the
bucket width).
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
import time


# engine stats keys that only carry signal under --speculative: excluded
# from plain rows so the schema-pinned key set is unchanged when the flag
# is off (the --resize pattern)
_SPEC_FIELDS = frozenset((
    "spec_passes", "spec_drafted", "spec_accepted", "decode_tokens",
    "spec_accept_rate", "tokens_per_pass"))

# engine stats keys that only carry signal under --deadline-slack
# (admission shedding / timeout cancellation): same flag-gating pattern
_CHAOS_FIELDS = frozenset(("shed", "timeouts"))

# stats keys only the disaggregated server emits (handoff wire-byte
# accounting): gated so a plain aggregated row keeps the pinned schema
# even if a future server grows the counters
_DISAGG_FIELDS = frozenset((
    "shipped_requests", "shipped_pages", "shipped_payload_bytes",
    "shipped_sidecar_bytes", "shipped_checksum_bytes"))

# engine stats keys that only carry signal when the SDC checksum ledger
# is armed (--scrub here; --corrupt in servechaos): plain rows stay
# byte-identical in schema — the engine always counts, the row only
# shows the counters when the flag asked for them
_SDC_FIELDS = frozenset((
    "sdc_injected", "sdc_detected", "sdc_quarantined", "sdc_recovered",
    "sdc_scrubbed", "sdc_recompute_checks", "sdc_wire_detected",
    "sdc_wire_repaired"))


def parse_disaggregate(spec, perr):
    """Parse ``--disaggregate P:D`` (prefill:decode replica counts) —
    shared with servechaos. Returns (P, D) or None for an absent spec."""
    if not spec:
        return None
    try:
        p_s, d_s = spec.split(":")
        pd = (int(p_s), int(d_s))
    except ValueError:
        perr(f"--disaggregate wants P:D (prefill:decode replicas), "
             f"got {spec!r}")
    if pd[0] < 1 or pd[1] < 1:
        perr(f"--disaggregate {spec!r}: both fleets need >= 1 replica")
    return pd


def _round6(v):
    """round(_, 6) through nested timeline/breakdown structures so the
    JSON stays bitwise-reproducible and diff-friendly."""
    if isinstance(v, float):
        return round(v, 6)
    if isinstance(v, dict):
        return {k: _round6(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_round6(x) for x in v]
    return v


def parse_retry(spec, perr):
    """Parse a ``--retry N:B`` spec (shared by servebench and servechaos
    so the sibling tools cannot diverge on bounds): N >= 1 resubmissions,
    base backoff B >= 0. Returns (N, B) or None for an absent spec."""
    if not spec:
        return None
    try:
        n_s, b_s = spec.split(":")
        retry = (int(n_s), float(b_s))
    except ValueError:
        perr(f"--retry wants N:B (retries:base_backoff), got {spec!r}")
    if retry[0] < 1 or retry[1] < 0:
        perr(f"--retry {spec!r}: N >= 1 and B >= 0")
    return retry


def parse_autoscale(spec, perr):
    """Parse ``--autoscale LO:HI`` (replica clamps for the closed-loop
    controller) — shared with servechaos. Returns (lo, hi) or None."""
    if not spec:
        return None
    try:
        lo_s, hi_s = spec.split(":")
        lohi = (int(lo_s), int(hi_s))
    except ValueError:
        perr(f"--autoscale wants LO:HI (min:max replicas), got {spec!r}")
    if lohi[0] < 1 or lohi[1] < lohi[0]:
        perr(f"--autoscale {spec!r}: needs 1 <= LO <= HI")
    return lohi


def shed_accounting(requests, completed, shed, timeouts, driver_stats):
    """Terminal-state accounting shared by servebench and servechaos —
    the cross-tool no-loss gate must come from ONE formula: every request
    ends completed, timed out, or rejected; anything else is lost
    (``requests_lost == 0`` is the invariant the chaos gates pin)."""
    retries = driver_stats.get("retries", 0)
    rejected = driver_stats.get("rejected", 0)
    submissions = requests + retries
    return {
        "retries": retries,
        "rejected": rejected,
        "requests_lost": requests - completed - timeouts - rejected,
        # zero-requests guard: the degenerate row stays schema-stable
        # with all-zero rates, never a ZeroDivisionError (the
        # serve_summary contract)
        "shed_rate": (round(shed / submissions, 6) if submissions else 0.0),
        "timeout_rate": (round(timeouts / requests, 6)
                         if requests else 0.0),
        "retry_amplification": (round(submissions / requests, 6)
                                if requests else 1.0),
    }


def _resize_fn(n: int):
    def fire(server, clock):
        rep = server.resize(n, now=clock)
        print(f"servebench: resize @ {clock:g} -> {n} replicas "
              f"(evicted {rep['evicted']}, redistributed "
              f"{rep['redistributed']})", file=sys.stderr, flush=True)
    return fire


def _merge_events(resizes, events):
    """One sorted ``(at, fn(server, clock))`` schedule from the legacy
    ``(at, n)`` resize specs plus arbitrary chaos injections (servechaos
    passes kill/stall closures through ``events``)."""
    ev = [(at, _resize_fn(n)) for at, n in (resizes or [])]
    ev.extend(events or [])
    ev.sort(key=lambda e: e[0])
    return ev


def _fire_events(server, clock: float, events):
    """Fire every due ``(at, fn)`` event (a sorted list the caller
    consumes) — resizes, replica kills, stalls."""
    while events and clock >= events[0][0]:
        at, fn = events.pop(0)
        fn(server, clock)


class _Submitter:
    """Driver-side admission with the bounded retry-with-backoff policy
    (ISSUE 15): a SHED submission (deadline admission control refused the
    request) retries after ``backoff * 2**attempt`` time units, up to
    ``retries`` times, then goes terminal as REJECTED — so shed rate and
    retry amplification become reported numbers instead of silent driver
    behavior. ``stats`` collects ``retries``/``rejected`` for the JSON
    row. With no deadlines in the traffic nothing is ever shed and this
    reduces to plain ``server.submit``."""

    def __init__(self, server, retry=None, deadline_slack=None, stats=None):
        self.server = server
        self.retries, self.backoff = retry if retry else (0, 1.0)
        self.slack = deadline_slack
        self.pending = []  # (due, rid, attempt, req), sorted by due
        self.stats = stats if stats is not None else {}
        self.stats.setdefault("retries", 0)
        self.stats.setdefault("rejected", 0)

    def offer(self, req, clock: float, attempt: int = 0) -> str:
        """One submission attempt -> "ok" | "retry" | "rejected"."""
        if req.arrival is None:
            req.arrival = clock  # closed loop stamps at release
        if self.slack is not None and req.deadline is None:
            # closed-loop deadline stamp: the workload could not know the
            # release time (open-loop requests arrive pre-stamped)
            req.deadline = req.arrival + self.slack
        if self.server.submit(req, now=clock):
            return "ok"
        if attempt < self.retries:
            self.stats["retries"] += 1
            bisect.insort(self.pending,
                          (clock + self.backoff * (2 ** attempt),
                           req.rid, attempt + 1, req))
            return "retry"
        self.stats["rejected"] += 1
        return "rejected"

    def release_due(self, clock: float) -> int:
        """Fire due retries; returns how many went terminal (rejected)."""
        dead = 0
        while self.pending and self.pending[0][0] <= clock:
            _, _, attempt, req = self.pending.pop(0)
            if self.offer(req, clock, attempt) == "rejected":
                dead += 1
        return dead

    def next_due(self):
        return self.pending[0][0] if self.pending else None


def _advance_controllers(controllers, clock: float):
    """Kick every autoscale controller up to the virtual clock — called
    after each global step and idle jump so decisions land at
    deterministic instants (serve/autoscaler.py's driver contract)."""
    for c in controllers or ():
        c.advance(clock)


def run_open_loop(server, reqs, resizes=None, events=None, retry=None,
                  deadline_slack=None, driver_stats=None, controllers=None):
    """Release requests at their arrival times; returns the final clock.
    ``events`` is a list of timed ``(at, fn(server, clock))`` injections
    (resizes are sugar for them); ``retry=(N, backoff)`` arms the shed
    retry policy and ``driver_stats`` (a dict) receives its counters;
    ``controllers`` are autoscale FleetControllers advanced in lockstep
    with the virtual clock (they resize/repair the fleet live)."""
    clock, i = 0.0, 0
    ev = _merge_events(resizes, events)
    sub = _Submitter(server, retry, deadline_slack, driver_stats)
    pend = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    while i < len(pend) or sub.pending or server.has_work():
        _fire_events(server, clock, ev)
        sub.release_due(clock)
        while i < len(pend) and pend[i].arrival <= clock:
            sub.offer(pend[i], clock)
            i += 1
        if not server.has_work():
            # idle: jump to the next arrival, pending retry, or
            # scheduled injection — skipping events here would fire a
            # kill/stall/resize under DIFFERENT load than its schedule
            # asked for (events dated past the end of all work still
            # never fire; the loop exits first, surfaced by the caller)
            nxts = [t for t in (
                pend[i].arrival if i < len(pend) else None,
                sub.next_due(),
                ev[0][0] if ev else None) if t is not None]
            if not nxts:
                break
            clock = max(clock, min(nxts))
            # the controller sees idle time too — that is where the
            # diurnal trough's scale-downs come from
            _advance_controllers(controllers, clock)
            continue
        rep = server.step(clock)
        clock += rep.cost
        _advance_controllers(controllers, clock)
    return clock


def run_closed_loop(server, reqs, concurrency: int, resizes=None,
                    events=None, retry=None, deadline_slack=None,
                    driver_stats=None, controllers=None):
    """Keep ``concurrency`` requests in flight; each TERMINAL event —
    completion, timeout, or a shed request exhausting its retries —
    releases the next. Returns the final clock."""
    clock, nxt, done = 0.0, 0, 0
    ev = _merge_events(resizes, events)
    sub = _Submitter(server, retry, deadline_slack, driver_stats)
    n = len(reqs)
    outstanding = 0  # released and not yet terminal (incl. pending retry)

    def top_up():
        nonlocal nxt, done, outstanding
        while nxt < n and outstanding < concurrency:
            st = sub.offer(reqs[nxt], clock)
            nxt += 1
            if st == "rejected":
                done += 1
            else:
                outstanding += 1

    top_up()
    while done < n:
        _fire_events(server, clock, ev)
        dead = sub.release_due(clock)
        done += dead
        outstanding -= dead
        top_up()
        if not server.has_work():
            # jump to the next retry or scheduled injection (same
            # fire-at-the-scheduled-load contract as the open loop)
            nxts = [t for t in (sub.next_due(),
                                ev[0][0] if ev else None)
                    if t is not None]
            if nxts:
                clock = max(clock, min(nxts))
                _advance_controllers(controllers, clock)
                continue
            if outstanding:
                # a server-INTERNAL shed (failover/drain/resize under
                # deadlines retires a request without any driver-visible
                # completion/timeout) would otherwise hold its
                # concurrency slot forever and strand the rest of the
                # workload — reconcile: the vanished requests are
                # terminal (they surface in requests_lost) and their
                # slots release the tail
                done += outstanding
                outstanding = 0
                top_up()
                continue
            break  # everything released went terminal
        rep = server.step(clock)
        clock += rep.cost
        _advance_controllers(controllers, clock)
        term = len(rep.completed) + len(rep.timed_out)
        done += term
        outstanding -= term
        top_up()
    return clock


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--model", default="transformer_s")
    p.add_argument("-b", "--benchmark", default="synthtext")
    p.add_argument("--policies", default="continuous,static",
                   help="comma list among continuous,static — each runs "
                        "the same workload at the same pool size")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--pool-pages", type=int, default=64)
    p.add_argument("--page", type=int, default=16)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="tokens per prefill call (page multiple; default: "
                        "one page; 0 = whole prompt in one padded call)")
    p.add_argument("--token-budget", type=int, default=0,
                   help="tokens one step may pack (0 = max_batch + 2 "
                        "prefill chunks)")
    p.add_argument("--replicas", type=int, default=1,
                   help="independent data-parallel serving replicas "
                        "(least-loaded dispatch)")
    p.add_argument("--serve-tp", type=int, default=1, metavar="N",
                   help="tensor-parallel width of ONE replica: the serve "
                        "programs shard Megatron-style over a mesh "
                        "'model' axis (N devices per replica share one "
                        "page table), so models larger than one chip's "
                        "HBM serve at all. Default 1 keeps the single-"
                        "chip programs bitwise-unchanged")
    p.add_argument("--disaggregate", default=None, metavar="P:D",
                   help="disaggregated serving: a P-replica PREFILL fleet "
                        "feeds a D-replica DECODE fleet by KV-page "
                        "shipping (serve/handoff.py) — int8 pools ship "
                        "f32/4 payload bytes. Token streams pin bitwise "
                        "vs the aggregated fleet; the row gains "
                        "disaggregate/prefill_replicas/decode_replicas + "
                        "shipped_* fields. Continuous policy only; "
                        "replaces --replicas and excludes --resize")
    p.add_argument("--resize", action="append", default=[], metavar="AT:N",
                   help="live replica resize schedule (repeatable): at "
                        "virtual time AT scale the fleet to N replicas "
                        "under load — scale-down drains replicas (in-"
                        "flight requests evicted onto the recompute path, "
                        "queues redistributed least-loaded), scale-up "
                        "shares the jitted callables. No request is lost "
                        "and token streams stay bitwise vs an un-resized "
                        "control (pinned); the JSON row gains "
                        "resize_events/requests_lost fields")
    p.add_argument("--autoscale", default=None, metavar="LO:HI",
                   help="close the loop: a FleetController "
                        "(serve/autoscaler.py) watches windowed SLO "
                        "attainment/goodput + shed/timeout/queue signals "
                        "and actuates resize() live — scale-up under "
                        "pressure, scale-down in idle troughs, AUTO-REPAIR "
                        "of dead/heartbeat-drained replicas through the "
                        "factory spawn — with the fleet clamped to "
                        "[LO, HI]. The row gains replica_hours/"
                        "scale_events/repairs/autoscale_attainment + the "
                        "decision ledger, and the tool exits nonzero if "
                        "the run loses a request. Excludes --resize "
                        "(the controller owns the schedule); with "
                        "--disaggregate each fleet gets its own "
                        "controller")
    p.add_argument("--scale-window", type=float, default=32.0, metavar="W",
                   help="autoscale observation-window width in time units "
                        "(one decision per window)")
    p.add_argument("--scale-cooldown", type=float, default=64.0,
                   metavar="C",
                   help="min time between same-direction autoscale "
                        "actuations (repair is exempt: restoring capacity "
                        "the policy already chose is not a scale decision)")
    p.add_argument("--arrival", default="poisson",
                   choices=("poisson", "bursty", "closed"))
    p.add_argument("--shape", default=None,
                   choices=("diurnal", "ramp", "spike"),
                   help="traffic shape layered on --arrival poisson: the "
                        "rate curve (daily cycle / linear ramp / flash "
                        "crowd) modulates inter-arrivals drawn from a "
                        "separate seeded stream, so prompts stay bitwise-"
                        "identical across shapes (the autoscale A/B "
                        "fixture)")
    p.add_argument("--rate", type=float, default=0.5,
                   help="open-loop arrival rate (requests per model pass; "
                        "with --shape, the PEAK rate)")
    p.add_argument("--burst-size", type=int, default=8)
    p.add_argument("--burst-factor", type=float, default=4.0)
    p.add_argument("--concurrency", type=int, default=16,
                   help="closed-loop in-flight request count")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--prompt-lens", default="4,16,64",
                   help="lo,typical,hi of the heavy-tail prompt mixture")
    p.add_argument("--out-lens", default="2,16,64",
                   help="lo,typical,hi of the heavy-tail output mixture")
    p.add_argument("--tail-frac", type=float, default=0.25)
    p.add_argument("--shared-prefix", default=None, metavar="G:P",
                   help="shared-prefix traffic: G prefix groups of P "
                        "tokens each; every prompt = one group's prefix + "
                        "a unique heavy-tail tail (the prefix-cache A/B "
                        "workload)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="enable the cross-request prefix cache on the "
                        "continuous policy (admissions bind cached prompt "
                        "pages and prefill only the tail; the static "
                        "baseline always runs cache-off and reports the "
                        "cache counters as 0)")
    p.add_argument("--kv-dtype", default=None,
                   choices=("float32", "bfloat16", "int8"),
                   help="KV-pool storage dtype: bfloat16 halves pool "
                        "bytes, int8 quarters them (pages quantize at the "
                        "write boundary with per-page scales + stochastic "
                        "rounding; dequant fused into the attention "
                        "kernels). The row gains a kv_dtype field; "
                        "default float32 keeps the pinned schema")
    p.add_argument("--speculative", default=None, metavar="ngram:N:K",
                   help="self-drafting speculative decoding: an N-gram "
                        "drafter proposes up to K tokens per decode row "
                        "from the row's own stream, verified in one "
                        "K+1-wide pass priced as ONE model pass; greedy "
                        "acceptance keeps token streams bitwise identical "
                        "to non-speculative. The row gains speculative/"
                        "spec_*/tokens_per_pass fields")
    p.add_argument("--scrub", type=int, default=None, metavar="N",
                   help="arm the SDC checksum ledger (serve/integrity.py) "
                        "and scrub N stamped pool pages per step (0 = "
                        "boundary verification only) — the clean-traffic "
                        "overhead measurement for the defense servechaos "
                        "exercises under --corrupt. The row gains the "
                        "sdc_* counters (all zero without injected "
                        "faults); plain rows keep the pinned schema")
    p.add_argument("--sample", default=None, metavar="temperature:T[,top-k:K]",
                   help="sample instead of greedy argmax: softmax(logits/T)"
                        " with optional top-k restriction, counter-based "
                        "per-request seeds (run seed + request id + token "
                        "index) so streams stay bitwise-reproducible; "
                        "default greedy")
    p.add_argument("--slo-ttft", type=float, default=16.0,
                   help="TTFT SLO in time units (model passes)")
    p.add_argument("--slo-itl", type=float, default=2.0,
                   help="mean inter-token-latency SLO in time units")
    p.add_argument("--deadline-slack", type=float, default=None,
                   metavar="S",
                   help="per-request completion deadline = arrival + S "
                        "time units: the engine SHEDS a request at "
                        "admission when its projected completion already "
                        "misses the deadline (named rejection; see "
                        "--retry) and cancels an expired one into the "
                        "named `timeout` terminal state with all pages "
                        "freed. The row gains shed/timeouts/retries/"
                        "rejected/requests_lost + rate fields; plain rows "
                        "keep the pinned schema")
    p.add_argument("--retry", default=None, metavar="N:B",
                   help="bounded retry-with-backoff for SHED requests: up "
                        "to N resubmissions, the k-th after B*2^k time "
                        "units — after N the request is terminally "
                        "rejected. Only meaningful with --deadline-slack")
    p.add_argument("--tier-mix", type=float, default=None, metavar="F",
                   help="SLO tiers (ROADMAP 2c): each request is drawn "
                        "tier=batch with probability F (else interactive)."
                        " Interactive admits ahead of batch and batch is "
                        "the preemptible eviction lane; the row gains "
                        "per-tier TTFT/ITL/goodput/attainment splits")
    p.add_argument("--heartbeat", type=float, default=0.0, metavar="W",
                   help="serve-side straggler heartbeat: a replica "
                        "holding work with no progress for > W time units "
                        "is drained and its requests redistribute to the "
                        "survivors (0 = off; mostly exercised by "
                        "servechaos stall injection)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="record the request-lifecycle trace (virtual-time "
                        "spans/counters, one track per request per replica)"
                        " and write Chrome trace-event JSON here — "
                        "PATH.<policy> when several policies run. Metrics-"
                        "neutral: the JSON line is bitwise identical with "
                        "or without this flag")
    p.add_argument("--trace-capacity", type=int, default=200_000,
                   help="trace ring size in events (the ring keeps the "
                        "newest window and the metadata records drops)")
    p.add_argument("--timeline", action="store_true",
                   help="with --trace: reduce the trace via telemetry/"
                        "serveview and embed the windowed SLO/goodput "
                        "table + TTFT/ITL component breakdowns in the "
                        "JSON line")
    p.add_argument("--window", type=float, default=32.0,
                   help="timeline bucket width in time units "
                        "(with --timeline)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--wall-clock", action="store_true",
                   help="also report real elapsed seconds (off by default "
                        "so the JSON stays bitwise-reproducible)")
    p.add_argument("--audit", default=None, metavar="PATH",
                   help="emit the serve programs' compiled audit manifests "
                        "(telemetry/audit.py: flops / HBM / collective "
                        "ledger + pool_page_bytes tie-out) into one ledger "
                        "JSON next to the row")
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    if args.timeline and not args.trace:
        p.error("--timeline reduces a recorded trace; pass --trace PATH")
    if args.window <= 0:
        p.error("--window must be > 0 time units")
    apply_platform(args.platform)

    import jax

    from ddlbench_tpu.distributed import (enable_compilation_cache,
                                          record_provenance)

    enable_compilation_cache()
    prov = record_provenance(args.platform, "servebench")

    from ddlbench_tpu.config import DATASETS, ServeConfig
    from ddlbench_tpu.models import init_model
    from ddlbench_tpu.models.zoo import get_model
    from ddlbench_tpu.serve.engine import make_server, supports_serve
    from ddlbench_tpu.serve.workload import make_workload
    from ddlbench_tpu.telemetry.stats import serve_summary

    spec = DATASETS[args.benchmark]
    if spec.kind != "tokens":
        p.error(f"-b {args.benchmark!r} is not a causal-LM token workload; "
                "the serving engine serves causal LMs (pick a 'tokens' "
                "benchmark, e.g. synthtext)")
    model = get_model(args.model, spec)
    if not supports_serve(model):
        p.error(f"{args.model} has layers without serving support")
    params, state, _ = init_model(model, jax.random.key(0))

    plo, ptyp, phi = (int(x) for x in args.prompt_lens.split(","))
    olo, otyp, ohi = (int(x) for x in args.out_lens.split(","))
    policies = [s.strip() for s in args.policies.split(",") if s.strip()]
    groups = prefix_len = 0
    if args.shared_prefix:
        try:
            groups, prefix_len = (int(x)
                                  for x in args.shared_prefix.split(":"))
        except ValueError:
            p.error("--shared-prefix wants G:P (groups:prefix_tokens), "
                    f"got {args.shared_prefix!r}")
    retry = parse_retry(args.retry, p.error)
    disagg = parse_disaggregate(args.disaggregate, p.error)
    autoscale = parse_autoscale(args.autoscale, p.error)
    if autoscale:
        if args.resize:
            p.error("--autoscale closes the resize loop itself; it does "
                    "not compose with a scripted --resize schedule")
        if args.scale_window <= 0:
            p.error("--scale-window must be > 0 time units")
        if args.scale_cooldown < 0:
            p.error("--scale-cooldown must be >= 0 time units")
    if args.shape and args.arrival != "poisson":
        p.error("--shape modulates the poisson arrival process; pass "
                "--arrival poisson")
    if args.serve_tp < 1:
        p.error("--serve-tp must be >= 1")
    if disagg:
        if policies != ["continuous"]:
            p.error("--disaggregate serves the continuous policy only "
                    "(pass --policies continuous); the static baseline's "
                    "fill/drain barrier has no phase boundary to ship at")
        if args.replicas != 1:
            p.error("--disaggregate P:D sets both fleet sizes; drop "
                    "--replicas")
        if args.resize:
            p.error("--resize scales one aggregated fleet; it does not "
                    "compose with --disaggregate")
    if args.deadline_slack is not None and args.deadline_slack <= 0:
        p.error("--deadline-slack must be > 0 time units")
    if args.retry and args.deadline_slack is None:
        p.error("--retry retries SHED submissions; nothing is ever shed "
                "without --deadline-slack")
    if args.tier_mix is not None and not 0.0 <= args.tier_mix <= 1.0:
        p.error("--tier-mix is a probability in [0, 1]")
    if args.heartbeat < 0:
        p.error("--heartbeat must be >= 0 time units (0 = off)")
    if args.scrub is not None and args.scrub < 0:
        p.error("--scrub must be >= 0 pages per step (0 arms the ledger "
                "with boundary verification only)")
    resizes = []
    for rspec in args.resize:
        try:
            at_s, n_s = rspec.split(":")
            at, nrep = float(at_s), int(n_s)
        except ValueError:
            p.error(f"--resize wants AT:N (virtual_time:replicas), "
                    f"got {rspec!r}")
        if at < 0 or nrep < 1:
            p.error(f"--resize {rspec!r}: AT >= 0 and N >= 1")
        resizes.append((at, nrep))
    resizes.sort()
    temperature, top_k = 0.0, 0
    if args.sample:
        for part in args.sample.split(","):
            key, _, val = part.partition(":")
            if key == "temperature":
                temperature = float(val)
            elif key == "top-k":
                top_k = int(val)
            else:
                p.error(f"--sample parts are temperature:T and top-k:K, "
                        f"got {part!r}")
        if temperature <= 0.0:
            p.error("--sample needs temperature:T with T > 0 "
                    "(omit --sample for greedy)")
    # under --autoscale the INITIAL fleet is --replicas clamped into the
    # band (start inside the clamps; the controller takes it from there)
    replicas0 = (max(autoscale[0], min(autoscale[1], args.replicas))
                 if autoscale else args.replicas)
    base = ServeConfig(
        max_batch=args.max_batch, pool_pages=args.pool_pages,
        page=args.page, max_len=min(args.max_len, spec.seq_len),
        token_budget=args.token_budget,
        prefill_chunk=(args.page if args.prefill_chunk is None
                       else args.prefill_chunk),
        replicas=replicas0, tp=args.serve_tp,
        temperature=temperature, top_k=top_k,
        sample_seed=args.seed, trace=bool(args.trace),
        slo_ttft=args.slo_ttft, slo_itl=args.slo_itl,
        heartbeat=args.heartbeat,
        kv_dtype=args.kv_dtype or "float32",
        speculative=args.speculative or "none",
        integrity=args.scrub is not None, scrub=args.scrub or 0)

    shared_fns = None
    rc = 0
    for policy in policies:
        # the static baseline is cache-off by definition (it measures
        # whole-batch scheduling); its JSON rows still carry the prefix
        # counters — as zeros — so the schema is stable across policies
        cfg = base.replace(
            policy=policy,
            prefix_cache=args.prefix_cache and policy == "continuous")
        cfg.validate()
        # fresh workload per policy: ServeRequest.arrival is stamped by the
        # closed-loop driver, and both policies must see identical traffic
        reqs = make_workload(
            seed=args.seed, n_requests=args.requests,
            vocab=spec.num_classes, arrival=args.arrival, rate=args.rate,
            shape=args.shape,
            burst_size=args.burst_size, burst_factor=args.burst_factor,
            prompt_lo=plo, prompt_typical=ptyp, prompt_hi=phi,
            out_lo=olo, out_typical=otyp, out_hi=ohi,
            tail_frac=args.tail_frac, prefix_groups=groups,
            prefix_len=prefix_len, max_len=cfg.max_len,
            deadline_slack=args.deadline_slack,
            batch_frac=args.tier_mix or 0.0)
        # policy rows share the compiled programs (identical model and
        # shapes — policy/prefix_cache are host-side decisions), so only
        # the first row pays the trace
        if disagg:
            from ddlbench_tpu.serve.handoff import make_disaggregated

            server = make_disaggregated(model, params, state, cfg,
                                        disagg[0], disagg[1],
                                        shared_fns=shared_fns)
        else:
            server = make_server(model, params, state, cfg,
                                 shared_fns=shared_fns)
        shared_fns = server.engines[0].jit_fns()
        controllers = None
        if autoscale:
            from ddlbench_tpu.serve.autoscaler import (
                AutoscalePolicy, combined_attainment, make_controllers,
                replica_hours)

            policy_cfg = AutoscalePolicy(
                lo=autoscale[0], hi=autoscale[1],
                window=args.scale_window,
                cooldown_up=args.scale_cooldown,
                cooldown_down=args.scale_cooldown)
            controllers = make_controllers(server, policy_cfg)
        if args.audit:
            # compiled-program audit for this serve layout: every engine
            # shares the compiled programs, so engine[0] speaks for the
            # fleet (one ledger per run; policies share shapes)
            from ddlbench_tpu.telemetry.audit import (audit_serve_engine,
                                                      write_manifests)

            mans, pool_audit = audit_serve_engine(
                server.engines[0], prefix=f"serve/{args.model}")
            write_manifests(args.audit, mans,
                            header={**prov, "tool": "servebench"})
            print(f"servebench: {len(mans)} audit manifests -> "
                  f"{args.audit} (pool_ok={pool_audit['ok']})",
                  file=sys.stderr, flush=True)
            args.audit = None
        # one fresh bounded ring per policy row, installed process-global
        # (the engines look it up lazily) and restored afterwards —
        # recording never reorders the scheduler, so the run below is
        # bitwise identical traced or not (pinned)
        tracer = prev_tracer = None
        if args.trace:
            from ddlbench_tpu.telemetry.tracer import (Tracer, get_tracer,
                                                       set_tracer)

            prev_tracer = get_tracer()
            tracer = set_tracer(Tracer(args.trace_capacity)).enable()
        dstats = {}
        t0 = time.perf_counter()
        try:
            if args.arrival == "closed":
                duration = run_closed_loop(server, reqs, args.concurrency,
                                           resizes=resizes, retry=retry,
                                           deadline_slack=args.deadline_slack,
                                           driver_stats=dstats,
                                           controllers=controllers)
            else:
                duration = run_open_loop(server, reqs, resizes=resizes,
                                         retry=retry,
                                         deadline_slack=args.deadline_slack,
                                         driver_stats=dstats,
                                         controllers=controllers)
            if controllers:
                # settle the ledgers at the final clock (integrates
                # replica-hours through any trailing idle segment)
                _advance_controllers(controllers, duration)
        finally:
            if tracer is not None:
                tracer.disable()
                set_tracer(prev_tracer)
        wall = time.perf_counter() - t0
        if resizes and len(server.resize_events) < len(resizes):
            unfired = [f"{at:g}:{n}" for at, n in
                       resizes[len(server.resize_events):]]
            print(f"servebench: WARNING {len(unfired)} --resize event(s) "
                  f"dated past the end of work never fired "
                  f"({', '.join(unfired)}); the run drained at "
                  f"{duration:g}", file=sys.stderr, flush=True)
        timeline_fields = {}
        if tracer is not None:
            from ddlbench_tpu.telemetry.export import export_chrome_trace

            if args.timeline:
                from ddlbench_tpu.telemetry.serveview import breakdown

                bd = breakdown(tracer, slo_ttft=args.slo_ttft,
                               slo_itl=args.slo_itl, window=args.window,
                               per_request=False)
                timeline_fields = {
                    "window": args.window,
                    "timeline": _round6(bd["timeline"]),
                    "ttft_breakdown": _round6(bd["ttft"]),
                    "itl_breakdown": _round6(bd["itl"]),
                    "decomp_exact": bd["decomp_exact"],
                }
            path = (args.trace if len(policies) == 1
                    else f"{args.trace}.{policy}")
            n = export_chrome_trace(tracer, path, extra_metadata={
                "serve": {"tool": "servebench", "policy": policy,
                          "tp": cfg.tp, "replicas": cfg.replicas,
                          "slo_ttft": args.slo_ttft,
                          "slo_itl": args.slo_itl,
                          "time_unit": "model_pass",
                          "seed": args.seed}})
            print(f"servebench: {n} trace events written to {path}"
                  + (f" ({tracer.dropped_events} dropped: ring full)"
                     if tracer.dropped_events else ""),
                  file=sys.stderr, flush=True)
        fin = server.finished
        summary = serve_summary(fin, duration=duration,
                                slo_ttft=args.slo_ttft,
                                slo_itl=args.slo_itl,
                                per_tier=args.tier_mix is not None)
        eng_stats = server.stats_summary()
        chaos = args.deadline_slack is not None
        sdc = args.scrub is not None
        acct = shed_accounting(args.requests, len(fin),
                               int(eng_stats["shed"]),
                               int(eng_stats["timeouts"]), dstats)
        lost = acct["requests_lost"]
        rec = {
            "tool": "servebench",
            "model": args.model,
            "benchmark": args.benchmark,
            "policy": policy,
            "arrival": args.arrival,
            # --shape only (plain rows keep the pinned schema): the
            # traffic rate curve the arrivals followed
            **({"shape": args.shape} if args.shape else {}),
            "rate": args.rate if args.arrival != "closed" else None,
            "concurrency": (args.concurrency if args.arrival == "closed"
                            else None),
            "requests": args.requests,
            "seed": args.seed,
            "max_batch": cfg.max_batch,
            "pool_pages": cfg.pool_pages,
            "page": cfg.page,
            "max_len": cfg.max_len,
            "prefill_chunk": cfg.resolved_prefill_chunk(),
            "token_budget": cfg.resolved_token_budget(),
            "replicas": cfg.replicas,
            "prefix_cache": cfg.prefix_cache,
            "shared_prefix": args.shared_prefix,
            "sample": args.sample,
            "time_unit": "model_pass",
            **{k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in summary.items()},
            **{k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in eng_stats.items()
               # serve_summary already reports completed; the speculative
               # and deadline counters are flag-gated (all zero when the
               # flags are off) so a plain row keeps the schema-pinned
               # key set
               if k != "completed"
               and (args.speculative or k not in _SPEC_FIELDS)
               and (chaos or k not in _CHAOS_FIELDS)
               and (disagg or k not in _DISAGG_FIELDS)
               and (sdc or k not in _SDC_FIELDS)},
            # --serve-tp only (plain rows keep the pinned schema): the
            # tp-group width every replica runs at
            **({"serve_tp": cfg.tp} if args.serve_tp > 1 else {}),
            # --disaggregate only: the fleet split (shipped_* counters
            # ride the stats merge above under the same gate)
            **({"disaggregate": args.disaggregate,
                "prefill_replicas": disagg[0],
                "decode_replicas": disagg[1]} if disagg else {}),
            # --kv-dtype / --speculative only (plain rows keep the
            # schema-pinned key set): the A/B axis made explicit
            **({"kv_dtype": cfg.kv_dtype} if args.kv_dtype else {}),
            **({"speculative": cfg.speculative}
               if args.speculative else {}),
            # --scrub only (plain rows keep the schema-pinned key set):
            # the scrub budget behind the sdc_* counters riding the
            # stats merge above
            **({"scrub": cfg.scrub} if sdc else {}),
            # --timeline only: windowed SLO/goodput series + TTFT/ITL
            # component breakdowns (absent otherwise so a plain row stays
            # bitwise identical traced or untraced)
            **timeline_fields,
            # --deadline-slack only (plain rows keep the schema-pinned
            # key set): the deadline knob, the driver's retry policy
            # outcome, and the shed/timeout economics as rates
            **({"deadline_slack": args.deadline_slack,
                "retry": args.retry, **acct}
               if chaos else {}),
            # --tier-mix only: the per-tier summary split rides the
            # serve_summary merge above; this records the mix itself
            **({"tier_mix": args.tier_mix}
               if args.tier_mix is not None else {}),
            # --heartbeat only: straggler drains (servechaos's stall
            # injections are where these fire)
            **({"heartbeat": args.heartbeat,
                "heartbeat_drains": len(server.heartbeat_events)}
               if args.heartbeat else {}),
            # --resize only (plain rows keep the schema-pinned key set):
            # the resize schedule, what each event displaced, the final
            # fleet size, and the no-request-lost invariant made explicit
            **({"resize": args.resize,
                "resize_events": server.resize_events,
                # schedule entries dated past the end of work never fire
                # (the drivers only resize while work remains) — surfaced
                # rather than silently compared against a fleet that
                # never reached its scheduled size
                "resizes_unfired": len(resizes) - len(server.resize_events),
                "final_replicas": len(server.engines),
                "requests_lost": lost}
               if args.resize else {}),
            # --autoscale only (plain rows keep the schema-pinned key
            # set): the closed-loop economics — replica-hours actually
            # consumed (the static baseline pays replicas * duration),
            # every decision with its triggering signal, and the
            # no-loss invariant the tool's exit code gates on
            **({"autoscale": args.autoscale,
                "scale_window": args.scale_window,
                "scale_cooldown": args.scale_cooldown,
                "replica_hours": round(replica_hours(controllers), 6),
                "scale_events": sum(c.scale_events for c in controllers),
                "repairs": sum(c.repairs for c in controllers),
                "autoscale_attainment": round(
                    combined_attainment(controllers), 6),
                "autoscale_events": _round6(
                    [e for c in controllers for e in c.events]),
                "final_replicas": len(server.engines),
                "requests_lost": lost}
               if autoscale else {}),
            # actual backend record (distributed.backend_provenance)
            **prov,
        }
        if args.wall_clock:
            rec["wall_s"] = round(wall, 3)
        print(json.dumps(rec), flush=True)
        if autoscale and lost != 0:
            # the no-loss gate extends from the chaos tools to the
            # controller path: a self-scaling fleet that loses requests
            # is a broken controller, and CI must see it
            print(f"servebench: FAILED no-loss gate under --autoscale: "
                  f"requests_lost={lost} on policy {policy}",
                  file=sys.stderr, flush=True)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
