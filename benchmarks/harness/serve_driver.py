"""Window driver for ``kind: serve`` mixes: ``make_server(...)``'s ``submit``
and ``step`` under a closed loop of clients, on the wall clock.

Every timestamp is the benchmark's own ``time.perf_counter()``: a token is
stamped when the ``step`` that produced it has returned to the host (the
engine's own ``token_times`` add model passes to the clock it is handed and
are not read). Set-up warms every program the mix can reach, then runs a
ramp of the same closed loop so that the window opens with rows at mixed
depths. Only requests submitted inside the window count for the tails; tokens
of any request that the host received inside the window count for the rate.
After the close no client submits again and the loop drains what the window
submitted (a late answer is late, not wrong; one that never comes fails).
"""

from __future__ import annotations

import random
import sys
import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.harness.stats import percentile
from benchmarks.harness.traffic import RequestSource

DRAIN_LIMIT_S = 60.0


def build(config: Dict, traffic: Dict):
    from ddlbench_tpu import config as pcfg
    from ddlbench_tpu.models.zoo import get_model

    ds = config["dataset"]
    spec = pcfg.DatasetSpec(ds["name"], tuple(ds["sample_shape"]),
                            ds["num_classes"], 1 << 30, 1 << 20,
                            kind=ds["kind"])
    model = get_model(config["arch"], spec)
    scfg = pcfg.ServeConfig(**traffic["serve_config"])
    scfg.validate()
    return model, scfg


def seeded_server(model, scfg, seed: int, rules: Dict, shared_fns=None):
    """``make_server`` over the benchmark's weights (float32, as the
    configuration states)."""
    import jax

    from ddlbench_tpu.models.layers import init_model
    from ddlbench_tpu.serve.engine import make_server

    names = [l.name for l in model.layers]
    shapes = jax.eval_shape(lambda k: init_model(model, k)[:2],
                            jax.random.key(0))
    specs = weights.flat_specs(shapes[0], names)
    flat = weights.make_weights(seed, specs, rules)
    params = weights.unflatten(flat, shapes[0], names)
    state = jax.tree.map(lambda s: jax.numpy.zeros(s.shape, s.dtype),
                         shapes[1])
    return make_server(model, params, state, scfg,
                       shared_fns=shared_fns), flat


def reachable_page_counts(traffic: Dict):
    """The live-page counts (``npl``) this mix can reach: last prefill
    chunks end at any prompt length, earlier ones at chunk multiples; decode
    runs from the shortest prompt to the stream's cap."""
    page = traffic["serve_config"]["page"]
    chunk = traffic["serve_config"]["prefill_chunk"]
    lo, _, hi = traffic["prompt"]
    hi = min(hi, traffic["max_total"] - traffic["output"][0])
    ends = set(range(lo, hi + 1)) | set(range(chunk, hi + 1, chunk))
    prefill = sorted({-(-e // page) for e in ends})
    decode = list(range(lo // page + 1, (traffic["max_total"] - 1) // page + 2))
    top = traffic["serve_config"]["max_len"] // page
    return prefill, [n for n in decode if n <= top]


def warm_up(server, scfg, traffic: Dict) -> int:
    """Compile (or load) every decode and prefill program the mix can reach,
    against the scratch slot, through the engine's own jitted callables."""
    import jax
    import jax.numpy as jnp

    eng = server.engines[0]
    decode_jit, prefill_jit = eng.jit_fns()[:2]
    prefill_npl, decode_npl = reachable_page_counts(traffic)
    B, C = scfg.max_batch, scfg.resolved_prefill_chunk()
    table = jnp.zeros((B, scfg.npg_max()), jnp.int32)
    toks, pos = jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32)
    chunk = jnp.zeros((1, C), jnp.int32)
    for npl in decode_npl:
        _, eng.pools = decode_jit(eng.params, eng.state, eng.pools, table,
                                  toks, pos, npl)
    for npl in prefill_npl:
        _, eng.pools = prefill_jit(eng.params, eng.state, eng.pools,
                                   table[:1], chunk, np.int32(0), np.int32(0),
                                   npl)
    jax.block_until_ready(eng.pools)
    return len(decode_npl) + len(prefill_npl)


class ClosedLoop:
    """``clients`` callers, each submitting its next request when its last
    one completes. Keeps, per request, the submit time and the host time of
    every token."""

    def __init__(self, server, source: RequestSource, clients: int):
        from jax.profiler import TraceAnnotation

        from ddlbench_tpu.serve.workload import ServeRequest

        self._span = TraceAnnotation
        self._mk = lambda r: ServeRequest(rid=r.rid, prompt=r.prompt,
                                          max_new=r.max_new)
        self.server, self.eng = server, server.engines[0]
        self.source, self.clients = source, clients
        self.requests: Dict[int, object] = {}
        self.submit_t: Dict[int, float] = {}
        self.token_t: Dict[int, List[float]] = {}
        self.tokens: Dict[int, List[int]] = {}
        self.first_dispatch_t: Dict[int, float] = {}
        self.in_flight = 0
        self.accepting = True
        self._seen: Dict[int, int] = {}
        self._prefilled: Dict[int, int] = {}
        self._n_finished = 0
        # per step: (t0, t1, prefill chunks [(start, n_real)], decode depths)
        self.steps: List = []

    def fill(self) -> None:
        with self._span("bench/submit"):
            while self.accepting and self.in_flight < self.clients:
                r = self.source.next()
                now = time.perf_counter()
                self.requests[r.rid] = r
                self.submit_t[r.rid] = now
                self.token_t[r.rid] = []
                self.server.submit(self._mk(r), now=now)
                self.in_flight += 1

    def step(self) -> None:
        eng = self.eng
        depths = [a.decode_pos for a in eng.rows
                  if a is not None and a.state == "decode"]
        t0 = time.perf_counter()
        with self._span("bench/engine_step"):
            self.server.step(now=t0)
        t1 = time.perf_counter()
        with self._span("bench/bookkeeping"):
            chunks = []
            for a in eng.rows:
                if a is None:
                    continue
                rid = a.req.rid
                done = a.prefill_done
                before = self._prefilled.get(rid, 0)
                if done > before:
                    chunks.append((before, done - before))
                    self._prefilled[rid] = done
                    self.first_dispatch_t.setdefault(rid, t0)
                n = len(a.out)
                seen = self._seen.get(rid, 0)
                if n > seen:
                    self.token_t[rid].extend([t1] * (n - seen))
                    self._seen[rid] = n
            fin = eng.finished
            for rec in fin[self._n_finished:]:
                rid = rec["rid"]
                n = rec["n_tokens"]
                self.token_t[rid].extend([t1] * (n - self._seen.get(rid, 0)))
                self.tokens[rid] = rec["tokens"]
                # a request that prefilled its last chunk and finished in
                # one step never shows in the rows above
                before = self._prefilled.pop(rid, 0)
                if rec["prompt_len"] > before:
                    chunks.append((before, rec["prompt_len"] - before))
                    self.first_dispatch_t.setdefault(rid, t0)
                self._seen.pop(rid, None)
                self.in_flight -= 1
            self._n_finished = len(fin)
            self.steps.append((t0, t1, chunks, depths))


def served_gaps(reference, config: Dict, flat, sample, loop, max_total: int,
                control: str = None):
    """For each sampled request the reference runs once over prompt + served
    tokens; per served token, the gap by which its logit lies below the
    reference's best (0 where the served token IS the best). Returns
    (widest gap, tokens compared, control's widest gap or None). The
    control reads, at each of the same positions, the gap of the token that
    the lower precision ``control`` puts first."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import common

    # one shape for every run (the mix's cap), so the reference compiles once
    T = min(max_total, config["n_positions"])
    toks = np.zeros((len(sample), T), np.int32)
    want = np.zeros((len(sample), T), bool)
    for i, rid in enumerate(sample):
        p, out = loop.requests[rid].prompt, loop.tokens[rid]
        seq = np.concatenate([p, np.asarray(out, np.int32)])
        toks[i, :len(seq)] = seq
        # position j predicts token j + 1: the served tokens sit at
        # [len(p), len(seq)), predicted from [len(p) - 1, len(seq) - 1)
        want[i, len(p) - 1:len(seq) - 1] = True
    want_d = jnp.asarray(want)

    def widest(rounding):
        rnd = common.ROUNDINGS[rounding]
        return jax.jit(lambda P, t: reference.logits(P, t, config, rnd, rnd))(
            flat, jnp.asarray(toks))

    logits = widest("float32")
    best = jnp.max(logits, axis=-1)

    def gap_of(tokens):
        at = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return float(jnp.max(jnp.where(want_d, best - at, 0.0)))

    gap = gap_of(jnp.asarray(np.roll(toks, -1, axis=1)))
    control_gap = None
    if control:
        control_gap = gap_of(jnp.argmax(widest(control), axis=-1))
    return gap, int(want.sum()), control_gap


def run(rc, shared_fns=None, control: str = None) -> Dict:
    """``shared_fns`` (a prior server's jitted callables) and ``control``
    are for ``readings.py``, which reads many seeds in one process."""
    from jax.profiler import TraceAnnotation

    config, traffic = rc.config, rc.traffic
    rc.mark("imports, backend")
    model, scfg = build(config, traffic)
    server, flat = seeded_server(model, scfg, rc.seed, config["weights"],
                                 shared_fns)
    rc.mark("server over seeded weights")
    n_programs = warm_up(server, scfg, traffic)
    rc.mark(f"{n_programs} programs warmed")
    fns = server.engines[0].jit_fns()
    source = RequestSource(traffic, config["vocab_size"], rc.seed)
    loop = ClosedLoop(server, source, int(traffic["clients"]))

    # -- ramp: the same closed loop, a fixed number of engine steps -------
    loop.fill()
    for _ in range(int(traffic["ramp_steps"])):
        loop.step()
        loop.fill()
    rc.mark("ramp")
    ramp_rids = set(loop.submit_t)
    n_ramp_steps = len(loop.steps)

    # -- the window --------------------------------------------------------
    seconds = rc.window_seconds(traffic)
    rc.open_window()
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while time.perf_counter() - t0 < seconds:
            loop.step()
            loop.fill()
    t1 = time.perf_counter()
    rc.close_window()
    window_s = t1 - t0
    loop.accepting = False
    win_rids = [r for r in loop.submit_t if r not in ramp_rids]
    win_steps = loop.steps[n_ramp_steps:]

    # -- drain what the window submitted -----------------------------------
    t_close = time.perf_counter()
    while loop.in_flight and time.perf_counter() - t_close < DRAIN_LIMIT_S:
        loop.step()
    memory_peak = rc.read_memory_peak()

    never = [r for r in win_rids if r not in loop.tokens]
    t_end = time.perf_counter()
    ttft = [(loop.token_t[r][0] if loop.token_t[r] else t_end)
            - loop.submit_t[r] for r in win_rids]
    itl = [b - a for r in win_rids
           for a, b in zip(loop.token_t[r], loop.token_t[r][1:])]
    queue_wait = [loop.first_dispatch_t[r] - loop.submit_t[r]
                  for r in win_rids if r in loop.first_dispatch_t]
    out_tokens = sum(1 for ts in loop.token_t.values()
                     for t in ts if t0 <= t <= t1)
    prefill_wall = sum(b - a for a, b, chunks, _ in win_steps if chunks)
    decode_calls = [d for _, _, _, d in win_steps if d]
    prefill_calls = [c for _, _, chunks, _ in win_steps for c in chunks]
    # every token processed: decode tokens with the head, prompt tokens
    # without, and the head once more where a prompt yields its first token
    tok = rc.reference.served_token_flops
    model_flops = sum(tok(config, d, True)
                      for depths in decode_calls for d in depths)
    model_flops += sum(tok(config, p, False)
                       for start, n in prefill_calls
                       for p in range(start, start + n))
    model_flops += sum(tok(config, 0, True) - tok(config, 0, False)
                       for r in win_rids if loop.token_t[r])

    # -- free the engine, then the reference --------------------------------
    finished = [r for r in win_rids if r in loop.tokens]
    longest = max(finished, key=lambda r: len(loop.requests[r].prompt)
                  + len(loop.tokens[r]))
    rest = sorted(set(finished) - {longest})
    random.Random(rc.seed).shuffle(rest)
    sample = [longest] + rest[:int(traffic["check_requests"]) - 1]
    del server, loop.server, loop.eng
    t_ref = time.perf_counter()
    gap, n_tok, control_gap = served_gaps(
        rc.reference, config, flat, sample, loop, int(traffic["max_total"]),
        control)
    print(f"serve: reference over {len(sample)} requests, {n_tok} served "
          f"tokens, {time.perf_counter() - t_ref:.1f}s; {n_programs} programs "
          f"warmed; {len(win_rids)} requests in the window, {len(never)} "
          f"never finished", file=sys.stderr)
    lim = config["limits"]
    numbers = [compare.Compared("served_logit_gap", gap,
                                lim["served_logit_gap"]),
               compare.Compared("never_finished", float(len(never)), 0.0)]

    return {
        "jit_fns": fns, "control_gap": control_gap,
        "end_to_end": {
            "serve_out_tokens_per_s": out_tokens / window_s,
            "serve_itl_p95_s": percentile(itl, 95.0)},
        "window_s": window_s, "attempted": len(win_rids),
        "failed": len(never), "memory_peak_bytes": memory_peak,
        "numbers": numbers,
        "counters": {
            "steps": len(win_steps), "requests": len(win_rids),
            "out_tokens": out_tokens, "model_flops": model_flops,
            "queue_wait_s": queue_wait, "prefill_step_wall_s": prefill_wall,
            "decode_calls": decode_calls, "prefill_calls": prefill_calls,
            "ttft_s": ttft, "itl_s": itl},
    }
