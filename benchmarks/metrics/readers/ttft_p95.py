"""95th percentile over ALL requests submitted in the window of (first token
on the host - submit); one with no token by the end of the drain counts its
whole wait. In a closed loop that keeps the engine saturated this is mostly
queueing, so it stands among the per-layer metrics, not under a bound."""

from benchmarks.harness.stats import percentile


def read(ctx):
    waits = ctx.counters["ttft_s"]
    return percentile(waits, 95.0) if waits else None
