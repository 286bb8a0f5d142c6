"""95th percentile over the window's requests of (first prefill chunk
dispatched - submit), on the benchmark's clock."""

from benchmarks.harness.stats import percentile


def read(ctx):
    waits = ctx.counters["queue_wait_s"]
    return percentile(waits, 95.0) if waits else None
