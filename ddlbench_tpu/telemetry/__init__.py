"""Step-level telemetry: unified host/device tracing + latency percentiles.

The reference's observability is print-scraped throughput lines (SURVEY.md
§5.5) — they say *that* a strategy is slow, never *where* the time went.
This package is the decomposition layer the ROADMAP north star needs:

* :mod:`telemetry.tracer` — a thread-safe, ring-buffered span/counter
  tracer on monotonic clocks. Disabled (the default) it is a single
  attribute check returning a cached no-op context manager, so the hot
  loop pays nothing; enabled, every producer/consumer/watchdog thread
  records into one bounded buffer.
* :mod:`telemetry.export` — Chrome trace-event JSON (``traceEvents``)
  loadable in Perfetto / ``chrome://tracing``: one track per thread (main
  loop, prefetch producer, watchdog), named via ``thread_name`` metadata
  events.
* :mod:`telemetry.stats` — step-latency aggregation: p50/p95/p99/max per
  epoch plus explicit warmup/compile-time accounting, feeding the epoch
  log lines, JSONL, ``summary()``, and ``bench.py`` JSON.
* :mod:`telemetry.serveview` — the serving-side reducer: request-
  lifecycle traces (serve/engine.py under ``ServeConfig.trace``, stamped
  in virtual model-pass units on one track per request per replica)
  reduce to exact TTFT queue/prefill/decode/sched-gap decompositions,
  ITL decode/preempted splits, and the windowed SLO-attainment + goodput
  time series ROADMAP item 2c's autoscaler consumes.
* :mod:`telemetry.audit` — the compiled-program audit plane: per-program
  manifests (flops / HBM components / the per-collective ledger walked
  out of the optimized HLO) cross-checked EXACTLY against the analytic
  models (``comm_stats`` wire bytes, the planner's HBM model,
  ``pool_page_bytes``), plus the ``auditbench diff`` regression gate.
* :mod:`telemetry.compiles` — jax's own compile path, tallied: each
  trace, lowering, compile or cache load with its seconds and the stamp it
  ended at on the tracer's clock, installed once per process by
  ``distributed.enable_compilation_cache``; ``compile/<kind>`` ring events
  while the ring is on.

Host spans align with device traces through
``jax.profiler.StepTraceAnnotation`` wrapping in ``train/loop.py`` and the
windowed ``--xla-trace-steps A:B`` capture next to ``--trace-dir``
(ddlbench_tpu/cli.py).

Telemetry is metrics-neutral by construction: it only reads clocks, so
losses are bitwise identical with tracing on or off (pinned by
tests/test_telemetry.py).
"""

from ddlbench_tpu.telemetry.tracer import (  # noqa: F401
    Tracer,
    get_tracer,
    set_tracer,
)
from ddlbench_tpu.telemetry.compiles import CompileTally  # noqa: F401
from ddlbench_tpu.telemetry.export import (  # noqa: F401
    export_chrome_trace,
    trace_truncation,
    warn_if_truncated,
)
from ddlbench_tpu.telemetry.audit import (  # noqa: F401
    AUDIT_SCHEMA_VERSION,
    CollectiveOp,
    collective_ledger,
    diff_manifests,
    lower_manifest,
    program_manifest,
    reconcile_train,
    serve_pool_audit,
)
from ddlbench_tpu.telemetry.overlap import overlap_fraction  # noqa: F401
from ddlbench_tpu.telemetry.bubble import bubble_fraction  # noqa: F401
from ddlbench_tpu.telemetry.serveview import breakdown  # noqa: F401
from ddlbench_tpu.telemetry.stats import (  # noqa: F401
    StepLatencyStats,
    percentile,
    request_slo_ok,
    serve_summary,
)
