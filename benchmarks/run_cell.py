#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one new process:

    python3 benchmarks/run_cell.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Refuses anything but a TPU backend with the chips the cell asks for (exit
nonzero, nothing that looks like a result), keeps jax's compile cache inside
the checkout, builds weights and inputs on the device from ``--seed``, warms
up this cell's shapes (all of that is ``setup_s``), measures for
``--seconds`` and prints one JSON object as its last line. ``--trace 0``
gives the cell's end-to-end metrics. ``--trace 1`` is a run of its own under
``jax.profiler``: a few seconds of the steady window are traced and reduced
by ``harness/trace.py`` into the cell's per-layer metrics and the breakdown.

``--check-manifest`` checks BENCHMARK.json and the files it indexes against
the rules a driver refuses on, and touches no device. With ``--against <a
checkout of the parent commit>`` it also lists what a PR that may only ADD to
the benchmark is refused on: every file under ``paths`` that the parent has
and this tree changed, and every entry of the parent's BENCHMARK.json that
changed otherwise than by gaining a name in ``workloads``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare, manifest  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RunContext:
    """What a window driver and the metric readers see of one run."""

    def __init__(self, man, args, devices):
        """``args``: workload, seed, seconds, trace."""
        self.manifest = man
        self.cell = man.workload(args.workload)
        self.config = man.config(self.cell["config"])
        self.reference = man.reference(self.config)
        self.traffic = man.traffic(self.cell["traffic"])
        self.chips = self.cell["chips"]
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.devices = devices
        self.device_kind = devices[0].device_kind
        self.compiles = 0  # programs compiled or loaded, counted by jax
        self.window_compiles = 0
        self.setup_s = None
        self.trace_dir = os.path.join(OUT_DIR, "trace", self.cell["name"])
        self.trace_summary = None
        self.counters = {}
        self.window_s = None
        self.memory_peak_bytes = None
        self._in_window = False

    # -- hooks the drivers call -------------------------------------------
    def window_seconds(self, traffic) -> float:
        if self.trace:
            return min(self.seconds, traffic.get("trace_seconds", 4))
        return self.seconds

    def open_window(self) -> None:
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.setup_s = time.perf_counter() - T_PROCESS_START
        self._in_window = True

    def close_window(self) -> None:
        import jax

        self._in_window = False
        if self.trace:
            jax.profiler.stop_trace()

    def on_compile(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            if self._in_window:
                self.window_compiles += 1

    def mark(self, phase: str) -> None:
        """Seconds since process start at the end of a set-up phase, on
        stderr: where ``setup_s`` goes."""
        print(f"setup: {time.perf_counter() - T_PROCESS_START:7.2f}s "
              f"{phase}", file=sys.stderr)

    def read_memory_peak(self) -> int:
        """Peak HBM on the fullest chip: the allocator's peak of live
        buffers PLUS the peak reserved for programs' scratch. On this
        runtime ``peak_bytes_in_use`` leaves the scratch out (XLA reserves
        it "at the bottom of memory"); ``peak_bytes_reserved`` is it
        (PERF.md section 6 has the experiment)."""
        stats = [d.memory_stats() for d in self.devices[:self.chips]]
        print(f"memory_stats: {stats[0]}", file=sys.stderr)
        return int(max(s["peak_bytes_in_use"] + s["peak_bytes_reserved"]
                       for s in stats))

    def spread(self, batches, flat):
        """Batches sharded by rows over the cell's chips, weights on each —
        for a reference that follows a global batch."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(self.devices[:self.chips], ("rows",))
        rows, rep = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
        return ([tuple(jax.device_put(a, rows) for a in b) for b in batches],
                jax.device_put(flat, rep))

    # -- after the window ---------------------------------------------------
    def reduce_trace(self):
        from benchmarks.harness import trace

        paths = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb under "
                               f"{self.trace_dir}, found {paths}")
        events = trace.load_xplane(paths[0])
        self.trace_summary = trace.reduce(events)
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def cached_jax():
    """jax with its persistent compile cache inside the checkout
    (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
    otherwise — the program's own rule) and every program kept in it, the
    sub-second ones too: the second run of a cell compiles nothing."""
    import jax

    from ddlbench_tpu.distributed import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def refuse(msg: str) -> int:
    print(f"run_cell: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check-manifest", action="store_true")
    ap.add_argument("--against", metavar="PARENT_CHECKOUT", default=None)
    args = ap.parse_args(argv)

    man = manifest.Manifest()
    if args.check_manifest:
        errors = manifest.check(man)
        if args.against:
            errors += manifest.against(man, args.against)
        for e in errors:
            print(f"manifest: {e}", file=sys.stderr)
        print(f"manifest: {len(errors)} fault(s) in BENCHMARK.json and the "
              f"files it indexes")
        return 1 if errors else 0
    if args.workload is None:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(man.index["run_seconds"])

    jax = cached_jax()
    if jax.default_backend() != "tpu":
        return refuse(f"needs a TPU backend; jax found "
                      f"{jax.default_backend()!r}")
    devices = jax.devices()
    rc = RunContext(man, args, devices)
    if len(devices) < rc.chips:
        return refuse(f"{rc.cell['name']} asks for {rc.chips} chips; jax "
                      f"found {len(devices)}")
    from benchmarks.harness.peaks import device_peaks

    rc.peaks = device_peaks(rc.device_kind)
    jax.monitoring.register_event_duration_secs_listener(rc.on_compile)

    # the window driver of the mix's kind: benchmarks/harness/<kind>_driver.py
    kind = rc.traffic["kind"]
    try:
        driver = importlib.import_module(f"benchmarks.harness.{kind}_driver")
    except ModuleNotFoundError:
        return refuse(f"traffic kind {kind!r} has no window driver")
    out = driver.run(rc)

    rc.counters = dict(out["counters"], window_compiles=rc.window_compiles)
    rc.window_s = out["window_s"]
    rc.memory_peak_bytes = out["memory_peak_bytes"]
    device = {"platform": devices[0].platform, "kind": rc.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rc.memory_peak_bytes}
    result = {}
    if rc.trace:
        rc.reduce_trace()
        ts = rc.trace_summary
        device.update(busy_s=ts.busy_s, window_s=ts.window_s)
        metrics = {}
        for m in man.per_layer_of(rc.cell["name"]):
            spec = man.metric_file(m["name"])
            value = man.reader(spec).read(rc, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ts.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in ts.idle_gaps[:10]]}
    else:
        values = dict(out["end_to_end"], setup_s=rc.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in man.end_to_end_of(rc.cell["name"])}
    faults = manifest.check_printed(man, rc.cell["name"], args.trace, metrics)
    if faults:
        return refuse("; ".join(faults))
    numbers = out["numbers"]
    correct = compare.report(numbers)
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **result,
            "compared": {c.name: {"value": c.value, "limit": c.limit}
                         for c in numbers}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
