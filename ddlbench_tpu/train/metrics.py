"""Metrics accumulation and the log-line schema.

The reference's observability is print-based and machine-scraped; the exact line
formats are its public metric interface (SURVEY.md §5.5):

* per-interval train line: ``train | <e>/<E> epoch (<p>%) | <X> samples/sec | ...``
  with peak memory (benchmark/mnist/mnist_pytorch.py:79-97),
* final summary: ``valid accuracy: <A> | <X> samples/sec, <S> sec/epoch (average)``
  (benchmark/mnist/mnist_pytorch.py:225-226),
* ``AverageMeter`` val/avg accumulators
  (pipedream-fork/runtime/image_classification/main_with_runtime.py:587-602).

We keep the same schema so the reference's log scrapers
(pipedream-fork/runtime/scripts/process_output.py) would parse our output, and
substitute TPU HBM stats (jax ``memory_stats``) for ``torch.cuda.memory_stats``.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, Optional

import jax


class AverageMeter:
    """Running value/average/sum/count accumulator.

    Reference-parity API (PipeDream's AverageMeter,
    main_with_runtime.py:587-602 — SURVEY.md §5.5), kept exported for
    external consumers even though the benchmark loop itself now
    accumulates metrics on device (train/loop.py) rather than through
    host-side meters."""

    def __init__(self, name: str = ""):
        self.name = name
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1) -> None:
        self.val = float(val)
        self.sum += float(val) * n
        self.count += n
        self.avg = self.sum / max(1, self.count)


def device_memory_gb(device: Optional[Any] = None) -> Dict[str, float]:
    """Peak/in-use device memory in GB (TPU analog of torch.cuda.memory_stats).

    The CPU backend keeps no such statistics (``memory_stats()`` is None
    there) and reports zeros; a TPU that returns none is an error, not a
    0.00 GB log line."""
    dev = device or jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats is None:
        if dev.platform == "tpu":
            raise RuntimeError(f"{dev} reported no memory_stats()")
        stats = {}
    gb = 1024.0**3
    return {
        "in_use": stats.get("bytes_in_use", 0) / gb,
        "peak": stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)) / gb,
        "limit": stats.get("bytes_limit", 0) / gb,
    }


class MetricLogger:
    """Produces the reference-schema log lines plus a structured JSONL stream."""

    def __init__(self, total_epochs: int, log_interval: int = 25, jsonl_path: Optional[str] = None, rank: int = 0):
        self.total_epochs = total_epochs
        self.log_interval = log_interval
        self.rank = rank
        self._jsonl = open(jsonl_path, "a") if jsonl_path else None
        self.epoch_throughputs: list[float] = []
        self.epoch_times: list[float] = []
        # per-epoch input-stall time (data/prefetch.py): how long the train
        # loop sat blocked waiting for input — the signal that separates
        # input-bound from compute-bound regimes in the throughput curves
        self.epoch_stall_ms: list[float] = []
        # per-epoch validation curve (reference protocol: one validation
        # accuracy per train epoch, mnist_pytorch.py:102-133); surfaced in
        # summary() so accuracy-parity artifacts carry the full curve
        self.valid_history: list[Dict[str, float]] = []

    def _emit(self, line: str, record: Dict[str, Any]) -> None:
        if self.rank == 0:
            print(line, flush=True)
            if self._jsonl:
                self._jsonl.write(json.dumps(record) + "\n")
                self._jsonl.flush()

    def train_interval(self, epoch: int, progress_pct: float, samples_per_sec: float, loss: float) -> None:
        mem = device_memory_gb()
        line = (
            f"train | {epoch}/{self.total_epochs} epoch ({progress_pct:.0f}%) | "
            f"{samples_per_sec:.2f} samples/sec | loss {loss:.4f} | "
            f"mem {mem['in_use']:.2f} GB in use, {mem['peak']:.2f} GB peak"
        )
        self._emit(
            line,
            {
                "kind": "train_interval",
                "epoch": epoch,
                "progress_pct": progress_pct,
                "samples_per_sec": samples_per_sec,
                "loss": loss,
                **{f"mem_{k}_gb": v for k, v in mem.items()},
            },
        )

    def epoch_done(self, epoch: int, samples_per_sec: float, epoch_seconds: float,
                   input_stall_ms: Optional[float] = None,
                   step_ms: Optional[Dict[str, float]] = None) -> None:
        self.epoch_throughputs.append(samples_per_sec)
        self.epoch_times.append(epoch_seconds)
        line = (
            f"epoch {epoch}/{self.total_epochs} done | {samples_per_sec:.2f} samples/sec | "
            f"{epoch_seconds:.2f} sec"
        )
        record = {
            "kind": "epoch",
            "epoch": epoch,
            "samples_per_sec": samples_per_sec,
            "epoch_seconds": epoch_seconds,
        }
        if input_stall_ms is not None:
            # appended so the reference-schema prefix keeps matching existing
            # scrapers (same convention as the valid line's top5 suffix)
            self.epoch_stall_ms.append(input_stall_ms)
            line += f" | input stall {input_stall_ms:.1f} ms"
            record["input_stall_ms"] = input_stall_ms
        if step_ms:
            # step-latency percentiles (telemetry/stats.py) — appended after
            # the stall field, same suffix convention
            line += (f" | step p50 {step_ms['p50_ms']:.2f} ms, "
                     f"p95 {step_ms['p95_ms']:.2f} ms")
            record["step_time_p50_ms"] = step_ms["p50_ms"]
            record["step_time_p95_ms"] = step_ms["p95_ms"]
            record["step_time_p99_ms"] = step_ms["p99_ms"]
            record["step_time_max_ms"] = step_ms["max_ms"]
        self._emit(line, record)

    def valid_epoch(self, epoch: int, loss: float, accuracy: float,
                    top5: Optional[float] = None) -> None:
        line = (f"valid | {epoch}/{self.total_epochs} epoch | "
                f"loss {loss:.4f} | accuracy {accuracy:.4f}")
        record = {"kind": "valid", "epoch": epoch, "loss": loss,
                  "accuracy": accuracy}
        hist = {"epoch": epoch, "loss": loss, "accuracy": accuracy}
        if top5 is not None:
            # prec@5 (PipeDream parity); appended so top-1-only scrapers
            # keep matching the line prefix
            line += f" | top5 {top5:.4f}"
            record["top5"] = top5
            hist["top5"] = top5
        # keyed by epoch: a post-resume re-validation of an epoch restored
        # from a checkpoint (train/loop.py) replaces the restored entry
        # instead of duplicating it in the summary's curve
        self.valid_history = [h for h in self.valid_history
                              if h["epoch"] != epoch] + [hist]
        self._emit(line, record)

    def summary(self, valid_accuracy: float,
                step_time: Optional[Dict[str, float]] = None) -> Dict[str, float]:
        """Final line matching mnist_pytorch.py:225-226's schema.

        ``step_time`` is the run-level step-latency aggregate
        (telemetry/stats.py ``StepLatencyStats.run_summary``): percentiles
        over all recorded steps plus the warmup/compile accounting. The
        printed line keeps the reference schema; the JSONL record and the
        returned dict carry the percentiles.
        """
        avg_tp = sum(self.epoch_throughputs) / max(1, len(self.epoch_throughputs))
        avg_t = sum(self.epoch_times) / max(1, len(self.epoch_times))
        record = {
            "kind": "summary",
            "valid_accuracy": valid_accuracy,
            "samples_per_sec": avg_tp,
            "sec_per_epoch": avg_t,
        }
        result = {
            "valid_accuracy": valid_accuracy,
            "samples_per_sec": avg_tp,
            "sec_per_epoch": avg_t,
            # full per-epoch curve (printed lines keep the reference
            # schema; the dict is the structured superset)
            "valid_history": list(self.valid_history),
        }
        if step_time:
            extras = {
                "step_time_p50_ms": step_time["p50_ms"],
                "step_time_p95_ms": step_time["p95_ms"],
                "step_time_p99_ms": step_time["p99_ms"],
                "step_time_max_ms": step_time["max_ms"],
            }
            extras.update({k: v for k, v in step_time.items()
                           if k.startswith("warmup_")})
            record.update(extras)
            result.update(extras)
        if self.epoch_stall_ms:
            result["input_stall_ms_per_epoch"] = (
                sum(self.epoch_stall_ms) / len(self.epoch_stall_ms))
        self._emit(
            f"valid accuracy: {valid_accuracy:.4f} | "
            f"{avg_tp:.2f} samples/sec, {avg_t:.2f} sec/epoch (average)",
            record,
        )
        return result

    def state_dict(self) -> Dict[str, Any]:
        """Resumable counters (checkpointed by train/loop.py so a restarted
        run's summary covers the WHOLE trajectory, not just the tail after
        the last crash)."""
        return {
            "epoch_throughputs": list(self.epoch_throughputs),
            "epoch_times": list(self.epoch_times),
            "epoch_stall_ms": list(self.epoch_stall_ms),
            "valid_history": [dict(h) for h in self.valid_history],
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.epoch_throughputs = list(state.get("epoch_throughputs", []))
        self.epoch_times = list(state.get("epoch_times", []))
        self.epoch_stall_ms = list(state.get("epoch_stall_ms", []))
        self.valid_history = [dict(h)
                              for h in state.get("valid_history", [])]

    def close(self) -> None:
        if self._jsonl:
            self._jsonl.close()
            self._jsonl = None


class Stopwatch:
    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt = now - self.t0
        self.t0 = now
        return dt
