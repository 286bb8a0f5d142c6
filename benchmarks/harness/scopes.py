"""Device time by the program's own named scopes.

The program names its device work with ``jax.named_scope``
(``ddlbench_tpu/telemetry/scopes.py``): every instruction of the compiled
step carries the path it was traced under in ``metadata={op_name="..."}``,
e.g. ``jit(train_step)/transpose(jvp(group1_block3))/bn/reduce_sum``. A
reduced trace (``harness/trace.Summary.op_seconds``) holds the device
seconds of every instruction NAME in the window and nothing else of it. So
the scope readers join the two by instruction name:

    step_hlo(rc)          -> the cell's compiled step, as HLO text
    scope_table(text)     -> {instruction: op_name}
    classify(op_name)     -> (phase, kind)
    device_time(rc)       -> ScopeTimes: seconds by phase and by kind

Limits of the join, which the metrics' readers inherit:

* a fusion is one instruction and goes whole to its ROOT's scope. XLA fuses
  a BatchNorm apply with the ReLU and the residual add that follow, and the
  root decides which kind gets the time;
* ``op_seconds`` is keyed by instruction name over EVERY program that ran in
  the window (the step and the few-microsecond batch generator). An
  instruction of another program whose name also stands in the step's table
  is counted with the step's; it is bounded by the other programs' device
  time (under 0.01% of the accepted cells);
* a program without scopes (the parent of the PR that added them) has
  op_names but none of the vocabulary: every reader then returns nothing.

A named scope is debug info, and jax's compile-cache key strips debug info:
under one key the cache serves whichever executable was compiled first, a
scope-less parent's to a scoped change (resnet50-single's step did run the
parent's entry on the chip, PR 24). That is harmless to the run (the
instructions are the same) and would be fatal to the table, so ``step_hlo``
never takes its text from that cache (see there). On the run's side XLA's
own instruction names do not depend on metadata; a Pallas kernel's does
(``jvp_flash_attn_fwd_.12`` without scopes, ``flash_attn_fwd.12`` within
one), but so does its Mosaic payload, which is no debug info to jax's key:
a step with kernels gets an entry of its own when a scope around them
changes (gpt2s-train's did), and its window runs under the table's names.

The vocabulary is the benchmark's own copy (``tests/`` holds it to the
program's), so these files also run against a program that has none. It is
DATA: the ``kinds`` lists of the ``scope_kinds*.json`` files under
``benchmarks/metrics/``, in the order of the files' names. A later PR whose
model opens a scope of a new kind adds a ``scope_kinds.<its name>.json`` of
its own beside the metric file that reads the kind; nothing that exists is
edited.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import hashlib
import json
import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

METRICS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def vocabulary(metrics_dir: str = METRICS_DIR) -> Tuple[str, ...]:
    """The kinds that the ``scope_kinds*.json`` files of ``metrics_dir``
    list, each once, in the order of the files' names."""
    kinds: List[str] = []
    for path in sorted(glob.glob(os.path.join(metrics_dir,
                                              "scope_kinds*.json"))):
        with open(path) as f:
            kinds += [k for k in json.load(f)["kinds"] if k not in kinds]
    return tuple(kinds)


KINDS = vocabulary()
STEP_PHASES = ("optimizer", "grad_sync")  # scopes that name a step phase
FORWARD, BACKWARD, UNSCOPED = "forward", "backward", "unscoped"
# wrappers that name a function and not a scope: jit(relu), pjit(..)
FUNCTION_WRAPPERS = ("jit", "pjit")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of the module,
    fusion instructions and the instructions inside fusions included; an
    instruction without metadata maps to ``""``."""
    table: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line)
        table.setdefault(m.group(1), name.group(1) if name else "")
    return table


def _tokens(op_name: str) -> List[str]:
    """The path split on ``/`` outside parentheses."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    out.append("".join(cur))
    return out


def classify(op_name: str) -> Tuple[str, Optional[str]]:
    """``(phase, kind)`` of one op_name.

    Wrappers are unwrapped (``transpose(jvp(block3))`` is the scope
    ``block3``, transposed); ``jit(f)`` names a function, not a scope.
    ``phase``: a step-phase token on the path (``optimizer``,
    ``grad_sync``) wins; else ``backward`` if a ``transpose(`` wraps a
    scope; else ``forward`` if a ``jvp(`` does or a kind token is on the
    path; else ``unscoped``. A wrapper around nothing (``jvp()``: code
    differentiated outside every scope, as all of a scope-less program is)
    marks no phase. ``kind`` is the innermost kind token, or None (a ReLU
    or residual add directly under its layer instance). Where XLA merged
    instructions the op_name lists several paths: the first counts.
    """
    wrappers, scopes = set(), []
    for token in _tokens(op_name.split(";", 1)[0]):
        seen = []
        m = _WRAPPED.match(token)
        while m is not None:
            seen.append(m.group(1))
            token = m.group(2)
            m = _WRAPPED.match(token)
        if any(w in FUNCTION_WRAPPERS for w in seen):
            continue
        if token:
            wrappers.update(seen)
        scopes.append(token)
    kind = next((t for t in reversed(scopes) if t in KINDS), None)
    for phase in STEP_PHASES:
        if phase in scopes:
            return phase, kind
    if "transpose" in wrappers:
        return BACKWARD, kind
    if "jvp" in wrappers or kind is not None:
        return FORWARD, kind
    return UNSCOPED, kind


@contextlib.contextmanager
def _jax_settings(**values):
    import jax

    before = {k: getattr(jax.config, k) for k in values}
    try:
        for k, v in values.items():
            jax.config.update(k, v)
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def compile_fresh(lowered) -> str:
    """``as_text()`` of a compile that no entry of jax's compile cache can
    stand in for and that leaves none: the key holds the metadata for once
    (nothing else is ever written under such a key, so it misses), and no
    compile is slow enough to be kept. ``lowered`` is of a function traced
    for this call: jax keeps one executable per traced function in memory,
    wherever its first compile took it from."""
    with _jax_settings(
            jax_compilation_cache_include_metadata_in_key=True,
            jax_persistent_cache_min_compile_time_secs=float("inf")):
        return lowered.compile().as_text()


def step_hlo(rc) -> str:
    """The HLO text of the cell's compiled train step, memoised on ``rc``.

    Rebuilds the strategy from the cell's two data files and lowers its
    step on shapes alone (``jax.eval_shape``: no weights are drawn, no
    array is put on the device); across chips with the shardings of the
    window's own arguments (``rc.step_shardings``). Reached from the scope
    readers only, never from an untraced run.

    The text must come from a compile of THIS program's metadata, which
    jax's compile cache cannot promise (its key strips debug info, so the
    entry may be a scope-less parent's). So the compile here is
    ``compile_fresh``: it costs one compile of the step and leaves the
    cache that the untraced runs share with the parent as it was (an entry
    of its own would be 29 MB of a cache the chip machine holds to 192 MiB:
    PERF.md section 6). What is kept instead is the text, gzipped
    beside the compile cache under a name made of the lowered module WITH
    its locations (every scope and traced line is in it, cut to the
    operation's own frame so that the caller's stack is not), the compiler
    and the device: the first traced run of a program pays the compile, the
    next ones read a file."""
    text = getattr(rc, "_step_hlo", None)
    if text is None:
        import jax
        import jax.numpy as jnp
        import jaxlib

        from benchmarks.harness import train_driver
        from benchmarks.harness.traffic import SeededBatches

        cfg, strategy = train_driver.build(rc.config, rc.traffic)
        ds = rc.config["dataset"]
        state = jax.eval_shape(strategy.init, jax.random.key(0))
        x, y = jax.eval_shape(lambda: SeededBatches(
            0, ds["kind"], tuple(ds["sample_shape"]),
            rc.config.get("vocab_size", ds["num_classes"]),
            cfg.global_batch()).batch(0, 0))
        if rc.chips == 1:
            # train_driver.seeded_state commits the weights it lays in to
            # the chip, and a committed argument is annotated in the
            # lowered module: the same here, so that this is the module the
            # window ran.
            chip = jax.sharding.SingleDeviceSharding(rc.devices[0])
            state = state._replace(params=jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=chip), state.params))
        else:
            # across chips the step is partitioned by its arguments'
            # shardings, which are the strategy's own: the window's driver
            # keeps them (train_driver.run), nothing rebuilds them here
            state, x, y = jax.tree.map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sh),
                (state, x, y), rc.step_shardings)
        step = strategy.train_step
        if not hasattr(step, "lower"):  # dp's explicit engines wrap theirs
            step = strategy._jit_train_step
        t0 = time.perf_counter()
        with _jax_settings(jax_traceback_in_locations_limit=1):
            lowered = step.lower(state, x, y, jax.ShapeDtypeStruct(
                (), jnp.float32))
        device = jax.devices()[0]
        name = hashlib.sha256("\n".join((
            lowered.as_text(debug_info=True), jax.__version__,
            jaxlib.__version__, device.client.platform_version,
            device.device_kind, os.environ.get("XLA_FLAGS", ""),
            os.environ.get("LIBTPU_INIT_ARGS", ""))).encode()).hexdigest()
        directory = jax.config.jax_compilation_cache_dir
        path = directory and os.path.join(directory,
                                          f"step_hlo-{name}.txt.gz")
        if path and os.path.exists(path):
            how = "read from " + path
            with gzip.open(path, "rt") as f:
                text = f.read()
        else:
            how = "compiled"
            text = compile_fresh(lowered)
            try:
                if path:
                    os.makedirs(directory, exist_ok=True)
                    with gzip.open(f"{path}.{os.getpid()}", "wt") as f:
                        f.write(text)
                    os.replace(f"{path}.{os.getpid()}", path)
            except OSError as e:  # the next traced run compiles again
                how += f", not kept: {e}"
        print(f"scopes: the step's HLO text in "
              f"{time.perf_counter() - t0:.1f}s ({how})", file=sys.stderr)
        rc._step_hlo = text
    return text


@dataclasses.dataclass
class ScopeTimes:
    """Device seconds of the traced window by scope; each value is
    ``[seconds, instruction count]``."""
    by_phase: Dict[str, List[float]]
    by_kind: Dict[str, List[float]]
    unscoped: List[Tuple[str, float]]  # instruction, seconds; largest first
    total_s: float
    scoped_instructions: int  # of the table: a kind or a step phase on it

    def seconds(self, phase=None, kinds=None) -> Tuple[float, int]:
        picked = ([self.by_phase[phase]] if phase in self.by_phase else []) \
            + [self.by_kind[k] for k in (kinds or ()) if k in self.by_kind]
        return sum(p[0] for p in picked), int(sum(p[1] for p in picked))


def reduce_scopes(op_seconds: Dict[str, float],
                  table: Dict[str, str]) -> ScopeTimes:
    """The join: each traced instruction's seconds to its phase and kind;
    instructions in no scope, or not in the table, are ``unscoped``."""
    classes = {name: classify(op) for name, op in table.items()}
    by_phase: Dict[str, List[float]] = {}
    by_kind: Dict[str, List[float]] = {}
    unscoped = []
    for name, seconds in op_seconds.items():
        phase, kind = classes.get(name, (UNSCOPED, None))
        p = by_phase.setdefault(phase, [0.0, 0])
        p[0] += seconds
        p[1] += 1
        if kind is not None:
            k = by_kind.setdefault(kind, [0.0, 0])
            k[0] += seconds
            k[1] += 1
        if phase == UNSCOPED:
            unscoped.append((name, seconds))
    return ScopeTimes(
        by_phase, by_kind, sorted(unscoped, key=lambda x: -x[1]),
        sum(op_seconds.values()),
        sum(1 for phase, kind in classes.values()
            if kind is not None or phase in STEP_PHASES))


def device_time(rc) -> Optional[ScopeTimes]:
    """``reduce_scopes`` of the run's trace and its step, memoised on
    ``rc``; None where the program has no scopes. Prints the parts on
    stderr once."""
    if not hasattr(rc, "_scope_times"):
        times = reduce_scopes(rc.trace_summary.op_seconds,
                              scope_table(step_hlo(rc)))
        # jax names a few scopes of its own (an einsum's equation), so a
        # program counts as scoped by the vocabulary, not by any scope
        if times.scoped_instructions == 0:
            print("scopes: the compiled step carries none of the program's "
                  "named scopes; the scope metrics are left out",
                  file=sys.stderr)
            times = None
        else:
            report(times, rc.counters["steps"])
        rc._scope_times = times
    return rc._scope_times


def report(times: ScopeTimes, steps: int) -> None:
    ms = 1000.0 / max(steps, 1)
    for title, parts in (("phase", times.by_phase), ("kind", times.by_kind)):
        for name, (seconds, n) in sorted(parts.items(),
                                         key=lambda x: -x[1][0]):
            print(f"scopes: {title} {name:10s} {seconds * ms:9.3f} ms/step "
                  f"{n:5d} instructions", file=sys.stderr)
    for name, seconds in times.unscoped[:5]:
        print(f"scopes: unscoped {name} {seconds * ms:.3f} ms/step",
              file=sys.stderr)
