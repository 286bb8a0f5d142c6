#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the train and serve main paths once, through the entry points a user
calls (``ddlbench_tpu.cli.main``, ``ddlbench_tpu.tools.servebench.main``), in
ONE process on a TPU, at the full width of the widest models the repo has
(resnet50/imagenet at batch 128; transformer_m — 12 x d768, H=12, dh=64,
T=1024, V=32768), with seeded random weights and a few steps / requests:

    kernels    every Pallas kernel in ops/ jitted on the chip and compared
               with its own jnp reference within a stated tolerance
               (cheapest compiles first, so a Mosaic refusal shows in seconds)
    train_cnn  cli: resnet50 / imagenet, bf16, batch 128, a few steps + eval
    train_lm   cli: transformer_m / synthtext, bf16, batch 16 — the compiled
               step must hold the flash-attention and fused-xent kernels
    serve      servebench: transformer_m, continuous batching, f32 and int8
               KV pools — every request completes and the decode /
               chunk-prefill programs hold the paged kernels
    multichip  (>= 4 devices) cli: dp / gpipe / pipedream at -g 4 and dp
               ZeRO-1 on transformer_m, servebench --serve-tp 2 — the train
               state must span four devices that all hold live bytes

There is no CPU branch, no interpret switch and no child process: with no TPU
backend the script exits nonzero before it runs a step; any failed phase
raises. The details (per-phase seconds and compile seconds, the compile-cache
directory, the Pallas kernels found per program, ``multichip``: ``ran`` or
``not_run (N devices)``) go out as one ``chip_smoke: report {...}`` line and
to ``chiprun_out/chip_smoke/report.json``. The last stdout line is the
verdict, one JSON object with exactly these keys, the device as jax reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage: python chip_smoke.py
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

FLASH_KERNELS = {"flash_attn_fwd", "flash_attn_dq_dkv"}
XENT_KERNELS = {"fused_xent_fwd", "fused_xent_dh_dw"}


class _Tee(io.TextIOBase):
    """stdout that also keeps what went through it (the entry points print
    their result lines; nothing here re-implements them)."""

    def __init__(self, stream):
        self.stream, self.buf = stream, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def _call(main, argv):
    """Run an entry point in-process; returns its stdout lines. A nonzero
    return code is a failed phase."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    if rc:
        raise RuntimeError(f"{main.__module__}.main({argv}) returned {rc}")
    return tee.buf.getvalue().splitlines()


def _manifests(path):
    with open(path) as f:
        return {m["name"]: m for m in json.load(f)["programs"]}


def _require_kernels(found, expected, what):
    missing = sorted(set(expected) - set(found))
    if missing:
        raise RuntimeError(
            f"{what}: compiled program holds no Mosaic call for {missing} "
            f"(found {sorted(found)}) — a kernel dispatch fell back to XLA")


# -- kernels ----------------------------------------------------------------


def _close(name, got, ref, tol):
    """max|got - ref| <= tol * max|ref| (nan fails)."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    if not (scale > 0 and err <= tol * scale):
        raise RuntimeError(f"kernels: {name} err {err:.3e} > tol {tol:g} "
                           f"x max|ref| {scale:.3g}")
    return f"{name} {err / scale:.1e}"


def phase_kernels():
    """Each kernel vs its jnp reference at the shapes the main path uses.
    References run with highest matmul precision: XLA's default f32 dot on
    TPU multiplies in bf16, which would make the reference the noisy side."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddlbench_tpu.models.transformer import causal_attention
    from ddlbench_tpu.ops import paged_decode as pd
    from ddlbench_tpu.ops.flash_attention import flash_attention
    from ddlbench_tpu.ops.fused_xent import fused_linear_xent

    notes = []
    key = jax.random.key

    # paged decode + chunk-prefill, H=12 dh=64 page=16, shuffled serving
    # table, pools filled through the real write primitive (so the int8
    # pool carries its real scale sidecar)
    rows, H, dh, page, npl, C = 4, 12, 64, 16, 4, 16
    n_pages = rows * npl + 1
    slots = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    table = jnp.asarray(slots.reshape(rows, npl), jnp.int32)
    kk = jax.random.normal(key(1), (rows, npl * page, H, dh), jnp.float32)
    vv = jax.random.normal(key(2), (rows, npl * page, H, dh), jnp.float32)
    pos = jnp.asarray([npl * page - 1, 37, 16, 5], jnp.int32)
    start = jnp.asarray([(npl - 1) * page, 16, 32, 0], jnp.int32)
    for dt, tol in ((jnp.float32, 2e-3), (jnp.bfloat16, 3e-2),
                    (jnp.int8, 2e-3)):
        pool = pd.serve_pool_init(n_pages, page, H, dh, dt)
        cache = jax.jit(lambda c, k, v: pd.paged_table_chunk_write(
            c, k, v, jnp.int32(0), page))({**pool, "table": table}, kk, vv)
        qdt = jnp.bfloat16 if dt == jnp.bfloat16 else jnp.float32
        q1 = jax.random.normal(key(3), (rows, H, dh), qdt)
        qc = jax.random.normal(key(4), (rows, H, C, dh), qdt)
        name = jnp.dtype(dt).name
        for label, fn, q, at in (
                ("paged_decode", pd.paged_attention, q1, pos),
                ("paged_chunk", pd.paged_chunk_attention, qc, start)):
            got = jax.jit(lambda q, c, a, fn=fn: fn(
                q, c, a, npl, page, use_kernel=True))(q, cache, at)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, c, a, fn=fn: fn(
                    q, c, a, npl, page, use_kernel=False))(q, cache, at)
            notes.append(_close(f"{label}/{name}", got, ref, tol))

    # flash attention fwd+bwd: resident (one-pass backward) at T=1024; at
    # T=8192 both the resident pick and the forced streaming grid.
    # Reference: the XLA einsum path on the same bf16 values upcast to f32.
    def fwd_bwd(fn):
        def f(q, k, v, w):
            o, vjp = jax.vjp(fn, q, k, v)
            return (o, *vjp(w.astype(o.dtype)))
        return jax.jit(f)

    def ref_attn(q, k, v):
        return causal_attention(q, k, v, backend="xla")

    for T, Hh, stream in ((1024, 12, None), (8192, 2, None), (8192, 2, True)):
        q, k, v = (jax.random.normal(key(10 + i), (1, Hh, T, 64),
                                     jnp.bfloat16) for i in range(3))
        w = jax.random.normal(key(13), (1, Hh, T, 64), jnp.float32)
        got = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, stream=stream))(q, k, v, w)
        with jax.default_matmul_precision("highest"):
            ref = fwd_bwd(ref_attn)(
                *(a.astype(jnp.float32) for a in (q, k, v)), w)
        tag = f"flash/T{T}" + ("/stream" if stream else "")
        for n, g, r in zip(("o", "dq", "dk", "dv"), got, ref):
            notes.append(_close(f"{tag}/{n}", g, r, 3e-2))

    # fused projection + cross-entropy, fwd+bwd at D=768 / V=32768, against
    # its own chunked-XLA path
    N, D, V = 2048, 768, 32768
    h = (jax.random.normal(key(20), (N, D), jnp.float32) * 0.5
         ).astype(jnp.bfloat16)
    wh = (jax.random.normal(key(21), (D, V), jnp.float32) * 0.02
          ).astype(jnp.bfloat16)
    labels = jax.random.randint(key(22), (N,), 0, V, jnp.int32)

    def xent(backend):
        def f(h, w):
            obj, ce, _ = fused_linear_xent(h, w, labels, 0.1, 512, backend)
            return (obj + ce) / N
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    got, ref = xent("pallas")(h, wh), xent("xla")(h, wh)
    notes.append(_close("fused_xent/loss", got[0], ref[0], 2e-3))
    notes.append(_close("fused_xent/dh", got[1][0] * N, ref[1][0] * N, 3e-2))
    notes.append(_close("fused_xent/dw", got[1][1] * N, ref[1][1] * N, 3e-2))
    return {"checks": notes}


# -- train ------------------------------------------------------------------


def _train(tag, argv, expect_kernels=(), expect_devices=1):
    """One cli.main run; checks its result line and compiled step."""
    from ddlbench_tpu import cli

    audit = os.path.join(OUT_DIR, f"{tag}.audit.json")
    lines = _call(cli.main, [*argv, "-e", "1", "--steps-per-epoch", "4",
                             "-p", "2", "--audit", audit])
    result = json.loads(next(
        ln for ln in reversed(lines) if ln.startswith("result: "))[8:])
    losses = [float(m) for ln in lines if ln.startswith("train |")
              for m in re.findall(r"\| loss (\S+) \|", ln)]
    losses += [e["loss"] for e in result["valid_history"]]
    if not losses or not all(x == x and abs(x) != float("inf")
                             for x in losses):
        raise RuntimeError(f"{tag}: non-finite or missing loss {losses}")
    if not result["valid_history"] or result["samples_per_sec"] <= 0:
        raise RuntimeError(f"{tag}: the run took no steps: {result}")
    dev = result["device"]
    if dev["state_devices"] != expect_devices or \
            sum(b > 0 for b in dev["bytes_in_use"]) < expect_devices:
        raise RuntimeError(
            f"{tag}: train state on {dev['state_devices']} devices, live "
            f"bytes per device {dev['bytes_in_use']}; wanted "
            f"{expect_devices} devices all holding live buffers")
    (man,) = _manifests(audit).values()
    _require_kernels(man["pallas_kernels"], expect_kernels, tag)
    return {"loss": losses[-1], "warmup_compile_s": result["warmup_compile_s"],
            "state_devices": dev["state_devices"],
            "bytes_in_use": dev["bytes_in_use"],
            "kernels": man["pallas_kernels"]}


def phase_train_cnn():
    return _train("train_cnn", ["-b", "imagenet", "-f", "single", "-m",
                                "resnet50", "--dtype", "bfloat16",
                                "--batch-size", "128"])


def phase_train_lm():
    # T=1024: flash_pays_off must pick the flash kernel and the fused head
    # must pass _pallas_feasible — both dispatches fall back to XLA without
    # a word, so the compiled step is what is checked
    return _train("train_lm", ["-b", "synthtext", "-f", "single", "-m",
                               "transformer_m", "--dtype", "bfloat16",
                               "--batch-size", "16"],
                  expect_kernels=FLASH_KERNELS | XENT_KERNELS)


# -- serve ------------------------------------------------------------------


def _serve(tag, extra):
    from ddlbench_tpu.tools import servebench

    requests = 6
    audit = os.path.join(OUT_DIR, f"{tag}.audit.json")
    lines = _call(servebench.main, [
        "-m", "transformer_m", "-b", "synthtext", "--policies", "continuous",
        "--wall-clock", "--requests", str(requests), "--max-batch", "4",
        "--max-len", "64", "--prompt-lens", "4,12,24", "--out-lens", "2,8,16",
        "--audit", audit, *extra])
    row = json.loads(next(ln for ln in reversed(lines)
                          if ln.startswith('{"tool": "servebench"')))
    if row["completed"] != requests or row["jax_backend"] != "tpu":
        raise RuntimeError(f"{tag}: {row['completed']}/{requests} requests "
                           f"completed on {row['jax_backend']}: {row}")
    for field in ("ttft_p50", "itl_p50", "goodput_tokens_per_unit"):
        if not row[field] == row[field] or row[field] < 0:  # nan / negative
            raise RuntimeError(f"{tag}: {field} = {row[field]}")
    mans = _manifests(audit)
    kernels = {}
    for prog, kernel in (("decode", "paged_decode_attn"),
                         ("prefill", "paged_chunk_attn")):
        found = mans[f"serve/transformer_m/{prog}"]["pallas_kernels"]
        _require_kernels(found, {kernel}, f"{tag}/{prog}")
        kernels[prog] = found
    return {"completed": row["completed"], "wall_s": row["wall_s"],
            "kernels": kernels}


def phase_serve():
    return {"float32": _serve("serve_f32", []),
            "int8": _serve("serve_int8", ["--kv-dtype", "int8"])}


# -- four chips -------------------------------------------------------------


def phase_multichip():
    cnn = ["-b", "imagenet", "-m", "resnet50", "--dtype", "bfloat16", "-g", "4"]
    out = {}
    for tag, argv, kernels in (
            ("dp4", [*cnn, "-f", "dp"], ()),
            ("gpipe4", [*cnn, "-f", "gpipe"], ()),
            ("pipedream4", [*cnn, "-f", "pipedream"], ()),
            # explicit shard_map engine: the kernels run per shard
            ("dp4_zero1_lm", ["-b", "synthtext", "-m", "transformer_m",
                              "--dtype", "bfloat16", "-g", "4", "-f", "dp",
                              "--dp-shard-update"],
             FLASH_KERNELS | XENT_KERNELS)):
        t0 = time.perf_counter()
        out[tag] = _train(f"multichip_{tag}", argv, kernels, expect_devices=4)
        out[tag]["seconds"] = round(time.perf_counter() - t0, 1)
    # the paged kernels inside the tp shard_map (serve/engine.py)
    out["serve_tp2"] = _serve("multichip_serve_tp2", ["--serve-tp", "2"])
    return out


# -- driver -----------------------------------------------------------------


def verdict_line(ok, devices) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` — whoever runs the
    script parses it by that shape, so the report does not ride along."""
    d0 = devices[0]
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(d0.platform),
                                  "kind": str(d0.device_kind),
                                  "count": len(devices)}})


def main() -> int:
    t_start = time.perf_counter()
    import jax

    from ddlbench_tpu.distributed import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU backend; jax found "
              f"{jax.default_backend()!r} ({jax.devices()})", file=sys.stderr)
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)

    # compile seconds per phase, from jax's own monitoring events (a warm
    # persistent cache shows up here, not in a guess)
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devices = jax.devices()
    phases = [("kernels", phase_kernels), ("train_cnn", phase_train_cnn),
              ("train_lm", phase_train_lm), ("serve", phase_serve)]
    if len(devices) >= 4:
        phases.append(("multichip", phase_multichip))
    report = {}
    for name, fn in phases:
        t0, c0 = time.perf_counter(), compile_s[0]
        detail = fn()
        secs = time.perf_counter() - t0
        report[name] = {"ok": True, "seconds": round(secs, 1),
                        "compile_seconds": round(compile_s[0] - c0, 1),
                        **detail}
        print(f"chip_smoke: {name} ok {secs:.1f}s "
              f"(compile {compile_s[0] - c0:.1f}s)", flush=True)

    report_line = json.dumps({
        "seconds": round(time.perf_counter() - t_start, 1),
        "compile_seconds": round(compile_s[0], 1),
        "compile_cache_dir": cache_dir,
        "multichip": ("ran" if "multichip" in report
                      else f"not_run ({len(devices)} devices)"),
        "phases": report,
    })
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        f.write(report_line + "\n")
    print(f"chip_smoke: report {report_line}", flush=True)
    print(verdict_line(True, devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
