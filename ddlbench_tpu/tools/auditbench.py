"""auditbench: the CPU-runnable compiled-program audit gate.

Two verbs:

``run``
    Compile the tieable engine matrix at tiny shapes (dp ZeRO-1 bucketed,
    dp int8 incl. scale sidecars, gpipe replicated + hybrid ZeRO-1, the
    Megatron-in-stage tp pipeline) plus the serve-program layouts
    (kv_dtype x tp), extract each program's audit manifest
    (telemetry/audit.py — flops / HBM components / per-collective ledger
    out of the optimized HLO), cross-check ``comm_stats`` and
    ``pool_page_bytes`` against them, and write one ledger JSON. Exits
    nonzero when any tie-out fails — every analytic byte formula is
    checked against the program XLA actually built, on any backend.

``diff``
    Compare two ledgers (e.g. the committed golden in
    ``perf_runs/audit_golden/`` vs a fresh run): unexplained growth in
    flops / peak HBM / wire bytes / per-kind collective counts exits
    nonzero — a regression gate that needs no chip.

Examples::

    python -m ddlbench_tpu.tools.auditbench run --out /tmp/audit.json
    python -m ddlbench_tpu.tools.auditbench diff \
        perf_runs/audit_golden/cpu8.json /tmp/audit.json

The virtual 8-device CPU mesh must be up before jax initializes::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python -m ddlbench_tpu.tools.auditbench run ...
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _train_matrix():
    """The tieable train-engine matrix at tiny shapes: (name, cfg)."""
    from ddlbench_tpu.config import RunConfig

    base = dict(benchmark="mnist", num_devices=8, compute_dtype="float32",
                batch_size=2, steps_per_epoch=2)
    pipe = dict(benchmark="mnist", strategy="gpipe", num_devices=8,
                num_stages=4, dp_replicas=2, micro_batch_size=2,
                num_microbatches=4, compute_dtype="float32",
                steps_per_epoch=2)
    tpp = dict(benchmark="synthtext", arch="transformer_t",
               strategy="gpipe", num_devices=8, num_stages=2, tp_size=2,
               dp_replicas=2, micro_batch_size=2, num_microbatches=4,
               compute_dtype="float32", steps_per_epoch=2)
    matrix = [
        ("train/dp-zero1-b3",
         RunConfig(strategy="dp", dp_shard_update=True, comm_buckets=3,
                   **base)),
        ("train/dp-zero1-int8-b3",
         RunConfig(strategy="dp", dp_shard_update=True, comm_buckets=3,
                   allreduce_dtype="int8", **base)),
        ("train/gpipe-dp2", RunConfig(**pipe)),
        ("train/gpipe-dp2-zero1",
         RunConfig(dp_shard_update=True, **pipe)),
        ("train/tpp-s2-tp2-dp2", RunConfig(**tpp)),
    ]
    for _, cfg in matrix:
        cfg.validate()
    return matrix


def _serve_matrix():
    from ddlbench_tpu.config import ServeConfig

    out = []
    for kv in ("float32", "int8"):
        for tp in (1, 2):
            cfg = ServeConfig(max_batch=4, pool_pages=20, page=4,
                              max_len=16, prefill_chunk=4, kv_dtype=kv,
                              tp=tp)
            out.append((f"serve/kv={kv}/tp={tp}", cfg))
    return out


def run_audits(out_path: Optional[str], include_serve: bool = True,
               quiet: bool = False) -> int:
    import jax

    from ddlbench_tpu.distributed import record_provenance
    from ddlbench_tpu.models import init_model
    from ddlbench_tpu.models.zoo import get_model
    from ddlbench_tpu.config import DATASETS
    from ddlbench_tpu.serve.engine import ServeEngine
    from ddlbench_tpu.telemetry.audit import (audit_serve_engine,
                                              audit_train_config,
                                              write_manifests)

    prov = record_provenance(None, "auditbench")
    manifests = []
    failed: List[str] = []

    for name, cfg in _train_matrix():
        man, _ = audit_train_config(cfg, name)
        manifests.append(man)
        rec = man["reconcile"]
        ok = rec.get("ok", False)
        if not ok:
            failed.append(name)
        if not quiet:
            n_bad = sum(1 for c in rec["checks"] if not c["ok"])
            print(f"{name}: tieable={rec['tieable']} ok={ok} "
                  f"checks={len(rec['checks'])} failed={n_bad} "
                  f"unexplained={len(rec['unexplained'])} "
                  f"wire={man['wire_bytes_total']:.0f}B", flush=True)

    if include_serve:
        spec = DATASETS["synthtext"]
        model = get_model("transformer_t", spec)
        params, state, _ = init_model(model, jax.random.key(0))
        for name, scfg in _serve_matrix():
            eng = ServeEngine(model, params, state, scfg)
            mans, pool = audit_serve_engine(eng, prefix=name)
            manifests.extend(mans)
            if not pool["ok"]:
                failed.append(name)
            if not quiet:
                print(f"{name}: pool_ok={pool['ok']} "
                      f"page_bytes={pool['pool_page_bytes']:.0f} "
                      f"programs={len(mans)}", flush=True)

    if out_path:
        write_manifests(out_path, manifests, header=prov)
        if not quiet:
            print(f"wrote {len(manifests)} manifests -> {out_path}",
                  flush=True)
    if failed:
        print(f"AUDIT FAILED: {', '.join(failed)}", file=sys.stderr,
              flush=True)
        return 1
    return 0


def run_diff(old_path: str, new_path: str, tolerance: float,
             quiet: bool = False) -> int:
    from ddlbench_tpu.telemetry.audit import (diff_manifests,
                                              load_manifests)

    report = diff_manifests(load_manifests(old_path),
                            load_manifests(new_path), tolerance=tolerance)
    if not quiet:
        print(f"compared {len(report['compared'])} programs "
              f"(+{len(report['added'])} added, "
              f"-{len(report['removed'])} removed)", flush=True)
        for r in report["regressions"]:
            growth = (f"{r['growth'] * 100:+.1f}%"
                      if r["growth"] not in (float("inf"),) else "new")
            print(f"REGRESSION {r['program']}: {r['metric']} "
                  f"{r['old']:.0f} -> {r['new']:.0f} ({growth})",
                  flush=True)
    if not report["ok"]:
        print(f"auditbench diff: {len(report['regressions'])} "
              f"unexplained regression(s)", file=sys.stderr, flush=True)
        return 1
    if not quiet:
        print("auditbench diff: clean", flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="auditbench",
        description="compiled-program audit gate (telemetry/audit.py)")
    sub = p.add_subparsers(dest="verb", required=True)
    pr = sub.add_parser("run", help="audit the engine matrix")
    pr.add_argument("--out", default=None,
                    help="write the ledger JSON here (atomic)")
    pr.add_argument("--no-serve", action="store_true",
                    help="skip the serve-program layouts")
    pr.add_argument("--quiet", action="store_true")
    pd = sub.add_parser("diff", help="diff two ledgers; nonzero on growth")
    pd.add_argument("old")
    pd.add_argument("new")
    pd.add_argument("--tolerance", type=float, default=None,
                    help="relative growth tolerated before flagging "
                         "(default telemetry/audit.DIFF_TOLERANCE)")
    pd.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    if args.verb == "run":
        from ddlbench_tpu.distributed import force_host_mesh_platform

        force_host_mesh_platform()
        return run_audits(args.out, include_serve=not args.no_serve,
                          quiet=args.quiet)
    from ddlbench_tpu.telemetry.audit import DIFF_TOLERANCE

    tol = args.tolerance if args.tolerance is not None else DIFF_TOLERANCE
    return run_diff(args.old, args.new, tol, quiet=args.quiet)


if __name__ == "__main__":
    raise SystemExit(main())
