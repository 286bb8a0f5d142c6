"""Compiled-program audit plane: tie every analytic model to the program
XLA actually built.

The framework prices everything analytically — ``train/comm_stats.py``
wire bytes, the planner's HBM model, ``serve.pool_page_bytes`` KV
accounting — and XLA already knows the truth at compile time: ``compiled.cost_analysis()``
/ ``memory_analysis()`` give exact flops and buffer bytes on ANY backend,
and the optimized HLO text lists every collective with its shape, dtype
and replica groups. This module walks those out into a per-program
**audit manifest** and cross-checks the analytic models against it:

* :func:`collective_ledger` — parse the optimized HLO into
  :class:`CollectiveOp` records (kind, dtype, shape, per-participant
  payload bytes, replica groups incl. the iota ``[G,g]<=[N]`` form,
  ring-model wire bytes), one per OPERAND: an instruction the compiler
  combined out of several collectives gives several records.
* :func:`program_manifest` — flops / bytes-accessed / memory components
  / the ledger, with graceful degradation: on backends where
  cost_analysis or memory_analysis are unavailable the fields are
  ``None``, never a ``KeyError``.
* :func:`reconcile_train` — per-engine exact tie-outs of ``comm_stats``
  against the ledger (dp ZeRO-1 bucketed, int8 incl. scale sidecars,
  gpipe conveyor + padded-row sync, tp per-collective payload classes).
  GSPMD-compiled engines (replicated dp, monolithic ZeRO-1) lower to an
  irregular collective soup and are reported ``tieable: False`` by
  design — exact ties target the explicit shard_map engines.
* :func:`serve_pool_audit` — ``pool_page_bytes`` vs the actual pool
  buffer bytes the compiled serve programs take as arguments, across
  tp / kv_dtype layouts (int8 payload exactly f32/4).
* :func:`planner_stage_hbm_audit` — signed per-stage error of the
  planner's HBM model vs ``memory_analysis()``, recorded in the
  partition.json idiom.
* :func:`diff_manifests` — the regression gate ``auditbench diff``
  uses: unexplained growth in flops / peak HBM / wire bytes / collective
  counts between two manifests exits nonzero.

Wire conventions (ring model, matching ``comm_stats``): for one op with
``G`` replica groups of size ``g`` and per-participant payload ``p``
bytes — all-reduce ``G * 2(g-1)/g * p``; reduce-scatter (HLO shows the
per-shard OUTPUT, full = out*g) ``G * (g-1) * out``; all-gather (HLO
shows the gathered output = full) ``G * (g-1)/g * out``; all-to-all
``G * (g-1) * p``; collective-permute ``payload * n_pairs``. Dynamic
trip counts (conveyors inside while loops) are the ANALYTIC side's job:
the ledger records the static op, ``comm_stats``'s physical_* twins
price op x trips.
"""

from __future__ import annotations

import json
import math
import os
import re
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

AUDIT_SCHEMA_VERSION = 1

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "u4": 1, "s4": 1,
}

_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
          "collective-permute", "all-to-all")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)|[\w\[\],{}:]+)\s+"
    r"(?P<kind>" + "|".join(_KINDS) + r")(?P<phase>-start|-done)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[\d,{}]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


@dataclass
class CollectiveOp:
    """One collective instruction walked out of the optimized HLO."""
    name: str
    kind: str
    dtype: str
    shape: Tuple[int, ...]
    elements: int
    payload_bytes: float          # per-participant bytes as shown in HLO
    scalar: bool                  # metric psums etc. (1 element)
    groups: Optional[List[List[int]]] = None
    n_groups: int = 1
    group_size: int = 1
    n_pairs: int = 0              # collective-permute only
    axes: Optional[str] = None    # mesh axes resolved from replica groups
    wire_bytes: float = 0.0       # ring-model wire for one execution


def _tuple_parts(tok: str) -> List[str]:
    """Top-level components of an HLO tuple shape ``(a, b, (c, d))``; the
    token itself when it is no tuple. Commas inside ``[..]``, ``{..}`` and
    nested ``(..)`` do not split."""
    tok = tok.strip()
    if not tok.startswith("("):
        return [tok]
    parts, depth, start = [], 0, 1
    for i, ch in enumerate(tok[1:-1], 1):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(tok[start:i].strip())
            start = i + 1
    parts.append(tok[start:-1].strip())
    return [p for p in parts if p]


def _operand_shapes(tok: str, kind: str, phase: Optional[str]) -> List[str]:
    """One result-shape token per OPERAND of a collective instruction.

    XLA's combiner passes merge collectives that share replica groups into
    ONE instruction with a tuple result — ``(f32[1,1040], f32[1,1], f32[],
    f32[]) all-reduce(%grads, %state, %loss, %norm)`` is a gradient row, a
    state row and two metric psums (the jax 0.9.0 CPU compiler does this to
    every step audited in tests/test_audit.py) — and the analytic side
    prices operands, so the ledger holds one record a component. The async
    starts wrap their results: ``all-gather-start`` is (operands, results),
    ``collective-permute-start`` (operand, result, context words..); an
    ``all-reduce-start`` has its all-reduce's shape."""
    parts = _tuple_parts(tok)
    wrapped = phase == "-start" and kind in ("all-gather",
                                             "collective-permute")
    return _tuple_parts(parts[1]) if wrapped and len(parts) >= 2 else parts


def _parse_shape(tok: str) -> Tuple[str, Tuple[int, ...], int, float]:
    """Parse an HLO result-shape token (possibly a tuple) into
    (dtype, dims-of-first-component, total elements, total bytes)."""
    comps = _SHAPE_RE.findall(tok)
    if not comps:
        return "unknown", (), 0, 0.0
    total_elems, total_bytes = 0, 0.0
    for dt, dims in comps:
        if dt not in _DTYPE_BYTES:      # token[], tuple wrappers, opaque
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        n = math.prod(shape) if shape else 1
        total_elems += n
        total_bytes += n * _DTYPE_BYTES[dt]
    dt0, dims0 = comps[0]
    shape0 = tuple(int(d) for d in dims0.split(",") if d)
    return dt0, shape0, total_elems, total_bytes


def _parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        inner = m.group(1)
        return [[int(x) for x in grp.split(",") if x]
                for grp in re.findall(r"\{([\d,]*)\}", "{" + inner + "}")]
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota form: arange(prod(dims)).reshape(dims).T(perm).reshape(G, g)
        num_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = list(range(math.prod(dims)))
        if m.group(4):
            import numpy as np
            perm = [int(x) for x in m.group(4).split(",")]
            ids = list(np.arange(math.prod(dims)).reshape(dims)
                       .transpose(perm).reshape(-1))
        return [[int(ids[i * group_size + j]) for j in range(group_size)]
                for i in range(num_groups)]
    return None


def _ring_wire(kind: str, payload: float, g: int, n_groups: int,
               n_pairs: int) -> float:
    if kind == "collective-permute":
        return payload * n_pairs
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        per = 2.0 * (g - 1) / g * payload
    elif kind == "reduce-scatter":
        per = (g - 1) * payload       # payload = per-shard output
    elif kind == "all-gather":
        per = (g - 1) / g * payload   # payload = gathered output
    elif kind == "all-to-all":
        per = (g - 1) * payload
    else:
        per = 0.0
    return n_groups * per


def resolve_axes(groups: Optional[List[List[int]]],
                 mesh_axes: Sequence[Tuple[str, int]]) -> Optional[str]:
    """Which mesh-axis subset a replica-group partition varies over.

    Compares ``groups`` (as an unordered partition of device ids) against
    the canonical partition of the row-major mesh for every non-empty
    subset of axes; returns '+'-joined axis names on a match, else None.
    """
    if not groups or not mesh_axes:
        return None
    names = [n for n, _ in mesh_axes]
    sizes = [s for _, s in mesh_axes]
    world = math.prod(sizes)
    if sum(len(g) for g in groups) != world:
        return None
    want = frozenset(frozenset(g) for g in groups)
    import itertools
    import numpy as np
    arr = np.arange(world).reshape(sizes)
    k = len(names)
    for r in range(1, k + 1):
        for subset in itertools.combinations(range(k), r):
            rest = [i for i in range(k) if i not in subset]
            part = arr.transpose(rest + list(subset)).reshape(
                -1, math.prod(sizes[i] for i in subset))
            got = frozenset(frozenset(int(x) for x in row) for row in part)
            if got == want:
                return "+".join(names[i] for i in subset)
    return None


def collective_ledger(hlo_text: str,
                      mesh_axes: Optional[Sequence[Tuple[str, int]]] = None,
                      ) -> List[CollectiveOp]:
    """Walk the optimized HLO text into one record per collective op."""
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m or m.group("phase") == "-done":
            continue
        name, kind = m.group("name"), m.group("kind")
        groups = _parse_replica_groups(line)
        n_pairs = 0
        if kind == "collective-permute":
            pm = _PAIRS_RE.search(line)
            if pm:
                n_pairs = pm.group(1).count("{")
        n_groups = len(groups) if groups else 1
        g = len(groups[0]) if groups else 1
        axes = resolve_axes(groups, mesh_axes or ())
        parts = _operand_shapes(m.group("shape"), kind, m.group("phase"))
        for i, part in enumerate(parts):
            dtype, shape, elems, payload = _parse_shape(part)
            ops.append(CollectiveOp(
                # a combined instruction's operands: name[0], name[1], ..
                name=name if len(parts) == 1 else f"{name}[{i}]",
                kind=kind, dtype=dtype, shape=shape,
                elements=elems, payload_bytes=payload,
                # rank-0 single elements are the metric/scale psums; a
                # rank>=1 single element (a padded [1] state row) is payload
                scalar=(elems <= 1 and not shape), groups=groups,
                n_groups=n_groups, group_size=g, n_pairs=n_pairs, axes=axes,
                wire_bytes=_ring_wire(kind, payload, g, n_groups, n_pairs),
            ))
    return ops


_PALLAS_CALL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')


def pallas_kernels(hlo_text: str) -> Dict[str, int]:
    """``{kernel name: call count}`` of the Mosaic (Pallas TPU) custom
    calls in an optimized HLO module. A compiled kernel surfaces as a
    ``tpu_custom_call`` whose ``op_name`` ends ``<name>/pallas_call``,
    ``<name>`` being the ``name=`` its pallas_call was given (wrapped as
    ``jvp(<name>)`` / ``transpose(jvp(<name>))`` under autodiff); interpret
    mode and the jnp reference paths leave no such call — which is what
    makes this the check that the kernels, not their fallbacks, are what a
    program runs."""
    out: Dict[str, int] = {}
    for op_name in _PALLAS_CALL_RE.findall(hlo_text):
        parts = op_name.split("/")
        if len(parts) > 1 and parts[-1] == "pallas_call":
            name = re.search(r"(\w+)\)*$", parts[-2]).group(1)
        else:
            name = op_name
        out[name] = out.get(name, 0) + 1
    return out


def _mesh_axes_of(mesh) -> Optional[List[Tuple[str, int]]]:
    if mesh is None:
        return None
    try:
        return [(str(k), int(v)) for k, v in dict(mesh.shape).items()]
    except Exception:
        return None


def _cost_dict(compiled) -> Optional[Dict[str, float]]:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return ca


def _memory_dict(compiled) -> Optional[Dict[str, Optional[float]]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = {
        "argument_bytes": "argument_size_in_bytes",
        "output_bytes": "output_size_in_bytes",
        "temp_bytes": "temp_size_in_bytes",
        "alias_bytes": "alias_size_in_bytes",
        "generated_code_bytes": "generated_code_size_in_bytes",
    }
    out: Dict[str, Optional[float]] = {}
    for k, attr in fields.items():
        v = getattr(ma, attr, None)
        out[k] = float(v) if v is not None else None
    present = [out[k] for k in ("argument_bytes", "output_bytes",
                                "temp_bytes") if out[k] is not None]
    if present:
        out["peak_bytes"] = (sum(present)
                             - (out.get("alias_bytes") or 0.0))
    else:
        out["peak_bytes"] = None
    return out


def program_manifest(compiled, name: str, mesh=None,
                     extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The audit manifest for one compiled program.

    Degrades gracefully: any introspection surface the backend lacks
    yields ``None`` fields (and an empty ledger when the HLO text is
    unavailable) — never a KeyError.
    """
    import jax

    cost = _cost_dict(compiled)
    mem = _memory_dict(compiled)
    mesh_axes = _mesh_axes_of(mesh)
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = None
    ledger = collective_ledger(hlo, mesh_axes) if hlo else []
    totals: Dict[str, Dict[str, float]] = {}
    scalar_counts: Dict[str, int] = {}
    wire_total = 0.0
    for op in ledger:
        if op.scalar and op.kind == "all-reduce":
            scalar_counts[op.dtype] = scalar_counts.get(op.dtype, 0) + 1
            continue
        t = totals.setdefault(op.kind, {"count": 0, "payload_bytes": 0.0,
                                        "wire_bytes": 0.0})
        t["count"] += 1
        t["payload_bytes"] += op.payload_bytes
        t["wire_bytes"] += op.wire_bytes
        wire_total += op.wire_bytes
    return {
        "audit_schema_version": AUDIT_SCHEMA_VERSION,
        "name": name,
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(__import__("jaxlib"), "__version__", None),
        "backend": jax.default_backend(),
        "mesh_axes": mesh_axes,
        "flops": (float(cost["flops"])
                  if cost and "flops" in cost else None),
        "bytes_accessed": (float(cost["bytes accessed"])
                           if cost and "bytes accessed" in cost else None),
        "memory": mem,
        "hlo_available": hlo is not None,
        "pallas_kernels": pallas_kernels(hlo) if hlo else {},
        "collectives": [asdict(op) for op in ledger],
        "collective_totals": totals,
        "scalar_collectives": scalar_counts,
        "wire_bytes_total": wire_total,
        **(extra or {}),
    }


def lower_manifest(jitfn, args: Sequence[Any], name: str, mesh=None,
                   extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """AOT-lower + compile ``jitfn(*args)`` and manifest it. Lowering
    never executes, so donated arguments are safe to reuse after."""
    compiled = jitfn.lower(*args).compile()
    return program_manifest(compiled, name, mesh=mesh, extra=extra)


# ---------------------------------------------------------------------------
# comm_stats tie-outs per engine
# ---------------------------------------------------------------------------


def _check(name: str, expected: float, actual: float,
           tol: float = 0.0) -> Dict[str, Any]:
    ok = (abs(actual - expected) <= tol * max(abs(expected), 1.0)
          if tol else actual == expected)
    return {"check": name, "expected": float(expected),
            "actual": float(actual), "ok": bool(ok)}


def _ops(manifest: Dict[str, Any], kind: Optional[str] = None,
         scalar: Optional[bool] = None) -> List[Dict[str, Any]]:
    out = []
    for op in manifest.get("collectives", []):
        if kind is not None and op["kind"] != kind:
            continue
        if scalar is not None and op["scalar"] != scalar:
            continue
        out.append(op)
    return out


def reconcile_train(strategy, manifest: Dict[str, Any]) -> Dict[str, Any]:
    """Exact per-collective tie-out of ``comm_stats`` vs the ledger.

    Returns ``{"engine", "tieable", "checks": [...], "unexplained": [...],
    "comm_stats": {...}}``; ``ok`` is the AND of all checks AND an empty
    unexplained list. Engines compiled through GSPMD sharding propagation
    (replicated dp, monolithic ZeRO-1 without the explicit wire engine)
    produce compiler-chosen collective soup — those come back
    ``tieable: False`` with the manifest still attached.
    """
    from ddlbench_tpu.train.comm_stats import comm_stats

    name = type(strategy).__name__
    cs = comm_stats(strategy)
    res: Dict[str, Any] = {"engine": name, "tieable": True,
                           "checks": [], "unexplained": [],
                           "comm_stats": cs}
    checks: List[Dict[str, Any]] = res["checks"]
    if not manifest.get("hlo_available"):
        res["tieable"] = False
        res["ok"] = False
        return res

    if name == "DPStrategy":
        meta = getattr(strategy, "_flat_meta", None)
        if meta is None:
            res["tieable"] = False     # GSPMD pmean engine
            res["ok"] = False
            return res
        import numpy as np
        r = strategy.world_size
        nb = int(meta.num_buckets)
        wire_dtype = np.dtype(getattr(strategy, "wire_dtype", "float32"))
        int8 = wire_dtype == np.dtype(np.int8)
        wire_name = {1: "s8", 2: "bf16", 4: "f32"}.get(
            wire_dtype.itemsize, "f32")
        if getattr(strategy, "shard_update", False):
            rs = _ops(manifest, "reduce-scatter")
            ag = [op for op in _ops(manifest, "all-gather")
                  if op["dtype"] == "f32"]
            checks.append(_check("rs_op_count", nb, len(rs)))
            checks.append(_check("ag_op_count", nb, len(ag)))
            checks.append(_check(
                "rs_wire_bytes", cs["physical_reduce_scatter_bytes"],
                sum(op["wire_bytes"] for op in rs)))
            checks.append(_check(
                "ag_wire_bytes", cs["physical_all_gather_bytes"],
                sum(op["wire_bytes"] for op in ag)))
            checks.append(_check(
                "rs_wire_dtype", nb,
                sum(1 for op in rs if op["dtype"] == wire_name)))
        else:
            ar = _ops(manifest, "all-reduce", scalar=False)
            checks.append(_check(
                "ar_wire_bytes", cs["physical_allreduce_bytes"],
                sum(op["wire_bytes"] for op in ar
                    if op["dtype"] == wire_name)))
        if int8:
            # scale sidecars: exactly one scalar f32 psum per bucket on
            # top of the 2 scalar f32 metric psums (loss/norm)
            n_f32 = manifest.get("scalar_collectives", {}).get("f32", 0)
            checks.append(_check("scalar_f32_psums", 2 + nb, n_f32))
            checks.append(_check(
                "scale_wire_bytes", cs["scale_bytes"],
                (n_f32 - 2) * (2.0 * (r - 1) / r * 4.0)))

    elif name == "GPipeStrategy":
        itemsize = strategy.compute_dtype.itemsize
        S, dp = strategy.num_stages, strategy.dp
        M = strategy.num_microbatches
        V = strategy.num_chunks // S
        T = M * V + S - 1
        cp = _ops(manifest, "collective-permute")
        act = float(strategy._act_size) * itemsize
        checks.append(_check("cp_op_count", 2, len(cp)))
        for op in cp:
            checks.append(_check(
                f"cp_payload[{op['name']}]", act, op["payload_bytes"]))
            checks.append(_check(
                f"cp_pairs[{op['name']}]", (S - 1) * dp, op["n_pairs"]))
        checks.append(_check(
            "conveyor_wire_bytes", cs.get("physical_boundary_bytes", 0.0),
            T * sum(op["wire_bytes"] for op in cp)))
        if getattr(strategy, "pipe_shard", False):
            rs = _ops(manifest, "reduce-scatter")
            ag = [op for op in _ops(manifest, "all-gather")
                  if op["dtype"] == "f32"]
            checks.append(_check(
                "rs_wire_bytes", cs["physical_reduce_scatter_bytes"],
                sum(op["wire_bytes"] for op in rs)))
            checks.append(_check(
                "ag_wire_bytes", cs["physical_all_gather_bytes"],
                sum(op["wire_bytes"] for op in ag)))
        elif dp > 1:
            ar = _ops(manifest, "all-reduce", scalar=False)
            classes = {cs["gp_grad_row_bytes"], cs["gp_state_row_bytes"]}
            for op in ar:
                if op["payload_bytes"] not in classes:
                    res["unexplained"].append(op)
            checks.append(_check(
                "grad_state_wire_bytes", cs["physical_allreduce_bytes"],
                sum(op["wire_bytes"] for op in ar)))

    elif name == "TPGPipeStrategy":
        itemsize = strategy.compute_dtype.itemsize
        S, dp, tp = strategy.num_stages, strategy.dp, strategy.tp
        M = strategy.num_microbatches
        T = M + S - 1
        cp = _ops(manifest, "collective-permute")
        act = float(strategy._act_size) * itemsize
        checks.append(_check("cp_op_count", 2, len(cp)))
        for op in cp:
            checks.append(_check(
                f"cp_payload[{op['name']}]", act, op["payload_bytes"]))
            checks.append(_check(
                f"cp_pairs[{op['name']}]", (S - 1) * dp * tp,
                op["n_pairs"]))
        checks.append(_check(
            "conveyor_wire_bytes", cs.get("physical_boundary_bytes", 0.0),
            T * sum(op["wire_bytes"] for op in cp)))
        # every nonscalar all-reduce must land in one analytic payload
        # class, keyed by (mesh axes, per-participant payload)
        classes = {
            ("model", cs["tp_psum_payload_bytes"]): "tp_psum",
            ("data", cs["tp_grad_sliced_row_bytes"]): "grad_sliced",
            ("data+model", cs["tp_grad_repl_row_bytes"]): "grad_repl",
            ("data", cs["tp_state_row_bytes"]): "state",
            ("model", cs["tp_state_row_bytes"]): "state",
        }
        grad_state_wire = 0.0
        n_psum = 0
        for op in _ops(manifest, "all-reduce", scalar=False):
            key = (op.get("axes"), op["payload_bytes"])
            label = classes.get(key)
            if label is None:
                res["unexplained"].append(op)
            elif label == "tp_psum":
                n_psum += 1
            else:
                grad_state_wire += op["wire_bytes"]
        res["tp_psum_ops"] = n_psum
        checks.append(_check(
            "grad_state_wire_bytes", cs["physical_allreduce_bytes"],
            grad_state_wire))

    else:
        res["tieable"] = False

    res["ok"] = (res["tieable"] and not res["unexplained"]
                 and all(c["ok"] for c in checks))
    return res


# ---------------------------------------------------------------------------
# serve KV-pool tie-out
# ---------------------------------------------------------------------------


def serve_pool_audit(engine) -> Dict[str, Any]:
    """Tie ``pool_page_bytes`` to the actual KV-pool buffers the compiled
    serve programs take as (donated) arguments: the pool_k/pool_v payload
    leaves must equal ``pages * pool_page_bytes`` exactly per layer and in
    total (scale sidecars and the kv_seed scalar split out, never
    counted), and an int8 pool reports exactly f32/4 per element —
    the invariant the handoff wire accounting inherits."""
    import math as _math

    from ddlbench_tpu.ops.paged_decode import pool_page_bytes

    page_axis = engine._page_axis
    n_pages = int(engine.cfg.pool_pages)
    per_page = 0.0
    per_page_f32 = 0.0
    payload, sidecar = 0.0, 0.0
    checks: List[Dict[str, Any]] = []
    for li, pool in enumerate(engine.pools):
        if pool is None:
            continue
        layer_page = float(pool_page_bytes(pool, page_axis))
        per_page += layer_page
        layer_payload = 0.0
        for key, leaf in sorted(pool.items()):
            nbytes = float(_math.prod(leaf.shape) * leaf.dtype.itemsize)
            if key in ("pool_k", "pool_v"):
                layer_payload += nbytes
                per_page_f32 += (4.0 * _math.prod(leaf.shape)
                                 / leaf.shape[page_axis])
            elif key != "kv_seed":
                sidecar += nbytes
        payload += layer_payload
        checks.append(_check(
            f"layer[{li}]_payload_bytes", layer_page * n_pages,
            layer_payload))
    checks.append(_check("pool_page_bytes", per_page,
                         float(engine.bytes_per_page)))
    checks.append(_check("pool_payload_bytes", per_page * n_pages,
                         payload))
    import jax.numpy as jnp
    if engine.dtype == jnp.int8:
        checks.append(_check("int8_page_is_f32_quarter",
                             per_page_f32 / 4.0, per_page))
    # SDC checksum sidecar (serve/integrity.py): when the ledger is armed
    # the handoff wire ships one CHECKSUM_BYTES word per (pool layer,
    # page) next to payload + scale sidecars — tie this audit's own pool
    # walk against integrity's notion of the checksum domain, the exact
    # per-page constant behind the fleet's shipped_checksum_bytes.
    from ddlbench_tpu.serve.integrity import CHECKSUM_BYTES, pool_layers
    integrity_on = getattr(engine, "integrity", None) is not None
    pooled_layers = sum(1 for pool in engine.pools if pool is not None)
    checksum_page = float(CHECKSUM_BYTES * pooled_layers
                          if integrity_on else 0)
    if integrity_on:
        checks.append(_check(
            "checksum_bytes_per_page",
            float(CHECKSUM_BYTES * len(pool_layers(engine))),
            checksum_page))
    res = {
        "kv_dtype": str(engine.cfg.kv_dtype),
        "tp": int(engine.cfg.tp),
        "page_axis": page_axis,
        "pool_page_bytes": per_page,
        "n_pages": n_pages,
        "payload_bytes": payload,
        "sidecar_bytes": sidecar,
        "integrity": integrity_on,
        "checksum_bytes_per_page": checksum_page,
        "checks": checks,
        "ok": all(c["ok"] for c in checks),
    }
    return res


# ---------------------------------------------------------------------------
# planner HBM audit
# ---------------------------------------------------------------------------


def planner_stage_hbm_audit(candidate_record: Dict[str, Any],
                            manifest: Dict[str, Any],
                            world: int) -> Optional[Dict[str, Any]]:
    """Signed per-stage error of the planner's HBM model vs the compiled
    program's ``memory_analysis()``.

    The measured side is the per-chip live-byte estimate
    ``(argument + output + temp - alias) / world`` — memory_analysis
    aggregates over the executable's devices, and uniform pipelines place
    one stage column per chip, so each stage's prediction is compared
    against the same per-chip measurement (the planner's stage_mem IS a
    per-chip number). Returns None when memory_analysis is unavailable
    or the candidate carries no per-stage predictions.
    """
    mem = manifest.get("memory")
    stage_mem = candidate_record.get("stage_mem")
    if not mem or mem.get("peak_bytes") is None or not stage_mem:
        return None
    chip = mem["peak_bytes"] / max(world, 1)
    stages = []
    for i, pred in enumerate(stage_mem):
        err = float(pred) - chip
        stages.append({
            "stage": i,
            "predicted_bytes": float(pred),
            "measured_chip_bytes": chip,
            "err_bytes": err,
            "err_frac": err / chip if chip else None,
        })
    return {
        "world": world,
        "measured": mem,
        "measured_chip_bytes": chip,
        "predicted_peak_bytes": float(max(stage_mem)),
        "stages": stages,
    }


def audit_train_config(cfg, name: Optional[str] = None
                       ) -> Tuple[Dict[str, Any], Any]:
    """Build ``cfg``'s registry strategy, AOT-lower one train step on a
    synthetic batch (lowering never executes — donation-safe), and return
    ``(manifest, strategy)`` with the comm_stats reconcile attached under
    ``manifest["reconcile"]``."""
    import jax
    import jax.numpy as jnp

    from ddlbench_tpu.data.synthetic import make_synthetic
    from ddlbench_tpu.parallel.api import make_strategy

    strategy = make_strategy(cfg)
    data = make_synthetic(cfg.dataset(), cfg.global_batch(),
                          steps_per_epoch=1)
    ts = strategy.init(jax.random.key(cfg.seed))
    x, y = data.batch(0, 0)
    xs, ys = strategy.shard_batch(x, y)
    lr = jnp.float32(cfg.resolved_lr())
    jit_step = (getattr(strategy, "_jit_train_step", None)
                or strategy.train_step)
    man = lower_manifest(
        jit_step, (ts, xs, ys, lr), name or f"train/{cfg.strategy}",
        mesh=getattr(strategy, "mesh", None))
    man["reconcile"] = reconcile_train(strategy, man)
    return man, strategy


def audit_serve_engine(engine, prefix: str = "serve"
                       ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Manifests for the engine's jitted serve programs plus the KV-pool
    tie-out. The pool audit rides each manifest under ``pool_audit`` and
    is also returned separately."""
    mesh = getattr(engine, "_mesh", None)
    pool = serve_pool_audit(engine)
    mans = []
    for name, fn, args in engine.audit_programs():
        mans.append(lower_manifest(fn, args, f"{prefix}/{name}",
                                   mesh=mesh, extra={"pool_audit": pool}))
    return mans, pool


def record_hbm_audit(cfg, hbm_audit: Dict[str, Any]) -> Optional[str]:
    """Merge an hbm audit under ``plan_auto["hbm_audit"]`` in the run's
    partition.json (the planner-decision idiom — atomic tmp+replace).
    Returns the path written, or None when there is no persisted plan to
    annotate (no checkpoint_dir / no plan_auto record)."""
    from ddlbench_tpu.parallel.api import _plan_path

    path = _plan_path(cfg)
    if path is None or not os.path.exists(path):
        return None
    with open(path) as f:
        doc = json.load(f)
    rec = doc.get("plan_auto")
    if not isinstance(rec, dict):
        return None
    rec["hbm_audit"] = hbm_audit
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=float)
        f.write("\n")
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# manifest IO + regression diff
# ---------------------------------------------------------------------------


def write_manifests(path: str, manifests: List[Dict[str, Any]],
                    header: Optional[Dict[str, Any]] = None) -> None:
    """Atomically write an audit ledger: ``{"audit_schema_version",
    ...header, "programs": [...]}``."""
    doc = {"audit_schema_version": AUDIT_SCHEMA_VERSION,
           **(header or {}), "programs": manifests}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=float)
        f.write("\n")
    os.replace(tmp, path)


def load_manifests(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


# Relative growth above which a metric is flagged. flops/HBM from
# cost/memory analysis are deterministic per jaxlib, but tiny layout
# deltas across versions are not regressions — the gate is for the
# unexplained 2x, not the 0.1% assembler burp.
DIFF_TOLERANCE = 0.01


def diff_manifests(old: Dict[str, Any], new: Dict[str, Any],
                   tolerance: float = DIFF_TOLERANCE) -> Dict[str, Any]:
    """Compare two audit ledgers program-by-program. Growth beyond
    ``tolerance`` in flops / bytes-accessed / peak HBM / total wire bytes
    / per-kind collective counts is a regression; programs present only
    in ``new`` are reported as added (not failures), programs that
    disappeared are flagged."""
    def by_name(doc):
        return {p.get("name"): p for p in doc.get("programs", [])}

    a, b = by_name(old), by_name(new)
    regressions: List[Dict[str, Any]] = []
    report: Dict[str, Any] = {
        "added": sorted(set(b) - set(a)),
        "removed": sorted(set(a) - set(b)),
        "regressions": regressions,
        "compared": sorted(set(a) & set(b)),
    }
    for name in report["compared"]:
        pa, pb = a[name], b[name]
        metrics = [
            ("flops", pa.get("flops"), pb.get("flops")),
            ("bytes_accessed", pa.get("bytes_accessed"),
             pb.get("bytes_accessed")),
            ("peak_bytes", (pa.get("memory") or {}).get("peak_bytes"),
             (pb.get("memory") or {}).get("peak_bytes")),
            ("wire_bytes_total", pa.get("wire_bytes_total"),
             pb.get("wire_bytes_total")),
        ]
        for kind in sorted(set(pa.get("collective_totals", {}))
                           | set(pb.get("collective_totals", {}))):
            ca = pa.get("collective_totals", {}).get(kind, {})
            cb = pb.get("collective_totals", {}).get(kind, {})
            metrics.append((f"collectives[{kind}].count",
                            ca.get("count", 0), cb.get("count", 0)))
            metrics.append((f"collectives[{kind}].wire_bytes",
                            ca.get("wire_bytes", 0.0),
                            cb.get("wire_bytes", 0.0)))
        for metric, va, vb in metrics:
            if va is None or vb is None:
                continue
            if vb > va * (1.0 + tolerance) + 1e-9:
                regressions.append({
                    "program": name, "metric": metric,
                    "old": float(va), "new": float(vb),
                    "growth": (vb / va - 1.0) if va else math.inf,
                })
    if report["removed"]:
        for name in report["removed"]:
            regressions.append({"program": name, "metric": "removed",
                                "old": 1.0, "new": 0.0, "growth": -1.0})
    report["ok"] = not regressions
    return report
