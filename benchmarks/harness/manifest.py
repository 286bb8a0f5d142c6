"""``BENCHMARK.json`` is the index; everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of its
own, found by the name in the index:

    benchmarks/configs/<config>.json        the configuration as it is run;
                                            its ``reference`` key names
    benchmarks/reference/<module>.py        the plain reference and the model
                                            FLOPs: loss_and_grads(P, x, y,
                                            config, rnd),
                                            train_flops_per_sample(config,
                                            sample_shape)
    benchmarks/traffic/<traffic>.json       the mix: kind, program settings,
                                            batch or lengths and clients
    benchmarks/harness/<kind>_driver.py     run(ctx): the window driver of
                                            the mixes of that kind
    benchmarks/metrics/<metric>.json        what the metric is: layer, unit,
                                            moves, reader (+ its arguments)
    benchmarks/metrics/readers/<reader>.py  read(ctx, **args) -> float | None
    benchmarks/metrics/scope_kinds*.json    the scope kinds, in order; a new
                                            kind comes in a file of its own
    benchmarks/kernels/<kernel>.py          work from shapes, trace names;
                                            the call shapes come from the
                                            reference's kernel_calls(kernel,
                                            config, traffic)

WHERE a metric is reported stands in the index alone (the ``workloads`` list
of its entry), so a cell joins a metric by gaining a name there: adding a
configuration with its cell is new files and new entries, and not one byte
changed in a file that was there.

``check`` holds the rules a driver refuses a manifest on, and ``against``
those a later PR that may only add is refused on, so that a refusal is found
here and not on submission.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import re
from typing import Dict, List, Optional

from benchmarks.harness import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|n_embd|n_inner"
                   r"|head_size|d_model|experts_per_tok")


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.index = _read(os.path.join(root, "BENCHMARK.json"))
        # data files sit in the first of ``paths`` unless a test points at
        # a directory of its own
        self.dir = bench_dir or os.path.join(root, self.index["paths"][0])

    def workload(self, name: str) -> Dict:
        for w in self.index["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has "
                       f"{[w['name'] for w in self.index['workloads']]})")

    def config(self, name: str) -> Dict:
        for c in self.index["configs"]:
            if c["name"] == name:
                return _read(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def reference(self, config: Dict):
        """The configuration's plain reference, by the path in its file."""
        return load_module(os.path.join(self.root, config["reference"]))

    def traffic(self, name: str) -> Dict:
        return _read(os.path.join(self.dir, "traffic", f"{name}.json"))

    def metric_file(self, name: str) -> Dict:
        return _read(os.path.join(self.dir, "metrics", f"{name}.json"))

    def reader(self, metric: Dict):
        return load_module(os.path.join(
            self.dir, "metrics", "readers", f"{metric['reader']}.py"))

    def kernel(self, name: str):
        return load_module(os.path.join(self.dir, "kernels", f"{name}.py"))

    def end_to_end_of(self, workload: str) -> List[Dict]:
        return [m for m in self.index["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer_of(self, workload: str) -> List[Dict]:
        e2e = {m["name"] for m in self.end_to_end_of(workload)}
        return [m for m in self.index["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_module(path: str):
    """Import a reader, kernel or reference file by path (a later PR's
    files need no registration anywhere)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(m: Manifest) -> List[str]:
    """Every rule broken, as a sentence; empty when the manifest is sound."""
    ix, err = m.index, []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(ix) != want:
        err.append(f"BENCHMARK.json keys {sorted(ix)} are not {sorted(want)}")
        return err

    def name_ok(what, n):
        if not isinstance(n, str) or not NAME.match(n):
            err.append(f"{what} name {n!r} is not 1-64 of [A-Za-z0-9_.-]")

    def line_ok(what, s):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            err.append(f"{what} is not one line of 1-200 characters: {s!r}")

    def keys_ok(what, d, need, may=()):
        if not set(need) <= set(d) <= set(need) | set(may):
            err.append(f"{what} has keys {sorted(d)}; wants {sorted(need)}"
                       + (f" and may add {sorted(may)}" if may else ""))

    if not 1 <= len(ix["paths"]) <= 16:
        err.append("paths wants 1 to 16 directories")
    for p in ix["paths"]:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") \
                or ".." in p.split("/"):
            err.append(f"path {p!r} is not a relative path inside the repo")
    if not 1 <= len(ix["command"]) <= 32:
        err.append("command wants 1 to 32 words")
    for word in ix["command"]:
        line_ok("command word", word)
        if word.startswith("/") or ".." in word.split("/"):
            err.append(f"command word {word!r} leaves the repo")
        if ("/" in word or os.path.exists(os.path.join(m.root, word))) \
                and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in ix["paths"]):
            err.append(f"command names {word!r}, outside paths")
    if not (isinstance(ix["run_seconds"], int)
            and 1 <= ix["run_seconds"] <= 51):
        err.append("run_seconds is not a whole number from 1 to 51")

    # configurations
    if not 1 <= len(ix["configs"]) <= 24:
        err.append("configs wants 1 to 24 entries")
    cfg_names, files = set(), set()
    for c in ix["configs"]:
        keys_ok(f"config {c.get('name')}", c,
                ("name", "source", "file", "reduced", "why"))
        name_ok("config", c.get("name"))
        line_ok(f"config {c.get('name')} source", c.get("source"))
        line_ok(f"config {c.get('name')} why", c.get("why"))
        if c.get("name") in cfg_names:
            err.append(f"two configurations named {c['name']!r}")
        cfg_names.add(c.get("name"))
        f = c.get("file", "")
        if f in files:
            err.append(f"two configurations share the file {f!r}")
        files.add(f)
        if not any(f.startswith(p.rstrip("/") + "/") for p in ix["paths"]):
            err.append(f"config file {f!r} is outside paths")
        elif not os.path.isfile(os.path.join(m.root, f)):
            err.append(f"config file {f!r} does not exist")
        else:
            ref = _read(os.path.join(m.root, f)).get("reference", "")
            if not any(ref.startswith(p.rstrip("/") + "/")
                       for p in ix["paths"]) \
                    or not os.path.isfile(os.path.join(m.root, ref)):
                err.append(f"config {c.get('name')}: its file's reference "
                           f"{ref!r} is no file under paths")
        red = c.get("reduced", [])
        if len(red) > 16:
            err.append(f"config {c.get('name')}: more than 16 reduced keys")
        for k in red:
            name_ok(f"config {c.get('name')} reduced key", k)
            if WIDTH.search(k):
                err.append(f"config {c.get('name')}: reduced names the "
                           f"width {k!r}")

    # cells
    cells = ix["workloads"]
    if not 1 <= len(cells) <= 24:
        err.append("workloads wants 1 to 24 cells")
    cell_names, pairs = set(), set()
    for w in cells:
        keys_ok(f"workload {w.get('name')}", w,
                ("name", "config", "traffic", "chips", "why"))
        for k in ("name", "config", "traffic"):
            name_ok(f"workload {k}", w.get(k))
        line_ok(f"workload {w.get('name')} why", w.get("why"))
        if w.get("name") in cell_names:
            err.append(f"two workloads named {w['name']!r}")
        cell_names.add(w.get("name"))
        if (w.get("config"), w.get("traffic")) in pairs:
            err.append(f"the pair ({w['config']}, {w['traffic']}) twice")
        pairs.add((w.get("config"), w.get("traffic")))
        if w.get("config") not in cfg_names:
            err.append(f"workload {w.get('name')}: unknown configuration "
                       f"{w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            err.append(f"workload {w.get('name')}: chips is not 1 or 4")
        tf = os.path.join(m.dir, "traffic", f"{w.get('traffic')}.json")
        if not os.path.isfile(tf):
            err.append(f"workload {w.get('name')}: no traffic file {tf}")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        err.append(f"{four} four-chip cells of {len(cells)}: at most "
                   f"{max(1, len(cells) // 4)} may ask for 4 chips")
    for c in cfg_names - {w.get("config") for w in cells}:
        err.append(f"configuration {c!r} has no cell")

    # metrics
    e2e = ix["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        err.append("end_to_end wants 1 to 16 metrics")
    names = set()
    for mt in e2e:
        keys_ok(f"end_to_end {mt.get('name')}", mt,
                ("name", "unit", "better", "bound", "source"), ("workloads",))
        if mt.get("source") not in ("host_clock", "device_trace"):
            err.append(f"end_to_end {mt.get('name')}: source must be "
                       f"host_clock or device_trace")
        b = mt.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            err.append(f"end_to_end {mt.get('name')}: bound {b!r} is not in "
                       f"[0.01, 0.1]")
    if "setup_s" not in {mt.get("name") for mt in e2e}:
        err.append("end_to_end has no setup_s")
    if len(e2e) - 1 > 4:
        err.append("more than four end-to-end metrics besides setup_s")
    pl = ix["per_layer"]
    if not 1 <= len(pl) <= 128:
        err.append("per_layer wants 1 to 128 metrics")
    for mt in pl:
        keys_ok(f"per_layer {mt.get('name')}", mt,
                ("name", "unit", "better", "source", "layer", "moves"),
                ("workloads",))
        line_ok(f"per_layer {mt.get('name')} layer", mt.get("layer"))
        if "workloads" not in mt:
            err.append(f"per_layer {mt.get('name')} lists no workloads")
    for mt in e2e + pl:
        name_ok("metric", mt.get("name"))
        if mt.get("name") in names:
            err.append(f"two metrics named {mt['name']!r}")
        names.add(mt.get("name"))
        if not isinstance(mt.get("unit"), str) or not UNIT.match(mt["unit"]):
            err.append(f"metric {mt.get('name')}: unit {mt.get('unit')!r}")
        if mt.get("better") not in ("lower", "higher"):
            err.append(f"metric {mt.get('name')}: better is not lower|higher")
        if mt.get("source") not in SOURCES:
            err.append(f"metric {mt.get('name')}: source {mt.get('source')!r}")
        for w in mt.get("workloads", []):
            if w not in cell_names:
                err.append(f"metric {mt.get('name')} lists the unknown "
                           f"workload {w!r}")
        if re.search(r"_roofline$", mt.get("name", "")) \
                and mt.get("unit") != "%":
            err.append(f"metric {mt['name']}: a roofline share's unit is %")

    # the arrows: a per-layer metric is reported only where the end-to-end
    # metric it moves is reported (the rule PR 22 was refused on)
    reports = {w: {x["name"] for x in m.end_to_end_of(w)} for w in cell_names}
    kinds = {}
    for w in cells:
        try:
            kinds[w["name"]] = m.traffic(w["traffic"]).get("kind")
        except (OSError, ValueError):
            pass
    for mt in pl:
        if mt.get("moves") not in {x.get("name") for x in e2e}:
            err.append(f"per_layer {mt.get('name')} moves "
                       f"{mt.get('moves')!r}, which is no end-to-end metric")
            continue
        for w in mt.get("workloads", []):
            if w in reports and mt["moves"] not in reports[w]:
                err.append(
                    f"per_layer metric {mt['name']} is reported on workload "
                    f"{w}, where {mt['moves']}, which it should move, is not")
        ks = {kinds.get(w) for w in mt.get("workloads", [])} - {None}
        if len(ks) > 1:
            err.append(f"per_layer metric {mt['name']} is listed on cells "
                       f"of kinds {sorted(ks)}: split it by suffix")
    for w in cell_names:
        rep = reports[w]
        if "setup_s" not in rep or len(rep) < 2:
            err.append(f"workload {w} reports {sorted(rep)}: wants setup_s "
                       f"and at least one other end-to-end metric")
        if not m.per_layer_of(w):
            err.append(f"workload {w} reports no per-layer metric")

    # the index agrees with the files it points at
    vocabulary = scopes.vocabulary(os.path.join(m.dir, "metrics"))
    for mt in pl:
        try:
            f = m.metric_file(mt["name"])
        except (OSError, ValueError) as e:
            err.append(f"per_layer {mt.get('name')}: no metric file ({e})")
            continue
        for k in ("unit", "better", "source", "layer", "moves"):
            if f.get(k) != mt.get(k):
                err.append(f"per_layer {mt['name']}: {k} is {mt.get(k)!r} in "
                           f"BENCHMARK.json and {f.get(k)!r} in its file")
        if not os.path.isfile(os.path.join(m.dir, "metrics", "readers",
                                           f"{f.get('reader')}.py")):
            err.append(f"per_layer {mt['name']}: no reader "
                       f"{f.get('reader')!r}")
        if "workloads" in f:
            err.append(f"per_layer {mt['name']}: its file lists workloads; "
                       f"the index alone says where a metric is reported")
        for kind in f.get("args", {}).get("kinds", ()):
            if kind not in vocabulary:
                err.append(f"per_layer {mt['name']}: its file reads the kind "
                           f"{kind!r}, which no scope_kinds*.json lists")
    if len(json.dumps(ix)) > 64 * 1024:
        err.append("BENCHMARK.json is over 64 KiB")
    return err


def tree_hashes(root: str, paths) -> Dict[str, str]:
    """``{path relative to root: sha256}`` of every file under ``paths``
    that git would keep (no ``__pycache__``, no ``.pyc``)."""
    out = {}
    for p in paths:
        for d, dirs, names in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for n in names:
                if n.endswith(".pyc"):
                    continue
                full = os.path.join(d, n)
                with open(full, "rb") as f:
                    out[os.path.relpath(full, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def against(m: Manifest, parent_root: str) -> List[str]:
    """What a PR that may only ADD to the benchmark is refused on, as
    sentences: every file under the parent's ``paths`` that this tree
    changed or deleted, and every entry of the parent's ``BENCHMARK.json``
    that changed otherwise than by gaining names in ``workloads``."""
    parent = _read(os.path.join(parent_root, "BENCHMARK.json"))
    err = []
    was = tree_hashes(parent_root, parent["paths"])
    now = tree_hashes(m.root, parent["paths"])
    for path in sorted(was):
        if path not in now:
            err.append(f"{path} is in the parent and was deleted")
        elif now[path] != was[path]:
            err.append(f"{path} is in the parent and differs")
    for key in ("command", "paths", "run_seconds"):
        if m.index.get(key) != parent[key]:
            err.append(f"BENCHMARK.json {key} changed from {parent[key]!r} "
                       f"to {m.index.get(key)!r}")
    rest = lambda e: {k: v for k, v in e.items() if k != "workloads"}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        mine = {e.get("name"): e for e in m.index.get(key, [])}
        for old in parent[key]:
            new = mine.get(old["name"])
            if new is None:
                err.append(f"BENCHMARK.json {key} entry {old['name']!r} of "
                           f"the parent was removed")
                continue
            lost = set(old.get("workloads", [])) - set(new.get("workloads",
                                                               []))
            if rest(old) != rest(new) or lost \
                    or ("workloads" in old) != ("workloads" in new):
                err.append(f"BENCHMARK.json {key} entry {old['name']!r} "
                           f"changed otherwise than by gaining a name in "
                           f"workloads: {old} -> {new}")
    return err


def check_printed(m: Manifest, workload: str, trace: int,
                  printed: Dict) -> List[str]:
    """A run's ``metrics`` keys against what the cell declares for that
    ``--trace`` value. End-to-end: exactly the declared set. Per-layer: no
    undeclared metric (a reader that found nothing leaves its metric out)."""
    if trace:
        declared = {x["name"] for x in m.per_layer_of(workload)}
        extra = set(printed) - declared
        return [f"{workload}: printed undeclared per-layer metrics "
                f"{sorted(extra)}"] if extra else []
    declared = {x["name"] for x in m.end_to_end_of(workload)}
    if set(printed) != declared:
        return [f"{workload}: printed {sorted(printed)}, declared "
                f"{sorted(declared)}"]
    return []
