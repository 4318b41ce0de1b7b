"""Everything the harness knows about a cell comes from files it finds by name.

`BENCHMARK.json` (at `root`) lists configurations, cells and metrics.  For the
cell `--workload` names, the files are

  benchmark/workloads/<cell>.json    kind (serve|train), engine or job sizes
  benchmark/configs/<config>.json    the model's sizes as they are run
  benchmark/traffic/<traffic>.json   parameters for benchmark/loadgen.py
  benchmark/metrics/<metric>.json    {"reader": <kind>, ...parameters}
  benchmark/readers/<kind>.py        read(spec, obs) -> number or None
  benchmark/peaks.json               chip peaks by device_kind

A later PR adds a cell, a configuration, a traffic mix or a metric as new
files plus one `BENCHMARK.json` entry; nothing here names any of them.
"""
from __future__ import annotations

import importlib
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: what `reduced` may never name (the contract's widths)
WIDTH_RE = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"head_size|expansion|experts_per_tok", re.I)


class ManifestError(ValueError):
    pass


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None


def load_manifest(root):
    return _load(os.path.join(root, "BENCHMARK.json"))


def check_manifest(m, root=None):
    """The contract's rules on names, units and cross references; raises
    ManifestError naming the first breach.  With `root`, also that every
    file a cell needs is there."""
    def name(x, what):
        if not isinstance(x, str) or not NAME_RE.match(x):
            raise ManifestError(f"{what} {x!r} is not a name")

    def line(x, what):
        if not isinstance(x, str) or not 1 <= len(x) <= 200 \
                or "\n" in x or "\t" in x:
            raise ManifestError(f"{what} must be 1..200 characters on a line")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(m) != want:
        raise ManifestError(f"keys {sorted(set(m) ^ want)} missing or extra")
    if not isinstance(m["run_seconds"], int) or not 1 <= m["run_seconds"] <= 51:
        raise ManifestError("run_seconds must be a whole number in 1..51")
    for w in m["command"]:
        line(w, "a word of command")
    configs = {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config {c.get('name')!r}: wrong keys")
        name(c["name"], "config")
        line(c["source"], "source")
        line(c["why"], "why")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in m["paths"]):
            raise ManifestError(f"{c['file']} is not under paths")
        for k in c["reduced"]:
            name(k, "reduced key")
            if WIDTH_RE.search(k):
                raise ManifestError(f"config {c['name']}: reduced names the width {k}")
        if c["name"] in configs:
            raise ManifestError(f"config {c['name']} twice")
        configs[c["name"]] = c
    cells, pairs = {}, set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"cell {w.get('name')!r}: wrong keys")
        name(w["name"], "cell")
        name(w["traffic"], "traffic")
        line(w["why"], "why")
        if w["config"] not in configs:
            raise ManifestError(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips must be 1 or 4")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"cell {w['name']} or its pair appears twice")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
    unused = set(configs) - {w["config"] for w in m["workloads"]}
    if unused:
        raise ManifestError(f"configs used by no cell: {sorted(unused)}")
    if sum(w["chips"] == 4 for w in cells.values()) > max(1, len(cells) // 4):
        raise ManifestError("too many four-chip cells")
    seen, e2e = set(), {}
    for kind, keys in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                       ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for x in m[kind]:
            if set(x) - {"workloads"} != keys:
                raise ManifestError(f"metric {x.get('name')!r}: wrong keys")
            name(x["name"], "metric")
            if not UNIT_RE.match(x["unit"]):
                raise ManifestError(f"metric {x['name']}: unit {x['unit']!r}")
            if x["better"] not in ("lower", "higher") or x["source"] not in SOURCES:
                raise ManifestError(f"metric {x['name']}: better or source")
            if x["name"] in seen:
                raise ManifestError(f"metric {x['name']} twice")
            seen.add(x["name"])
            for c in x.get("workloads", ()):
                if c not in cells:
                    raise ManifestError(f"metric {x['name']}: unknown cell {c}")
            if kind == "end_to_end":
                if x["source"] not in ("host_clock", "device_trace"):
                    raise ManifestError(f"{x['name']}: end-to-end source")
                if not 0.01 <= x["bound"] <= 0.1:
                    raise ManifestError(f"{x['name']}: bound outside 1%..10%")
                e2e[x["name"]] = x
            else:
                line(x["layer"], "layer")
                if x["moves"] not in e2e:
                    raise ManifestError(f"{x['name']} moves unknown {x['moves']}")
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        raise ManifestError("setup_s must be reported by every cell")
    for c in cells:
        mine = [x for x in m["end_to_end"] if c in x.get("workloads", cells)]
        if len(mine) < 2:
            raise ManifestError(f"cell {c} reports no end-to-end metric besides setup_s")
        layer = [x for x in m["per_layer"] if c in x.get("workloads", cells)]
        if not layer:
            raise ManifestError(f"cell {c} reports no per-layer metric")
        for x in layer:
            if x["moves"] not in {y["name"] for y in mine}:
                raise ManifestError(
                    f"{x['name']} moves {x['moves']}, which cell {c} does not report")
    if root is not None:
        for c in cells:
            load_cell(root, c, manifest=m)


def load_cell(root, cell_name, manifest=None):
    """All the data of one cell: {"cell", "job", "config", "traffic",
    "end_to_end": [...], "per_layer": [(entry, spec)], "peaks", "run_seconds"}."""
    m = manifest or load_manifest(root)
    cell = next((w for w in m["workloads"] if w["name"] == cell_name), None)
    if cell is None:
        raise ManifestError(
            f"no cell {cell_name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in m['workloads']]}")
    base = os.path.join(root, "benchmark")
    cfg_entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    mine = lambda xs: [x for x in xs  # noqa: E731
                       if cell_name in x.get("workloads", [cell_name])]
    per_layer = []
    for x in mine(m["per_layer"]):
        spec = _load(os.path.join(base, "metrics", x["name"] + ".json"))
        per_layer.append((x, spec))
    return {
        "cell": cell,
        "job": _load(os.path.join(base, "workloads", cell_name + ".json")),
        "config": _load(os.path.join(root, cfg_entry["file"])),
        "traffic": _load(os.path.join(base, "traffic", cell["traffic"] + ".json")),
        "end_to_end": mine(m["end_to_end"]),
        "per_layer": per_layer,
        "peaks": _load(os.path.join(base, "peaks.json")),
        "run_seconds": m["run_seconds"],
    }


def peaks_for(peaks, device_kind):
    """The chip's peaks; a kind the table lacks is an error, not a default."""
    for row in peaks["chips"]:
        if device_kind in row["device_kinds"]:
            return row
    raise ManifestError(
        f"benchmark/peaks.json has no device_kind {device_kind!r}; it knows "
        f"{[k for r in peaks['chips'] for k in r['device_kinds']]}")


def reader(kind):
    """benchmark/readers/<kind>.py, found by name."""
    if not NAME_RE.match(kind):
        raise ManifestError(f"reader {kind!r} is not a name")
    return importlib.import_module(f"benchmark.readers.{kind}").read


def read_metrics(entries, obs):
    """{name: {"value", "unit"}} for every reader that found something."""
    out = {}
    for entry, spec in entries:
        v = reader(spec["reader"])(spec, obs)
        if v is not None:
            out[entry["name"]] = {"value": float(v), "unit": entry["unit"]}
    return out
