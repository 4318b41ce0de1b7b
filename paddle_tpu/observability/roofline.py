"""Roofline residual plane (ISSUE 17): per-HLO measured-vs-predicted
attribution plus the perf-regression sentinel.

The profiling plane (``observability.xplane``) can name, per HLO, where
device time *goes*; the cost side (``census.per_op_census`` +
``cost_model.peak_flops_per_device`` / ``peak_hbm_bytes_per_sec``)
predicts where it *should* go.  This module joins them:

- **Prediction** is the min-time roofline: an op that moves ``bytes``
  and computes ``flops`` can never finish faster than
  ``max(flops / peak_flops, bytes / peak_bw)``.  Whichever term wins
  classifies the op ``compute``- or ``memory``-bound (ops with neither
  flops nor bytes — or no peaks to divide by — stay ``unknown``: an
  unpredicted op is a finding, not a zero).
- **Residual** is ``measured_us / predicted_us`` — 1.0 means the op runs
  at the roofline; 4.0 means 4x headroom.  ``wasted_us = measured -
  predicted`` ranks the table: the top row is the single best thing to
  optimize next (ROADMAP open item 5's "optimization shopping list").
- **Rounds** persist as ``ROOFLINE_<round>.json`` — content-addressed
  like the BENCH configs: ``key = sha256(hardware fingerprint + config
  hash + schema_version)``, so two rounds are comparable iff their keys
  match.
- **Sentinel**: :func:`diff_reports` compares two rounds per op under a
  relative residual-growth threshold with an absolute wasted-µs floor
  (noise on a 3 µs op must not page anyone); ``tools/roofline_report.py
  --diff`` exits non-zero iff an op regressed — the cron/CI perf gate.

The same numbers reach the live stack through the registry:
``roofline_residual_ratio{op}`` / ``roofline_bound_fraction{bound}``
gauges (on ``/metrics``, ``/varz``) and ``roofline_regressions_total``,
which the ``roofline_regression`` default delta alert rule watches.

Stdlib-only at module scope (same contract as ``xplane`` / ``metrics``);
jax is imported lazily inside :func:`hardware_fingerprint` only.
"""
from __future__ import annotations

import hashlib
import json
import os

from . import metrics as _metrics

__all__ = [
    "SCHEMA_VERSION", "DEFAULT_THRESHOLD", "DEFAULT_MIN_US",
    "match_name", "census_table", "predict_op", "residual_rows",
    "annotate_rows", "build_report", "merge_reports",
    "hardware_fingerprint", "config_hash", "round_key",
    "save_round", "load_round", "round_path", "newest_round",
    "diff_reports", "export_gauges", "record_diff",
    "render_text", "render_diff_text",
]

#: Version of the ROOFLINE_<round>.json document.  Bump on any row/summary
#: schema change — the sentinel refuses to diff across versions.
SCHEMA_VERSION = 1

#: Default sentinel thresholds: an op regresses when its residual ratio
#: grew by more than THRESHOLD (relative) AND its wasted time grew by
#: more than MIN_US (absolute) — the µs floor keeps sub-noise ops from
#: paging anyone, the relative term keeps a 10 ms op's 5% drift quiet.
DEFAULT_THRESHOLD = 0.25
DEFAULT_MIN_US = 50.0

_M_RESIDUAL = _metrics.gauge(
    "roofline_residual_ratio",
    "measured_us / roofline-predicted_us of the op, from the last "
    "exported residual round (top-K ops by wasted time)",
    labelnames=("op",))
_M_BOUND = _metrics.gauge(
    "roofline_bound_fraction",
    "share of measured device time in compute-bound / memory-bound / "
    "unknown (no cost-model match) ops, from the last exported round",
    labelnames=("bound",))
_M_ROUNDS = _metrics.counter(
    "roofline_rounds_total", "residual reports built (build_report calls)")
_M_REGRESSIONS = _metrics.counter(
    "roofline_regressions_total",
    "ops flagged regressed by sentinel diffs (record_diff) — feeds the "
    "roofline_regression default delta alert rule")


# -------------------------------------------------------------- name match
def match_name(event_name, census):
    """The trace_report join rule, factored here so the CLI and the
    roofline plane can never diverge: exact name first, then the trailing
    path component (trace names prefix ops with the program path —
    ``jit_step/dot.12``), then LONGEST containment either way (census row
    ``dot.12`` beats ``dot`` / ``dot.1`` for event ``.../dot.12``).
    ``census`` is any container of names; returns the matched census name
    or None."""
    if event_name in census:
        return event_name
    tail = event_name.rsplit("/", 1)[-1]
    if tail in census:
        return tail
    best = None
    for cname in census:
        if (cname in event_name or event_name in cname) \
                and (best is None or len(cname) > len(best)):
            best = cname
    return best


def census_table(rows):
    """``census.per_op_census()`` rows -> ``name -> {opcode, flops,
    bytes}`` (bytes = in + out: the roofline's memory term is total HBM
    traffic).  A mapping passes through with the same normalization."""
    out = {}
    if isinstance(rows, dict):
        items = [dict(v, name=k) for k, v in rows.items()]
    else:
        items = rows
    for row in items:
        name = str(row.get("name", "?"))
        prev = out.setdefault(name, {"opcode": str(row.get("opcode", "")),
                                     "flops": 0.0, "bytes": 0.0})
        prev["flops"] += float(row.get("flops", 0) or 0)
        # per_op_census rows carry all three, `bytes` being the sum
        prev["bytes"] += float(row["bytes"] or 0) if "bytes" in row \
            else float(row.get("bytes_in", 0) or 0) \
            + float(row.get("bytes_out", 0) or 0)
    return out


# -------------------------------------------------------------- prediction
def predict_op(flops, bytes_, peak_flops, peak_bw):
    """Min-time roofline of one op -> ``(predicted_us, bound)``.

    ``predicted_us = max(flops/peak_flops, bytes/peak_bw) * 1e6``; the
    winning term names the bound.  A term with no numerator OR no peak
    contributes 0 — an op with neither is ``("unknown", 0.0)``, never a
    division by zero (the zero-predicted guard the residual math relies
    on)."""
    t_flops = flops / peak_flops if flops > 0 and peak_flops > 0 else 0.0
    t_bytes = bytes_ / peak_bw if bytes_ > 0 and peak_bw > 0 else 0.0
    if t_flops <= 0 and t_bytes <= 0:
        return 0.0, "unknown"
    if t_flops >= t_bytes:
        return t_flops * 1e6, "compute"
    return t_bytes * 1e6, "memory"


def residual_rows(measured, census, peak_flops, peak_bw):
    """Join measured per-op timings against the census cost table into
    the residual table, sorted by wasted µs desc.

    ``measured`` is the ``xplane.per_op_summary`` /
    ``trace_report.load_timeline`` shape (``name -> {count, total_us}``);
    ``census`` is :func:`census_table` output (or per_op_census rows,
    normalized here).  Rows keep deterministic rounding so a report is
    byte-stable for the golden tests and the content-addressed key."""
    census = census_table(census) if not _is_table(census) else census
    rows = []
    used = set()
    for name, t in measured.items():
        cname = match_name(name, census)
        c = census.get(cname) if cname else None
        if cname:
            used.add(cname)
        rows.append(_one_row(name, int(t.get("count", 0)),
                             float(t.get("total_us", 0.0)), c,
                             peak_flops, peak_bw))
    for cname, c in census.items():
        if cname in used:
            continue
        # a census op that never showed up on the device: predicted time
        # with zero measured — attribution MISSING is a finding.  Flagged
        # matched=False like trace_report.join: "matched" means JOINED,
        # not merely costed.
        row = _one_row(cname, 0, 0.0, c, peak_flops, peak_bw)
        row["matched"] = False
        rows.append(row)
    rows.sort(key=lambda r: (-r["wasted_us"], -r["measured_us"],
                             r["name"]))
    return rows


def _is_table(census):
    return isinstance(census, dict) and all(
        isinstance(v, dict) and "bytes" in v for v in census.values()) \
        and census  # empty dict normalizes through census_table harmlessly


def _one_row(name, count, measured_us, c, peak_flops, peak_bw):
    flops = float((c or {}).get("flops", 0.0))
    bytes_ = float((c or {}).get("bytes", 0.0))
    predicted_us, bound = predict_op(flops, bytes_, peak_flops, peak_bw)
    secs = measured_us / 1e6
    ratio = round(measured_us / predicted_us, 4) if predicted_us > 0 \
        and measured_us > 0 else None
    return {
        "name": name,
        "count": count,
        "measured_us": round(measured_us, 3),
        "predicted_us": round(predicted_us, 3),
        "residual_ratio": ratio,
        "wasted_us": round(max(0.0, measured_us - predicted_us), 3)
        if predicted_us > 0 and measured_us > 0 else 0.0,
        "bound": bound,
        "opcode": (c or {}).get("opcode", ""),
        "flops": flops,
        "bytes": bytes_,
        "achieved_flops_per_sec": round(flops / secs, 1)
        if flops > 0 and secs > 0 else 0.0,
        "achieved_bytes_per_sec": round(bytes_ / secs, 1)
        if bytes_ > 0 and secs > 0 else 0.0,
        "matched": c is not None,
    }


def annotate_rows(rows, peak_flops, peak_bw):
    """Residual-annotate ``trace_report.join()`` rows in place (adds
    predicted_us / residual_ratio / wasted_us / bound from each row's own
    flops/bytes) — the ``trace_report --roofline`` path, where the rows
    already exist and only the prediction is missing."""
    for r in rows:
        predicted_us, bound = predict_op(float(r.get("flops", 0.0)),
                                         float(r.get("bytes", 0.0)),
                                         peak_flops, peak_bw)
        measured_us = float(r.get("total_us", 0.0))
        r["predicted_us"] = round(predicted_us, 3)
        r["bound"] = bound
        r["residual_ratio"] = round(measured_us / predicted_us, 4) \
            if predicted_us > 0 and measured_us > 0 else None
        r["wasted_us"] = round(max(0.0, measured_us - predicted_us), 3) \
            if predicted_us > 0 and measured_us > 0 else 0.0
    return rows


# ----------------------------------------------------------------- reports
def hardware_fingerprint(peak_flops=0.0, peak_bw=0.0):
    """The comparability identity of a round: backend platform, device
    kind and count, plus the peaks the predictions were divided by (two
    rounds predicted against different peaks are NOT comparable, even on
    the same chip).  jax is imported lazily and its absence tolerated —
    the sentinel must run where only stdlib exists."""
    platform, kind, count = "unknown", "unknown", 0
    try:
        import jax
        devs = jax.devices()
        platform = jax.default_backend()
        kind = devs[0].device_kind if devs else "unknown"
        count = len(devs)
    except Exception:
        pass
    return {"platform": str(platform), "device_kind": str(kind),
            "device_count": int(count),
            "peak_flops_per_sec": float(peak_flops),
            "peak_hbm_bytes_per_sec": float(peak_bw)}


def config_hash(config):
    """sha256 of the canonical-JSON config dict, 12 hex chars."""
    blob = json.dumps(config or {}, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def round_key(hardware, cfg_hash):
    """Content address of a round: hardware fingerprint + config hash +
    schema version, 16 hex chars.  Equal keys = comparable rounds."""
    blob = json.dumps({"hardware": hardware, "config_hash": cfg_hash,
                       "schema_version": SCHEMA_VERSION},
                      sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def build_report(measured, census, peak_flops, peak_bw, config=None,
                 hardware=None, top_k=None):
    """Residual table + summary + content address, the
    ``ROOFLINE_<round>.json`` document body.

    ``hardware`` overrides the fingerprint (tests pin it for byte-exact
    goldens); ``top_k`` truncates the persisted rows (the summary always
    covers ALL rows, so truncation can't hide total waste)."""
    rows = residual_rows(measured, census, peak_flops, peak_bw)
    total_meas = sum(r["measured_us"] for r in rows)
    total_pred = sum(r["predicted_us"] for r in rows if r["measured_us"] > 0)
    bound_us = {"compute": 0.0, "memory": 0.0, "unknown": 0.0}
    for r in rows:
        bound_us[r["bound"]] += r["measured_us"]
    hw = hardware if hardware is not None \
        else hardware_fingerprint(peak_flops, peak_bw)
    cfg_hash = config_hash(config)
    report = {
        "schema_version": SCHEMA_VERSION,
        "hardware": hw,
        "config": config or {},
        "config_hash": cfg_hash,
        "key": round_key(hw, cfg_hash),
        "summary": {
            "ops": len(rows),
            "matched_ops": sum(1 for r in rows if r["matched"]),
            "timed_matched_ops": sum(1 for r in rows
                                     if r["matched"]
                                     and r["measured_us"] > 0),
            "measured_us": round(total_meas, 3),
            "predicted_us": round(total_pred, 3),
            "wasted_us": round(sum(r["wasted_us"] for r in rows), 3),
            "residual_ratio": round(total_meas / total_pred, 4)
            if total_pred > 0 else None,
            "bound_fraction": {
                b: round(us / total_meas, 4) if total_meas > 0 else 0.0
                for b, us in sorted(bound_us.items())},
        },
        "rows": rows[:int(top_k)] if top_k else rows,
    }
    _M_ROUNDS.inc()
    return report


def merge_reports(reports):
    """Fold per-config reports into ONE round document: rows namespaced
    ``<config>/<op>`` so the sentinel diffs each config's ops separately,
    summaries summed, the merged config hash chaining every member's.
    ``reports`` is an ordered ``{config_name: report}`` mapping; all
    members must share a hardware fingerprint (they ran in one
    process)."""
    if not reports:
        raise ValueError("merge_reports needs at least one report")
    names = sorted(reports)
    first = reports[names[0]]
    hw = first["hardware"]
    rows = []
    bound_us = {"compute": 0.0, "memory": 0.0, "unknown": 0.0}
    total_meas = total_pred = total_waste = 0.0
    config = {}
    for name in names:
        rep = reports[name]
        if rep["hardware"] != hw:
            raise ValueError(
                f"config {name!r} ran on different hardware than "
                f"{names[0]!r} — merged rounds must share a fingerprint")
        config[name] = rep["config"]
        s = rep["summary"]
        total_meas += s["measured_us"]
        total_pred += s["predicted_us"]
        total_waste += s["wasted_us"]
        for b, frac in s["bound_fraction"].items():
            bound_us[b] += frac * s["measured_us"]
        for r in rep["rows"]:
            rows.append(dict(r, name=f"{name}/{r['name']}"))
    rows.sort(key=lambda r: (-r["wasted_us"], -r["measured_us"],
                             r["name"]))
    cfg_hash = config_hash(config)
    return {
        "schema_version": SCHEMA_VERSION,
        "hardware": hw,
        "config": config,
        "config_hash": cfg_hash,
        "key": round_key(hw, cfg_hash),
        "summary": {
            "ops": len(rows),
            "matched_ops": sum(1 for r in rows if r["matched"]),
            "timed_matched_ops": sum(1 for r in rows
                                     if r["matched"]
                                     and r["measured_us"] > 0),
            "measured_us": round(total_meas, 3),
            "predicted_us": round(total_pred, 3),
            "wasted_us": round(total_waste, 3),
            "residual_ratio": round(total_meas / total_pred, 4)
            if total_pred > 0 else None,
            "bound_fraction": {
                b: round(us / total_meas, 4) if total_meas > 0 else 0.0
                for b, us in sorted(bound_us.items())},
        },
        "rows": rows,
    }


# ------------------------------------------------------------- persistence
def round_path(root, round_name):
    return os.path.join(root, f"ROOFLINE_{round_name}.json")


def save_round(report, root, round_name):
    """Persist as ``ROOFLINE_<round>.json`` (sorted keys, stable indent:
    the document is content-addressed, so serialization must be
    deterministic).  Returns the path."""
    path = round_path(root, round_name)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_round(path):
    """Load + schema-gate one round.  A version mismatch raises — the
    sentinel must never silently compare documents whose row semantics
    differ."""
    with open(path) as f:
        doc = json.load(f)
    ver = doc.get("schema_version")
    if ver != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: schema_version {ver!r} != supported "
            f"{SCHEMA_VERSION} — regenerate the round with this tree's "
            f"tools/roofline_report.py")
    return doc


def newest_round(root, exclude=None):
    """Path of the lexically-newest committed ``ROOFLINE_*.json`` under
    ``root`` (the docs_lint / BENCH 'newest = last glob match' idiom), or
    None.  ``exclude`` drops one path (diffing the newest round against
    the baseline must not pick itself)."""
    import glob
    paths = sorted(glob.glob(os.path.join(root, "ROOFLINE_*.json")))
    if exclude is not None:
        ex = os.path.abspath(exclude)
        paths = [p for p in paths if os.path.abspath(p) != ex]
    return paths[-1] if paths else None


# ---------------------------------------------------------------- sentinel
def diff_reports(old, new, threshold=DEFAULT_THRESHOLD,
                 min_us=DEFAULT_MIN_US):
    """Per-op regression verdict between two rounds.

    An op REGRESSES when, between ``old`` and ``new``:
    ``new_ratio > old_ratio * (1 + threshold)`` AND
    ``new_wasted - old_wasted > min_us`` — both the relative and the
    absolute test must trip (see DEFAULT_* notes).  Ops only one side
    knows are reported informationally (``new_ops`` / ``gone_ops``),
    never as regressions: a renamed HLO must not page anyone, the
    ``comparable`` flag (key equality) is the signal that the join is
    trustworthy.  Pure — counters move in :func:`record_diff`."""
    old_rows = {r["name"]: r for r in old.get("rows", [])}
    new_rows = {r["name"]: r for r in new.get("rows", [])}
    regressions, improvements = [], []
    for name, nr in new_rows.items():
        orow = old_rows.get(name)
        if orow is None:
            continue
        o_ratio, n_ratio = orow.get("residual_ratio"), \
            nr.get("residual_ratio")
        if o_ratio is None or n_ratio is None:
            continue
        delta_wasted = nr["wasted_us"] - orow["wasted_us"]
        entry = {"name": name, "old_ratio": o_ratio, "new_ratio": n_ratio,
                 "old_wasted_us": orow["wasted_us"],
                 "new_wasted_us": nr["wasted_us"],
                 "delta_wasted_us": round(delta_wasted, 3),
                 "bound": nr["bound"]}
        if n_ratio > o_ratio * (1.0 + threshold) and delta_wasted > min_us:
            regressions.append(entry)
        elif o_ratio > n_ratio * (1.0 + threshold) \
                and -delta_wasted > min_us:
            improvements.append(entry)
    regressions.sort(key=lambda e: -e["delta_wasted_us"])
    improvements.sort(key=lambda e: e["delta_wasted_us"])
    return {
        "threshold": float(threshold),
        "min_us": float(min_us),
        "comparable": old.get("key") == new.get("key"),
        "old_key": old.get("key"),
        "new_key": new.get("key"),
        "regressions": regressions,
        "improvements": improvements,
        "new_ops": sorted(set(new_rows) - set(old_rows)),
        "gone_ops": sorted(set(old_rows) - set(new_rows)),
    }


def record_diff(diff):
    """Land a sentinel verdict on the registry:
    ``roofline_regressions_total`` += the regression count (the
    ``roofline_regression`` default delta rule fires on any increase).
    Returns the count so callers can exit on it."""
    n = len(diff.get("regressions", ()))
    if n:
        _M_REGRESSIONS.inc(n)
    return n


def export_gauges(report, top_k=16):
    """Put a report's numbers on the live registry — the same table
    ``/metrics`` and ``/varz`` serve: ``roofline_residual_ratio{op}`` for
    the top-K rows by wasted µs (bounded: op names are an unbounded label
    space) and ``roofline_bound_fraction{bound}``."""
    for b, frac in report["summary"]["bound_fraction"].items():
        _M_BOUND.labels(bound=b).set(frac)
    for r in report["rows"][:int(top_k)]:
        if r["residual_ratio"] is not None:
            _M_RESIDUAL.labels(op=r["name"]).set(r["residual_ratio"])
    return report["summary"]


# --------------------------------------------------------------- rendering
def _eng(n, unit=""):
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= div:
            return f"{n / div:.2f}{suf}{unit}"
    return f"{n:.0f}{unit}"


def render_text(report_or_rows, top=20):
    """The operator table: top-K by wasted µs, residual + bound + achieved
    rates per row, bound split in the footer."""
    if isinstance(report_or_rows, dict) and "rows" in report_or_rows:
        rows = report_or_rows["rows"]
        summary = report_or_rows.get("summary")
    else:
        rows, summary = list(report_or_rows), None
    head = (f"{'op':36s} {'count':>5s} {'meas_ms':>9s} {'pred_ms':>9s} "
            f"{'resid':>7s} {'bound':>7s} {'GF/s':>8s} {'GB/s':>8s} "
            f"{'waste_ms':>9s}")
    lines = [head, "-" * len(head)]
    for r in rows[:top]:
        resid = f"{r['residual_ratio']:.2f}" \
            if r.get("residual_ratio") is not None else "-"
        mark = "" if r.get("matched", True) else " *"
        # tolerate trace_report join rows, which carry total_us instead
        meas = r.get("measured_us", r.get("total_us", 0.0))
        lines.append(
            f"{(r['name'] + mark)[:36]:36s} {r.get('count', 0):5d} "
            f"{meas / 1e3:9.3f} {r['predicted_us'] / 1e3:9.3f} "
            f"{resid:>7s} {r['bound']:>7s} "
            f"{r.get('achieved_flops_per_sec', 0.0) / 1e9:8.2f} "
            f"{r.get('achieved_bytes_per_sec', 0.0) / 1e9:8.2f} "
            f"{r['wasted_us'] / 1e3:9.3f}")
    shown = min(top, len(rows))
    tail = (f"({shown}/{len(rows)} ops shown, sorted by wasted time; "
            f"* = no census match; resid '-' = nothing predicted)")
    if summary:
        bf = summary["bound_fraction"]
        tail += (f"\nbound split of measured time: "
                 f"compute {bf.get('compute', 0.0):.0%} / "
                 f"memory {bf.get('memory', 0.0):.0%} / "
                 f"unknown {bf.get('unknown', 0.0):.0%}; "
                 f"total residual "
                 f"{summary['residual_ratio'] if summary['residual_ratio'] is not None else '-'}")
    lines.append(tail)
    return "\n".join(lines)


def render_diff_text(diff):
    lines = []
    if not diff["comparable"]:
        lines.append(
            f"WARNING: rounds are not content-comparable (old key "
            f"{diff['old_key']}, new key {diff['new_key']}) — different "
            f"hardware, peaks, or config; verdicts below are advisory")
    for kind, entries in (("REGRESSED", diff["regressions"]),
                          ("improved", diff["improvements"])):
        for e in entries:
            lines.append(
                f"{kind}: {e['name']} residual {e['old_ratio']:.2f} -> "
                f"{e['new_ratio']:.2f} ({e['bound']}-bound, "
                f"{e['delta_wasted_us'] / 1e3:+.3f} ms wasted)")
    if diff["new_ops"]:
        lines.append(f"new ops (no baseline): "
                     f"{', '.join(diff['new_ops'][:8])}"
                     + (" ..." if len(diff["new_ops"]) > 8 else ""))
    if diff["gone_ops"]:
        lines.append(f"gone ops (baseline only): "
                     f"{', '.join(diff['gone_ops'][:8])}"
                     + (" ..." if len(diff["gone_ops"]) > 8 else ""))
    lines.append(
        f"{len(diff['regressions'])} regression(s), "
        f"{len(diff['improvements'])} improvement(s) at threshold "
        f"{diff['threshold']:.0%} / floor {diff['min_us']:.0f}us")
    return "\n".join(lines)
