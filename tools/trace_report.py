#!/usr/bin/env python
"""trace_report — join per-op census costs with recorded span timings.

The ROADMAP's census<->timeline join: the cost model knows how much
compute/traffic each op SHOULD cost (``census.per_op_census`` /
``collective_census``), the timeline knows how long each span ACTUALLY
took (chrome-trace JSON from ``Profiler.export`` / the flight recorder's
``*.trace.json``, or the span events inside a flight-recorder JSONL dump).
This tool joins the two by name into a top-K per-op cost-attribution
table — the first thing to read when MFU drops: which op eats the time,
and whether its measured share matches its analytic share.

Inputs
------
--trace trace.json          chrome-trace document ({"traceEvents": [...]}
                            or a bare event list; complete 'X' events and
                            'B'/'E' pairs both count)
--flight dump.jsonl         alternative timing source: a flight-recorder
                            dump whose `span` events carry duration_s
--tracez trace.json         alternative timing source: a `/tracez` JSON
                            trace (one trace's span tree), a
                            `traces_*.json` store dump, or a list of
                            traces — per-op census attribution on a
                            SINGLE sampled request
--xplane dump               per-HLO DEVICE timings from a
                            `jax.profiler.trace()` dump: a `.xplane.pb`
                            file or any logdir above one
                            (observability.xplane — measured GF/s per
                            op instead of a span-name substring join)
--census census.json        per-op cost table: the per_op_census() list,
                            or a {name: {flops, bytes}} mapping, or a
                            collective_census() dict
--top K                     rows to print (default 20, by total time,
                            then by flops for time-less census rows)
--json out.json             also write the full joined table as JSON
--roofline                  residual-annotate joined rows against the
                            min-time roofline (observability.roofline):
                            predicted µs, measured/predicted ratio,
                            compute-/memory-bound; peaks default to the
                            cost_model lookups, overridable with
                            --peak-flops / --peak-bw

Join rule: a device dump (``--xplane``) joins by (module, instruction),
exactly: ``fusion.12`` of ``jit_llm_decode`` is not ``fusion.12`` of
``jit_llm_prefill_chunk`` (a census row without a module joins by its
instruction's name, exactly).  The span sources join by exact name first,
else substring containment either way (census op ``dot.4`` matches
timeline event ``jit_step/dot.4``).  Census rows without a timed event and
events without census costs both stay in the table (flagged) —
unattributed time is a finding, not noise.

Exit code: 0 on a usable table; 1 when there is nothing to attribute at
all; 2 when a census was supplied but NOT ONE timed row joined it — CI
can gate on "the profile and the cost model describe the same program".
``--json`` writes ``{"schema_version": 2, "rows": [...]}``.

Usage::

    python tools/trace_report.py --trace prof/worker.json \
        --census per_op.json --top 15
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import OrderedDict

__all__ = ["load_timeline", "load_census", "join", "render_text", "main",
           "SCHEMA_VERSION"]

#: Version of the --json document ({"schema_version", "rows"}).  v1 was
#: the bare row list; v2 wrapped it so consumers can detect drift.
SCHEMA_VERSION = 2


# ------------------------------------------------------------------ loading
def load_timeline(path=None, events=None, flight_path=None,
                  tracez_path=None, xplane_path=None):
    """-> OrderedDict name -> {"count", "total_us"} aggregated timings (a
    device dump: "module/instruction" -> the same plus "module" and
    "instruction", observability.xplane.to_timeline)."""
    if xplane_path is not None:
        return _timeline_from_xplane(xplane_path)
    if tracez_path is not None:
        events = _events_from_tracez(tracez_path)
    elif flight_path is not None:
        events = _events_from_flight(flight_path)
    elif path is not None:
        with open(path) as f:
            doc = json.load(f)
        events = doc.get("traceEvents", doc) if isinstance(doc, dict) \
            else doc
    out: "OrderedDict[str, dict]" = OrderedDict()
    open_begins: dict = {}
    for e in events or []:
        if not isinstance(e, dict):
            continue
        name, ph = e.get("name"), e.get("ph", "X")
        if name is None:
            continue
        if ph == "X" and "dur" in e:
            dur = float(e["dur"])
        elif ph == "B":
            open_begins.setdefault((e.get("tid", 0), name), []).append(
                float(e.get("ts", 0.0)))
            continue
        elif ph == "E":
            stack = open_begins.get((e.get("tid", 0), name))
            if not stack:
                continue
            dur = float(e.get("ts", 0.0)) - stack.pop()
        else:
            continue
        row = out.setdefault(name, {"count": 0, "total_us": 0.0})
        row["count"] += 1
        row["total_us"] += max(0.0, dur)
    return out


def _timeline_from_xplane(path):
    """Device self time by (module, instruction) of a profiler dump, via
    the plane's one reducer (observability.xplane.device_seconds; imported
    lazily: the other sources must keep working without the package on
    sys.path)."""
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.observability import xplane
    return xplane.to_timeline(path)


def _events_from_flight(path):
    """Span-close events of a flight-recorder JSONL dump as chrome 'X'
    events (mirrors FlightRecorder.to_chrome_trace, but offline)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "span" and "duration_s" in rec:
                events.append({"name": rec.get("name", "?"), "ph": "X",
                               "dur": float(rec["duration_s"]) * 1e6})
    return events


def _events_from_tracez(path):
    """Span tree(s) of a `/tracez` JSON document as chrome 'X' events.

    Accepts the three shapes the tracing plane writes: one trace dict
    (``/tracez?trace_id=...``), a store dump ``{"traces": [...]}``
    (``traces_<reason>_*.json`` next to a flight black box), or a bare
    list of trace dicts."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        traces = doc["traces"] if "traces" in doc else [doc]
    else:
        traces = doc
    events = []

    def walk(span):
        dur = span.get("duration_s")
        if dur is not None:
            events.append({"name": span.get("name", "?"), "ph": "X",
                           "dur": float(dur) * 1e6})
        for child in span.get("children", ()):
            walk(child)

    for t in traces:
        if not isinstance(t, dict):
            continue
        for s in t.get("spans", ()):
            walk(s)
    return events


def load_census(path):
    """-> OrderedDict name -> {"opcode", "flops", "bytes"}; accepts the
    three shapes documented in the module docstring."""
    with open(path) as f:
        doc = json.load(f)
    out: "OrderedDict[str, dict]" = OrderedDict()
    if isinstance(doc, list):  # per_op_census() rows
        for row in doc:
            name = str(row.get("name", "?"))
            if row.get("module"):  # keyed as a device dump's rows are
                name = f"{row['module']}/{name}"
            prev = out.setdefault(name, {"opcode": row.get("opcode", ""),
                                         "flops": 0.0, "bytes": 0.0})
            prev["flops"] += float(row.get("flops", 0) or 0)
            # `bytes` is the sum where a row carries all three
            prev["bytes"] += float(row["bytes"]) if "bytes" in row \
                else float(row.get("bytes_out", 0) or 0) \
                + float(row.get("bytes_in", 0) or 0)
        return out
    if isinstance(doc, dict) and "counts" in doc:  # collective_census()
        for key, op in (("bytes_allreduce", "all-reduce"),
                        ("bytes_allgather", "all-gather"),
                        ("bytes_reducescatter", "reduce-scatter"),
                        ("bytes_ppermute", "collective-permute"),
                        ("bytes_alltoall", "all-to-all")):
            if doc.get(key):
                out[op] = {"opcode": op, "flops": 0.0,
                           "bytes": float(doc[key])}
        return out
    if isinstance(doc, dict):  # {name: {flops, bytes}}
        for name, row in doc.items():
            out[str(name)] = {"opcode": str(row.get("opcode", "")),
                              "flops": float(row.get("flops", 0) or 0),
                              "bytes": float(row.get("bytes", 0) or 0)}
        return out
    raise ValueError(f"unrecognized census document shape in {path}")


# ------------------------------------------------------------------ joining
def _roofline():
    """The roofline plane, imported lazily with the same sys.path dance as
    `_timeline_from_xplane` (stdlib-only module, so this stays cheap)."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from paddle_tpu.observability import roofline
    return roofline


def _match(event_name, census):
    # the join rule lives in roofline.match_name (one matcher for the CLI
    # and the residual plane); inline fallback keeps this tool usable as a
    # bare script with the package unreachable
    try:
        return _roofline().match_name(event_name, census)
    except ImportError:
        pass
    if event_name in census:
        return event_name
    # trace names prefix ops with the program path ("jit_step/dot.12"):
    # try the trailing component exactly before any fuzzy containment
    tail = event_name.rsplit("/", 1)[-1]
    if tail in census:
        return tail
    # fuzzy fallback: LONGEST containment wins, so census row "dot.12"
    # beats "dot" / "dot.1" for event ".../dot.12"
    best = None
    for cname in census:
        if (cname in event_name or event_name in cname) \
                and (best is None or len(cname) > len(best)):
            best = cname
    return best


def join(timeline, census):
    """-> list of rows {name, count, total_us, flops, bytes, opcode,
    gflops_per_s, matched} sorted by total time desc, then flops desc.
    Census ops no event timed keep total_us=0 (matched=False) so missing
    attribution is visible."""
    rows, used = [], set()
    for name, t in timeline.items():
        if "instruction" in t:
            # a device dump: (module, instruction) or, for a census with
            # no module column, the instruction; never a near miss
            cname = next((k for k in (name, t["instruction"])
                          if k in census), None)
            name = t["instruction"]
        else:
            cname = _match(name, census)
        c = census.get(cname) if cname else None
        if cname:
            used.add(cname)
        secs = t["total_us"] / 1e6
        rows.append({
            "name": name, "module": t.get("module"), "count": t["count"],
            "total_us": round(t["total_us"], 3),
            "opcode": (c or {}).get("opcode", ""),
            "flops": (c or {}).get("flops", 0.0),
            "bytes": (c or {}).get("bytes", 0.0),
            "gflops_per_s": round((c["flops"] / secs) / 1e9, 3)
            if c and c["flops"] and secs > 0 else 0.0,
            "matched": c is not None,
        })
    for cname, c in census.items():
        if cname in used:
            continue
        rows.append({"name": cname, "module": None, "count": 0,
                     "total_us": 0.0,
                     "opcode": c.get("opcode", ""), "flops": c["flops"],
                     "bytes": c["bytes"], "gflops_per_s": 0.0,
                     "matched": False})
    rows.sort(key=lambda r: (-r["total_us"], -r["flops"], -r["bytes"],
                             r["name"]))
    return rows


# ---------------------------------------------------------------- rendering
def _human(n, unit=""):
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= div:
            return f"{n / div:.2f}{suf}{unit}"
    return f"{n:.0f}{unit}"


def render_text(rows, top=20):
    total_us = sum(r["total_us"] for r in rows) or 1.0
    head = (f"{'op':40s} {'count':>6s} {'time_ms':>10s} {'time%':>6s} "
            f"{'flops':>9s} {'bytes':>9s} {'GF/s':>8s}")
    lines = [head, "-" * len(head)]
    for r in rows[:top]:
        mark = "" if r["matched"] or r["total_us"] == 0 else " *"
        lines.append(
            f"{(r['name'] + mark)[:40]:40s} {r['count']:6d} "
            f"{r['total_us'] / 1e3:10.3f} "
            f"{100.0 * r['total_us'] / total_us:6.1f} "
            f"{_human(r['flops']):>9s} {_human(r['bytes']):>9s} "
            f"{r['gflops_per_s']:8.2f}")
    shown = min(top, len(rows))
    lines.append(f"({shown}/{len(rows)} ops shown; * = no census match; "
                 f"time-less rows are census ops never seen on the "
                 f"timeline)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--trace", help="chrome-trace JSON (Profiler.export)")
    src.add_argument("--flight",
                     help="flight-recorder JSONL dump (span events)")
    src.add_argument("--tracez",
                     help="/tracez JSON trace or traces_*.json store dump "
                          "(per-request span tree)")
    src.add_argument("--xplane",
                     help="jax.profiler .xplane.pb dump (or a logdir "
                          "above one): per-HLO device timings")
    ap.add_argument("--census", default=None,
                    help="per-op census JSON (per_op_census / "
                         "collective_census output)")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the full joined table as JSON here")
    ap.add_argument("--roofline", action="store_true",
                    help="residual-annotate the joined rows (predicted "
                         "min-time, measured/predicted ratio, compute- vs "
                         "memory-bound) and print the residual table")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="roofline FLOP/s denominator (default: "
                         "cost_model.peak_flops_per_device)")
    ap.add_argument("--peak-bw", type=float, default=None,
                    help="roofline HBM bytes/s denominator (default: "
                         "cost_model.peak_hbm_bytes_per_sec)")
    args = ap.parse_args(argv)

    timeline = load_timeline(path=args.trace, flight_path=args.flight,
                             tracez_path=args.tracez,
                             xplane_path=args.xplane)
    census = load_census(args.census) if args.census else OrderedDict()
    rows = join(timeline, census)
    if not rows:
        print("trace_report: no timed events and no census ops — nothing "
              "to attribute")
        return 1
    print(render_text(rows, top=args.top))
    if args.roofline:
        roofline = _roofline()
        pf, pbw = args.peak_flops, args.peak_bw
        if pf is None or pbw is None:
            from paddle_tpu import cost_model
            pf = cost_model.peak_flops_per_device() if pf is None else pf
            pbw = cost_model.peak_hbm_bytes_per_sec() if pbw is None \
                else pbw
        roofline.annotate_rows(rows, pf, pbw)
        print()
        print(roofline.render_text(
            sorted(rows, key=lambda r: (-r["wasted_us"], -r["total_us"],
                                        r["name"])), top=args.top))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION, "rows": rows},
                      f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json_out}")
    if census and not any(r["matched"] and r["total_us"] > 0
                          for r in rows):
        # a census that joins NOTHING timed means the profile and the
        # cost model describe different programs — fail loudly so CI
        # can gate on it, and show WHAT failed to match so the operator
        # can tell a naming-scheme drift from an empty dump
        print("trace_report: census joined zero timed rows — the "
              "timeline and the census do not describe the same program",
              file=sys.stderr)
        timed = sorted((r for r in rows if r["total_us"] > 0),
                       key=lambda r: -r["total_us"])
        costed = sorted((r for r in rows if r["total_us"] == 0
                         and not r["matched"]),
                        key=lambda r: (-r["flops"], -r["bytes"]))
        for label, side in (("timeline", timed), ("census", costed)):
            names = ", ".join(r["name"] for r in side[:5]) or "(empty)"
            print(f"  unmatched {label} names (top {min(5, len(side))}): "
                  f"{names}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
