#!/usr/bin/env python
"""roofline_report — per-HLO measured-vs-predicted residual table and the
perf-regression sentinel (paddle_tpu.observability.roofline as a CLI).

Measure mode — join one profiler dump against one census into a residual
round::

    python tools/roofline_report.py --xplane prof/ --census per_op.json \
        --round r02_tpu --out .

    --xplane dump       `jax.profiler.trace()` dump: a `.xplane.pb` file
                        or any logdir above one (per-HLO device µs)
    --census f.json     per-op cost table (census.per_op_census rows or a
                        {name: {flops, bytes}} mapping)
    --peak-flops N      roofline FLOP/s denominator (default:
                        cost_model.peak_flops_per_device)
    --peak-bw N         roofline HBM bytes/s denominator (default:
                        cost_model.peak_hbm_bytes_per_sec)
    --round NAME        also persist as ROOFLINE_<NAME>.json under --out
    --out DIR           where --round writes (default: repo root)
    --top K             rows to print (default 20; persisted rounds keep
                        every row)
    --json out.json     write the report document here too

Diff mode — the sentinel::

    python tools/roofline_report.py --diff OLD.json [NEW.json] \
        [--threshold 0.25] [--min-us 50]

With one argument the round is compared against the lexically-newest
committed ``ROOFLINE_*.json`` (itself excluded) — the cron one-liner.
An op REGRESSES when its residual ratio grew by more than ``--threshold``
(relative) AND its wasted µs grew by more than ``--min-us`` (absolute).

Exit codes: 0 usable table / clean diff; 1 nothing to attribute (or no
baseline to diff against); 2 = the sentinel tripped — a census that
joined zero timed rows in measure mode, or ≥1 regressed op in diff mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

__all__ = ["main"]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plane():
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.observability import roofline
    return roofline


def _measure(args) -> int:
    roofline = _plane()
    from paddle_tpu.observability import xplane
    sys.path[0:0] = [os.path.join(_REPO, "tools")]
    import trace_report
    measured = xplane.to_timeline(xplane.find_dump(args.xplane))
    census = trace_report.load_census(args.census) if args.census else {}
    pf, pbw = args.peak_flops, args.peak_bw
    if pf is None or pbw is None:
        from paddle_tpu import cost_model
        pf = cost_model.peak_flops_per_device() if pf is None else pf
        pbw = cost_model.peak_hbm_bytes_per_sec() if pbw is None else pbw
    report = roofline.build_report(
        measured, census, pf, pbw,
        config={"xplane": os.path.basename(str(args.xplane)),
                "census": os.path.basename(str(args.census or ""))})
    if not report["rows"]:
        print("roofline_report: no timed events and no census ops — "
              "nothing to attribute")
        return 1
    print(roofline.render_text(report, top=args.top))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote report to {args.json_out}")
    if args.round:
        path = roofline.save_round(report, args.out or _REPO, args.round)
        print(f"persisted round as {path} (key {report['key']})")
    if census and report["summary"]["timed_matched_ops"] == 0:
        print("roofline_report: census joined zero timed rows — the "
              "profile and the cost model do not describe the same "
              "program", file=sys.stderr)
        timed = [r for r in report["rows"] if r["measured_us"] > 0]
        costed = [r for r in report["rows"]
                  if r["measured_us"] == 0
                  and (r["flops"] > 0 or r["bytes"] > 0)]
        costed.sort(key=lambda r: (-r["flops"], -r["bytes"]))
        for label, side in (("measured", timed), ("census", costed)):
            names = ", ".join(r["name"] for r in side[:5]) or "(empty)"
            print(f"  unmatched {label} names (top {min(5, len(side))}): "
                  f"{names}", file=sys.stderr)
        return 2
    return 0


def _diff(args) -> int:
    roofline = _plane()
    old_path = args.diff[0]
    if len(args.diff) > 1:
        new_path = args.diff[1]
    else:
        # one argument = compare against the newest committed baseline
        # (excluding the argument itself), oldest side first
        new_path = old_path
        old_path = roofline.newest_round(args.out or _REPO,
                                         exclude=new_path)
        if old_path is None:
            print("roofline_report: no committed ROOFLINE_*.json "
                  "baseline to diff against", file=sys.stderr)
            return 1
    diff = roofline.diff_reports(roofline.load_round(old_path),
                                 roofline.load_round(new_path),
                                 threshold=args.threshold,
                                 min_us=args.min_us)
    print(f"diff {os.path.basename(old_path)} -> "
          f"{os.path.basename(new_path)}")
    print(roofline.render_diff_text(diff))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(diff, f, indent=1, sort_keys=True)
    return 2 if roofline.record_diff(diff) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--xplane",
                      help="profiler dump (.xplane.pb file or logdir): "
                           "measure mode")
    mode.add_argument("--diff", nargs="+", metavar="ROUND.json",
                      help="diff mode: OLD NEW, or one round against the "
                           "newest committed ROOFLINE_*.json baseline")
    ap.add_argument("--census", default=None,
                    help="per-op census JSON (measure mode)")
    ap.add_argument("--peak-flops", type=float, default=None)
    ap.add_argument("--peak-bw", type=float, default=None)
    ap.add_argument("--round", default=None,
                    help="persist the report as ROOFLINE_<NAME>.json")
    ap.add_argument("--out", default=None,
                    help="directory for --round / baseline discovery "
                         "(default: repo root)")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the report / diff document here")
    ap.add_argument("--threshold", type=float, default=None,
                    help="relative residual-growth threshold "
                         "(default 0.25)")
    ap.add_argument("--min-us", type=float, default=None,
                    help="absolute wasted-µs floor for a regression "
                         "(default 50)")
    args = ap.parse_args(argv)
    roofline = _plane()
    if args.threshold is None:
        args.threshold = roofline.DEFAULT_THRESHOLD
    if args.min_us is None:
        args.min_us = roofline.DEFAULT_MIN_US
    if args.diff:
        if len(args.diff) > 2:
            ap.error("--diff takes one or two round files")
        return _diff(args)
    return _measure(args)


if __name__ == "__main__":
    sys.exit(main())
