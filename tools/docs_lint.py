#!/usr/bin/env python
"""Docs staleness lint (tpulint rule `docs-stale`; standalone CLI kept).

PROJECTION.md's pod-scale estimates are anchored to measured single-chip
rates from a ``BENCH_r*.json`` round.  ``tools/project_pod.py`` always reads
the NEWEST round (lexically last glob match), so a PROJECTION.md citing an
older round is stale output that no longer matches what the generator would
produce — the projections and the measurements have drifted apart.

Checks (each absent-tolerant: no rounds on disk = nothing to cite):

- the basename stem of the newest ``BENCH_r*.json`` (e.g. ``BENCH_r05``)
  must appear in PROJECTION.md;
- once a ``ROOFLINE_*.json`` residual round exists (the roofline plane's
  content-addressed artifact), the newest one's stem must appear too —
  the projections cite the measured-vs-predicted round they were checked
  against, same idiom as the BENCH anchor.

Fix for both: ``python tools/project_pod.py --validate --write``.

Usage: ``python tools/docs_lint.py [--root DIR]``; exit 1 on findings.
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import sys

_BENCH_CITE_RE = re.compile(r"BENCH_r[0-9][0-9a-z_]*")
_ROOFLINE_CITE_RE = re.compile(r"ROOFLINE_r[0-9][0-9a-z_]*")


def newest_bench(root: str):
    """Basename of the newest bench round, or None.  Lexical sort matches
    tools/project_pod.py's ``paths[-1]`` — the two must agree on 'newest'."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    return os.path.basename(paths[-1]) if paths else None


def newest_roofline(root: str):
    """Basename of the newest roofline residual round, or None (same
    lexical-sort contract as ``newest_bench`` /
    ``observability.roofline.newest_round``)."""
    paths = sorted(glob.glob(os.path.join(root, "ROOFLINE_*.json")))
    return os.path.basename(paths[-1]) if paths else None


def _check_citation(lines, newest, cite_re, what):
    """One round-family citation check -> findings list."""
    stem = newest[:-len(".json")] if newest.endswith(".json") else newest
    cited_lines = []  # (lineno, {stems cited on that line})
    for i, line in enumerate(lines, 1):
        hits = set(cite_re.findall(line))
        if hits:
            cited_lines.append((i, hits))
    all_cited = set().union(*(h for _, h in cited_lines)) if cited_lines \
        else set()
    if stem in all_cited:
        return []
    if not cited_lines:
        return [("PROJECTION.md", 1,
                 f"cites no {what} round at all — newest is {newest}; "
                 f"regenerate with `python tools/project_pod.py --validate "
                 f"--write`")]
    line_no, stale = cited_lines[0]
    return [("PROJECTION.md", line_no,
             f"cites {sorted(stale)[0]} but the newest {what} round is "
             f"{newest} — regenerate with `python tools/project_pod.py "
             f"--validate --write`")]


def check(root: str):
    """Return findings as (relpath, line, message) tuples; empty = clean."""
    proj = os.path.join(root, "PROJECTION.md")
    if not os.path.exists(proj):
        return []
    with open(proj, encoding="utf-8") as f:
        lines = f.read().splitlines()
    findings = []
    bench = newest_bench(root)
    if bench is not None:
        findings.extend(_check_citation(lines, bench, _BENCH_CITE_RE,
                                        "bench"))
    roofline = newest_roofline(root)
    if roofline is not None:
        findings.extend(_check_citation(lines, roofline,
                                        _ROOFLINE_CITE_RE, "roofline"))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    if not os.path.exists(os.path.join(args.root, "PROJECTION.md")):
        print("docs_lint: no PROJECTION.md, nothing to check")
        return 0
    findings = check(args.root)
    for path, line, msg in findings:
        print(f"{path}:{line}: docs-stale {msg}")
    if not findings:
        print("docs_lint: PROJECTION.md cites the newest rounds "
              f"(bench {newest_bench(args.root)}, roofline "
              f"{newest_roofline(args.root)})")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
