"""From a JAX profiler trace (`*.xplane.pb`) to the numbers the metrics read.

Built on `jax.profiler.ProfileData` alone.  A device is a plane whose name
matches DEVICE_PLANE; its operations are the events of the line OPS_LINE.
Everything is clipped to the traced window: the span of the host annotation
WINDOW_MARK if the run wrote one, else first device event to last.

  busy_s      seconds in which some operation ran, a union of intervals,
              averaged over the device planes
  ops         {name: seconds} summed device durations (averaged over planes);
              a parent op that contains others counts its own span; names
              are the HLO instructions' (`paged_attention.32`)
  gaps        idle gaps of the first device, each attributed to the innermost
              host annotation among HOST_MARKS open at its middle
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_MARK = "bench_trace_window"
HOST_MARKS = ("llm_decode_tick", "llm_prefill_chunk", "bench_submit",
              "train_step", "train_feed")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union_seconds(intervals):
    """Total length of a union of (start, end) intervals, in their unit."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals, lo, hi):
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if hi > end:
        out.append((end, hi))
    return out


def short_name(name):
    """A device event is named by its whole HLO instruction
    ("%paged_attention.32 = bf16[...] custom-call(...)"): keep the
    instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(name):
    """`fusion.252` -> `fusion`: the instances of one op, summed."""
    return re.sub(r"(\.\d+)+$", "", name)


def _events(line):
    return [(short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _clip(evs, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in evs if b > lo and a < hi]


def reduce(profile):
    """ProfileData -> {"window_s", "busy_s", "devices", "ops", "gaps"}."""
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operation")
    marks = [(a, b) for n, a, b in host if n == WINDOW_MARK]
    if marks:
        lo, hi = marks[0][0], marks[0][1]
    else:
        lo = min(a for evs in devices for _, a, _ in evs)
        hi = max(b for evs in devices for _, _, b in evs)
    devices = [_clip(evs, lo, hi) for evs in devices]
    n = len(devices)
    busy = sum(union_seconds([(a, b) for _, a, b in evs]) for evs in devices) / n
    ops = {}
    for evs in devices:
        for name, a, b in evs:
            ops[name] = ops.get(name, 0.0) + (b - a) / n
    gaps = _attribute(_gaps([(x, y) for _, x, y in devices[0]], lo, hi),
                      host, HOST_MARKS)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns, "busy_s": busy * ns, "devices": n,
        "ops": {k: v * ns for k, v in ops.items()},
        "gaps": {k: v * ns for k, v in gaps.items()},
    }


def _attribute(gaps, host, host_marks):
    """{mark or "unattributed": total gap length}: each gap goes to the
    shortest marked host span open at its middle.  Spans of one name never
    overlap (one thread writes them in turn), so a sorted search finds the
    one candidate of each name."""
    import numpy as np

    if not gaps:
        return {}
    g = np.asarray(gaps, np.float64)
    mid, length = g.mean(axis=1), g[:, 1] - g[:, 0]
    best = np.full(len(g), np.inf)
    who = np.full(len(g), -1)
    for k, name in enumerate(host_marks):
        sp = np.asarray(sorted((a, b) for nm, a, b in host if nm == name), np.float64)
        if not len(sp):
            continue
        i = np.searchsorted(sp[:, 0], mid, side="right") - 1
        ok = (i >= 0) & (sp[np.maximum(i, 0), 1] > mid)
        dur = np.where(ok, sp[np.maximum(i, 0), 1] - sp[np.maximum(i, 0), 0], np.inf)
        take = dur < best
        best, who = np.where(take, dur, best), np.where(take, k, who)
    out = {}
    for k in np.unique(who):
        name = host_marks[k] if k >= 0 else "unattributed"
        out[name] = float(length[who == k].sum())
    return out


def seconds_matching(ops, patterns):
    """Summed device seconds of the ops whose name holds one of `patterns`."""
    return sum(v for k, v in ops.items() if any(p in k for p in patterns))


def breakdown(red, top=10):
    """The device ops that took most time, by family, and the idle gaps by
    what the host was doing."""
    rank = lambda d: [[k, v] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    fams = {}
    for k, v in red["ops"].items():
        fams[family(k)] = fams.get(family(k), 0.0) + v
    return {"device_ops": rank(fams), "idle_gaps": rank(red["gaps"])}
