"""Dependency-free reader for the ``.xplane.pb`` dumps ``jax.profiler``
writes — the device-tracer half of the profiling plane (ISSUE 14).

``jax.profiler.trace(logdir)`` (and ``start_trace``/``stop_trace``)
serializes an XSpace protobuf under
``<logdir>/plugins/profile/<run>/<host>.xplane.pb``: per-device planes of
per-HLO events with picosecond timings — the ground truth the census
cost model (``distributed.census.per_op_census``) wants to be joined
against.  Importing tensorflow (or protobuf) for the schema would drag a
second framework into the image, so this module hand-rolls the protobuf
wire format the same way ``scrape.py`` hand-rolls the Prometheus text
format: stdlib only, one pass per message, strict about what it
understands and silent about what it doesn't (unknown fields are legal
protobuf and are skipped, not errors).

Wire format notes (README §Observability, "Profiling plane"):

- A protobuf message is a flat sequence of ``(tag, payload)`` records;
  ``tag = field_number << 3 | wire_type``.  Wire types used by XSpace:
  0 = varint, 1 = fixed 64-bit (doubles), 2 = length-delimited
  (strings, nested messages, maps).
- Field numbers (``tsl/profiler/protobuf/xplane.proto``):
  XSpace.planes=1; XPlane id=1 name=2 lines=3 event_metadata=4
  stat_metadata=5 stats=6; XLine id=1 name=2 timestamp_ns=3 events=4
  duration_ps=9 display_name=11; XEvent metadata_id=1 offset_ps=2
  duration_ps=3 stats=4 num_occurrences=5; XStat metadata_id=1
  double_value=2 uint64_value=3 int64_value=4 str_value=5 bytes_value=6
  ref_value=7; X{Event,Stat}Metadata id=1 name=2.
- Map fields (``event_metadata``/``stat_metadata``) encode each entry as
  a nested message with key=1, value=2.
- ``ref_value`` is string interning: the stat's value is the NAME of the
  stat_metadata entry it points at (XLA uses it for ``hlo_op`` /
  ``hlo_category`` strings repeated across thousands of events).
- int64 fields are plain varints; negatives arrive as 10-byte two's
  complement, so a decoded value >= 2**63 folds down by 2**64.

Event timings are ``line.timestamp_ns`` + ``event.offset_ps``, lasting
``event.duration_ps``.  On TPU the interesting planes are
``/device:TPU:*``; a CPU run (what tier-1 exercises) has the same ops on
the ``/host:CPU`` plane's XLA-client lines (``tf_XLA...`` /
``TfrtCpuClient``), with the per-op ``hlo_op`` / ``hlo_module`` /
``program_id`` stats resolved through the metadata maps either way.

No jax / numpy imports (same contract as ``observability.metrics``) —
the parser must be loadable in a stdlib-only context.
"""
from __future__ import annotations

import bisect
import os
import re
import struct
from collections import OrderedDict

__all__ = [
    "XStat", "XEvent", "XLine", "XPlane", "XSpace",
    "parse_xspace", "load_xspace", "find_dump",
    "iter_events", "device_seconds", "timeline", "to_timeline",
    "per_op_summary",
    "short_name", "family",
]

_WIRE_VARINT, _WIRE_FIXED64, _WIRE_LEN, _WIRE_FIXED32 = 0, 1, 2, 5


# ------------------------------------------------------------ wire reading
def _read_varint(buf, pos, end):
    """Little-endian base-128 varint at ``pos`` -> (value, next_pos)."""
    result = 0
    shift = 0
    while True:
        if pos >= end:
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint wider than 64 bits")


def _fields(buf, pos, end):
    """Yield ``(field_number, wire_type, value)`` records of one message.

    ``value`` is an int for varints, a float for fixed64 (every fixed64
    in xplane.proto is a double), and a ``(start, end)`` byte span for
    length-delimited payloads — spans keep nested decoding copy-free."""
    while pos < end:
        tag, pos = _read_varint(buf, pos, end)
        field, wire = tag >> 3, tag & 7
        if wire == _WIRE_VARINT:
            value, pos = _read_varint(buf, pos, end)
        elif wire == _WIRE_LEN:
            size, pos = _read_varint(buf, pos, end)
            if pos + size > end:
                raise ValueError(
                    f"length-delimited field {field} overruns the buffer")
            value = (pos, pos + size)
            pos += size
        elif wire == _WIRE_FIXED64:
            if pos + 8 > end:
                raise ValueError(f"truncated fixed64 field {field}")
            value = struct.unpack_from("<d", buf, pos)[0]
            pos += 8
        elif wire == _WIRE_FIXED32:
            if pos + 4 > end:
                raise ValueError(f"truncated fixed32 field {field}")
            value = struct.unpack_from("<f", buf, pos)[0]
            pos += 4
        else:  # groups (3/4) predate proto3; XLA never emits them
            raise ValueError(f"unsupported wire type {wire} "
                             f"(field {field})")
        yield field, wire, value


def _int64(v):
    """Fold a 64-bit varint into a signed int (negatives arrive as
    two's complement)."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


# ------------------------------------------------------- decoded structure
class XStat:
    """One resolved stat: metadata name + the oneof value (int, float,
    str or bytes; ``ref_value`` already chased to its interned string)."""

    __slots__ = ("name", "value")

    def __init__(self, name, value):
        self.name = name
        self.value = value

    def __repr__(self):
        return f"XStat({self.name}={self.value!r})"


class XEvent:
    __slots__ = ("name", "offset_ps", "duration_ps", "num_occurrences",
                 "stats", "timed")

    def __init__(self):
        self.name = ""
        self.offset_ps = 0
        self.timed = False  # offset_ps was written: a place on the line
        self.duration_ps = 0
        self.num_occurrences = 0  # aggregated-event form (offset absent)
        self.stats = {}  # stat name -> resolved value

    @property
    def duration_us(self):
        return self.duration_ps / 1e6


class XLine:
    __slots__ = ("id", "name", "display_name", "timestamp_ns",
                 "duration_ps", "events")

    def __init__(self):
        self.id = 0
        self.name = ""
        self.display_name = ""
        self.timestamp_ns = 0
        self.duration_ps = 0
        self.events = []


class XPlane:
    __slots__ = ("id", "name", "lines", "stats")

    def __init__(self):
        self.id = 0
        self.name = ""
        self.lines = []
        self.stats = {}  # plane-level stats, resolved


class XSpace:
    __slots__ = ("planes", "hostnames")

    def __init__(self):
        self.planes = []
        self.hostnames = []


# ------------------------------------------------------------ message walk
def _decode_metadata_map(buf, span):
    """An ``event_metadata``/``stat_metadata`` map entry -> (id, name).

    Entry: key=1 (varint id), value=2 (XEventMetadata/XStatMetadata,
    whose own fields are id=1, name=2)."""
    key, name = 0, ""
    for field, wire, value in _fields(buf, *span):
        if field == 1 and wire == _WIRE_VARINT:
            key = value
        elif field == 2 and wire == _WIRE_LEN:
            for f2, w2, v2 in _fields(buf, *value):
                if f2 == 1 and w2 == _WIRE_VARINT:
                    key = key or v2  # metadata carries its own id too
                elif f2 == 2 and w2 == _WIRE_LEN:
                    name = _text(buf, v2)
    return key, name


def _decode_stat(buf, span, stat_meta):
    """XStat -> resolved ``XStat`` (ref_value chased through the
    stat_metadata name table)."""
    name, value = "", None
    for field, wire, v in _fields(buf, *span):
        if field == 1 and wire == _WIRE_VARINT:  # metadata_id
            name = stat_meta.get(v, f"stat_{v}")
        elif field == 2:                          # double_value
            value = v
        elif field == 3 and wire == _WIRE_VARINT:  # uint64_value
            value = v
        elif field == 4 and wire == _WIRE_VARINT:  # int64_value
            value = _int64(v)
        elif field == 5 and wire == _WIRE_LEN:     # str_value
            value = _text(buf, v)
        elif field == 6 and wire == _WIRE_LEN:     # bytes_value
            value = bytes(buf[v[0]:v[1]])
        elif field == 7 and wire == _WIRE_VARINT:  # ref_value -> interned
            value = stat_meta.get(v, f"ref_{v}")
    return XStat(name, value)


def _decode_event(buf, span, event_meta, stat_meta):
    ev = XEvent()
    for field, wire, v in _fields(buf, *span):
        if field == 1 and wire == _WIRE_VARINT:    # metadata_id
            ev.name = event_meta.get(v, f"event_{v}")
        elif field == 2 and wire == _WIRE_VARINT:  # offset_ps (oneof)
            ev.offset_ps = _int64(v)
            ev.timed = True
        elif field == 3 and wire == _WIRE_VARINT:  # duration_ps
            ev.duration_ps = _int64(v)
        elif field == 4 and wire == _WIRE_LEN:     # stats
            st = _decode_stat(buf, v, stat_meta)
            ev.stats[st.name] = st.value
        elif field == 5 and wire == _WIRE_VARINT:  # num_occurrences (oneof)
            ev.num_occurrences = v
    return ev


def _decode_line(buf, span, event_meta, stat_meta):
    ln = XLine()
    for field, wire, v in _fields(buf, *span):
        if field == 1 and wire == _WIRE_VARINT:
            ln.id = _int64(v)
        elif field == 2 and wire == _WIRE_LEN:
            ln.name = _text(buf, v)
        elif field == 3 and wire == _WIRE_VARINT:
            ln.timestamp_ns = _int64(v)
        elif field == 4 and wire == _WIRE_LEN:
            ln.events.append(_decode_event(buf, v, event_meta, stat_meta))
        elif field == 9 and wire == _WIRE_VARINT:
            ln.duration_ps = _int64(v)
        elif field == 11 and wire == _WIRE_LEN:
            ln.display_name = _text(buf, v)
    return ln


def _decode_plane(buf, span):
    """Two passes: serializers write fields in number order so the
    metadata maps (fields 4/5) trail the lines (field 3) — collect raw
    line spans first, resolve names second."""
    plane = XPlane()
    line_spans, stat_spans = [], []
    event_meta, stat_meta = {}, {}
    for field, wire, v in _fields(buf, *span):
        if field == 1 and wire == _WIRE_VARINT:
            plane.id = v
        elif field == 2 and wire == _WIRE_LEN:
            plane.name = _text(buf, v)
        elif field == 3 and wire == _WIRE_LEN:
            line_spans.append(v)
        elif field == 4 and wire == _WIRE_LEN:
            k, name = _decode_metadata_map(buf, v)
            event_meta[k] = name
        elif field == 5 and wire == _WIRE_LEN:
            k, name = _decode_metadata_map(buf, v)
            stat_meta[k] = name
        elif field == 6 and wire == _WIRE_LEN:
            stat_spans.append(v)
    for s in stat_spans:
        st = _decode_stat(buf, s, stat_meta)
        plane.stats[st.name] = st.value
    for s in line_spans:
        plane.lines.append(_decode_line(buf, s, event_meta, stat_meta))
    return plane


def parse_xspace(data) -> XSpace:
    """Parse serialized XSpace bytes -> :class:`XSpace`.

    Concatenated serializations merge (standard protobuf semantics:
    repeated fields accumulate) — ``parse_xspace(a + b)`` sees both
    dumps' planes."""
    buf = memoryview(bytes(data))
    space = XSpace()
    for field, wire, v in _fields(buf, 0, len(buf)):
        if field == 1 and wire == _WIRE_LEN:
            space.planes.append(_decode_plane(buf, v))
        elif field == 4 and wire == _WIRE_LEN:
            space.hostnames.append(_text(buf, v))
    return space


# --------------------------------------------------------------- file I/O
def find_dump(path):
    """Resolve ``path`` to one ``.xplane.pb`` file.

    A file path is returned as-is; a directory (a profiler ``logdir`` or
    any parent of ``plugins/profile/<run>/``) is searched recursively and
    the newest dump wins (ties broken by name, so the pick is
    deterministic under equal mtimes)."""
    if os.path.isfile(path):
        return path
    best = None
    for root, _dirs, files in os.walk(path):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                full = os.path.join(root, fn)
                key = (os.path.getmtime(full), full)
                if best is None or key > best[0]:
                    best = (key, full)
    if best is None:
        raise FileNotFoundError(
            f"no .xplane.pb under {path!r} — did the profiler session "
            f"actually run (jax.profiler.trace writes "
            f"<logdir>/plugins/profile/<run>/<host>.xplane.pb)?")
    return best[1]


def load_xspace(path) -> XSpace:
    """``find_dump`` + ``parse_xspace``."""
    with open(find_dump(path), "rb") as f:
        return parse_xspace(f.read())


# ----------------------------------------------------------- op extraction
#: Host-plane lines that are Python/runtime bookkeeping, never HLO ops.
_HOST_NOISE_LINES = ("python", "TensorFlow Name Scope", "TensorFlow Ops",
                     "Launch Stats", "Steps", "Framework Name Scope")


def _op_lines(space):
    """The (plane, line) pairs whose events are per-HLO op executions.

    Device planes (``/device:...``) win when present (a real TPU run);
    otherwise the ``/host:CPU`` plane's XLA-client lines (the TFRT
    thread-pool lines a CPU run records) carry the same events."""
    device = [(p, ln) for p in space.planes
              if p.name.startswith("/device:") for ln in p.lines]
    if device:
        return device
    return [(p, ln) for p in space.planes if p.name == "/host:CPU"
            for ln in p.lines if ln.name not in _HOST_NOISE_LINES]


def iter_events(space, lines=None):
    """Yield ``(plane, line, event)`` over the per-HLO op lines (or an
    explicit ``lines`` list of (plane, line) pairs)."""
    for plane, line in (lines if lines is not None else _op_lines(space)):
        for ev in line.events:
            yield plane, line, ev


_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


def short_name(name):
    """A device event is named by its whole HLO instruction
    ("%paged_attention.32 = bf16[...] custom-call(...)"): keep the
    instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(name):
    """`fusion.252` -> `fusion`: the instances of one op, summed."""
    return re.sub(r"(\.\d+)+$", "", name)


def _module_of(name):
    """`jit_llm_decode(123)` -> `jit_llm_decode`."""
    return re.sub(r"\(\d+\)$", "", name)


def _span_ps(line, ev):
    """(start, end) in whole picoseconds: a float loses the nanoseconds of
    a Unix-epoch timestamp."""
    a = line.timestamp_ns * 1000 + ev.offset_ps
    return a, a + ev.duration_ps


def _streams(space):
    """The dump as ``(devices, program_ids)``: a device is ``(ops,
    modules, calls)`` — ``ops`` ``[(start_ps, end_ps, module or None,
    instruction, occurrences, timed)]``, ``modules`` its program
    executions ``[(start_ps, end_ps, module)]``, ``calls`` ``{module:
    executions}`` where the dump has no such line — and ``program_ids``
    is ``{module: program_id}`` where events carry both (CPU dumps).

    A TPU plane: the events of the ``XLA Ops`` line, each given the module
    of the ``XLA Modules`` event whose interval holds its start (a v5e
    event carries no ``hlo_module``).  Without such planes, the XLA-client
    lines of ``/host:CPU`` together as one device: an op event there
    carries ``hlo_module`` (and ``run_id``, which counts the calls);
    events without one (runtime bookkeeping) have no module."""
    def op(line, ev, name, mod):
        a, b = _span_ps(line, ev)
        return (a, b, mod, short_name(name),
                max(1, int(ev.num_occurrences or 1)), ev.timed)

    out = []
    for plane in space.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        mods = sorted(_span_ps(line, ev) + (_module_of(ev.name),)
                      for line in plane.lines if line.name == _MODULES_LINE
                      for ev in line.events)
        starts = [m[0] for m in mods]
        ops = []
        for line in plane.lines:
            if line.name != _OPS_LINE:
                continue
            for ev in line.events:
                mod = ev.stats.get("hlo_module")
                if mod is None:
                    a = line.timestamp_ns * 1000 + ev.offset_ps
                    i = bisect.bisect_right(starts, a) - 1
                    if i >= 0 and a < mods[i][1]:
                        mod = mods[i][2]
                ops.append(op(line, ev, ev.name, mod))
        if ops:
            out.append((ops, mods, {}))
    if out:
        return out, {}
    ops, runs, ids = [], {}, {}
    for _plane, line in _op_lines(space):
        for ev in line.events:
            name = ev.stats.get("hlo_op") or ev.name
            if not name:
                continue
            mod = ev.stats.get("hlo_module")
            if mod is not None:
                mod = str(mod)
                runs.setdefault(mod, set()).add(
                    ev.stats.get("run_id", len(ops)))
                if "program_id" in ev.stats:
                    ids.setdefault(mod, ev.stats["program_id"])
            ops.append(op(line, ev, str(name), mod))
    calls = {m: len(r) for m, r in runs.items()}
    return ([(ops, [], calls)] if ops else []), ids


def _window_of(space, window):
    """``window`` in picoseconds: a (start_ns, end_ns) pair, or a string
    naming a host annotation whose first event is the window."""
    if window is None:
        return None
    if not isinstance(window, str):
        return int(window[0] * 1000), int(window[1] * 1000)
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window:
                        return _span_ps(line, ev)
    return None


def device_seconds(space, census=None, window=None):
    """The plane's one aggregation: device seconds of a dump by (program,
    named scope).

    ``census`` is ``{module: {instruction: row}}`` (``census.by_module`` of
    ``per_op_census`` rows, ``LLMEngine.program_census()``): an event's key
    is (its module, its instruction's name), and the row gives its scope,
    bytes and flops.  ``census=None`` reports every module with no scopes
    (every event unmatched); with a census, a module it does not hold
    goes to ``other_programs``.  ``window`` clips to (start_ns, end_ns),
    or to the first host annotation of that name.

    Time is SELF time: an event that contains others on its line (a
    ``while`` over its body's ops, a ``conditional``) counts its span less
    what its direct children cover, so the seconds add up to ``busy_s``
    (the union of the events, computed on its own).  Several devices are
    averaged.  Result::

        {"window_s", "busy_s", "devices",
         "programs": {module: {"calls", "seconds", "program_id",
                               "scopes": {scope: {"seconds", "events",
                                                  "bytes", "flops"}},
                               "unscoped_s", "unmatched_s",
                               "unmatched": {family: seconds},
                               "ops": {instruction: {"events", "seconds",
                                                     "span_s"}}}},
         "other_programs": {module: {"calls", "seconds"}},
         "unmatched_s", "unmatched": {family: seconds},
         "no_module": {name: {"events", "seconds", "span_s"}}}

    ``unmatched`` holds what no census row names: events of a known
    program without a row (by family, also under the program) and events
    outside every module (also by name under ``no_module``).  Every second
    of ``busy_s`` is in exactly one of: a program's scopes (``unscoped``
    among them), ``unmatched``, ``other_programs``.
    """
    devices, program_ids = _streams(space)
    win = _window_of(space, window)
    known = census or {}
    n = len(devices)
    out = {"window_s": 0.0, "busy_s": 0.0, "devices": n, "programs": {},
           "other_programs": {}, "unmatched_s": 0.0, "unmatched": {},
           "no_module": {}}
    if not n:
        return out
    lo = min(op[0] for ops, _, _ in devices for op in ops)
    hi = max(op[1] for ops, _, _ in devices for op in ops)
    if win is not None:
        lo, hi = win
    ns = 1e-12 / n  # picoseconds of one device -> seconds, averaged
    out["window_s"] = (hi - lo) * 1e-12

    def program(mod):
        return out["programs"].setdefault(mod, {
            "calls": 0, "seconds": 0.0,
            "program_id": program_ids.get(mod), "scopes": {},
            "unscoped_s": 0.0, "unmatched_s": 0.0, "unmatched": {},
            "ops": {}})

    def other(mod):
        return out["other_programs"].setdefault(
            mod, {"calls": 0, "seconds": 0.0})

    def op_row(table, name):
        return table.setdefault(name, {"events": 0, "seconds": 0.0,
                                       "span_s": 0.0})

    for ops, mods, calls in devices:
        evs = sorted(((max(a, lo), min(b, hi), mod, name, occ, timed)
                      for a, b, mod, name, occ, timed in ops
                      if b >= lo and a <= hi),
                     key=lambda e: (e[0], -e[1]))
        # nesting: an event's direct children are the events that start
        # inside it while it is the innermost one open.  An event in the
        # aggregated form (no offset) has no place on the line: it nests
        # nowhere and its whole duration is busy.
        child = [0] * len(evs)
        stack, busy, reach = [], 0, lo
        for i, (a, b, _, _, _, timed) in enumerate(evs):
            if not timed:
                busy += b - a
                continue
            while stack and evs[stack[-1]][1] <= a:
                stack.pop()
            if stack:
                child[stack[-1]] += min(b, evs[stack[-1]][1]) - a
            stack.append(i)
            if b > reach:
                busy += b - max(a, reach)
                reach = b
        out["busy_s"] += busy * ns
        for a, b, m in mods:  # the executions inside the window
            if b > lo and a < hi:
                calls[m] = calls.get(m, 0) + 1
        for m, c in calls.items():
            (program if census is None or m in census else other)(m)[
                "calls"] += c / n
        for (a, b, mod, name, occ, _), inside in zip(evs, child):
            own, span = max(0, b - a - inside) * ns, (b - a) * ns
            if mod is None:
                fam = family(name)
                out["unmatched"][fam] = out["unmatched"].get(fam, 0.0) + own
                out["unmatched_s"] += own
                r = op_row(out["no_module"], name)
            elif census is not None and mod not in census:
                other(mod)["seconds"] += own
                continue
            else:
                prog = program(mod)
                prog["seconds"] += own
                row = known.get(mod, {}).get(name)
                if row is None:
                    fam = family(name)
                    for table in (prog["unmatched"], out["unmatched"]):
                        table[fam] = table.get(fam, 0.0) + own
                    prog["unmatched_s"] += own
                    out["unmatched_s"] += own
                else:
                    sc = prog["scopes"].setdefault(row["scope"], {
                        "seconds": 0.0, "events": 0, "bytes": 0,
                        "flops": 0})
                    sc["seconds"] += own
                    sc["events"] += occ
                    sc["bytes"] += occ * row.get("bytes", 0)
                    sc["flops"] += occ * row.get("flops", 0)
                    if row["scope"] == "unscoped":  # census.UNSCOPED
                        prog["unscoped_s"] += own
                r = op_row(prog["ops"], name)
            r["events"] += occ
            r["seconds"] += own
            r["span_s"] += span
    return out


def timeline(reduced, census=None):
    """A :func:`device_seconds` result flattened to the
    ``trace_report.load_timeline`` shape, one row an instruction of a
    program: ``"module/instruction" -> {count, total_us, module,
    instruction, scope, hlo_module, program_id}`` (an event outside every
    module: its bare name), in order of device time.  ``total_us`` is self
    time."""
    rows = []
    for mod, prog in reduced["programs"].items():
        known = (census or {}).get(mod, {})
        for name, r in prog["ops"].items():
            rows.append((f"{mod}/{name}", r, mod, name,
                         known.get(name, {}).get("scope"),
                         prog["program_id"]))
    for name, r in reduced["no_module"].items():
        rows.append((name, r, None, name, None, None))
    rows.sort(key=lambda t: (-t[1]["seconds"], t[0]))
    out: "OrderedDict[str, dict]" = OrderedDict()
    for key, r, mod, name, scope, pid in rows:
        out[key] = {"count": r["events"], "total_us": r["seconds"] * 1e6,
                    "module": mod, "instruction": name, "scope": scope,
                    "hlo_module": mod, "program_id": pid}
    return out


def to_timeline(path_or_space, census=None, window=None):
    """:func:`timeline` of a dump path / logdir / parsed space: the
    ``--xplane`` entry point."""
    space = path_or_space if isinstance(path_or_space, XSpace) \
        else load_xspace(path_or_space)
    return timeline(device_seconds(space, census, window), census)


def per_op_summary(space) -> "OrderedDict[str, dict]":
    """The by-name table of before the census knew modules: ``name ->
    {count, total_us, hlo_module, program_id}``, two programs' ``fusion.3``
    summed into one row.  Kept for the dumps and reports recorded in that
    shape; a projection of :func:`device_seconds`, which every caller in
    the package uses instead (keyed by module and instruction)."""
    out: "OrderedDict[str, dict]" = OrderedDict()
    for row in to_timeline(space).values():
        prev = out.setdefault(row["instruction"], {
            "count": 0, "total_us": 0.0,
            "hlo_module": row["hlo_module"],
            "program_id": row["program_id"]})
        prev["count"] += row["count"]
        prev["total_us"] += row["total_us"]
    return out
