"""Collective-traffic census of a compiled step (perf evidence for meshes
the attached hardware cannot run).

Ref analog: the reference's cost model + profiler count NCCL bytes per step
(fleet/meta_optimizers' cost models); here the numbers come straight from
the optimized HLO: every cross-device collective op's output bytes, per
device, per step.  Used by the driver dryrun to record
{bytes_allreduce, bytes_ppermute, ...} for the hybrid LLaMA step.
"""
from __future__ import annotations

import re

_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def _shape_bytes(text, reduce="sum"):
    """Bytes of the `dtype[d0,d1,...]` groups in `text`.

    Async `-start` tuples print the aliased operand group(s) alongside the
    result group(s), so per-op conventions recover the payload:
    - 'half_sum' (all-reduce / permute / all-to-all: operand size == result
      size, possibly VARIADIC combined): sum/2 — a max would undercount the
      combined case.
    - 'max' (all-gather / reduce-scatter: operand and result sizes differ):
      the larger group is the full participating buffer, i.e. the payload.
    """
    sizes = []
    for dt, dims in re.findall(r"(\w+)\[([0-9,]*)\]", text):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DT_BYTES[dt])
    if not sizes:
        return 0
    if reduce == "half_sum":
        return sizes[0] if len(sizes) == 1 else sum(sizes) // 2
    if reduce == "max":
        return max(sizes)
    return sum(sizes)


def collective_census(compiled):
    """{op: {"count": n, "bytes": per-device output bytes}} + est_flops.

    `compiled` is a jax Compiled (jitted.lower(*args).compile()).  Bytes are
    the collectives' OUTPUT payloads summed over the program — the per-step,
    per-device traffic the interconnect must carry (a while-loop body is
    counted once; multiply by trip count externally if needed).
    """
    txt = compiled.as_text()
    out = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for line in txt.splitlines():
        for op in _COLLECTIVES:
            # match the sync opcode OR the async -start form (XLA's default
            # on TPU); -done carries the same payload and is skipped so each
            # collective is counted once
            m = re.search(rf"=\s*(.*?)\s{re.escape(op)}(-start)?\(", line)
            if m and f"{op}-done" not in line:
                out[op]["count"] += 1
                if m.group(2):  # async form: tuple aliases operands
                    red = ("max" if op in ("all-gather", "reduce-scatter")
                           else "half_sum")
                else:
                    red = "sum"
                out[op]["bytes"] += _shape_bytes(m.group(1), reduce=red)
                break
    flops = None
    try:
        flops = float(compiled.cost_analysis().get("flops", 0.0))
    except Exception:
        pass
    return {
        "bytes_allreduce": out["all-reduce"]["bytes"],
        "bytes_allgather": out["all-gather"]["bytes"],
        "bytes_reducescatter": out["reduce-scatter"]["bytes"],
        "bytes_ppermute": out["collective-permute"]["bytes"],
        "bytes_alltoall": out["all-to-all"]["bytes"],
        "counts": {op: v["count"] for op, v in out.items()},
        "est_step_flops": flops,
    }


# ------------------------------------------------------------- per-op census
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
# the opcode is the bare word between the result type (which ends in ']',
# '}' or ')') and its '(' argument list
_OPCODE_RE = re.compile(r"[\])}]\s+([a-z][a-z0-9\-]*)\(")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

#: Bookkeeping opcodes that carry no compute and clutter attribution.
_TRIVIAL_OPCODES = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "partition-id", "after-all",
})


def _entry_lines(txt):
    """Lines of the ENTRY computation only.  The body ends at the first
    closing ``}`` on its own line — nested braces inside the body occur
    only in same-line attributes (layouts ``{1,0}``, sharding specs), never
    as standalone lines."""
    out, in_entry = [], False
    for line in txt.splitlines():
        if not in_entry:
            if line.lstrip().startswith("ENTRY "):
                in_entry = True
            continue
        if line.strip() == "}":
            break
        out.append(line)
    return out


def _dims(group_text):
    """First `dtype[d0,d1,...]` group in ``group_text`` -> list of dims."""
    m = re.search(r"(\w+)\[([0-9,]*)\]", group_text)
    if not m or m.group(1) not in _DT_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def per_op_census(compiled, include_trivial=False):
    """Per-op cost table of a compiled program: ``[{name, opcode,
    bytes_out, bytes_in, flops}]`` in program order.

    ``compiled`` is a jax Compiled (``jitted.lower(*args).compile()``).
    Bytes come from the printed operand/result shapes; ``flops`` is an
    analytic 2*M*N*K estimate for ``dot`` ops (contracting dims read off
    the HLO attributes) and 0 elsewhere — enough to RANK ops for the
    census<->timeline attribution join (`tools/trace_report.py`), not a
    replacement for the backend cost model.

    Only the ENTRY computation is scanned: fused-computation bodies repeat
    the fusion's internal ops, which would double-count the fusion row's
    bytes and pad the table with names no timeline event carries.
    """
    ops = []
    for line in _entry_lines(compiled.as_text()):
        nm = _NAME_RE.match(line)
        if nm is None:
            continue
        m = _OPCODE_RE.search(line)
        if m is None:
            continue
        opcode = m.group(1)
        if opcode in _TRIVIAL_OPCODES and not include_trivial:
            continue
        result_txt = line[nm.end():m.start() + 1]
        operand_txt = line[m.end():]
        flops = 0
        if opcode == "dot":
            out_dims = _dims(result_txt)
            lhs_dims = _dims(operand_txt)
            cm = _CONTRACT_RE.search(line)
            if out_dims is not None and lhs_dims is not None and cm:
                k = 1
                for i in (int(d) for d in cm.group(1).split(",") if d):
                    if i < len(lhs_dims):
                        k *= lhs_dims[i]
                n = 1
                for d in out_dims:
                    n *= d
                flops = 2 * n * k
        ops.append({
            "name": nm.group(1),
            "opcode": opcode,
            "bytes_out": _shape_bytes(result_txt, reduce="sum"),
            "bytes_in": _shape_bytes(operand_txt, reduce="sum"),
            "flops": flops,
        })
    return ops
