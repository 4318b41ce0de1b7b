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
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s*")
# the opcode is the bare word between the result type (which ends in ']',
# '}' or ')') and its '(' argument list
_OPCODE_RE = re.compile(r"[\])}]\s+([a-z][a-z0-9\-]*)\(")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->.*\{\s*$")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%([^\s,(){}]+)")

#: Bookkeeping opcodes that carry no compute and clutter attribution.
_TRIVIAL_OPCODES = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "partition-id", "after-all",
})

#: Opcodes that run other computations as device events of their own: the
#: attribute that names them.  Their own bytes and flops are their
#: children's, so the row carries none.
_CALLERS = {
    "while": ("condition", "body"),
    "conditional": ("branch_computations", "true_computation",
                    "false_computation"),
    "call": ("to_apply",),
    "async-start": ("calls",),
}

#: op_name components that are JAX's wrappers, not a ``jax.named_scope``:
#: the transforms print as ``name(...)``, the rest are these words.
_WRAPPERS = frozenset({
    "pjit", "while", "body", "cond", "closed_call", "checkpoint",
    "pallas_call", "remat", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "scan", "shard_map",
    "xla_call", "named_call",
})
_BRANCH_RE = re.compile(r"^branch_\d+(_fun)?$")

UNSCOPED = "unscoped"


def scope_of(op_name):
    """The ``jax.named_scope`` path of an HLO ``op_name``.

    The rule: split at ``/``; drop the primitive at the tail; drop every
    wrapper — a transform printed with parentheses (``jit(f)``,
    ``vmap(...)``, ``jvp(...)``, ``transpose(...)``), ``pjit``, ``while`` /
    ``body`` / ``cond``, ``branch_N``, ``closed_call``, ``checkpoint``,
    ``pallas_call`` and their kin (``_WRAPPERS``), a traced function's
    qualified name (``f.<locals>.g``), an einsum's subscripts
    (``bhd,bkd->bhk``), a component the path already holds (a function
    traced under its caller's scope and its own); what is left, in order,
    joined by ``/``, is the scope (``attention/paged_attention``).  Nothing
    left: ``"unscoped"``.  ``pallas_call`` at the tail is the primitive and
    goes like any other."""
    kept = []
    for p in [p for p in (op_name or "").split("/") if p][:-1]:
        if not _is_wrapper(p) and p not in kept:
            kept.append(p)
    return "/".join(kept) or UNSCOPED


def _is_wrapper(component):
    """An op_name component that is no named scope and no primitive (the
    empty one included)."""
    return not component or any(c in component for c in "()<>,") \
        or component in _WRAPPERS or bool(_BRANCH_RE.match(component))


def module_name(compiled):
    """The module's name as a device's ``XLA Modules`` line prints it
    (``jit_llm_decode``): the header of the HLO text."""
    txt = compiled if isinstance(compiled, str) else compiled.as_text()
    m = re.match(r"\s*HloModule\s+([^\s,]+)", txt)
    return m.group(1) if m else ""


def _dims(group_text):
    """First `dtype[d0,d1,...]` group in ``group_text`` -> list of dims."""
    m = re.search(r"(\w+)\[([0-9,]*)\]", group_text)
    if not m or m.group(1) not in _DT_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def _closing(text, start):
    """Index of the ')' that closes the '(' just before ``start``."""
    depth = 1
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text)


def _computations(txt):
    """HLO text -> ({computation: [instruction]}, entry name).  An
    instruction is a dict of what its line prints: name, opcode, result
    type text, operand names (or inline operand types, on texts that print
    them), the attribute text and the ``op_name``.  A computation ends at
    the first ``}`` on a line of its own (braces inside one are
    same-line attributes: layouts, sharding)."""
    comps, entry, cur = {}, None, None
    for line in txt.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        nm = _INSTR_RE.match(line)
        if nm is None:
            continue
        m = _OPCODE_RE.search(line, nm.end() - 1)
        if m is None:
            continue
        close = _closing(line, m.end())
        operands = line[m.end():close]
        attrs = line[close + 1:]
        on = _OP_NAME_RE.search(attrs)
        cur.append({
            "name": nm.group(2), "root": bool(nm.group(1)),
            "opcode": m.group(1), "result": line[nm.end():m.start() + 1],
            "operands": operands, "attrs": attrs,
            "op_name": on.group(1) if on else None,
        })
    return comps, entry


def _called(ins, keys):
    """Names of the computations ``ins`` names under the attributes
    ``keys`` (``body=%b``, ``branch_computations={%a, %b}``)."""
    out = []
    for key in keys:
        m = re.search(rf"\b{key}=(\{{[^}}]*\}}|%?[^\s,]+)", ins["attrs"])
        if m:
            out += [n.lstrip("%") for n in
                    re.findall(r"%?([^\s,{}]+)", m.group(1))]
    return out


def _fused(ins, comps):
    """The instructions of a ``fusion``'s fused computation."""
    return [f for c in _called(ins, ("calls",)) for f in comps.get(c, [])]


def _operand_types(ins, types):
    """The operands' type texts: inline where the text prints them, else
    looked up by name among the computation's results."""
    if re.search(r"\w+\[[0-9,]*\]", ins["operands"]):
        return [ins["operands"]]
    return [types[n] for n in _OPERAND_RE.findall(ins["operands"])
            if n in types]


def _matmul_flops(ins, types):
    """2 x M x N x K of a ``dot`` (contracting dims off its attributes) or
    a ``convolution`` (the TPU's form of a dot: output elements x the
    kernel's input-feature and spatial dims, off ``dim_labels``); 0 for
    any other opcode."""
    if ins["opcode"] not in ("dot", "convolution"):
        return 0
    out_dims = _dims(ins["result"])
    ops = _operand_types(ins, types)
    if out_dims is None or not ops:
        return 0
    n = 1
    for d in out_dims:
        n *= d
    k = 1
    if ins["opcode"] == "dot":
        lhs = _dims(ops[0])
        cm = _CONTRACT_RE.search(ins["attrs"])
        if lhs is None or cm is None:
            return 0
        for i in (int(d) for d in cm.group(1).split(",") if d):
            if i < len(lhs):
                k *= lhs[i]
    else:
        groups = re.findall(r"\w+\[[0-9,]*\]", " ".join(ops))
        lm = _DIM_LABELS_RE.search(ins["attrs"])
        rhs = _dims(groups[1]) if len(groups) > 1 else None
        if rhs is None or lm is None or len(lm.group(2)) != len(rhs):
            return 0
        for label, d in zip(lm.group(2), rhs):
            if label != "o":  # input features and the window
                k *= d
    return 2 * n * k


def per_op_census(compiled, include_trivial=False):
    """Per-op cost table of a compiled program, a row for every
    instruction that runs as a device event of its own: ``[{module,
    computation, name, opcode, scope, mixed, inherited, bytes_in,
    bytes_out, bytes, flops}]``, ENTRY first, then the computations it
    reaches.

    ``compiled`` is a jax Compiled (``jitted.lower(*args).compile()``) or
    its ``as_text()``.  ``module`` is the name a device prints for the
    program (``jit_llm_decode``).

    Which instructions: those of ENTRY and of every computation that runs
    as events of its own — the condition and body of a ``while``, the
    branches of a ``conditional``, what a ``call`` applies — and never the
    insides of a fused computation or of a reducer (``to_apply`` of a
    ``reduce``, ``scatter``, ``sort``): the device runs those inside their
    caller's event.  ``while`` / ``conditional`` / ``call`` rows carry no
    bytes or flops: theirs are their children's rows, the rows of the
    computations the row lists under ``calls``.

    ``bytes`` = ``bytes_in`` + ``bytes_out`` from the printed result type
    and the operands' (looked up by name where the text prints operands
    without types).  An upper bound: an in-place update of a large buffer
    counts the buffer on both sides.  ``flops`` is 2*M*N*K of a ``dot``
    or ``convolution``, and for a ``fusion`` the sum over the ``dot``s
    and ``convolution``s of its fused computation; 0 elsewhere — enough
    to RANK ops for the census<->timeline join, not a replacement for the
    backend cost model.

    ``scope`` is :func:`scope_of` the instruction's own ``op_name``; a
    fusion without one takes its root's, else the last fused op's that
    has one.  A fusion whose fused ops lie under more than one first
    scope component is ``mixed`` and still filed under that one scope.  An
    instruction the compiler made (its ``op_name`` names no primitive) is
    filed under its nearest user's scope, ``inherited`` (``_scopes``).

    A persistent compilation cache keys programs WITHOUT their metadata
    (``jax_compilation_cache_include_metadata_in_key`` is off by default):
    a ``Compiled`` that came from it prints the ``op_name``s of the code
    that first compiled it.  Compile for a census with that option on.
    """
    txt = compiled if isinstance(compiled, str) else compiled.as_text()
    module = module_name(txt)
    comps, entry = _computations(txt)
    if entry is None:
        return []
    rows, order, seen = [], [entry], {entry}
    while order:
        cname = order.pop(0)
        body = comps.get(cname, [])
        types = {i["name"]: i["result"] for i in body}
        scopes = _scopes(body, comps)
        for ins in body:
            opcode = ins["opcode"]
            callees = [c for c in _called(ins, _CALLERS.get(opcode, ()))
                       if c in comps]
            for callee in callees:
                if callee not in seen:
                    seen.add(callee)
                    order.append(callee)
            if opcode in _TRIVIAL_OPCODES and not include_trivial:
                continue
            scope, mixed, inherited = scopes[ins["name"]]
            if opcode == "fusion":
                fused = _fused(ins, comps)
                ftypes = {f["name"]: f["result"] for f in fused}
                flops = sum(_matmul_flops(f, ftypes) for f in fused)
            else:
                flops = _matmul_flops(ins, types)
            b_in = b_out = 0
            if opcode not in _CALLERS:
                b_out = _shape_bytes(ins["result"])
                b_in = sum(_shape_bytes(t)
                           for t in _operand_types(ins, types))
            rows.append({
                "module": module, "computation": cname,
                "name": ins["name"], "opcode": opcode, "scope": scope,
                "mixed": mixed, "inherited": inherited, "bytes_in": b_in,
                "bytes_out": b_out, "bytes": b_in + b_out, "flops": flops,
            })
            if callees:  # the computations whose rows are its children
                rows[-1]["calls"] = callees
    return rows


def _scopes(body, comps):
    """{instruction: (scope, mixed, inherited)} of one computation.

    An instruction's own scope first (a fusion's: its own ``op_name``'s,
    else its root's, else the last fused op's that has one).  An
    instruction whose ``op_name`` names no primitive — none at all, or one
    that ends in a wrapper (``.../while/body/closed_call``: a copy hoisted
    out of the loop it is labelled with) — is the compiler's, not the
    program's: an async copy or slice that prefetches a weight, a layout
    copy, a rewritten dot.  It inherits: the scope of the nearest
    instruction that uses its result, else of the nearest that produces
    an operand (breadth first, program order)."""
    own, mixed = {}, {}
    for ins in body:
        scope, mix = scope_of(ins["op_name"]), False
        if ins["opcode"] == "fusion":
            fused = _fused(ins, comps)
            inner = [scope_of(f["op_name"]) for f in fused
                     if f["opcode"] not in _TRIVIAL_OPCODES]
            inner = [s for s in inner if s != UNSCOPED]
            mix = len({s.split("/")[0] for s in inner}) > 1
            if scope == UNSCOPED:
                root = [scope_of(f["op_name"]) for f in fused if f["root"]]
                scope = next((s for s in root + inner[::-1]
                              if s != UNSCOPED), UNSCOPED)
        own[ins["name"]], mixed[ins["name"]] = scope, mix
    operands = {i["name"]: [n for n in _OPERAND_RE.findall(i["operands"])
                            if n in own] for i in body}
    users = {i["name"]: [] for i in body}
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)
    anonymous = {i["name"] for i in body
                 if own[i["name"]] == UNSCOPED and _is_wrapper(
                     (i["op_name"] or "").rsplit("/", 1)[-1])}
    out = {}
    for ins in body:
        name = ins["name"]
        scope, inherited = own[name], False
        if name in anonymous:
            for edges in (users, operands):
                level, seen = [name], {name}
                while level and scope == UNSCOPED:
                    nxt = [n for x in level for n in edges[x]
                           if n not in seen]
                    seen.update(nxt)
                    scope = next((own[n] for n in nxt
                                  if own[n] != UNSCOPED), UNSCOPED)
                    # only the compiler's own instructions pass a scope on
                    level = [n for n in nxt if n in anonymous]
                if scope != UNSCOPED:
                    inherited = True
                    break
        out[name] = (scope, mixed[name], inherited)
    return out


def by_module(*censuses):
    """``per_op_census`` row lists -> ``{module: {instruction: row}}``, the
    shape ``observability.xplane.device_seconds`` joins a dump against
    (instruction names are unique within a module)."""
    out = {}
    for rows in censuses:
        for row in rows:
            out.setdefault(row["module"], {})[row["name"]] = row
    return out
