"""Census of one compiled fragment program, by plan operator.

`census(compiled)` reads the optimized HLO text of a `jax.stages.Compiled`
(`as_text()`) and its `memory_analysis()` once, when the fragment is
compiled, and returns one plain dict that stays on the jit-cache entry and
is put into every kernel profile of that program as `programCensus` (a
reference).  Nothing here runs on a warm query.

**Scopes.**  `_TraceCtx.visit` traces every operator inside
`jax.named_scope("<PlanNodeType>#<ordinal>")` and the lowering steps
(`permute_lanes`, `build_direct`, ...) inside a sub-scope, so an HLO
instruction's `metadata={op_name="jit(frag)/Aggregate#2/Join#4/probe_direct/
gather"}` says where it came from.  An instruction's scope is
`<operator>/<step>`: the operator is the LAST `Name#n` component of the
path (visits nest, the innermost operator did the work), the step every
component after it but the last (the primitive), transformations
(`jit(...)`, `while`, `body`, `cond`, `branch_k`, `shard_map`, ...) left
out.

**The attribution rule** (deterministic; the one place it is written):

1. An instruction with an operator in its own metadata has that scope.
2. A fusion (or `call`) is classed by the costliest opcode class inside the
   computations it calls, `scatter > gather > sort > cumulative
   (reduce-window) > collective > customCall > elementwise`, and takes the
   scope of the first instruction of that class, in program order, that has
   one; failing that the scope most instructions inside it have (first seen
   wins a tie); failing that its own metadata.  A fusion whose instructions
   name more than one operator is also counted under `mixedFusions`.
3. What has no scope yet inherits its first operand's that has one, in
   program order (a cached lowering such as `cumsum`'s loses the name
   stack: it reads as its input's operator).
4. Anything else stays `""` and counts as unattributed.

Every entry of `ops` says which rule named it, `[scope, kind, shape, rule]`:
1, 2 (a fusion named by an instruction inside it that has rule 1), 3 (rule
3, and a fusion named by an instruction inside it that has rule 3), 0 (no
scope).  Rule 3 is a guess by data flow; the readers count it apart
(`inheritedPct`), so that `attributedPct` can fail.

`scopedInstructions` counts rule 1 only, so an executable compiled before
the scopes existed (loaded from a persistent cache) reads 0 and every
consumer knows the map is empty rather than wrong.

A `while` body is counted once: the trip count is not known at compile
time (`whileLoops` says how many there are).  On a mesh the program is one
shard's (SPMD).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

# costliest first; `kind` of a fusion is the first class found inside it
KINDS = ("scatter", "gather", "sort", "cumulative", "collective",
         "customCall", "elementwise")
_RANK = {k: i for i, k in enumerate(KINDS)}
_COLLECTIVES = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
)
# the counters of `totals` and of each `byOperator` entry
COUNTERS = (
    "instructions", "fusions", "mixedFusions", "gathers", "gatherElements",
    "scatters", "scatterUpdates", "sorts", "sortOperandElements",
    "cumulativeOps", "collectives", "collectiveBytes", "customCalls",
    "whileLoops",
)
MEMORY_KEYS = {
    "tempBytes": "temp_size_in_bytes",
    "argumentBytes": "argument_size_in_bytes",
    "outputBytes": "output_size_in_bytes",
    "generatedCodeBytes": "generated_code_size_in_bytes",
}
# every key of a census (scripts/check_metric_names.py lints them)
CENSUS_FIELDS = COUNTERS + tuple(MEMORY_KEYS) + (
    "scopedInstructions", "byOperator", "ops", "fragment", "fragments",
)

_OPERATOR = re.compile(r"^[A-Z][A-Za-z]*#\d+$")
_TRANSFORM = re.compile(
    r"^(jit|pjit|shard_map|vmap|remat|checkpoint|custom_jvp|custom_vjp"
    r"|xla_call|closed_call|core_call)\(|^(while|body|cond|body_fun"
    r"|cond_fun|branch_\d+|scan|shard_map|pallas_call)$"
)
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}"
)
_REF = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")
_BITS = re.compile(r"(\d+)$")


def scope_of(op_name: str) -> str:
    """`<operator>/<step>` of an instruction's `op_name` metadata, `""`
    where the path names no operator (docstring, "Scopes")."""
    parts = op_name.split("/")
    at = max((i for i, p in enumerate(parts) if _OPERATOR.match(p)),
             default=-1)
    if at < 0:
        return ""
    step = [p for p in parts[at + 1:-1] if not _TRANSFORM.match(p)]
    return "/".join([parts[at]] + step)


def operator_of(scope: str) -> str:
    return scope.split("/", 1)[0]


def _match_paren(s: str, at: int) -> int:
    """Index just past the parenthesis that closes the one at `s[at]`."""
    depth = 0
    for i in range(at, len(s)):
        c = s[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def shape_arrays(shape: str) -> List[Tuple[str, int]]:
    """`(dtype, elements)` of every array of an HLO shape (a tuple's
    members in order); layouts are ignored."""
    out = []
    for dtype, dims in _ARRAY.findall(shape):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, n))
    return out


def _elements(shape: str) -> int:
    return sum(n for _, n in shape_arrays(shape))


def _bytes(shape: str) -> int:
    total = 0
    for dtype, n in shape_arrays(shape):
        m = _BITS.search(dtype)
        bits = int(m.group(1)) if m else 8   # pred, token
        total += n * max(bits, 8) // 8
    return total


def short_shape(shape: str) -> str:
    """The shape without layouts: `u32[16777216,4]`, a tuple as
    `(s64[8], s32[8])`."""
    return re.sub(r"\{[^{}]*\}", "", shape)


def _class_of(opcode: str) -> str:
    if opcode == "scatter":
        return "scatter"
    if opcode == "gather":
        return "gather"
    if opcode == "sort":
        return "sort"
    if opcode == "reduce-window":
        return "cumulative"
    if opcode.startswith(_COLLECTIVES):
        return "collective"
    if opcode == "custom-call":
        return "customCall"
    return "elementwise"


class _Instr:
    __slots__ = ("name", "shape", "opcode", "operands", "called", "scope",
                 "rule")

    def __init__(self, name, shape, opcode, operands, called, scope):
        self.name, self.shape, self.opcode = name, shape, opcode
        self.operands, self.called = operands, called
        self.scope = scope          # rules 1-3, filled in as they apply
        self.rule = int(bool(scope))   # the rule that named it, 0: none


def parse(text: str):
    """The module as `({computation: [_Instr]}, entry computation)`."""
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    cur: Optional[List[_Instr]] = None
    for line in text.splitlines():
        if cur is None:
            m = _HEADER.match(line)
            if m:
                cur = comps[m.group(2)] = []
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(3)
        end = _match_paren(rest, 0) if rest.startswith("(") \
            else (rest.find(" ") if " " in rest else len(rest))
        shape = rest[:end]
        op = _OPCODE.match(rest, end)
        if not op:
            continue
        args_end = _match_paren(rest, op.end() - 1)
        attrs = rest[args_end:]
        called = []
        for one, many in _CALLED.findall(attrs):
            called.extend([one] if one else _REF.findall(many))
        name = _OP_NAME.search(attrs)
        cur.append(_Instr(
            m.group(2), shape, op.group(1),
            _REF.findall(rest[op.end():args_end]), called,
            scope_of(name.group(1)) if name else "",
        ))
    return comps, entry


def _inside(comps, instr, seen=None) -> List[_Instr]:
    """Every instruction of the computations `instr` calls, nested calls
    included, in program order."""
    out = []
    seen = set() if seen is None else seen
    for c in instr.called:
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for i in comps[c]:
            out.append(i)
            out.extend(_inside(comps, i, seen))
    return out


def _inherit(instr: _Instr, by_name: Dict[str, _Instr]) -> None:
    """Rule 3."""
    if not instr.scope and instr.opcode != "parameter":
        instr.scope = next(
            (by_name[o].scope for o in instr.operands
             if o in by_name and by_name[o].scope), "")
        instr.rule = 3 if instr.scope else 0


_HOLDERS = ("fusion", "call", "async-start")
_LOOPS = ("while", "conditional")


def _attribute(comps, instrs: List[_Instr], ops: dict, totals: dict) -> None:
    """Rules 2 and 3 over one top-level instruction list (the entry
    computation, a `while` body, a `conditional` branch), into `ops`.
    What a fusion holds and no rule has named yet takes the fusion's
    scope, so the counters land under the operator that pays for them."""
    by_name = {i.name: i for i in instrs}
    for i in instrs:
        kind = i.opcode if i.opcode in _LOOPS else _class_of(i.opcode)
        inner = _inside(comps, i) if i.opcode in _HOLDERS else []
        if inner:
            classes = [_class_of(x.opcode) for x in inner]
            kind = min(classes, key=_RANK.__getitem__)
            scoped = [x.scope for x in inner if x.scope]
            pick = next((x for x, c in zip(inner, classes)
                         if c == kind and x.scope), None)
            if pick is None and scoped:
                most = max(dict.fromkeys(scoped), key=scoped.count)
                # one that names it itself, where there is one
                pick = min((x for x in inner if x.scope == most),
                           key=lambda x: x.rule != 1)
            if pick is not None:
                i.scope, i.rule = pick.scope, 2 if pick.rule == 1 else 3
            if len({operator_of(s) for s in scoped}) > 1:
                totals["mixedFusions"] += 1
        _inherit(i, by_name)
        for x in inner:
            x.scope = x.scope or i.scope
        if i.opcode not in ("parameter", "constant", "tuple",
                            "get-tuple-element"):
            ops[i.name] = [i.scope, kind, short_shape(i.shape), i.rule]


def census_of_text(text: str) -> dict:
    """The census of one optimized HLO module text (no memory numbers)."""
    comps, entry = parse(text)
    # rule 1, before the other rules rename anything
    scoped = sum(i.rule for instrs in comps.values() for i in instrs)
    totals = dict.fromkeys(COUNTERS, 0)
    by_op: Dict[str, Dict[str, int]] = {}
    ops: Dict[str, list] = {}

    # the top levels: the entry, and every loop body or branch under one
    top = [entry] if entry in comps else []
    for name in top:   # grows while it is walked
        for i in comps[name]:
            if i.opcode in _LOOPS:
                top.extend(c for c in i.called
                           if c in comps and c not in top)
    for name, instrs in comps.items():
        if name not in top:
            by_name = {i.name: i for i in instrs}
            for i in instrs:
                _inherit(i, by_name)
    for name in top:
        _attribute(comps, comps[name], ops, totals)

    def count(instr, key, n=1):
        totals[key] += n
        if instr.scope:
            rec = by_op.setdefault(
                operator_of(instr.scope), dict.fromkeys(COUNTERS, 0))
            rec[key] += n

    for instrs in comps.values():
        by_name = {i.name: i for i in instrs}
        for i in instrs:
            count(i, "instructions")
            cls = _class_of(i.opcode)
            if i.opcode == "fusion":
                count(i, "fusions")
            elif i.opcode == "while":
                count(i, "whileLoops")
            elif cls == "gather":
                count(i, "gathers")
                count(i, "gatherElements", _elements(i.shape))
            elif cls == "scatter":
                count(i, "scatters")
                # operands: n operands, the indices, n updates
                n_upd = (len(i.operands) - 1) // 2
                count(i, "scatterUpdates", sum(
                    _elements(by_name[o].shape)
                    for o in i.operands[len(i.operands) - n_upd:]
                    if o in by_name))
            elif cls == "sort":
                count(i, "sorts")
                count(i, "sortOperandElements", _elements(i.shape))
            elif cls == "cumulative":
                count(i, "cumulativeOps")
            elif cls == "collective" and not i.opcode.endswith("-done"):
                count(i, "collectives")
                count(i, "collectiveBytes", _bytes(i.shape))
            elif cls == "customCall":
                count(i, "customCalls")
    out = dict(totals)
    out["scopedInstructions"] = scoped
    out["byOperator"] = by_op
    out["ops"] = ops
    return out


def census(compiled) -> dict:
    """The census of a `jax.stages.Compiled`.  An executable whose text
    cannot be read (a backend that keeps none) gives the memory numbers
    and `instructions` 0."""
    try:
        text = compiled.as_text() or ""
    except Exception:  # noqa: BLE001 — observability never fails a query
        text = ""
    out = census_of_text(text)
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        mem = None
    for key, attr in MEMORY_KEYS.items():
        out[key] = int(getattr(mem, attr, 0) or 0)
    return out


def without_ops(census: Optional[dict]) -> Optional[dict]:
    """The census without its instruction map (each fragment's too): what
    leaves the process.  `ops` joins a trace of this process's own
    program and is most of the census's size."""
    if not census:
        return census
    out = {k: v for k, v in census.items() if k != "ops"}
    if "fragments" in out:
        out["fragments"] = {
            d: without_ops(c) for d, c in out["fragments"].items()}
    return out


def merge(into: Optional[dict], one: dict) -> dict:
    """A streamed query's outer census: the fragments' counters summed
    (a program that ran as several tiles once), each fragment's own census
    kept whole under its digest."""
    if into is not None and "fragments" not in into:
        into = merge(None, into)   # one program's own census came first
    if into is None:
        into = dict.fromkeys(COUNTERS + ("scopedInstructions",), 0)
        into.update(dict.fromkeys(MEMORY_KEYS, 0), fragments={})
    digest = one.get("fragment", "")
    if digest in into["fragments"]:
        return into
    into["fragments"][digest] = one
    for k in COUNTERS + ("scopedInstructions",):
        into[k] += one.get(k, 0)
    for k in MEMORY_KEYS:   # the programs run one after another
        into[k] = max(into[k], one.get(k, 0))
    return into
