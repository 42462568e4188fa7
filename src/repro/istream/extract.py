"""Compiled-IR extraction: parse optimized HLO text, weight the pass loop.

The OSACA idea ("Automatic Throughput and Critical Path Analysis ...") applied
at the level the jax toolchain exposes: we cannot see machine code, but
``jax.jit(case).lower(...).compile().as_text()`` gives the *optimized* HLO the
backend executes — fusions, while loops with trip counts, materialized
buffers.  This module is the pure-text half: a small structural parser
(computations -> instructions -> operands/attrs) plus element-weighted
counting and a dependence-critical-path walk over the measurement pass loop.

Counting conventions (the documented limits — see README.md):

* everything is weighted in *elements*, not instructions: an ``add`` over
  f32[64,128] counts 8192 arithmetic element-ops (what a fixed-width vector
  unit must issue), a scalar bookkeeping add counts 1.
* **fusions compute output-wise**: a kLoop fusion whose root is a scalar
  only evaluates the one element its root demands, however many full-shape
  intermediate instructions appear inside.  Counts inside fused computations
  are therefore *demand-weighted* — demand propagates backwards from the
  fusion root (a scalar root demands 1 element of each full-shape operand
  chain; a full root demands everything).  Region-level (while body / entry)
  instructions always execute in full and are counted at full shape.
* loads = elements read from materialized buffers: parameter/loop-state
  arrays everywhere, plus — at region level, where every instruction output
  is a buffer — reads of non-free producer results (a standalone
  reduce-window re-reading a fusion's materialized output is real traffic).
* stores = elements materialized per iteration: dynamic-update-slice updates
  (the in-place target is neither read nor re-written), fused-computation
  roots (fusion outputs are written), and region-level non-free results.
* ``dot`` counts 2*K arithmetic element-ops per output element (the
  multiply-accumulate depth of the contraction), not its operand size.
* unrecognized opcodes are counted as arithmetic (conservative: the issue
  path cannot silently shrink) but raise a loud ``UnknownOpcodeWarning``
  and land in the ``unknown`` bucket so compiler upgrades cannot quietly
  skew audit or classify results.
* the critical path uses a unit latency per element-op level, ``log2(n)``
  for reductions (tree depth), zero for free ops (tuples, bitcasts,
  reshapes) — relative chain lengths, not cycles.
"""
from __future__ import annotations

import math
import re
import warnings
from dataclasses import dataclass, field

# -- opcode categories ------------------------------------------------------

FREE_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "reshape", "after-all", "opt-barrier", "partition-id",
    "replica-id",
})
REDUCE_OPS = frozenset({"reduce", "reduce-window", "dot", "convolution"})
MOVE_OPS = frozenset({
    "copy", "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "pad", "reverse", "transpose", "broadcast", "gather", "scatter", "iota",
})
#: ops that consume their result elements as stores (materialized writes)
SLICING_OPS = frozenset({"slice", "dynamic-slice", "get-tuple-element"})
CONTROL_OPS = frozenset({"while", "fusion", "call", "conditional",
                         "custom-call"})
#: elementwise arithmetic the extractor recognizes explicitly — anything not
#: in one of the category sets is an *unknown* opcode (see
#: UnknownOpcodeWarning), not silently arithmetic
ARITH_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "negate", "abs", "sign",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "sqrt",
    "rsqrt", "cbrt", "power", "maximum", "minimum", "compare", "select",
    "and", "or", "xor", "not", "convert", "clamp", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "tanh", "sine", "cosine",
    "tan", "atan2", "is-finite", "shift-left", "shift-right-arithmetic",
    "shift-right-logical", "remainder", "stochastic-convert", "erf",
    "logistic", "popcnt", "count-leading-zeros", "real", "imag", "complex",
    "map", "rng", "rng-bit-generator",
})

KNOWN_OPS = FREE_OPS | REDUCE_OPS | MOVE_OPS | CONTROL_OPS | ARITH_OPS


class UnknownOpcodeWarning(UserWarning):
    """An HLO opcode outside every category set was counted as arithmetic.

    Compiler upgrades introduce opcodes; counting them silently would skew
    the audit and the bandwidth-vs-issue-bound classifier without a trace.
    The count still lands in ``arith`` (conservative — issue work cannot
    silently shrink) and is echoed in ``OpCounts.unknown``.
    """


@dataclass(frozen=True)
class HloInstr:
    name: str
    opcode: str
    elems: int                      # result elements (0 for tuple-typed)
    operands: tuple[str, ...]
    attrs: dict = field(default_factory=dict)   # calls/body/condition/...


@dataclass
class HloComputation:
    name: str
    instrs: dict[str, HloInstr]     # definition order (topological in HLO)
    root: str


@dataclass
class HloModule:
    computations: dict[str, HloComputation]
    entry: str

    def computation(self, name: str) -> HloComputation:
        return self.computations[name]


# -- parsing ----------------------------------------------------------------

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"^\s*([\w\-]+)")
_REF_RE = re.compile(r"%([\w.\-]+)")
_DIMS_RE = re.compile(r"\w+\[([\d,]*)\]")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")


def _balanced(s: str, open_ch: str = "(", close_ch: str = ")") -> int:
    """Index one past the balanced close of ``s`` (s[0] must be open_ch)."""
    depth = 0
    for i, ch in enumerate(s):
        if ch == open_ch:
            depth += 1
        elif ch == close_ch:
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _type_elems(type_str: str) -> int:
    """Element count of a non-tuple HLO type ('f32[64,128]{1,0}' -> 8192,
    'pred[]' -> 1); 0 for tuple types (consumers carry their own types)."""
    if type_str.startswith("("):
        return 0
    m = _DIMS_RE.search(type_str)
    if not m:
        return 1
    dims = [int(d) for d in m.group(1).split(",") if d]
    n = 1
    for d in dims:
        n *= d
    return n


def _parse_rhs(rhs: str) -> tuple[str, str, tuple[str, ...], dict]:
    """'f32[] add(%a, %b), meta' -> (type, opcode, operand names, attrs)."""
    rhs = rhs.strip()
    if rhs.startswith("("):                     # tuple-typed result
        cut = _balanced(rhs)
        type_str, rest = rhs[:cut], rhs[cut:]
    else:
        sp = rhs.find(" ")
        type_str, rest = rhs[:sp], rhs[sp:]
    m = _OPCODE_RE.match(rest)
    opcode = m.group(1) if m else "unknown"
    rest = rest[m.end():] if m else rest
    operands: tuple[str, ...] = ()
    attr_str = rest
    paren = rest.find("(")
    if paren >= 0:
        cut = paren + _balanced(rest[paren:])
        operands = tuple(_REF_RE.findall(rest[paren:cut]))
        attr_str = rest[cut:]
    attrs: dict = {}
    for key in ("calls", "body", "condition", "to_apply"):
        m = re.search(rf"{key}=%?([\w.\-]+)", attr_str)
        if m:
            attrs[key] = m.group(1)
    m = _TRIP_RE.search(attr_str)
    if m:
        attrs["trip_count"] = int(m.group(1))
    return type_str, opcode, operands, attrs


def parse_hlo(text: str) -> HloModule:
    """Structural parse of optimized HLO text — computations, instructions,
    operand references, the handful of attrs the profiler needs."""
    computations: dict[str, HloComputation] = {}
    entry = ""
    current: HloComputation | None = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            current = HloComputation(name=m.group(2), instrs={}, root="")
            computations[current.name] = current
            if m.group(1):
                entry = current.name
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        is_root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
        type_str, opcode, operands, attrs = _parse_rhs(rhs)
        instr = HloInstr(name=name, opcode=opcode,
                         elems=_type_elems(type_str),
                         operands=operands, attrs=attrs)
        current.instrs[name] = instr
        if is_root:
            current.root = name
    for comp in computations.values():          # root fallback: last instr
        if not comp.root and comp.instrs:
            comp.root = next(reversed(comp.instrs))
    if not entry and computations:
        entry = next(iter(computations))
    return HloModule(computations=computations, entry=entry)


# -- weighted counting ------------------------------------------------------

@dataclass
class OpCounts:
    """Element-weighted instruction counts for one computation execution."""
    loads: float = 0.0
    stores: float = 0.0
    arith: float = 0.0
    move: float = 0.0
    ops: int = 0                    # unweighted non-free HLO instructions
    opcodes: dict = field(default_factory=dict)
    unknown: dict = field(default_factory=dict)   # opcode -> element count

    def add(self, other: "OpCounts", weight: float = 1.0) -> None:
        self.loads += weight * other.loads
        self.stores += weight * other.stores
        self.arith += weight * other.arith
        self.move += weight * other.move
        self.ops += int(weight * other.ops)
        for k, v in other.opcodes.items():
            self.opcodes[k] = self.opcodes.get(k, 0) + int(weight * v)
        for k, v in other.unknown.items():
            self.unknown[k] = self.unknown.get(k, 0) + weight * v

    @property
    def issue_elems(self) -> float:
        """Total element-ops the issue/decode path must sustain."""
        return self.loads + self.stores + self.arith + self.move

    def to_dict(self) -> dict:
        return {"loads": self.loads, "stores": self.stores,
                "arith": self.arith, "move": self.move, "ops": self.ops,
                "opcodes": dict(self.opcodes),
                "unknown": dict(self.unknown)}


def _trip_count(module: HloModule, instr: HloInstr) -> int:
    """While trip count: ``known_trip_count`` when the compiler stamped it,
    else the largest integer constant in the loop condition (a
    ``compare(iv, bound)`` counted loop), else 1."""
    if "trip_count" in instr.attrs:
        return instr.attrs["trip_count"]
    cond = instr.attrs.get("condition")
    if cond and cond in module.computations:
        consts = [i.attrs["literal"]
                  for i in module.computation(cond).instrs.values()
                  if "literal" in i.attrs]
        if consts:
            return max(consts)
    return 1


def _dot_depth(comp: HloComputation, instr: HloInstr) -> float:
    """Contraction depth K of a ``dot``: (M,K) x (K,N) -> (M,N) has
    ``op0.elems * op1.elems / result.elems == K**2``."""
    if len(instr.operands) < 2 or not instr.elems:
        return 1.0
    a = comp.instrs.get(instr.operands[0])
    b = comp.instrs.get(instr.operands[1])
    if not a or not b or not a.elems or not b.elems:
        return 1.0
    k_sq = a.elems * b.elems / instr.elems
    return math.sqrt(k_sq) if k_sq > 0 else 1.0


def _operand_demand(instr: HloInstr, idx: int, src: HloInstr,
                    d: float) -> float:
    """Elements of operand ``idx`` one execution of ``instr`` touches when
    ``d`` elements of ``instr``'s result are demanded.  This single table
    drives both the backward demand propagation inside fused computations
    and the element-weighted load counting."""
    op = instr.opcode
    src_full = float(max(src.elems, 1))
    full = float(max(instr.elems, 1))
    if op in ("slice", "dynamic-slice"):
        return d if idx == 0 else 1.0
    if op == "dynamic-update-slice":
        if idx == 0:
            return 0.0              # in-place target: passed through, not read
        if idx == 1:
            return min(src_full, max(d, 1.0))
        return 1.0                  # start indices
    if op in REDUCE_OPS:            # every input element feeds the output
        return src_full * d / full
    if op == "broadcast":
        return min(src_full, d)
    if op == "concatenate":
        return src_full * d / full
    if op in CONTROL_OPS:           # fusion/call/while read via their callees
        return src_full
    if op == "tuple":
        return src_full
    return min(src_full, d)         # elementwise / reshape-like default


def _demand_map(comp: HloComputation) -> dict[str, float]:
    """Backward demand propagation from the root of a *fused* computation:
    how many elements of each instruction the fusion actually evaluates.
    kLoop fusions compute output-wise, so a scalar root demands one element
    of each full-shape chain feeding it, not the whole arrays."""
    demand: dict[str, float] = {n: 0.0 for n in comp.instrs}
    root = comp.instrs.get(comp.root)
    if root is None:
        return demand
    if root.opcode == "tuple":      # multi-output fusion: all outputs full
        for o in root.operands:
            src = comp.instrs.get(o)
            if src is not None:
                demand[o] += float(max(src.elems, 1))
    else:
        demand[comp.root] = float(max(root.elems, 1))
    # definition order is topological; reversed, every consumer is visited
    # before its operands, so demand has fully accumulated by then
    for iname in reversed(list(comp.instrs)):
        instr = comp.instrs[iname]
        cap = float(instr.elems) if instr.elems else float("inf")
        d = min(demand.get(iname, 0.0), cap)
        if d <= 0:
            continue
        for idx, o in enumerate(instr.operands):
            src = comp.instrs.get(o)
            if src is not None:
                demand[o] = demand.get(o, 0.0) \
                    + _operand_demand(instr, idx, src, d)
    return demand


def computation_counts(module: HloModule, name: str,
                       memo: dict | None = None,
                       virtual: bool = False) -> OpCounts:
    """Element-weighted counts for one execution of a computation, fusions
    inlined and nested whiles weighted by their trip counts.

    ``virtual=True`` means the computation is the body of a fusion: its
    instructions live in registers (no buffer reads/writes except params and
    the root) and are demand-weighted from the root.  ``virtual=False``
    (region/entry level) counts every instruction at full shape and treats
    every non-free result as a materialized buffer (written once, read by
    each non-free consumer)."""
    memo = {} if memo is None else memo
    key = (name, virtual)
    if key in memo:
        return memo[key]
    memo[key] = OpCounts()         # cycle guard (malformed input)
    comp = module.computation(name)
    counts = OpCounts()
    demand = _demand_map(comp) if virtual else None
    for iname, instr in comp.instrs.items():
        op = instr.opcode
        counts.opcodes[op] = counts.opcodes.get(op, 0) + 1
        full = float(max(instr.elems, 1))
        if virtual:
            cap = float(instr.elems) if instr.elems else float("inf")
            d = min(demand.get(iname, 0.0), cap)
            if d <= 0 and op not in FREE_OPS:
                continue            # dead inside the fusion: never evaluated
            d = max(d, 1.0)
        else:
            d = full
        if op in ("fusion", "call"):
            callee = instr.attrs.get("calls") or instr.attrs.get("to_apply")
            if callee and callee in module.computations:
                counts.add(computation_counts(module, callee, memo,
                                              virtual=True))
            counts.ops += 1
        elif op == "while":
            trips = _trip_count(module, instr)
            body = instr.attrs.get("body")
            cond = instr.attrs.get("condition")
            for sub in (body, cond):
                if sub and sub in module.computations:
                    counts.add(computation_counts(module, sub, memo),
                               weight=trips)
            counts.ops += 1
        elif op in FREE_OPS:
            continue
        elif op in CONTROL_OPS:     # conditional / custom-call: opaque
            counts.ops += 1
        else:
            counts.ops += 1
            if op in ("dot", "convolution"):
                counts.arith += d * 2.0 * _dot_depth(comp, instr)
            elif op in REDUCE_OPS:
                src = comp.instrs.get(instr.operands[0]) \
                    if instr.operands else None
                in_elems = src.elems if src and src.elems else full
                counts.arith += in_elems * d / full
            elif op in MOVE_OPS:
                if op == "dynamic-update-slice" and len(instr.operands) > 1:
                    upd = comp.instrs.get(instr.operands[1])
                    u = upd.elems if upd and upd.elems else 1
                    counts.move += u
                    counts.stores += u
                else:
                    counts.move += d
            elif op in ARITH_OPS:
                counts.arith += d
            else:                   # unrecognized: loud, conservative
                warnings.warn(
                    f"unrecognized HLO opcode {op!r} in computation "
                    f"{name!r}: counted as arithmetic ({d:.0f} elems)",
                    UnknownOpcodeWarning, stacklevel=2)
                counts.arith += d
                counts.unknown[op] = counts.unknown.get(op, 0.0) + d
            # loads: reads of materialized buffers — parameters and carried
            # loop state everywhere; at region level also the outputs of
            # non-free producers (every region-level result is a buffer)
            for idx, o in enumerate(instr.operands):
                src = comp.instrs.get(o)
                if src is None or src.elems <= 1:
                    continue
                is_buffer = src.opcode in ("parameter", "get-tuple-element") \
                    or (not virtual and src.opcode not in FREE_OPS)
                if is_buffer:
                    counts.loads += _operand_demand(instr, idx, src, d)
            # stores: every region-level non-free result is a written buffer
            # (dynamic-update-slice writes only its update, counted above)
            if (not virtual and instr.elems > 1
                    and op != "dynamic-update-slice"):
                counts.stores += full
    if virtual:
        # materialized root: the fusion's output buffer is written (a DUS
        # root aliases its target in place — the update is already counted).
        # A scalar root is loop state (a counter, a compare, a carried
        # index), not a buffer — the same rule as region-level results.
        root = comp.instrs.get(comp.root)
        if root is not None:
            if root.opcode == "tuple":
                seen = set()
                for o in root.operands:
                    src = comp.instrs.get(o)
                    if (src and o not in seen and src.elems > 1
                            and src.opcode not in FREE_OPS
                            and src.opcode not in CONTROL_OPS
                            and src.opcode != "dynamic-update-slice"):
                        counts.stores += src.elems
                        seen.add(o)
            elif (root.elems > 1
                  and root.opcode not in FREE_OPS
                  and root.opcode not in CONTROL_OPS
                  and root.opcode != "dynamic-update-slice"):
                counts.stores += root.elems
    memo[key] = counts
    return counts


# -- dependence critical path ----------------------------------------------

def _latency(module: HloModule, comp: HloComputation, instr: HloInstr,
             cp_memo: dict) -> float:
    op = instr.opcode
    if op in FREE_OPS:
        return 0.0
    if op in ("fusion", "call"):
        callee = instr.attrs.get("calls") or instr.attrs.get("to_apply")
        return critical_path(module, callee, cp_memo) \
            if callee in module.computations else 1.0
    if op == "while":
        trips = _trip_count(module, instr)
        body = instr.attrs.get("body")
        return trips * critical_path(module, body, cp_memo) \
            if body in module.computations else float(trips)
    if op in REDUCE_OPS:
        src = comp.instrs.get(instr.operands[0]) if instr.operands else None
        n = src.elems if src and src.elems else max(instr.elems, 2)
        return math.ceil(math.log2(max(n, 2)))
    return 1.0


def critical_path(module: HloModule, name: str,
                  cp_memo: dict | None = None) -> float:
    """Longest dependence chain through one execution of a computation, in
    abstract op-levels (unit per elementwise level, log2 per reduction)."""
    cp_memo = {} if cp_memo is None else cp_memo
    if name in cp_memo:
        return cp_memo[name]
    cp_memo[name] = 0.0            # cycle guard
    comp = module.computation(name)
    depth: dict[str, float] = {}
    for iname, instr in comp.instrs.items():   # definition order ~ topo order
        lat = _latency(module, comp, instr, cp_memo)
        depth[iname] = lat + max((depth[o] for o in instr.operands
                                  if o in depth), default=0.0)
    cp = max(depth.values(), default=0.0)
    cp_memo[name] = cp
    return cp


# -- the pass loop ----------------------------------------------------------

def find_pass_loop(module: HloModule, expected_trips: int | None = None
                   ) -> HloInstr | None:
    """The measurement pass loop: prefer a while in the entry computation
    whose trip count matches ``expected_trips``; else the entry while with
    the heaviest per-trip body; else the heaviest while anywhere."""
    def whiles_in(comp_name):
        return [i for i in module.computation(comp_name).instrs.values()
                if i.opcode == "while"]

    candidates = whiles_in(module.entry)
    if not candidates:
        candidates = [i for c in module.computations
                      for i in whiles_in(c) if i.opcode == "while"]
    if not candidates:
        return None
    if expected_trips is not None:
        hit = [i for i in candidates
               if _trip_count(module, i) == expected_trips]
        if hit:
            candidates = hit

    def weight(instr):
        body = instr.attrs.get("body")
        if body not in module.computations:
            return 0.0
        return computation_counts(module, body, {}).issue_elems

    return max(candidates, key=weight)


def extract_profile(hlo_text: str, expected_trips: int | None = None) -> dict:
    """Per-iteration instruction profile of the measurement pass loop in
    ``hlo_text``: element-weighted loads/stores/arith/move counts, the
    unweighted op count, the dependence critical path, and the loop trip
    count.  Falls back to whole-module counts at trips=1 when no loop is
    found (e.g. passes=1 fully unrolled away)."""
    module = parse_hlo(hlo_text)
    _attach_literals(module, hlo_text)
    loop = find_pass_loop(module, expected_trips)
    if loop is None:
        counts = computation_counts(module, module.entry)
        cp = critical_path(module, module.entry)
        return {"per_iter": counts.to_dict(), "critical_path": cp,
                "trips": 1, "loop": None}
    trips = _trip_count(module, loop)
    per_iter = OpCounts()
    cp = 0.0
    for sub in (loop.attrs.get("body"), loop.attrs.get("condition")):
        if sub and sub in module.computations:
            per_iter.add(computation_counts(module, sub, {}))
            cp = max(cp, critical_path(module, sub, {}))
    return {"per_iter": per_iter.to_dict(), "critical_path": cp,
            "trips": trips, "loop": loop.name}


_CONST_LINE_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*[su]\d+\[\]\s*constant\((\d+)\)")


def _attach_literals(module: HloModule, text: str) -> None:
    """Attach integer scalar constant literals (trip-count fallback for
    whiles the compiler didn't stamp with known_trip_count).  HloInstr is
    frozen; literals ride in a rebuilt instr's attrs."""
    literals = {m.group(1): int(m.group(2))
                for m in _CONST_LINE_RE.finditer(text)}
    if not literals:
        return
    for comp in module.computations.values():
        for name in list(comp.instrs):
            if name in literals and comp.instrs[name].opcode == "constant":
                old = comp.instrs[name]
                comp.instrs[name] = HloInstr(
                    name=old.name, opcode=old.opcode, elems=old.elems,
                    operands=old.operands,
                    attrs={**old.attrs, "literal": literals[name]})
