"""Loop-aware cost analysis over post-SPMD HLO text.

``compiled.cost_analysis()`` visits every while body exactly ONCE, so any
scan-over-layers program under-reports FLOPs by ~n_layers x (verified in
EXPERIMENTS.md §Roofline methodology). This module re-derives per-device
FLOPs / HBM bytes / collective link-bytes from ``compiled.as_text()`` with
while-loop trip counts multiplied through the call graph:

  * dot:             2 * prod(result dims) * prod(lhs contracting dims)
  * elementwise:     prod(result dims) (transcendentals cost 1 like XLA)
  * fusion:          FLOPs traverse inside; HBM bytes counted ONLY at the
                     call site (operands + result) — fused intermediates
                     never touch HBM, matching HloCostAnalysis.
  * while:           trip count x (body + cond); the trip count is the
                     integer constant compared against the induction var in
                     the condition computation (exact for lax.scan; an upper
                     bound for lax.while_loop with a dynamic predicate).
  * collectives:     ring-cost link bytes by kind, also trip-multiplied.

This is a static-analysis estimate of the same kind XLA itself makes; its
purpose is ROOFLINE TERMS, not cycle accuracy.
"""
from __future__ import annotations

import dataclasses
import re

import jax

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "s64": 8, "u64": 8, "s16": 2, "u16": 2, "pred": 1, "s8": 1,
             "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
             "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s*([\w\-]+)\(")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_ALT = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONST_RE = re.compile(r"s(?:32|64)\[\]\s+constant\((\d+)\)")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "rsqrt", "sqrt", "negate", "abs", "sign", "floor", "ceil", "cosine",
    "sine", "logistic", "atan2", "remainder", "and", "or", "xor", "not",
    "select", "compare", "clamp", "round-nearest-afz", "round-nearest-even",
    "cbrt", "erf", "shift-left", "shift-right-logical",
    "shift-right-arithmetic",
}
_REDUCES = {"reduce", "reduce-window"}
_DATA_MOVE = {"copy", "dynamic-slice", "dynamic-update-slice", "gather",
              "scatter", "pad", "slice", "concatenate", "reverse",
              "broadcast", "iota", "transpose", "reshape", "convert",
              "reduce", "reduce-window", "sort", "select-and-scatter",
              "cholesky", "triangular-solve", "rng", "rng-bit-generator"}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}
_ZERO_COST = {"parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "after-all", "partition-id", "replica-id",
              "custom-call", "optimization-barrier", "domain", "copy-start",
              "copy-done", "send", "recv", "infeed", "outfeed"}


def _shapes_in(text: str):
    return [(dt, [int(x) for x in dims.split(",") if x])
            for dt, dims in _SHAPE_RE.findall(text)]


def _nelems(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _bytes_of(shapes) -> int:
    return sum(_DT_BYTES.get(dt, 4) * _nelems(dims) for dt, dims in shapes)


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: dict = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in _COLLECTIVES})
    coll_counts: dict = dataclasses.field(
        default_factory=lambda: {k: 0 for k in _COLLECTIVES})

    def __iadd__(self, other):
        self.flops += other.flops
        self.bytes += other.bytes
        for k in self.coll:
            self.coll[k] += other.coll[k]
            self.coll_counts[k] += other.coll_counts[k]
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(self.flops * m, self.bytes * m,
                    {k: v * m for k, v in self.coll.items()},
                    {k: int(v * m) for k, v in self.coll_counts.items()})

    @property
    def coll_bytes(self) -> float:
        return sum(self.coll.values())


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        # long tuples embed ``/*index=5*/`` comments whose '=' breaks the
        # instruction regex — strip all inline comments first.
        stripped = _COMMENT_RE.sub("", line).strip()
        # computation headers: ``%name (params...) -> type {`` — params may
        # contain NESTED parens (tuple-typed while-body args), so match the
        # name and require the `-> ... {` tail rather than balancing parens.
        if (stripped.endswith("{") and "->" in stripped
                and " = " not in stripped.split("->", 1)[0]):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in stripped:
            comps[cur].append(stripped)
    return comps


def _entry_name(hlo: str) -> str | None:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.MULTILINE)
    return m.group(1) if m else None


_KNOWN_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _trip_count(while_line: str, cond_lines: list[str]) -> int:
    """Trip count of a while: prefer XLA's own ``known_trip_count`` backend
    config (exact for lax.scan). Fallback: the constant operand of the
    condition's ROOT compare (conditions can contain OTHER large constants
    — vocab sizes, sequence lengths — that must not be mistaken for trips);
    last resort, the max integer constant in the condition."""
    m = _KNOWN_TRIPS.search(while_line)
    if m:
        return int(m.group(1))
    consts: dict[str, int] = {}
    root_ops: list[str] = []
    for line in cond_lines:
        mm = re.match(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*s(?:32|64)\[\]\s+"
                      r"constant\((\d+)\)", line)
        if mm:
            consts[mm.group(1)] = int(mm.group(2))
        if line.startswith("ROOT") and " compare(" in line:
            inner = line.split(" compare(", 1)[1].split(")", 1)[0]
            root_ops = re.findall(r"%([\w.\-]+)", inner)
    for name in root_ops:
        if name in consts:
            return max(consts[name], 1)
    best = 1
    for line in cond_lines:
        for mm in _CONST_RE.finditer(line):
            best = max(best, int(mm.group(1)))
    return best


_OPERAND_NAME = re.compile(r"%([\w.\-]+)")


def _operand_text(line: str, op: str) -> str:
    """The text inside the op's call parens (balanced)."""
    paren = line.find(op + "(")
    if paren < 0:
        return ""
    rest = line[paren + len(op) + 1:]
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


_SLICE_READS = ("dynamic-slice", "slice", "gather")


def fusion_site_bytes(fusion_name: str, result_part: str, operands: str,
                      comps: dict, shape_map: dict) -> float:
    """HBM traffic of one fusion call, slice-aware.

    XLA hoists stacked (per-layer) buffers out of scan loops and the body
    fusion takes the FULL stack as an operand, slicing one layer inside —
    charging the full operand per trip inflates scan programs by ~n_layers
    x. Per-operand rule: if the matching fusion parameter is consumed ONLY
    by slice-family ops, charge the slice RESULTS; if consumed as the
    in-place target of a dynamic-update-slice, charge the update region;
    else charge the full operand. The fusion result is charged in full
    unless the fusion ROOT is itself a dynamic-update-slice (in-place
    region write).
    """
    lines = comps.get(fusion_name, ())
    params: dict[int, str] = {}
    consumers: dict[str, list] = {}
    root_op, root_operands = None, ""
    for line in lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, res_part, op = m.groups()
        if op == "parameter":
            mi = re.search(r"parameter\((\d+)\)", line)
            if mi:
                params[int(mi.group(1))] = name
            continue
        otext = _operand_text(line, op)
        for oname in _OPERAND_NAME.findall(otext):
            consumers.setdefault(oname, []).append(
                (op, _shapes_in(res_part), otext))
        if line.startswith("ROOT"):
            root_op, root_operands = op, otext

    total = 0.0
    op_names = _OPERAND_NAME.findall(operands)
    for i, oname in enumerate(op_names):
        full = _bytes_of(shape_map.get(oname, ()))
        pname = params.get(i)
        cons = consumers.get(pname, []) if pname else []
        if cons and all(c[0] in _SLICE_READS for c in cons):
            total += sum(_bytes_of(c[1]) for c in cons)
        elif cons and all(
                c[0] == "dynamic-update-slice"
                and c[2].split(",")[0].strip().lstrip("%") == pname
                for c in cons):
            total += 0.0        # in-place DUS target: write counted at root
        else:
            total += full

    res_shapes = _shapes_in(result_part)
    if root_op == "dynamic-update-slice":
        # region write: the update operand (2nd DUS arg; params and inner
        # instructions are both named in shape_map)
        upd_names = _OPERAND_NAME.findall(root_operands)[1:2]
        upd = sum(_bytes_of(shape_map.get(u, ())) for u in upd_names)
        total += 2.0 * upd
    else:
        total += _bytes_of(res_shapes)
    return total


def build_shape_map(comps: dict[str, list[str]]) -> dict[str, list]:
    """instruction name -> result shapes, across every computation.

    Post-optimization HLO prints operands as bare ``%name`` references
    (no inline types), so operand shapes must be resolved by definition.
    """
    out: dict[str, list] = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR_RE.match(line)
            if m:
                name, result_part, _ = m.groups()
                out[name] = _shapes_in(result_part)
    return out


def _instr_cost(line: str, op: str, result_part: str,
                shape_map: dict | None = None) -> Cost:
    c = Cost()
    res_shapes = _shapes_in(result_part)
    # operand shapes: inside the call parens
    paren = line.find(op + "(")
    operand_part = line[paren + len(op) + 1:]
    depth = 1
    end = 0
    for i, ch in enumerate(operand_part):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    operands = operand_part[:end]
    op_shapes = _shapes_in(operands)
    if not op_shapes and shape_map:
        # bare-name operands: resolve through the definition map
        for name in _OPERAND_NAME.findall(operands):
            op_shapes.extend(shape_map.get(name, ()))

    if op == "dot":
        mcon = _LHS_CONTRACT.search(line)
        contract = 1
        if mcon and op_shapes:
            lhs_dims = op_shapes[0][1]
            for idx in mcon.group(1).split(","):
                if idx:
                    contract *= lhs_dims[int(idx)]
        c.flops = 2.0 * _nelems(res_shapes[0][1]) * contract \
            if res_shapes else 0.0
        c.bytes = _bytes_of(op_shapes) + _bytes_of(res_shapes)
    elif op == "convolution":
        # not emitted by this code base; approximate as dot on shapes
        c.flops = 2.0 * _nelems(res_shapes[0][1]) if res_shapes else 0.0
        c.bytes = _bytes_of(op_shapes) + _bytes_of(res_shapes)
    elif op in _ELEMENTWISE:
        c.flops = float(_nelems(res_shapes[0][1])) if res_shapes else 0.0
        c.bytes = _bytes_of(op_shapes) + _bytes_of(res_shapes)
    elif op in _COLLECTIVES or op.removesuffix("-start") in _COLLECTIVES:
        kind = op.removesuffix("-start")
        out_b = _bytes_of(res_shapes)
        g = 1
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mg2 = _GROUPS_ALT.search(line)
            if mg2:
                g = int(mg2.group(2))
        if kind == "all-gather":
            moved = out_b * (g - 1) / max(g, 1)
        elif kind == "all-reduce":
            moved = 2.0 * out_b * (g - 1) / max(g, 1)
        elif kind == "reduce-scatter":
            moved = out_b * (g - 1)
        elif kind == "all-to-all":
            moved = out_b * (g - 1) / max(g, 1)
        else:
            moved = out_b
        if g > 1 or kind == "collective-permute":
            c.coll[kind] += moved
            c.coll_counts[kind] += 1
        c.bytes = _bytes_of(op_shapes) + _bytes_of(res_shapes)
    elif op in ("dynamic-slice", "slice", "gather"):
        # reads only the sliced REGION (~= result), not the whole operand —
        # counting the full buffer inflates scan-over-stacked-weights
        # programs by ~n_layers x. Index operands are negligible.
        c.bytes = 2.0 * _bytes_of(res_shapes)
    elif op in ("dynamic-update-slice", "scatter", "select-and-scatter"):
        # writes only the updated region: read update + write region.
        # The update is every operand except the (largest) target buffer.
        sizes = [_bytes_of([s]) for s in op_shapes]
        upd = sum(sizes) - max(sizes) if sizes else 0.0
        c.bytes = 2.0 * upd
    elif op in _DATA_MOVE:
        c.bytes = _bytes_of(op_shapes) + _bytes_of(res_shapes)
        if op == "reduce":
            c.flops = float(_nelems(op_shapes[0][1])) if op_shapes else 0.0
    elif op in _ZERO_COST:
        pass
    else:
        # unknown op: count result bytes, zero flops
        c.bytes = _bytes_of(res_shapes)
    return c


def analyze(hlo: str) -> Cost:
    comps = _split_computations(hlo)
    entry = _entry_name(hlo)
    shape_map = build_shape_map(comps)
    memo: dict[tuple[str, bool], Cost] = {}

    def comp_cost(name: str, inside_fusion: bool) -> Cost:
        key = (name, inside_fusion)
        if key in memo:
            return memo[key]
        total = Cost()
        for line in comps.get(name, ()):
            m = _INSTR_RE.match(line)
            if not m:
                continue
            _, result_part, op = m.groups()
            if op == "fusion":
                mc = _CALLS_RE.search(line)
                inner = comp_cost(mc.group(1), True) if mc else Cost()
                site = Cost()
                # HBM bytes at the call site only (slice-aware — see
                # fusion_site_bytes)
                site.bytes = fusion_site_bytes(
                    mc.group(1) if mc else "", result_part,
                    _operand_text(line, op), comps, shape_map)
                site.flops = inner.flops
                for k in inner.coll:
                    site.coll[k] = inner.coll[k]
                    site.coll_counts[k] = inner.coll_counts[k]
                total += site
            elif op == "while":
                mb = _CALLS_RE.search(line)       # body=
                mcnd = _COND_RE.search(line)
                body = comp_cost(mb.group(1), False) if mb else Cost()
                cond = comp_cost(mcnd.group(1), False) if mcnd else Cost()
                trips = _trip_count(line, comps.get(
                    mcnd.group(1) if mcnd else "", []))
                total += body.scaled(trips)
                total += cond.scaled(trips)
            elif op in ("call", "conditional", "async-start"):
                for cname in _CALLS_RE.findall(line):
                    total += comp_cost(cname, inside_fusion)
                mb = re.search(r"branch_computations=\{([^}]*)\}", line)
                if mb:
                    for cname in re.findall(r"%?([\w.\-]+)", mb.group(1)):
                        total += comp_cost(cname, inside_fusion)
            else:
                ic = _instr_cost(line, op, result_part, shape_map)
                if inside_fusion:
                    ic.bytes = 0.0   # fused intermediates stay on-chip
                total += ic
        memo[key] = total
        return total

    if entry is None:
        return Cost()
    return comp_cost(entry, False)


# ---------------------------------------------------------------------------
# compiled-program helpers (repro.analysis integration)
#
# The static auditor wants XLA's own cost/memory numbers NEXT TO the
# loop-aware text analysis above, in one dict. A backend may have no cost
# analysis (None), and ``memory_analysis()`` can return None or raise.


def xla_cost(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict, or ``{}`` when the backend
    provides none."""
    try:
        from repro.distributed.compat import cost_analysis
        return cost_analysis(compiled)
    except Exception:
        return {}


def xla_memory(compiled) -> dict:
    """``compiled.memory_analysis()`` as the dryrun report dict, all-None
    when the backend has no memory analysis (CPU)."""
    empty = {"bytes_per_device": None, "argument_bytes": None,
             "output_bytes": None, "peak_bytes": None}
    try:
        from repro.distributed.compat import memory_stats
        if compiled.memory_analysis() is None:
            return empty
        return memory_stats(compiled)
    except Exception:
        return empty


def compiled_cost_terms(fn, *args, **kwargs) -> dict:
    """Compile ``fn(*args, **kwargs)`` and return every static cost term in
    one dict: XLA's ``cost_analysis`` FLOPs/bytes (once-per-while-body, see
    module docstring), the compat-guarded memory analysis, and this
    module's loop-aware re-derivation over the HLO text. kwargs are closed
    over, so static (hashable) config objects pass through untouched."""
    compiled = jax.jit(lambda *a: fn(*a, **kwargs)).lower(*args).compile()
    xla = xla_cost(compiled)
    mem = xla_memory(compiled)
    loop = analyze(compiled.as_text())
    return {
        "xla_flops": xla.get("flops"),
        "xla_bytes_accessed": xla.get("bytes accessed"),
        "memory": mem,
        "flops": loop.flops,
        "hbm_bytes": loop.bytes,
        "coll_bytes": loop.coll_bytes,
        "coll_counts": {k: v for k, v in loop.coll_counts.items() if v},
    }
