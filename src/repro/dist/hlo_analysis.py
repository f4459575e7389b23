"""Trip-count-aware HLO text analysis (DESIGN.md §5.4).

``compiled.cost_analysis()`` counts a ``while`` body **once**, so a model
that scans over L layers under-reports dot FLOPs by ~L x.  This walker
parses the compiled HLO text instead: it recursively evaluates each
computation (following fusion/call/while/conditional edges), multiplies
``while`` bodies by their trip count (XLA annotates
``backend_config={"known_trip_count":{"n":...}}``; a compare-against-constant
loop condition is the fallback), and reports

  dot_flops           2 * |output| * |contracted| per dot, trip-weighted
  dot_flops_by_dtype  the same split by accumulator dtype (int8/int32 MXU
                      paths run at 2x the bf16 rate — the roofline re-prices)
  collectives         bytes by kind (all-reduce, all-gather, reduce-scatter,
                      all-to-all, collective-permute), trip-weighted

Consumed by launch/dryrun.py (per-cell artifacts) and benchmarks/roofline.py
(compute / memory / collective terms).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e8m0fnu": 1, "f8e3m4": 1,
    "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape string; tuples sum their elements.

    ``shape_bytes("f32[4,4]") == 64``; layout annotations (``{1,0}``) and
    nesting are ignored/flattened.
    """
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveReport:
    bytes_by_kind: dict[str, float] = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    def add(self, kind: str, nbytes: float) -> None:
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0.0) + nbytes

    def scaled(self, k: float) -> "CollectiveReport":
        return CollectiveReport(
            {kind: v * k for kind, v in self.bytes_by_kind.items()})

    def merged(self, other: "CollectiveReport") -> "CollectiveReport":
        out = CollectiveReport(dict(self.bytes_by_kind))
        for kind, v in other.bytes_by_kind.items():
            out.add(kind, v)
        return out

    def as_dict(self) -> dict:
        return {"total_bytes": self.total_bytes,
                "by_kind": dict(self.bytes_by_kind)}


@dataclass
class HloReport:
    dot_flops: float = 0.0
    dot_flops_by_dtype: dict[str, float] = field(default_factory=dict)
    collectives: CollectiveReport = field(default_factory=CollectiveReport)
    while_trip_counts: dict[str, int] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "dot_flops": self.dot_flops,
            "dot_flops_by_dtype": dict(self.dot_flops_by_dtype),
            "collectives": self.collectives.as_dict(),
            "while_trip_counts": dict(self.while_trip_counts),
        }


# --- parsing ----------------------------------------------------------------

_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"^\s*([\w\-]+)\(")
_CALLEE_RE = {
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
}
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"?(\d+)"?')
_CONST_RE = re.compile(r"constant\((\d+)\)")
_OPERAND_NAME_RE = re.compile(r"\s*%?([\w.\-]+)\s*[,)]")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _split_computations(text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur: list[str] | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            if stripped.endswith("{") and ("->" in stripped):
                m = _COMP_HEADER_RE.match(stripped)
                if m:
                    cur = []
                    comps[m.group(1)] = cur
        else:
            if stripped == "}":
                cur = None
            else:
                cur.append(stripped)
    return comps


def _parse_instruction(line: str) -> tuple[str, str, str, str] | None:
    """(name, shape, opcode, rest-of-line) for an instruction line, or None.

    The shape can be a tuple containing ``/*index=N*/`` comments (which hold
    ``=`` and defeat any non-greedy regex), so tuple shapes are scanned for
    their balancing close paren instead.
    """
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    name, rhs = m.groups()
    if rhs.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        shape, tail = rhs[: end + 1], rhs[end + 1:]
    else:
        cut = rhs.find(" ")
        if cut < 0:
            return None
        shape, tail = rhs[:cut], rhs[cut:]
    om = _OPCODE_RE.match(tail)
    if not om:
        return None
    opcode = om.group(1)
    rest = tail[om.end():]
    return name, shape, opcode, rest


def _dims(shape: str) -> list[int]:
    m = _SHAPE_RE.search(shape)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


def _dot_flops(shape: str, rest: str,
               shapes: dict[str, str]) -> tuple[float, str]:
    """(flops, accumulator dtype) for one dot instruction line.  The HLO
    text prints operands by name only (``dot(%a, %b)``), so ``shapes``
    maps the computation's instruction names to their shapes."""
    out_dims = _dims(shape)
    flops = 2.0
    for d in out_dims:
        flops *= d
    cm = _CONTRACT_RE.search(rest)
    m = _OPERAND_NAME_RE.match(rest)
    lhs_shape = shapes.get(m.group(1)) if m else None
    if cm is not None and lhs_shape is not None and cm.group(1):
        ldims = _dims(lhs_shape)
        for i in (int(x) for x in cm.group(1).split(",")):
            if i < len(ldims):
                flops *= ldims[i]
    dm = _SHAPE_RE.search(shape)
    dtype = dm.group(1) if dm else "f32"
    return flops, dtype


def _cond_trip_count(cond_lines: list[str]) -> int | None:
    """Fallback: compare(LT/LE) against a constant in the loop condition."""
    const = None
    direction = None
    for line in cond_lines:
        m = _CONST_RE.search(line)
        if m:
            const = int(m.group(1))
        if " compare(" in line:
            dm = re.search(r"direction=(\w+)", line)
            direction = dm.group(1) if dm else None
    if const is None:
        return None
    if direction == "LE":
        return const + 1
    return const


def analyze_hlo(text: str) -> HloReport:
    """Walk HLO text; returns trip-count-weighted FLOP/collective totals."""
    comps = _split_computations(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HEADER_RE.match(line.strip())
            if m:
                entry = m.group(1)
    report = HloReport()
    memo: dict[str, tuple[float, dict, CollectiveReport]] = {}

    def eval_comp(name: str) -> tuple[float, dict, CollectiveReport]:
        if name in memo:
            return memo[name]
        memo[name] = (0.0, {}, CollectiveReport())  # cycle guard
        flops = 0.0
        by_dtype: dict[str, float] = {}
        coll = CollectiveReport()
        shapes: dict[str, str] = {}
        for line in comps.get(name, ()):
            parsed = _parse_instruction(line)
            if parsed is None:
                continue
            iname, shape, opcode, rest = parsed
            shapes[iname] = shape
            if opcode == "dot":
                f, dt = _dot_flops(shape, rest, shapes)
                flops += f
                by_dtype[dt] = by_dtype.get(dt, 0.0) + f
            elif opcode.endswith("-done"):
                continue  # async pair: counted at -start
            elif opcode in _COLLECTIVE_KINDS or (
                    opcode.endswith("-start")
                    and opcode[:-6] in _COLLECTIVE_KINDS):
                if opcode.endswith("-start"):
                    # async spelling returns (operand, result, ctx...) — count
                    # only the payload (largest element), matching the bytes
                    # the sync spelling of the same op would report
                    kind = opcode[:-6]
                    sizes = [shape_bytes(f"{dt}[{dims}]")
                             for dt, dims in _SHAPE_RE.findall(shape)]
                    nbytes = max(sizes, default=0)
                else:
                    kind = opcode
                    nbytes = shape_bytes(shape)
                coll.add(kind, float(nbytes))
            elif opcode == "while":
                body = _CALLEE_RE["body"].search(rest)
                cond = _CALLEE_RE["condition"].search(rest)
                trip = None
                tm = _TRIP_RE.search(rest)
                if tm:
                    trip = int(tm.group(1))
                elif cond and cond.group(1) in comps:
                    trip = _cond_trip_count(comps[cond.group(1)])
                trip = trip if trip and trip > 0 else 1
                report.while_trip_counts[iname] = trip
                for callee, mult in ((body, trip), (cond, trip)):
                    if callee and callee.group(1) in comps:
                        cf, cd, cc = eval_comp(callee.group(1))
                        flops += cf * mult
                        for dt, v in cd.items():
                            by_dtype[dt] = by_dtype.get(dt, 0.0) + v * mult
                        coll = coll.merged(cc.scaled(mult))
            elif opcode == "conditional":
                bm = _CALLEE_RE["branches"].search(rest)
                names = []
                if bm:
                    names = [b.strip().lstrip("%")
                             for b in bm.group(1).split(",") if b.strip()]
                else:  # true/false computation spelling
                    names = re.findall(
                        r"(?:true|false)_computation=%?([\w.\-]+)", rest)
                # worst-case branch (upper bound, matches roofline use)
                best: tuple[float, dict, CollectiveReport] | None = None
                for bn in names:
                    if bn in comps:
                        cand = eval_comp(bn)
                        if best is None or cand[0] + cand[2].total_bytes > \
                                best[0] + best[2].total_bytes:
                            best = cand
                if best:
                    flops += best[0]
                    for dt, v in best[1].items():
                        by_dtype[dt] = by_dtype.get(dt, 0.0) + v
                    coll = coll.merged(best[2])
            else:
                for key in ("calls", "to_apply"):
                    cm = _CALLEE_RE[key].search(rest)
                    if cm and cm.group(1) in comps:
                        cf, cd, cc = eval_comp(cm.group(1))
                        flops += cf
                        for dt, v in cd.items():
                            by_dtype[dt] = by_dtype.get(dt, 0.0) + v
                        coll = coll.merged(cc)
        memo[name] = (flops, by_dtype, coll)
        return memo[name]

    if entry is not None:
        f, d, c = eval_comp(entry)
        report.dot_flops = f
        report.dot_flops_by_dtype = d
        report.collectives = c
    return report
