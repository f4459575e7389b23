"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain lists of ``(name, start_ns,
end_ns)``: the device's ops, its Pallas kernels by kind and its
executables (one list per chip), and the benchmark's own host spans
(``bench.*``).  Everything after that is plain
interval arithmetic on those lists, so it is checked on a small recorded
trace without a chip:

  busy_ns          union of the intervals in which an op ran, in a window
  self_times       op time by name, an enclosing op (a while loop) charged
                   only for what its nested ops leave uncovered
  time_matching    summed time and count of ops whose name matches
  idle_by_span     each idle stretch of the device charged to the innermost
                   benchmark span the host was in ("host" where none)
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

Interval = tuple[str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


#: the AXQ GEMM's calling convention (``kernels/axqmm.py``): the runtime
#: effective-bits scalar, then the int8 activation tile.  The program gives
#: these ``pallas_call``s no name, so the trace names them after whatever
#: encloses them (``closed_call.44``, ``step.1``).
AXQ_CALL = re.compile(r"custom-call\(s32\[1\]\{[^}]*\} %[^,]+, s8\[")
NAMED_KERNELS = ("flash_decode", "flash_attention")


@dataclass
class Trace:
    ops: dict[int, list[Interval]] = field(default_factory=dict)
    kernels: dict[int, list[Interval]] = field(default_factory=dict)
    modules: dict[int, list[Interval]] = field(default_factory=dict)
    spans: list[Interval] = field(default_factory=list)


def op_name(text: str) -> str:
    """``%flash_decode.7 = f32[...] custom-call(...)`` -> ``flash_decode.7``:
    a device op's event carries its whole HLO instruction, whose operand
    list names other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def kernel_of(text: str) -> str | None:
    """The Pallas kernel a device op is: ``flash_decode`` and
    ``flash_attention`` by the name their jitted wrappers give the call,
    ``axqmm`` by its calling convention; None for any other op."""
    head = text.split(", custom_call_target=", 1)[0]
    if " custom-call(" not in head:
        return None
    name = op_name(text)
    for k in NAMED_KERNELS:
        if name.startswith(k):
            return k
    return "axqmm" if AXQ_CALL.search(head) else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            raw = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            evs = [(op_name(n), s, e) for n, s, e in raw]
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                out.ops.setdefault(chip, []).extend(evs)
                out.kernels.setdefault(chip, []).extend(
                    (k, s, e) for k, s, e in
                    ((kernel_of(n), s, e) for n, s, e in raw) if k)
            elif m and line.name == MODULES_LINE:
                out.modules.setdefault(int(m.group(1)), []).extend(evs)
            elif not m:
                out.spans.extend(e for e in evs
                                 if e[0].startswith(SPAN_PREFIX))
    return out


def clip(evs: list[Interval], lo: int, hi: int) -> list[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def merged(evs: list[Interval]) -> list[tuple[int, int]]:
    """The union of the intervals, as sorted disjoint (start, end)."""
    out: list[list[int]] = []
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(evs: list[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(clip(evs, lo, hi)))


def self_times(evs: list[Interval]) -> dict[str, int]:
    """Op time by name, nested ops subtracted from the op enclosing them."""
    out: dict[str, int] = {}
    stack: list[list] = []      # [name, start, end, child_ns]

    def close(item):
        name, start, end, child = item
        out[name] = out.get(name, 0) + (end - start) - child

    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def time_matching(evs: list[Interval], pattern: str) -> tuple[int, int]:
    """(summed ns, count) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e - s for n, s, e in evs if rx.search(n)]
    return sum(hits), len(hits)


def _covered(busy: list[tuple[int, int]], a: int, b: int) -> int:
    """ns of [a, b) covered by the sorted disjoint intervals ``busy``."""
    i = bisect.bisect_right(busy, (a, a)) - 1
    total = 0
    for s, e in busy[max(i, 0):]:
        if s >= b:
            break
        total += max(0, min(e, b) - max(s, a))
    return total


def idle_by_span(evs: list[Interval], spans: list[Interval], lo: int,
                 hi: int) -> dict[str, int]:
    """Device-idle ns in [lo, hi) by the innermost ``bench.*`` span that
    covers it; "host" for idle time outside every span."""
    busy = merged(clip(evs, lo, hi))
    spans = clip(spans, lo, hi)
    # the timeline cut at every span edge; each piece has one innermost span
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)})
    out: dict[str, int] = {}
    for p, q in zip(cuts, cuts[1:]):
        idle = (q - p) - _covered(busy, p, q)
        if idle <= 0:
            continue
        inner, width = "host", None
        for n, s, e in spans:
            if s <= p and e >= q and (width is None or e - s < width):
                inner, width = n, e - s
        out[inner] = out.get(inner, 0) + idle
    return out


def in_spans(spans: list[Interval], name: str, lo: int, hi: int) -> int:
    """Number of spans called ``name`` that start in [lo, hi)."""
    return sum(1 for n, s, _ in spans if n == name and lo <= s < hi)


def idle_inside(evs: list[Interval], spans: list[Interval], name: str,
                lo: int, hi: int) -> int:
    """Device-idle ns inside the spans called ``name``."""
    busy = merged(clip(evs, lo, hi))
    return sum((e - s) - _covered(busy, s, e)
               for n, s, e in clip(spans, lo, hi) if n == name)
