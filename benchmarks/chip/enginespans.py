"""The program's own spans in a profiler trace, for the per-layer metrics
that split each engine tick's device-idle time by phase.

``repro.obs.trace`` turns every span of the serve engine into a profiler
annotation named ``engine.<span>``, with the span's args as its stats:
``engine.tick`` around each tick, and inside it ``engine.admit`` (with
``engine.prefill`` per admission call), ``engine.decode_tick`` (with
``engine.dispatch`` and ``engine.sync``) and ``engine.harvest``.

``of(ctx)`` gives a metric reader those spans in the traced stretch as
``(name, start_ns, end_ns, args)``.  It takes them from ``ctx.engine``
where the context carries that field; else it reads them from the trace
file whose ``bench.traced`` span is the context's stretch, among the
``bench_trace_*`` directories that ``run.py`` writes in the temporary
directory and removes only once every reader has run.  A program that
opens no such spans gives an empty list, and its readers return None.
"""

from __future__ import annotations

import functools
import glob
import os
import tempfile

import tracereduce as tr

PREFIX = "engine."
TICK = "engine.tick"
PREFILL = "engine.prefill"


@functools.lru_cache(maxsize=4)
def load(path: str) -> tuple:
    """``(traced, spans)`` of one ``.xplane.pb``: the ``(start, end)`` of
    its ``bench.traced`` span (None without one), and its host events
    named ``engine.*`` with their stats as a dict."""
    from jax.profiler import ProfileData

    traced, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                s = int(e.start_ns)
                end = int(e.start_ns + e.duration_ns)
                if name.startswith(PREFIX):
                    args = {k: v for k, v in e.stats}
                    spans.append((name, s, end, args))
                elif name == "bench.traced" and traced is None:
                    traced = (s, end)
    return traced, spans


def _from_trace_file(lo: int, hi: int) -> list:
    root = tempfile.gettempdir()
    files = glob.glob(os.path.join(root, "bench_trace_*", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        traced, spans = load(path)
        if traced == (lo, hi):
            return spans
    return []


def clip(evs: list, lo: int, hi: int) -> list:
    return [(n, max(s, lo), min(e, hi), a) for n, s, e, a in evs
            if e > lo and s < hi]


def of(ctx) -> list:
    """The ``engine.*`` spans of the traced stretch, clipped to it."""
    evs = getattr(ctx, "engine", None)
    if evs is None:
        evs = _from_trace_file(ctx.lo, ctx.hi)
    return clip(evs, ctx.lo, ctx.hi)


def idle_ms_per_tick(ctx, name: str):
    """Device-idle ms inside the spans called ``name`` (their union, so a
    span nested in one of its own name counts once), per ``engine.tick``
    that starts in the stretch; None without engine ticks."""
    spans = of(ctx)
    ticks = sum(1 for n, s, _, _ in spans
                if n == TICK and ctx.lo <= s < ctx.hi)
    if not ticks:
        return None
    union = [(name, s, e) for s, e in
             tr.merged([(n, s, e) for n, s, e, _ in spans if n == name])]
    return tr.idle_inside(ctx.ops, union, name, ctx.lo, ctx.hi) / ticks / 1e6


def prefill_sums(ctx) -> dict:
    """``requests``, ``tokens``, ``padded`` and ``waited_ms``, each summed
    over the ``engine.prefill`` spans of the stretch (0 without any)."""
    keys = ("requests", "tokens", "padded", "waited_ms")
    out = dict.fromkeys(keys, 0)
    for n, _, _, args in of(ctx):
        if n == PREFILL:
            for k in keys:
                out[k] += args.get(k, 0)
    return out
