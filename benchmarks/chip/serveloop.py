"""The open loop: submit each request when it is due, tick the engine, and
time every output token on the host.

One tick of ``ServeCore`` admits what is queued into free slots (bucketed,
packed prefill) and runs one fused decode step; it returns once the step's
tokens are on the host, so the device is idle at every tick boundary.  The
loop times from when a request was *due*, so a stall that delays later
submissions counts against them.  After the window it goes on ticking,
with no new arrivals, until every request due in the window has its first
token or ``drain_s`` has passed.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock):
``bench.tick`` around each engine tick, ``bench.submit`` around each
submission, ``bench.wait`` while the loop sleeps until the next arrival,
``bench.traced`` around the stretch a ``--trace 1`` run records.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

DRAIN_S = 60.0


@dataclass
class Record:
    due: float                    # absolute, engine clock
    prompt: np.ndarray
    out_len: int
    handle: object = None         # the engine's Request
    submitted: float = 0.0
    token_times: list = field(default_factory=list)


@dataclass
class TracedWork:
    """What the loop dispatched inside the traced stretch."""
    steps: list = field(default_factory=list)      # per step: positions
    prefills: list = field(default_factory=list)   # per call: prefix lens


@dataclass
class Window:
    t0: float
    seconds: float
    records: list
    end: float                    # when the drain stopped
    ticks: int
    compiles: int
    in_use_max: int = 0           # most bytes in use on a chip after a tick
    traced: Optional[TracedWork] = None


class CompileCounter:
    """Counts JAX compile-path events (tracing, lowering, backend compile,
    persistent-cache reads) while ``active``; ``close`` stops listening."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.names: dict[str, int] = {}

        def listen(event, *_a, **_k):
            if self.active and "compil" in event:
                self.count += 1
                self.names[event] = self.names.get(event, 0) + 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def run(eng, records: list[Record], seconds: float, *, clock, pack: int,
        counter: CompileCounter, trace_at: Optional[tuple] = None,
        trace_dir: Optional[str] = None, drain_s: float = DRAIN_S) -> Window:
    """Drive ``eng`` through ``records`` (``due`` relative to the window's
    start).  ``trace_at = (start_s, length_s)`` records a profiler trace of
    that stretch into ``trace_dir``."""
    import jax

    t0 = clock()
    for r in records:
        r.due += t0
    end_window = t0 + seconds
    nxt = 0
    live: list[Record] = []
    queued: list[Record] = []
    traced = None
    tracing = False
    trace_ann = None
    ticks = 0
    in_use_max = 0
    devices = jax.local_devices()
    counter.active = True
    while True:
        now = clock()
        if trace_at is not None and traced is None and \
                now >= t0 + trace_at[0] and now < end_window:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
            trace_ann = TraceAnnotation("bench.traced")
            trace_ann.__enter__()
            traced, tracing = TracedWork(), True
            trace_stop = clock() + trace_at[1]
        if tracing and now >= trace_stop:
            trace_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        while nxt < len(records) and records[nxt].due <= now:
            r = records[nxt]
            with TraceAnnotation("bench.submit"):
                r.handle = eng.submit(r.prompt, r.out_len)
            r.submitted = clock()
            live.append(r)
            queued.append(r)
            nxt += 1
        busy = eng.queue or any(s is not None for s in eng.slot_req)
        if not busy:
            if nxt >= len(records):
                break
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(records[nxt].due - clock(), 0.05)))
            continue
        with TraceAnnotation("bench.tick"):
            eng.tick()
        ticks += 1
        in_use_max = max(in_use_max, in_use(devices))
        t = clock()
        admitted = [r for r in queued if r.handle.t_admitted > 0]
        if admitted:
            queued = [r for r in queued if r not in admitted]
        positions = []
        for r in live:
            n = len(r.handle.out)
            if n > len(r.token_times):
                r.token_times.extend([t] * (n - len(r.token_times)))
                positions.append(len(r.prompt) - 1 + n - 1)
        live = [r for r in live if not r.handle.done]
        if tracing:
            if positions:
                traced.steps.append(positions)
            lens = [r.handle.admitted_units for r in admitted]
            traced.prefills.extend(lens[i:i + pack]
                                   for i in range(0, len(lens), pack))
        if t >= end_window and nxt >= len(records):
            waiting = any(not r.token_times for r in records)
            if not waiting or t >= end_window + drain_s:
                break
    if tracing:
        trace_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    counter.active = False
    return Window(t0=t0, seconds=seconds, records=records, end=clock(),
                  ticks=ticks, compiles=counter.count, in_use_max=in_use_max,
                  traced=traced)


def in_use(devices, key: str = "bytes_in_use") -> int:
    """``key`` of the devices' memory statistics, on the fullest chip (0
    where the platform keeps none)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def _options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


# ---------------------------------------------------------------------------
# end-to-end metrics of a window
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(w: Window) -> dict:
    """ttft over every request due in the window (a request with no first
    token counts with the time it had waited when the drain stopped); gaps
    between consecutive tokens that end in the window; tokens delivered in
    the window over its length."""
    hi = w.t0 + w.seconds
    mid = w.t0 + w.seconds / 2
    ttft, late, gaps, tokens = [], [], [], 0
    for r in w.records:
        first = r.token_times[0] if r.token_times else w.end
        ttft.append(first - r.due)
        if r.due >= mid:
            late.append(first - r.due)
        tt = r.token_times
        gaps.extend(b - a for a, b in zip(tt, tt[1:]) if w.t0 <= b < hi)
        tokens += sum(1 for t in tt if w.t0 <= t < hi)
    return {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
            "output_tok_s": tokens / w.seconds,
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p90_second_half_ms": (percentile(late, 90) * 1e3
                                        if late else None),
            "n_gaps": len(gaps), "n_tokens": tokens}


def lateness(w: Window) -> tuple[float, float]:
    """(mean, max) seconds by which submissions trailed their due time."""
    late = [r.submitted - r.due for r in w.records if r.handle is not None]
    return (float(np.mean(late)), float(np.max(late))) if late else (0.0, 0.0)
