"""The engine's spans on the profiler's clock, and the chip benchmark's
readers of them, on the CPU.

A tiny ``ServeEngine`` with packed, bucketed admission serves a few
prompts inside a profiler session, ticked the way the chip benchmark's
loop ticks it (``bench.traced`` around the stretch, ``bench.tick`` around
each tick).  The trace is reduced with ``tracereduce.load`` and the
readers in ``benchmarks/chip/metrics``.  A trace cut from a chip run
(``benchmarks/chip/testdata/trace_small.json``) checks that the program's
spans leave the benchmark's existing readers as they were.
"""
import json
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.models import build_model
from repro.obs.trace import Tracer
from repro.serve.admission import AdmissionConfig, bucket_for
from repro.serve.engine import ServeEngine

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import enginespans  # noqa: E402
import run as bench_run  # noqa: E402
import serveloop  # noqa: E402
import spec  # noqa: E402
import tracereduce  # noqa: E402

NEW = ("engine.admit_idle_ms_per_tick", "engine.step_idle_ms_per_tick",
       "engine.harvest_idle_ms_per_tick", "admission.queue_wait_ms",
       "prefill.pad_share")
EXISTING = ("engine.host_gap_ms_per_tick", "prefill.ms_per_1k_tok",
            "prefill.mfu", "decode.step_ms", "decode.mfu",
            "flash_decode_roofline", "axqmm_roofline")
PHASES = ("engine.admit", "engine.decode_tick", "engine.harvest")
BUCKETS, PACK = (8, 16, 32), 2
PROMPT_LENS = (3, 7, 12, 20, 5, 30)
CELL = "qwen2.5-3b-axq8.chat"


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def served():
    """One profiled run: the engine's trace file, its spans, the engine
    and its (disabled) tracer, and the number of ticks."""
    cfg = get_config("tinyllama-1.1b-smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), tp=1)
    tracer = Tracer(enabled=False)
    eng = ServeEngine(model, params, slots=4, max_len=64, seed=0,
                      tracer=tracer,
                      admission=AdmissionConfig(buckets=BUCKETS, pack=PACK))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32)
               for n in PROMPT_LENS]
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ticks = 0
    try:
        with TraceAnnotation("bench.traced"):
            for p in prompts:
                eng.submit(p, 3)
            while eng.queue or any(r is not None for r in eng.slot_req):
                with TraceAnnotation("bench.tick"):
                    eng.tick()
                ticks += 1
    finally:
        jax.profiler.stop_trace()
        eng.emitter.close()
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    yield SimpleNamespace(path=str(path), prompts=prompts, eng=eng,
                          tracer=tracer, ticks=ticks,
                          spans=enginespans.load(str(path))[1])
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)


def _ctx(path: str):
    trace = tracereduce.load(path)
    return bench_run.LayerCtx(trace, serveloop.TracedWork(),
                              spec.find_cell(CELL), {})


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_one_tick_span_per_tick_with_phases_inside(served):
    ticks = _named(served.spans, "engine.tick")
    assert len(ticks) == served.ticks
    steps = 0
    for t in ticks:
        phases = [next((s for s in served.spans if s[0] == name
                        and _inside(s, t)), None) for name in PHASES]
        assert phases[0] is not None           # every tick admits
        if phases[1] is None:
            continue
        steps += 1
        admit, step, harvest = phases
        # siblings, in order
        assert admit[2] <= step[1] and step[2] <= harvest[1]
        for child in ("engine.dispatch", "engine.sync"):
            assert sum(1 for s in served.spans
                       if s[0] == child and _inside(s, step)) == 1
    assert steps == served.eng.stats.decode_steps


def test_prefill_args_match_the_prompts(served):
    calls = _named(served.spans, "engine.prefill")
    args = [c[3] for c in calls]
    assert sum(a["requests"] for a in args) == len(served.prompts)
    assert sum(a["tokens"] for a in args) == sum(
        n - 1 for n in PROMPT_LENS)
    for a in args:
        assert a["padded"] == PACK * a["bucket"]
        assert a["bucket"] in BUCKETS and a["tokens"] <= a["padded"]
        assert a["waited_ms"] >= 0
    # a packed call's bucket is the smallest that holds its longest row
    lens = sorted(n - 1 for n in PROMPT_LENS)
    assert max(a["bucket"] for a in args) == bucket_for(lens[-1], BUCKETS)


def test_admit_and_harvest_args_count_the_tick(served):
    admits = [s[3] for s in _named(served.spans, "engine.admit")]
    assert sum(a["requests"] for a in admits) == len(served.prompts)
    assert sum(a["calls"] for a in admits) == len(
        _named(served.spans, "engine.prefill"))
    harvests = [s[3] for s in _named(served.spans, "engine.harvest")]
    assert sum(a["finished"] for a in harvests) == len(served.prompts)
    assert sum(a["emitted"] for a in harvests) == sum(
        len(r.out) for r in served.eng.done)


def test_disabled_ring_buffer_records_nothing(served):
    assert served.spans
    assert served.tracer.events == []


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_the_trace_file(served, name):
    """Through the route ``run.py`` gives them: the context carries no
    engine spans, so the reader finds the trace file by its stretch."""
    ctx = _ctx(served.path)
    assert not hasattr(ctx, "engine")
    value = bench_run.load_reader(name)(ctx)
    assert isinstance(value, float) and value >= 0
    if name == "prefill.pad_share":
        tokens = sum(n - 1 for n in PROMPT_LENS)
        padded = sum(s[3]["padded"]
                     for s in _named(served.spans, "engine.prefill"))
        assert value == pytest.approx(100 * (1 - tokens / padded))


@pytest.mark.parametrize("name", NEW)
def test_new_readers_none_without_engine_spans(served, name):
    ctx = _ctx(served.path)
    ctx.engine = []
    assert bench_run.load_reader(name)(ctx) is None
    # a trace file that is not the context's stretch is not read
    ctx = _ctx(served.path)
    ctx.lo -= 1
    assert bench_run.load_reader(name)(ctx) is None


# ---------------------------------------------------------------------------
# a trace cut from a chip run
# ---------------------------------------------------------------------------


def _recorded_ctx():
    rec = json.loads((CHIP / "testdata" / "trace_small.json").read_text())
    lo, hi = rec["window"]
    cell = spec.find_cell(CELL)
    work = serveloop.TracedWork(steps=[list(range(100, 116))] * 2,
                                prefills=[[300, 40]])
    ctx = SimpleNamespace(
        lo=lo, hi=hi, work=work, arch=cell.config["arch"],
        config=cell.config, peaks=spec.peaks("TPU v5 lite"),
        **{k: [tuple(e) for e in rec[k]]
           for k in ("ops", "kernels", "modules", "spans")})
    # the cut holds two decode steps and no admission: give the prefill
    # readers an executable to read
    ctx.modules.append(("jit__prefill_batch_impl", lo + 1000, lo + 2_001_000))
    return ctx


def _phases_tiling(ctx, admit_ns: int, harvest_ns: int):
    """Engine spans laid over each ``bench.tick``: the tick, admit at its
    start, harvest at its end, the step in between."""
    out = []
    for i, (_, s, e) in enumerate(_named(ctx.spans, "bench.tick")):
        out += [("engine.tick", s, e, {"tick": i}),
                ("engine.admit", s, s + admit_ns, {}),
                ("engine.prefill", s + 10, s + admit_ns - 10,
                 {"requests": 1, "tokens": 40, "padded": 128,
                  "bucket": 64, "waited_ms": 2.5}),
                ("engine.decode_tick", s + admit_ns, e - harvest_ns, {}),
                ("engine.harvest", e - harvest_ns, e, {})]
    return out


@pytest.mark.parametrize("name", EXISTING)
def test_existing_readers_unmoved_by_engine_spans(name):
    """The seven accepted readers select the benchmark's own spans by
    name: the program's spans beside them change no value."""
    read = bench_run.load_reader(name)
    bare = _recorded_ctx()
    before = read(bare)
    assert before is not None
    ctx = _recorded_ctx()
    engine = _phases_tiling(ctx, 500_000, 800_000)
    ctx.engine = engine
    ctx.spans = ctx.spans + [(n, s, e) for n, s, e, _ in engine]
    assert read(ctx) == before


def test_phases_that_tile_the_tick_close_the_host_gap():
    ctx = _recorded_ctx()
    ctx.engine = _phases_tiling(ctx, 500_000, 800_000)
    split = [bench_run.load_reader(n)(ctx) for n in NEW[:3]]
    gap = bench_run.load_reader("engine.host_gap_ms_per_tick")(ctx)
    assert all(v >= 0 for v in split)
    assert sum(split) == pytest.approx(gap, rel=1e-9)
    # the chip trace's device is busy through most of each step
    assert split[1] < gap
    assert bench_run.load_reader("admission.queue_wait_ms")(ctx) == 2.5
    assert bench_run.load_reader("prefill.pad_share")(ctx) == pytest.approx(
        100 * (1 - 40 / 128))
