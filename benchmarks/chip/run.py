"""Chip benchmark of LM serving: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is ``<config>.<traffic>`` in ``BENCHMARK.json`` (see ``spec.py``).
A run, in order: points JAX's persistent compilation cache at the
checkout (``repro.launch.compile_cache``) and lets it keep every
executable; makes the cell's weights on the device from the seed; prepacks
them for the configuration's policy; builds ``repro.serve.lm.ServeEngine``
as ``repro.launch.serve`` does, with the traffic's bucket ladder and
packing, which warms those buckets and the fused decode step; serves one
short request to warm the tick path; then drives the open-loop schedule
for ``--seconds`` (``serveloop.py``).  After the window it reads the
window's peak of device memory, frees the engine, and compares a sample of
the served requests with the plain float32 reference (``refmodel.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its limit.
Without a TPU, or with fewer chips than the cell needs, it prints no
result and exits 3.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402

import arrivals  # noqa: E402
import spec  # noqa: E402

#: served tokens the correctness sample aims for, and the most requests it
#: takes to get there
SAMPLE_TOKENS = 1000
SAMPLE_MAX_REQUESTS = 12
#: the traced stretch of a --trace 1 run: where it starts in the window (a
#: share of the window), and how long it lasts at most (seconds)
TRACE_START = 0.3
TRACE_SECONDS = 6.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def enable_cache() -> str:
    """The program's cache directory, with every executable kept: JAX's
    default skips programs that compile in under a second, which is most
    of the engine's bucket and step executables."""
    import jax

    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def build(cell: spec.Cell, seed: int, *, approx: str | None = None,
          parts: dict):
    """The cell's engine, warmed, on weights made from ``seed``."""
    import jax

    import weights
    from repro.configs.base import ArchConfig
    from repro.core.approx import policy_from_flag
    from repro.dist import meshctx
    from repro.models import build_model
    from repro.serve.admission import AdmissionConfig
    from repro.serve.engine import ServeEngine

    arch = cell.config["arch"]
    tr = cell.traffic
    meshctx.set_mesh(meshctx.make_mesh((1, 1), ("data", "model")))
    model = build_model(ArchConfig(**arch),
                        policy_from_flag(approx or cell.config["approx"]))
    t = time.time()
    params = weights.to_program(weights.make(arch, seed), arch)
    jax.block_until_ready(params)
    parts["weights_s"] = time.time() - t
    t = time.time()
    params = jax.jit(model.prepack, donate_argnums=0)(params)
    jax.block_until_ready(params)
    parts["prepack_s"] = time.time() - t
    t = time.time()
    eng = ServeEngine(
        model, params, slots=tr["slots"], max_len=tr["max_len"], tp=1,
        eos_id=-1, greedy=True, temperature=1e-6, top_k=0,
        seed=int(seed) % (2 ** 31), qos=None, prepack=False,
        admission=AdmissionConfig(buckets=tuple(tr["buckets"]),
                                  pack=tr["pack"]))
    parts["engine_warmup_s"] = time.time() - t
    t = time.time()
    eng.submit(np.arange(1, 9, dtype=np.int32), 2)
    eng.run_until_drained()
    eng.done.clear()
    parts["first_request_s"] = time.time() - t
    return eng


def free(eng) -> None:
    if eng.emitter is not None:
        eng.emitter.close()
    eng.params = eng.state = eng.workload = None
    gc.collect()


# ---------------------------------------------------------------------------
# correctness: served tokens against the float32 reference
# ---------------------------------------------------------------------------


def sample(records: list, seed: int) -> list:
    """Finished requests drawn from the seed, the longest first, until the
    sample holds SAMPLE_TOKENS served tokens."""
    done = [r for r in records if r.handle is not None and r.handle.done
            and r.handle.status == "ok"]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.handle.out))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    rng.shuffle(rest)
    out, n = [longest], len(longest.handle.out)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(r)
        n += len(r.handle.out)
    return out


def compare(cell: spec.Cell, seed: int, chosen: list) -> dict:
    """By how much a served token's reference logit lies below the
    reference's best logit at that position: the widest gap over the sample,
    the mean over its served tokens, and the mean over its requests of each
    request's mean gap, the number compared.  Greedy decoding on random
    weights falls into loops that repeat one token, and with it one
    rounding decision at every position; the mean over requests lets such a
    loop weigh as one request, not as every token it repeats."""
    import jax

    import refmodel
    import weights

    arch = cell.config["arch"]
    w = weights.make(arch, seed)
    worst, total, tokens, short, per_request = 0.0, 0.0, 0, 0, []
    for r in chosen:
        out = list(r.handle.out)
        short += int(len(out) != r.out_len)
        seq = np.concatenate([r.prompt, np.asarray(out[:-1], np.int32)])
        S = refmodel.padded_len(len(seq))
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        tgt = np.full(S, -1, np.int32)
        P = len(r.prompt)
        tgt[P - 1:P - 1 + len(out)] = out
        g = np.asarray(refmodel.gaps(w, arch, jax.numpy.asarray(toks),
                                     jax.numpy.asarray(tgt)))
        worst = max(worst, float(g.max()))
        total += float(g.sum())
        tokens += len(out)
        per_request.append(float(g.sum()) / len(out))
    return {"max_logit_gap": worst, "mean_logit_gap": total / max(tokens, 1),
            "request_mean_logit_gap": (float(np.mean(per_request))
                                       if per_request else 0.0),
            "tokens_compared": tokens, "requests_compared": len(chosen),
            "short_requests": short}


def checks(cell: spec.Cell, cmp: dict) -> dict:
    """Each compared number beside its limit (``cells/<cell>.json``
    ``limits``): upper limits all but ``tokens_compared``, a lower one."""
    lim = cell.cell["limits"]
    return {k: {"value": cmp[k], "limit": lim.get(k, 0)}
            for k in ("request_mean_logit_gap", "tokens_compared",
                      "short_requests")}


def passed(c: dict) -> bool:
    return all(v["value"] >= v["limit"] if k == "tokens_compared"
               else v["value"] <= v["limit"] for k, v in c.items())


# ---------------------------------------------------------------------------
# the traced stretch
# ---------------------------------------------------------------------------


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class LayerCtx:
    """What a per-layer metric reader gets: the traced stretch of chip 0
    and what the loop dispatched in it."""

    def __init__(self, trace, work, cell: spec.Cell, peaks: dict):
        import tracereduce as tr

        spans = [s for s in trace.spans if s[0] == "bench.traced"]
        if not spans:
            raise RuntimeError("the trace holds no bench.traced span")
        _, self.lo, self.hi = spans[0]
        self.ops = tr.clip(trace.ops.get(0, []), self.lo, self.hi)
        self.kernels = tr.clip(trace.kernels.get(0, []), self.lo, self.hi)
        self.modules = tr.clip(trace.modules.get(0, []), self.lo, self.hi)
        self.spans = tr.clip(trace.spans, self.lo, self.hi)
        self.work = work
        self.arch = cell.config["arch"]
        self.config = cell.config
        self.peaks = peaks


def reduce_trace(trace_dir: str, work, cell: spec.Cell, peaks: dict):
    import tracereduce as tr

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    trace = tr.load(files[0])
    ctx = LayerCtx(trace, work, cell, peaks)
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    chips = sorted(trace.ops) or [0]
    busy = [tr.busy_ns(trace.ops.get(c, []), ctx.lo, ctx.hi) for c in chips]
    selfs = tr.self_times(ctx.ops)
    idle = tr.idle_by_span(ctx.ops, ctx.spans, ctx.lo, ctx.hi)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return metrics, {"busy_s": float(np.mean(busy)) / 1e9,
                     "window_s": (ctx.hi - ctx.lo) / 1e9}, {
        "device_ops": top(selfs), "idle_gaps": top(idle)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def window_peak(setup_peak: int, process_peak: int, tick_max: int) -> int:
    """The peak of device memory in the window.  The process's peak counts
    set-up too (bf16 weights and their int8 packing live at once), so where
    the window did not raise it the window's peak is the most in use at
    its tick boundaries."""
    return process_peak if process_peak > setup_peak else tick_max


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             approx: str | None = None, rate_rps: float | None = None,
             t_start: float = T_START, peaks: dict | None = None) -> dict:
    import jax

    import serveloop

    parts = {"imports_s": time.time() - t_start}
    counter = serveloop.CompileCounter()
    eng = build(cell, seed, approx=approx, parts=parts)
    rate = rate_rps if rate_rps is not None else cell.rate_rps
    sched = arrivals.schedule(cell.traffic, rate, seconds, seed,
                              cell.config["arch"]["vocab"])
    records = [serveloop.Record(due=q.due_s, prompt=q.prompt,
                                out_len=q.out_len) for q in sched]
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    trace_at = ((TRACE_START * seconds, min(TRACE_SECONDS, 0.5 * seconds))
                if trace else None)
    parts["setup_s"] = time.time() - t_start
    log("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    devices = jax.local_devices()
    setup_peak = serveloop.in_use(devices, "peak_bytes_in_use")
    win = serveloop.run(eng, records, seconds, clock=time.time,
                        pack=cell.traffic["pack"], counter=counter,
                        trace_at=trace_at, trace_dir=trace_dir)
    counter.close()
    mem = window_peak(setup_peak, serveloop.in_use(devices,
                                                   "peak_bytes_in_use"),
                      win.in_use_max)
    log(f"device memory: set-up peak {setup_peak} B; window peak {mem} B "
        f"(most in use after a tick {win.in_use_max} B)")
    e2e = serveloop.end_to_end(win)
    late_mean, late_max = serveloop.lateness(win)
    got_first = sum(1 for r in records if r.token_times)
    finished = sum(1 for r in records if r.handle is not None
                   and r.handle.done and r.handle.status == "ok")
    failed = sum(1 for r in records if not r.token_times or (
        r.handle.done and r.handle.status != "ok"))
    log(f"window: {len(records)} requests due at {rate:g} req/s over "
        f"{seconds:g} s; {got_first} got a first token, {finished} finished, "
        f"{failed} failed; {win.ticks} ticks; drain ended "
        f"{win.end - win.t0 - seconds:.3f} s after the window")
    log(f"generator lateness: mean {late_mean * 1e3:.3f} ms, max "
        f"{late_max * 1e3:.3f} ms")
    log(f"compiles inside the window: {win.compiles} {counter.names}")
    log(f"end to end: " + ", ".join(f"{k} {v}" for k, v in e2e.items()))
    free(eng)
    del eng
    t = time.time()
    chosen = sample(records, seed)
    cmp = compare(cell, seed, chosen)
    log(f"reference: {cmp['requests_compared']} requests, "
        f"{cmp['tokens_compared']} tokens in {time.time() - t:.3f} s; "
        f"max gap {cmp['max_logit_gap']}, mean gap {cmp['mean_logit_gap']}, "
        f"mean over requests {cmp['request_mean_logit_gap']}")
    chk = checks(cell, cmp)
    chk["compiles_in_window"] = {"value": win.compiles, "limit": 0}
    correct = passed(chk)
    dev = device_info()
    dev["memory_peak_bytes"] = mem
    result = {"correct": correct, "attempted": len(records),
              "failed": failed}
    if trace:
        peaks = peaks or spec.peaks(dev["kind"])
        metrics, dev_t, breakdown = reduce_trace(trace_dir, win.traced,
                                                 cell, peaks)
        dev.update(dev_t)
        result.update(metrics=metrics, device=dev, breakdown=breakdown)
    else:
        values = {"ttft_p90_ms": e2e["ttft_p90_ms"],
                  "itl_p95_ms": e2e["itl_p95_ms"],
                  "output_tok_s": e2e["output_tok_s"],
                  "setup_s": parts["setup_s"]}
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=dev)
    result["compared"] = cmp
    result["checks"] = chk
    if trace_dir:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def emit(result: dict) -> None:
    for k, c in result["checks"].items():
        print(f"[bench] check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    import jax

    enable_cache()
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"[bench] {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {dev['count']} {dev['platform']} device(s)",
              file=sys.stderr)
        return 3
    from repro.kernels import dispatch

    dispatch.set_backend(None)
    if dispatch.resolved_backend() != "pallas":
        print("[bench] the kernel route did not resolve to pallas",
              file=sys.stderr)
        return 3
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
