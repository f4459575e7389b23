"""Serving entrypoint: continuous-batching engine over a selected workload.

  python -m repro.launch.serve --arch tinyllama-1.1b-smoke --requests 16
  # temperature/top-k sampling, per-request latency table, QoS degree loop:
  python -m repro.launch.serve --arch tinyllama-1.1b-smoke \
      --temperature 0.8 --top-k 40 --seed 7 --qos --metrics
  # per-layer approximation plan (repro.tune): serve the tuned degree
  # ladder, QoS stepping whole calibrated configurations:
  python -m repro.launch.serve --arch tinyllama-1.1b-smoke \
      --plan plans/approx_plan.json --qos --metrics
  # streaming DSP/vision pipeline (Ch. 7 accelerators) on the same engine:
  python -m repro.launch.serve --workload stream --requests 8 --qos --metrics
  # elastic sharded fleet: 3 tensor-parallel replicas, int8 ring
  # collectives, survive a seeded replica loss live (docs/distributed_serving.md):
  python -m repro.launch.serve --replicas 3 --tp 2 --ring \
      --faults replica_loss=0.02 --metrics

``--workload lm`` (default) decodes tokens; ``--workload stream`` serves
frame clips through the approximate FIR + conv2d pipeline
(repro.serve.stream) — same slot lifecycle, continuous batching, plan
ladder, QoS controller, and observability surfaces.

``--replicas N`` (N > 1) lifts either workload onto a
:class:`repro.dist.fleet.FleetSupervisor`: N data-parallel replica
engines — for lm, each a :class:`repro.serve.sharded.ShardedServeEngine`
on its own ``(1, tp)`` mesh slice — with least-loaded routing, fleet-level
``replica_loss`` fault injection, queue migration + in-flight rewind on
replica death, and ``plan_rescale`` survivor-mesh replanning.

On a TPU pod the full configs drive the same engine with the decode
sharding proven by the dry-run (KV cache TP over the model axis, optional
int8 cache via REPRO_KV_INT8=1).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.approx import policy_from_flag
from repro.core.dynamic import QoSController
from repro.dist import meshctx
from repro.kernels import dispatch as kdispatch
from repro.launch import compile_cache
from repro.models import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.engine import ServeEngine
from repro.serve.metrics import summarize


def _policy_from_args(args):
    """ServePolicy from the CLI flags, or None when no policy flag is set."""
    if (args.deadline_ms is None and args.retries is None
            and args.shed is None and not args.brownout):
        return None
    from repro.resil import ServePolicy

    if args.brownout and not args.qos:
        raise SystemExit("--brownout degrades the QoS ladder under "
                         "overload: it needs --qos (or --plan with "
                         "--qos) to have a ladder to walk")
    return ServePolicy(
        deadline_ms=args.deadline_ms,
        max_retries=args.retries if args.retries is not None else 2,
        max_queue=args.shed,
        brownout=args.brownout)


def _admission_from_args(args):
    """AdmissionConfig from the CLI flags, or None when no admission flag
    is set (the engine then runs the legacy exact-length admission path).
    ``--prefill-buckets auto`` derives the power-of-two ladder from the
    engine's max_len."""
    if not (args.prefill_buckets or args.pack > 1 or args.chunk_tokens):
        return None
    from repro.serve.admission import AdmissionConfig

    buckets: tuple = ()
    if args.prefill_buckets and args.prefill_buckets != "auto":
        buckets = tuple(int(b) for b in args.prefill_buckets.split(","))
    return AdmissionConfig(buckets=buckets, pack=max(args.pack, 1),
                           chunk_tokens=args.chunk_tokens)


def _resil_kwargs(args) -> dict:
    """Build the engine's resilience kwargs from the CLI flags (shared by
    both workloads — the resil subsystem is workload-generic).  Empty dict
    when no resilience flag is set: the engine then compiles and runs the
    exact legacy path."""
    kw: dict = {}
    if args.faults:
        from repro.resil import FaultPlan, FaultSpec, GuardConfig

        kw["faults"] = FaultPlan(FaultSpec.parse(args.faults),
                                 seed=args.fault_seed)
        kw["guards"] = GuardConfig()
    policy = _policy_from_args(args)
    if policy is not None:
        kw["policy"] = policy
    return kw


def _fleet_fault_plans(args, replicas: int):
    """Split ``--faults`` for a fleet: ``replica_loss`` is drawn by one
    fleet-level plan (the supervisor binds it to the replica count); the
    engine-level kinds become one plan per replica, seed-offset so the
    replicas see distinct storms, with ``replica_loss`` zeroed — engines
    record-but-ignore the kind, so leaving it in would silently drop the
    configured rate."""
    if not args.faults:
        return None, [None] * replicas
    import dataclasses

    from repro.resil import FaultPlan, FaultSpec

    spec = FaultSpec.parse(args.faults)
    fleet_plan = (FaultPlan(FaultSpec(replica_loss=spec.replica_loss),
                            seed=args.fault_seed)
                  if spec.replica_loss else None)
    espec = dataclasses.replace(spec, replica_loss=0.0)
    if not any((espec.seu_state, espec.seu_param, espec.nan, espec.spike,
                espec.drop)):
        return fleet_plan, [None] * replicas
    return fleet_plan, [FaultPlan(espec, seed=args.fault_seed + rid)
                        for rid in range(replicas)]


def _print_resil(eng, done) -> None:
    """Resilience summary lines (only when something happened)."""
    s = eng.stats
    def fam_total(fam) -> int:
        return sum(int(c.value) for c in fam.children.values())

    counts = {
        "faults_injected": fam_total(s.c_faults),
        "guard_trips": fam_total(s.c_guard_trips),
        "retries": int(s.c_retries.value),
        "shed": fam_total(s.c_shed),
        "deadline_miss": fam_total(s.c_deadline_miss),
        "brownout_rungs": int(s.c_brownout.value),
        "param_scrubs": int(s.c_scrubs.value),
    }
    if any(counts.values()):
        line = " ".join(f"{k}={v}" for k, v in counts.items() if v)
        print(f"[launch.serve]   resil: {line}")


def _write_obs(args) -> None:
    """Shared exit-time observability dumps (both workloads)."""
    if args.trace_out:
        obs_trace.get_tracer().write(args.trace_out)
        print(f"[launch.serve] wrote Chrome trace -> {args.trace_out}")
    if args.metrics_out:
        obs_metrics.get_registry().write(args.metrics_out)
        print(f"[launch.serve] wrote Prometheus metrics -> {args.metrics_out}")


def _serve_stream(args) -> None:
    """--workload stream: frame clips through the DSP/vision pipeline."""
    from repro.serve.stream import StreamAdapter, StreamServeEngine, make_clip

    adapter = StreamAdapter()
    cfg = adapter.cfg
    plan = None
    if args.plan is not None:
        from repro.tune import ApproxPlan

        plan = ApproxPlan.load(args.plan)      # ServeCore validates vs cfg
    qos = QoSController(
        ladder=[{"degrees": [e] * (cfg.n_layers + 1)} for e in (8, 7, 6, 5)],
        low_water=0.25, high_water=0.75, cooldown_steps=8,
    ) if args.qos else None
    registry = obs_metrics.get_registry() if args.metrics_out else None
    eng = StreamServeEngine(adapter, slots=args.slots, seed=args.seed,
                            qos=qos, plan=plan, registry=registry,
                            quality_every=args.quality_every,
                            **_resil_kwargs(args))
    t0 = time.time()
    for i in range(args.requests):
        eng.submit(make_clip(args.frames, cfg.frame, q=cfg.q, seed=i))
    done = eng.run_until_drained()
    dt = time.time() - t0
    frames = sum(len(r.out) for r in done)
    print(f"[launch.serve] stream: {len(done)} clips, {frames} frames, "
          f"{dt:.2f}s ({frames / max(dt, 1e-9):.1f} frames/s) "
          f"[kernels={kdispatch.resolved_backend()}]")
    if args.metrics:
        for k, v in summarize(done, eng.stats, wall_s=dt).items():
            print(f"[launch.serve]   {k:24s} {v}")
        if qos is not None:
            print(f"[launch.serve]   degree ladder visits: "
                  f"{[e for _, e in list(eng.stats.degree_history)[-8:]]} "
                  f"(last 8)")
        _print_resil(eng, done)
    _write_obs(args)


def _serve_fleet(args) -> None:
    """--replicas N: a data-parallel fleet of engines under a
    FleetSupervisor — per-replica mesh slices, least-loaded routing,
    replica-loss survival (migrate + rewind + plan_rescale).  Both
    workloads ride the same supervisor; lm replicas are sharded engines
    (tensor-parallel over the replica's model axis, optional int8 ring
    collectives on the decode path)."""
    from repro.dist.fleet import FleetSupervisor
    from repro.resil import GuardConfig

    tp = args.tp if args.tp else int(args.mesh.split("x")[1])
    fleet_plan, engine_plans = _fleet_fault_plans(args, args.replicas)
    policy = _policy_from_args(args)
    registry = obs_metrics.get_registry() if args.metrics_out else None

    def engine_kwargs(rid: int) -> dict:
        kw: dict = {"slots": args.slots, "seed": args.seed,
                    "registry": registry,
                    "quality_every": args.quality_every,
                    "prepack": not args.no_prepack}
        if engine_plans[rid] is not None:
            kw["faults"] = engine_plans[rid]
            kw["guards"] = GuardConfig()
        if policy is not None:
            kw["policy"] = policy
        return kw

    if args.workload == "stream":
        from repro.serve.stream import (StreamAdapter, StreamServeEngine,
                                        make_clip)

        adapter = StreamAdapter()
        scfg = adapter.cfg
        ladder = [{"degrees": [e] * (scfg.n_layers + 1)}
                  for e in (8, 7, 6, 5)]

        def build(mesh, rid):
            # QoS controllers are stateful: one per replica, never shared
            qos = QoSController(ladder=ladder, low_water=0.25,
                                high_water=0.75, cooldown_steps=8
                                ) if args.qos else None
            return StreamServeEngine(adapter, qos=qos, **engine_kwargs(rid))

        payloads = [make_clip(args.frames, scfg.frame, q=scfg.q, seed=i)
                    for i in range(args.requests)]
        budget = None
        unit = "frames"
    else:
        from repro.serve.sharded import ShardedServeEngine

        cfg = get_config(args.arch)
        plan = None
        if args.plan is not None:
            from repro.tune import ApproxPlan

            plan = ApproxPlan.load(args.plan)
            plan.validate_for(cfg)
            apolicy = plan.policy(dynamic=True)
        else:
            try:
                apolicy = policy_from_flag(args.approx, dynamic=args.qos)
            except ValueError as e:
                raise SystemExit(str(e))
        model = build_model(cfg, apolicy)
        params = model.init(jax.random.PRNGKey(0), tp=tp)

        admission = _admission_from_args(args)

        def build(mesh, rid):
            qos = QoSController(ladder=[{"ebits": e} for e in (8, 7, 6, 5)],
                                low_water=0.25, high_water=0.75,
                                cooldown_steps=8) if args.qos else None
            return ShardedServeEngine(
                model, params, mesh=mesh, ring=args.ring, max_len=512,
                eos_id=args.eos_id, greedy=args.temperature <= 0,
                temperature=max(args.temperature, 1e-6), top_k=args.top_k,
                qos=qos, plan=plan, admission=admission,
                **engine_kwargs(rid))

        rng = np.random.default_rng(args.seed)
        payloads = [rng.integers(0, cfg.vocab, int(rng.integers(2, 10)))
                    for _ in range(args.requests)]
        budget = args.new_tokens
        unit = "tokens"

    sup = FleetSupervisor(build, args.replicas, tp=tp, faults=fleet_plan,
                          policy=policy, registry=registry,
                          rescale_ms=args.rescale_ms,
                          route_by=args.route_by)
    t0 = time.time()
    for p in payloads:
        sup.submit(p, budget)
    done = sup.run_until_drained()
    dt = time.time() - t0
    units = sum(len(r.out) for r in done)
    counts = sup.status_counts()
    status = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    print(f"[launch.serve] fleet: {len(done)} reqs on {args.replicas} "
          f"replica(s) x tp={tp}, {len(sup.live)} up at exit, {units} "
          f"{unit}, {dt:.2f}s [{status}] "
          f"[kernels={kdispatch.resolved_backend()}]")
    if sup.rescales:
        plan = sup.rescales[-1]
        print(f"[launch.serve]   last rescale: data={plan.data} "
              f"model={plan.model} idle={plan.idle_devices} "
              f"({len(sup.rescales)} rescale(s))")
    if args.metrics:
        events: dict = {}
        for _, name, _ in sup.resil_log:
            events[name] = events.get(name, 0) + 1
        if events:
            line = " ".join(f"{k}={v}" for k, v in sorted(events.items()))
            print(f"[launch.serve]   fleet events: {line}")
        for r in sup.replicas:
            served = len(r.engine.done)
            state = "up" if r.alive else f"dead@tick{r.died_at}"
            print(f"[launch.serve]   replica {r.rid}: {state}, "
                  f"{served} reqs finished")
    _write_obs(args)


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm", choices=("lm", "stream"),
                    help="what to serve: lm (token decode, default) or "
                         "stream (frame-by-frame approximate DSP/vision "
                         "pipeline — repro.serve.stream)")
    ap.add_argument("--arch", default="tinyllama-1.1b-smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--frames", type=int, default=8,
                    help="frames per clip (--workload stream)")
    ap.add_argument("--mesh", default="1x1")
    # -- elastic fleet (repro.dist.fleet; docs/distributed_serving.md) ----
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve through a FleetSupervisor over N "
                         "data-parallel replica engines (N > 1); each lm "
                         "replica is a ShardedServeEngine on its own "
                         "(1, tp) mesh slice")
    ap.add_argument("--tp", type=int, default=0, metavar="M",
                    help="tensor-parallel degree per replica (fleet mode; "
                         "default: the model axis of --mesh)")
    ap.add_argument("--ring", action="store_true",
                    help="route the sharded decode's row-parallel "
                         "reductions through the int8 ppermute ring "
                         "(compressed wire bytes, calibrated error "
                         "envelope)")
    ap.add_argument("--rescale-ms", type=float, default=5.0,
                    help="modeled survivor-mesh re-shard latency charged "
                         "per rescale (repro_rescale_seconds histogram)")
    ap.add_argument("--route-by", default="slots",
                    choices=("slots", "backlog"),
                    help="fleet routing load signal: slots counts requests "
                         "(queued + in-slot); backlog counts admission "
                         "work in payload units, so chunked long prompts "
                         "weigh what they cost")
    # -- admission pipeline (repro.serve.admission; docs/serving.md) ------
    ap.add_argument("--prefill-buckets", default=None, metavar="LIST",
                    help="bucketed AOT prefill: comma list of ascending "
                         "prompt-prefix lengths (e.g. 16,32,64,128), or "
                         "'auto' for the power-of-two ladder up to "
                         "max_len; every bucket executable is traced at "
                         "startup, so no request compiles after warmup")
    ap.add_argument("--pack", type=int, default=1, metavar="N",
                    help="pack up to N short prompts into one bucketed "
                         "prefill call (each row scatters into its own "
                         "slot; bit-identical to sequential admission)")
    ap.add_argument("--chunk-tokens", type=int, default=0, metavar="C",
                    help="chunked prefill: split prompts longer than C "
                         "into C-token chunks admitted across ticks, "
                         "interleaved with decode, bounding short-request "
                         "TTFT behind long arrivals (0 = off; dense "
                         "full-attention archs only — others fall back to "
                         "whole-prompt bucketed prefill)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 enables categorical sampling")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k most likely tokens")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine PRNG seed (sampling is reproducible per seed)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop-token id; -1 disables EOS stopping")
    ap.add_argument("--qos", action="store_true",
                    help="drive the runtime approximation degree from load "
                         "(DyFXU ladder ebits 8->5, no recompilation)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the TTFT/TPOT/queue latency summary and "
                         "prefill-vs-decode token accounting")
    ap.add_argument("--kernels", default=None,
                    choices=("auto", "pallas", "xla"),
                    help="attention/GEMM kernel backend (default: "
                         "REPRO_KERNELS env or auto = pallas on TPU, xla "
                         "elsewhere)")
    ap.add_argument("--approx", default="exact",
                    help="projection arithmetic: exact | axqN (block-int8 "
                         "GEMMs at N effective bits, e.g. axq8/axq6); "
                         "ignored when --plan is given (the plan carries "
                         "its own policy)")
    ap.add_argument("--plan", default=None,
                    help="path to an ApproxPlan JSON (repro.tune): serve "
                         "with per-layer degrees; with --qos the controller "
                         "steps the plan's calibrated degree ladder")
    ap.add_argument("--no-prepack", action="store_true",
                    help="disable quantize-once weight residency (keep the "
                         "per-call weight quantization; A/B lever — prepack "
                         "is bit-identical and strictly cheaper)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(enqueue/prefill/decode/QoS-rung spans; open in "
                         "chrome://tracing or Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus text-format metrics (engine "
                         "counters, latency histograms, kernel routes, "
                         "degree gauges) at exit")
    ap.add_argument("--quality-every", type=int, default=0, metavar="N",
                    help="sample the live-vs-exact logit error every N "
                         "ticks into a per-rung histogram (0 = off; needs "
                         "--qos/--plan or an approx degree)")
    # -- resilience (repro.resil; docs/robustness.md) --------------------
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request e2e deadline; a request past it "
                         "terminates with status=deadline (queued or "
                         "in-slot), never silently")
    ap.add_argument("--retries", type=int, default=None, metavar="N",
                    help="guard-trip requeues before a request fails "
                         "(default 2; capped-exponential backoff)")
    ap.add_argument("--shed", type=int, default=None, metavar="Q",
                    help="queue-length backpressure cap: overflow sheds "
                         "newest-first (or browns out first, see "
                         "--brownout)")
    ap.add_argument("--brownout", action="store_true",
                    help="under overload force the QoS controller down the "
                         "approximation ladder BEFORE shedding (graceful "
                         "degradation; needs --qos)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="inject a seeded fault storm: comma list of "
                         "kind=rate — seu_state, seu_param, nan, spike, "
                         "drop (e.g. 'seu_state=0.02,nan=0.05'); enables "
                         "runtime guards + quarantine; with --replicas, "
                         "replica_loss=RATE kills whole replicas (drawn "
                         "fleet-level; the engine kinds keep their "
                         "per-replica storms)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault schedule seed: the same seed reproduces "
                         "the identical injected-fault sequence and "
                         "recovery trace")
    args = ap.parse_args()

    kdispatch.set_backend(args.kernels)
    if args.trace_out:
        obs_trace.enable()
    if args.replicas > 1:
        _serve_fleet(args)
        return
    if args.workload == "stream":
        _serve_stream(args)
        return

    d, m = (int(x) for x in args.mesh.split("x")[:2])
    if args.tp:
        m = args.tp
    meshctx.set_mesh(meshctx.make_mesh((d, m), ("data", "model")))
    cfg = get_config(args.arch)
    plan = None
    if args.plan is not None:
        from repro.tune import ApproxPlan

        plan = ApproxPlan.load(args.plan)
        plan.validate_for(cfg)
        # the plan pins mode/block; its degrees are the runtime knob
        policy = plan.policy(dynamic=True)
    else:
        try:
            policy = policy_from_flag(args.approx, dynamic=args.qos)
        except ValueError as e:
            raise SystemExit(str(e))
    model = build_model(cfg, policy)
    params = model.init(jax.random.PRNGKey(0), tp=m)
    if not args.no_prepack:
        # rebind: the f32 copies of packed weights are dropped here — the
        # engine holds only the int8 residency forms
        params = model.prepack(params)
    qos = QoSController(
        ladder=[{"ebits": e} for e in (8, 7, 6, 5)],
        low_water=0.25, high_water=0.75, cooldown_steps=8,
    ) if args.qos else None
    registry = obs_metrics.get_registry() if args.metrics_out else None
    eng = ServeEngine(model, params, slots=args.slots, max_len=512, tp=m,
                      eos_id=args.eos_id, greedy=args.temperature <= 0,
                      temperature=max(args.temperature, 1e-6),
                      top_k=args.top_k, seed=args.seed, qos=qos,
                      prepack=False, plan=plan, registry=registry,
                      quality_every=args.quality_every,
                      admission=_admission_from_args(args),
                      **_resil_kwargs(args))
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for _ in range(args.requests):
        eng.submit(rng.integers(0, cfg.vocab, int(rng.integers(2, 10))),
                   args.new_tokens)
    done = eng.run_until_drained()
    dt = time.time() - t0
    s = summarize(done, eng.stats, wall_s=dt)
    print(f"[launch.serve] {s['requests']} reqs, {s['generated_tokens']} "
          f"generated tokens, {dt:.2f}s ({s['gen_tok_per_s']:.1f} gen tok/s) "
          f"[kernels={kdispatch.resolved_backend()}]")
    if args.metrics:
        for k, v in s.items():
            print(f"[launch.serve]   {k:24s} {v}")
        if qos is not None:
            print(f"[launch.serve]   degree ladder visits: "
                  f"{[e for _, e in list(eng.stats.degree_history)[-8:]]} (last 8)")
        _print_resil(eng, done)
    _write_obs(args)


if __name__ == "__main__":
    main()
