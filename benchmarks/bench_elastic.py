"""Elastic fleet serving through replica loss (repro.dist.fleet, ISSUE 9).

The claims under test, on a 3-replica stream fleet driven by one
deterministic :class:`~repro.resil.policy.VirtualClock` (one fleet tick
costs BASE_TICK_MS at the slowest live engine's rung — replicas run in
parallel, so virtual time advances once per fleet tick however many
replicas serve):

* **kill-one-of-three** — a scripted ``replica_loss`` lands mid-serve.
  Rows carry goodput (ok completions per virtual second) *before* the
  kill, *during* the rescale window, and *after* on the survivor mesh;
  the gate's headline is goodput > 0 on both sides of the event and the
  survivor plan matching ``elastic.plan_rescale``.  Survivors absorb the
  capacity dip through their own brownout ladders before anything sheds.
* **exactly-once accounting** — fleet-wide lost / duplicated / short
  counts must all be 0, and every ok payload must be bit-identical to a
  clean single-engine reference run (``fleet_corrupt_payloads == 0``).
* **ragged planning** — 7 survivors under tp=4 plan to a usable
  power-of-two subset with ``idle_devices`` reported, instead of raising
  out of the recovery path.
* **determinism** — a seeded stochastic loss schedule re-run at the same
  seed must reproduce the injected kills, the fleet recovery trace, and
  every payload bit-for-bit.
* **collective budget** — the sharded LM decode step's wire bytes with
  the int8 ppermute ring must stay within half the exact-f32 budget
  (measured from compiled HLO; computed in a subprocess when the host
  has a single visible device).

REPRO_BENCH_TINY=1 shrinks the fleet/clips for the CI dist-serve smoke.
Committed record: benchmarks/BENCH_elastic.json (full-shape run).
"""
import os
import subprocess
import sys

import numpy as np

from repro.core.dynamic import QoSController
from repro.dist.elastic import plan_rescale
from repro.dist.fleet import FleetSupervisor
from repro.resil import (FaultEvent, FaultPlan, FaultSpec, GuardConfig,
                         ServePolicy, VirtualClock)
from repro.serve.stream import StreamAdapter, StreamServeEngine, make_clip
from repro.tune import vector_cost

_TINY = os.environ.get("REPRO_BENCH_TINY", "0") == "1"

#: virtual cost of one fleet tick at the exact rung (ms)
BASE_TICK_MS = 2.0
_LADDER_EBITS = (8, 7, 6, 5, 4)
RESCALE_MS = 5.0


def _ladder(cfg):
    return [{"degrees": [e] * (cfg.n_layers + 1)} for e in _LADDER_EBITS]


def _tick_cost_s(cfg, engines) -> float:
    """Virtual seconds one *fleet* tick costs: replicas step in parallel,
    the slowest live engine's rung sets the pace."""
    worst = 0.0
    for eng in engines:
        if eng.stats.degree_history:
            degrees = list(eng.stats.degree_history[-1][1])
        else:
            degrees = [8] * (cfg.n_layers + 1)
        worst = max(worst, vector_cost(cfg, degrees))
    return BASE_TICK_MS * (worst or 1.0) / 1e3


def _payload_key(req):
    return tuple(np.asarray(f).tobytes() for f in req.out)


def _statuses(reqs) -> dict:
    out: dict = {}
    for r in reqs:
        out[r.status] = out.get(r.status, 0) + 1
    return out


def _mix(st: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(st.items()))


def _fleet(*, replicas, slots, faults, clock, qos=True):
    """``qos=True`` arms the brownout ladder (approximate absorption of
    the capacity dip — payloads are then *approximate* by design);
    ``qos=False`` serves every request at the exact rung, the
    configuration the bit-identity oracle applies to."""
    cfg = StreamAdapter().cfg
    policy = ServePolicy(deadline_ms=None, ttft_deadline_ms=None,
                         max_queue=2 * slots if qos else None,
                         max_queue_age_ms=None, backoff_ms=0.5)

    def build(mesh, rid):
        return StreamServeEngine(
            slots=slots, clock=clock, policy=policy, guards=GuardConfig(),
            qos=QoSController(ladder=_ladder(cfg), low_water=0.25,
                              high_water=0.75, cooldown_steps=4)
            if qos else None)

    return FleetSupervisor(build, replicas, tp=1, clock=clock,
                           faults=faults, policy=policy,
                           rescale_ms=RESCALE_MS), cfg


def _drain(sup, clock, cfg, reqs, max_ticks=5000):
    """Tick the fleet until every request is terminal; returns the virtual
    timestamp of the replica-loss event (None if none fired)."""
    t_kill = None
    for _ in range(max_ticks):
        if all(r.done for r in reqs):
            break
        before = len([r for r in sup.replicas if not r.alive])
        sup.tick()
        if t_kill is None and \
                len([r for r in sup.replicas if not r.alive]) > before:
            t_kill = clock()   # rescale latency already charged this tick
        clock.advance(_tick_cost_s(cfg, [r.engine for r in sup.live]))
    assert all(r.done for r in reqs), "elastic scenario failed to drain"
    return t_kill


def _kill_scenario(*, replicas, slots, n_req, frames, kill_tick, qos=True):
    clock = VirtualClock()
    faults = FaultPlan(events=[FaultEvent(tick=kill_tick,
                                          kind="replica_loss", slot=1,
                                          target="replica")])
    sup, cfg = _fleet(replicas=replicas, slots=slots, faults=faults,
                      clock=clock, qos=qos)
    clips = [make_clip(frames, cfg.frame, q=cfg.q, seed=100 + i)
             for i in range(n_req)]
    t0 = clock()
    reqs = [sup.submit(c) for c in clips]
    t_kill = _drain(sup, clock, cfg, reqs)
    t_end = clock()
    return sup, cfg, clips, reqs, (t0, t_kill, t_end)


def _stochastic_run(seed, *, replicas, slots, n_req, frames):
    clock = VirtualClock()
    faults = FaultPlan(FaultSpec(replica_loss=0.04), seed=seed)
    sup, cfg = _fleet(replicas=replicas, slots=slots, faults=faults,
                      clock=clock)
    clips = [make_clip(frames, cfg.frame, q=cfg.q, seed=200 + i)
             for i in range(n_req)]
    reqs = [sup.submit(c) for c in clips]
    _drain(sup, clock, cfg, reqs)
    return sup, reqs


def _clean_reference(clips, *, slots):
    """Same clips, one engine, no faults: the payload oracle."""
    eng = StreamServeEngine(slots=slots, guards=GuardConfig(),
                            clock=VirtualClock())
    reqs = [eng.submit(c) for c in clips]
    for _ in range(5000):
        if all(r.done for r in reqs):
            break
        eng.tick()
    return [_payload_key(r) for r in reqs]


def _collective_bytes() -> tuple:
    """(ring_total, f32_total) wire bytes of one sharded smoke-LM decode
    step at tp=2.  Needs 2 devices — falls back to a subprocess with the
    host-device-count flag when the parent runs single-device.  The child
    is pinned to the CPU: a parent that holds a chip must never start a
    child that reaches for it."""
    import jax
    if len(jax.devices()) >= 2:
        from repro.serve.sharded import lm_decode_collective_bytes
        ring = lm_decode_collective_bytes(tp=2, ring=True)["total"]
        f32 = lm_decode_collective_bytes(tp=2, ring=False)["total"]
        return ring, f32
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=2'\n"
        "from repro.serve.sharded import lm_decode_collective_bytes as f\n"
        "print('RING', f(tp=2, ring=True)['total'])\n"
        "print('F32', f(tp=2, ring=False)['total'])\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env)
    vals = {}
    for line in r.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] in ("RING", "F32"):
            vals[parts[0]] = float(parts[1])
    assert "RING" in vals and "F32" in vals, r.stderr[-2000:]
    return vals["RING"], vals["F32"]


def rows():
    out = []
    replicas = 2 if _TINY else 3
    n_req, frames, slots = (8, 4, 2) if _TINY else (18, 6, 2)
    # kill after the first admission wave completes, while later waves are
    # mid-decode: the event must interrupt live work AND leave completions
    # on both sides of it
    kill_tick = frames + 2

    # ---- kill one replica mid-serve -----------------------------------
    sup, cfg, clips, reqs, (t0, t_kill, t_end) = _kill_scenario(
        replicas=replicas, slots=slots, n_req=n_req, frames=frames,
        kill_tick=kill_tick)
    assert t_kill is not None, "scripted replica loss never fired"
    window = RESCALE_MS / 1e3   # the rescale + first-recovery window
    ok = [r for r in reqs if r.status == "ok"]
    before = sum(1 for r in ok if r.t_done < t_kill - window)
    during = sum(1 for r in ok if t_kill - window <= r.t_done < t_kill)
    after = sum(1 for r in ok if r.t_done >= t_kill)
    gp_before = before / max(t_kill - window - t0, 1e-9)
    gp_during = during / window
    gp_after = after / max(t_end - t_kill, 1e-9)
    out.append(("elastic.fleet_goodput_before", 0.0, round(gp_before, 2)))
    out.append(("elastic.fleet_goodput_during", 0.0, round(gp_during, 2)))
    out.append(("elastic.fleet_goodput_after", 0.0, round(gp_after, 2)))
    out.append(("elastic.fleet_replicas", 0.0,
                f"{replicas}->{len(sup.live)}"))
    out.append(("elastic.fleet_mix", 0.0, _mix(_statuses(reqs))))
    assert before > 0, "no completions before the kill — move it later"
    assert after > 0, "no completions on the survivor mesh"

    # survivors degrade before they shed: brownout rungs fleet-wide
    rungs = sum(int(r.engine.stats.c_brownout.value) for r in sup.replicas)
    out.append(("elastic.fleet_brownout_rungs", 0.0, rungs))

    # ---- exactly-once accounting + payload integrity -------------------
    # integrity runs at the exact rung (qos=False): the brownout ladder
    # above produces *approximate* payloads by design, so the bit-identity
    # oracle only applies to an exact-serving fleet
    sup_x, cfg_x, clips_x, reqs_x, _t = _kill_scenario(
        replicas=replicas, slots=slots, n_req=n_req, frames=frames,
        kill_tick=kill_tick, qos=False)
    done = sup_x.done
    rids = [r.rid for r in done]
    lost = len(reqs_x) - len(done)
    dup = len(rids) - len(set(rids))
    short = sum(1 for r in reqs_x
                if r.status == "ok" and len(r.out) != frames)
    out.append(("elastic.fleet_accounting", 0.0,
                f"lost={lost},dup={dup},short={short}"))
    assert lost == 0 and dup == 0 and short == 0, (lost, dup, short)
    ref = _clean_reference(clips_x, slots=slots)
    corrupt = sum(1 for r, k in zip(reqs_x, ref)
                  if r.status == "ok" and _payload_key(r) != k)
    out.append(("elastic.fleet_corrupt_payloads", 0.0, corrupt))
    assert corrupt == 0, (
        f"{corrupt} fleet payloads diverged from the clean reference")

    # ---- the survivor mesh plan (and the injected latency) -------------
    plan = sup.rescales[-1]
    out.append(("elastic.rescale_plan", 0.0,
                f"data={plan.data},model={plan.model},"
                f"idle={plan.idle_devices}"))
    out.append(("elastic.rescale_ms", 0.0, RESCALE_MS))

    # ---- ragged survivor counts never crash the recovery path ----------
    ragged = plan_rescale(7, target_global_batch=64, tp=4)
    out.append(("elastic.ragged_plan", 0.0,
                f"devices=7,tp=4,data={ragged.data},model={ragged.model},"
                f"idle={ragged.idle_devices}"))
    assert ragged.pods * ragged.data * ragged.model \
        + ragged.idle_devices == 7

    # ---- determinism: same seed => same kills, trace, bits -------------
    seed = 23
    sup_a, reqs_a = _stochastic_run(seed, replicas=replicas, slots=slots,
                                    n_req=n_req, frames=frames)
    sup_b, reqs_b = _stochastic_run(seed, replicas=replicas, slots=slots,
                                    n_req=n_req, frames=frames)
    identical = (
        [(e.tick, e.kind, e.slot) for e in sup_a.faults.injected]
        == [(e.tick, e.kind, e.slot) for e in sup_b.faults.injected]
        and sup_a.resil_log == sup_b.resil_log
        and [(r.status, _payload_key(r)) for r in reqs_a]
        == [(r.status, _payload_key(r)) for r in reqs_b])
    out.append(("elastic.determinism", 0.0,
                "identical" if identical else "DIVERGED"))
    assert identical, "same loss seed diverged (schedule/trace/payloads)"

    # ---- decode-step collective bytes within the compressed budget -----
    ring, f32 = _collective_bytes()
    out.append(("elastic.decode_collective_bytes", 0.0,
                f"ring={int(ring)},f32={int(f32)}"))
    assert 0 < ring <= 0.5 * f32, (
        f"int8 ring decode bytes {ring} exceed half the f32 budget {f32}")
    return out
