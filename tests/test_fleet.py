"""repro.dist.fleet (ISSUE 9): replica fleets that survive replica loss.

Covers the fleet-level fault kind (parse alias, seeded scheduling, RNG
back-compat), supervisor routing, the hard-kill failure arc (queue
migration + in-flight rewind + rescale), the exactly-once fleet-wide
accounting partition, payload bit-identity against a clean single-engine
reference across arbitrary seeded loss schedules, same-seed recovery-trace
determinism, last-replica protection, graceful decommission, retry
exhaustion through the fleet rewind path, and the injectable rescale
clock.  (The multi-device sharded-serving twin lives in
test_sharded_serve.py; ragged rescale planning in test_elastic.py.)

Everything here runs on one host device: replicas get degenerate (1,1)
meshes sharing device 0 — the supervisor logic is device-count agnostic.
"""
import numpy as np
import pytest

from repro.dist.fleet import FleetSupervisor, fleet_meshes
from repro.resil import (FaultEvent, FaultPlan, FaultSpec, GuardConfig,
                         ServePolicy, VirtualClock)
from repro.serve.stream import StreamAdapter, StreamServeEngine, make_clip


def _clip(frames=4, seed=0):
    cfg = StreamAdapter().cfg
    return make_clip(frames, cfg.frame, q=cfg.q, seed=seed)


def _policy(**kw):
    kw.setdefault("deadline_ms", None)
    kw.setdefault("ttft_deadline_ms", None)
    kw.setdefault("max_queue", None)
    kw.setdefault("max_queue_age_ms", None)
    kw.setdefault("backoff_ms", 0.0)
    return ServePolicy(**kw)


def _fleet(replicas=3, *, slots=2, faults=None, policy=None, clock=None,
           rescale_ms=5.0, seed=0):
    clock = clock if clock is not None else VirtualClock()
    policy = policy if policy is not None else _policy()

    def build(mesh, rid):
        return StreamServeEngine(slots=slots, seed=seed, clock=clock,
                                 policy=policy, guards=GuardConfig())

    return FleetSupervisor(build, replicas, tp=1, clock=clock,
                           faults=faults, policy=policy,
                           rescale_ms=rescale_ms)


def _kill_at(tick, replica):
    return FaultPlan(events=[FaultEvent(tick=tick, kind="replica_loss",
                                        slot=replica, target="replica")])


def _payload_key(req):
    return tuple(np.asarray(f).tobytes() for f in req.out)


def _clean_reference(clips, *, slots=2):
    """Single-replica, no-fault run over the same clips: the payload
    oracle every fleet run must match bit-for-bit on its ok requests."""
    eng = StreamServeEngine(slots=slots)
    reqs = [eng.submit(c) for c in clips]
    eng.run_until_drained()
    assert all(r.status == "ok" for r in reqs)
    return {r.rid: _payload_key(r) for r in reqs}


# ---------------------------------------------------------------------------
# fault-kind plumbing
# ---------------------------------------------------------------------------


def test_replica_loss_spec_parse_aliases():
    sp = FaultSpec.parse("replica=0.25")
    assert sp.replica_loss == 0.25
    assert FaultSpec.parse("replica_loss=0.1").replica_loss == 0.1


def test_replica_loss_needs_fleet_binding():
    plan = FaultPlan(FaultSpec(replica_loss=1.0), seed=3)
    assert all(not evs for evs in
               (plan.events_at(t) for t in range(5)))   # unbound: no victims
    plan.bind_fleet(4)
    evs = [e for t in range(5) for e in plan.events_at(t)]
    assert evs and all(e.kind == "replica_loss" for e in evs)
    assert all(0 <= e.slot < 4 for e in evs)


def test_replica_loss_rate_zero_preserves_rng_streams():
    # adding the new kind must not shift the draw sequence of old plans:
    # a spec with replica_loss=0 yields tick-for-tick identical events
    spec = FaultSpec(seu_state=0.3, nan=0.3, spike=0.2, drop=0.2)
    a = FaultPlan(spec, seed=7)
    b = FaultPlan(spec, seed=7).bind_fleet(8)
    eng = StreamServeEngine(slots=2)
    a.bind(eng.state, eng.params, 2)
    b.bind(eng.state, eng.params, 2)
    for t in range(64):
        assert a.events_at(t) == b.events_at(t)


def test_replica_loss_schedule_is_deterministic():
    mk = lambda: FaultPlan(FaultSpec(replica_loss=0.5), seed=11).bind_fleet(3)
    a, b = mk(), mk()
    for t in range(32):
        assert a.events_at(t) == b.events_at(t)


# ---------------------------------------------------------------------------
# meshes + routing
# ---------------------------------------------------------------------------


def test_fleet_meshes_shapes_and_sharing():
    meshes = fleet_meshes(3, tp=1)
    assert len(meshes) == 3
    for m in meshes:
        assert m.axis_names == ("data", "model")
        assert m.devices.shape == (1, 1)


def test_fleet_meshes_refuses_to_stack_replicas_off_cpu(monkeypatch):
    """On an accelerator, too few devices raise instead of stacking every
    replica onto the same chips; the CPU keeps the shared form."""
    import jax

    from repro.dist import fleet

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.setattr(fleet.jax, "devices", lambda: [_Dev("tpu")])
    with pytest.raises(ValueError, match="need 2 tpu devices, have 1"):
        fleet_meshes(2, tp=1)
    monkeypatch.setattr(fleet.jax, "devices",
                        lambda: [_Dev("tpu") for _ in range(2)])
    with pytest.raises(ValueError, match="need 4 tpu devices, have 2"):
        fleet_meshes(2, tp=2)
    monkeypatch.undo()
    assert jax.devices()[0].platform == "cpu"
    assert len(fleet_meshes(4, tp=1)) == 4


def test_routing_is_least_loaded_then_lowest_rid():
    sup = _fleet(3)
    # empty fleet: ties break to replica 0, then spread round-robin-ish
    r0 = sup.submit(_clip(seed=0))
    assert r0 in sup.replicas[0].engine.queue
    r1 = sup.submit(_clip(seed=1))
    assert r1 in sup.replicas[1].engine.queue
    r2 = sup.submit(_clip(seed=2))
    assert r2 in sup.replicas[2].engine.queue
    r3 = sup.submit(_clip(seed=3))
    assert r3 in sup.replicas[0].engine.queue


def test_fleet_rids_are_unique_across_replicas():
    sup = _fleet(3)
    reqs = [sup.submit(_clip(seed=i)) for i in range(9)]
    assert sorted(r.rid for r in reqs) == list(range(9))


# ---------------------------------------------------------------------------
# the failure arc
# ---------------------------------------------------------------------------


def test_kill_migrates_rewinds_and_rescales():
    sup = _fleet(3, faults=_kill_at(2, 1))
    reqs = [sup.submit(_clip(6, seed=i)) for i in range(8)]
    done = sup.run_until_drained(max_ticks=800)
    assert len(done) == len(reqs)
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert all(r.status == "ok" for r in done)
    assert not sup.replicas[1].alive
    assert sup.replicas[1].died_at == 2
    names = [n for _, n, _ in sup.resil_log]
    assert "replica_lost" in names and "rescale" in names
    assert "rewind" in names          # slots were mid-decode at tick 2
    # survivor plan: 2 replicas * tp=1 -> data=2, nothing idle
    assert sup.rescales[-1].data == 2
    assert sup.rescales[-1].idle_devices == 0
    assert sup.status_counts() == {"ok": len(reqs)}


def test_fleet_payloads_bit_identical_after_kill():
    clips = [_clip(5, seed=i) for i in range(8)]
    ref = _clean_reference(clips)
    sup = _fleet(3, faults=_kill_at(3, 0))
    reqs = [sup.submit(c) for c in clips]
    sup.run_until_drained(max_ticks=800)
    got = {r.rid: _payload_key(r) for r in sup.done}
    assert got == ref


def test_last_live_replica_is_never_killed():
    # schedule hits every replica; the fleet must refuse the final kill
    events = [FaultEvent(tick=t, kind="replica_loss", slot=t,
                         target="replica") for t in range(3)]
    sup = _fleet(3, faults=FaultPlan(events=events))
    reqs = [sup.submit(_clip(5, seed=i)) for i in range(6)]
    done = sup.run_until_drained(max_ticks=800)
    assert len(sup.live) == 1
    assert len(done) == len(reqs) and all(r.status == "ok" for r in done)
    assert any(n == "replica_loss_skipped" for _, n, _ in sup.resil_log)


def test_rewind_exhaustion_fails_exactly_once():
    # max_retries=0: any in-flight rewind immediately fails the request —
    # the fleet-level terminal path must keep the accounting partition
    sup = _fleet(2, policy=_policy(max_retries=0), faults=_kill_at(2, 0))
    reqs = [sup.submit(_clip(6, seed=i)) for i in range(4)]
    done = sup.run_until_drained(max_ticks=800)
    assert len(done) == len(reqs)
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    counts = sup.status_counts()
    assert counts.get("failed", 0) >= 1    # the mid-decode victims
    assert sum(counts.values()) == len(reqs)
    assert any(n == "request_failed" for _, n, _ in sup.resil_log)


def test_decommission_drains_with_zero_rewinds():
    sup = _fleet(3)
    reqs = [sup.submit(_clip(5, seed=i)) for i in range(6)]
    for _ in range(2):
        sup.tick()
    plan = sup.decommission(1)
    assert plan is not None and not sup.replicas[1].alive
    done = sup.run_until_drained(max_ticks=800)
    assert len(done) == len(reqs) and all(r.status == "ok" for r in done)
    assert all(r.retries == 0 for r in done)          # graceful: no rewinds
    names = [n for _, n, _ in sup.resil_log]
    assert "decommission" in names and "rewind" not in names


def test_rescale_duration_is_injectable_and_observed():
    clock = VirtualClock()
    sup = _fleet(3, clock=clock, faults=_kill_at(1, 2), rescale_ms=40.0)
    [sup.submit(_clip(5, seed=i)) for i in range(6)]
    t0 = clock()
    sup.run_until_drained(max_ticks=800)
    # the virtual clock advanced by exactly the modeled rescale latency
    # (stream ticks themselves don't touch the clock)
    assert clock() - t0 == pytest.approx(0.040)
    hist = sup.registry.histogram("repro_rescale_seconds")
    assert hist.count == 1 and hist.sum == pytest.approx(0.040)


def test_replica_up_gauge_tracks_liveness():
    sup = _fleet(3, faults=_kill_at(1, 1))
    [sup.submit(_clip(5, seed=i)) for i in range(4)]
    g = sup.registry.gauge("repro_replica_up", labels=("replica",))
    assert [g.labels(replica=str(r)).value for r in range(3)] == [1, 1, 1]
    sup.run_until_drained(max_ticks=800)
    assert [g.labels(replica=str(r)).value for r in range(3)] == [1, 0, 1]
    assert sup.registry.counter("repro_replica_loss_total").value == 1


# ---------------------------------------------------------------------------
# exactly-once + determinism across seeded schedules (the property tests)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 5, 9, 13])
def test_property_exactly_once_and_ok_bit_identity(seed):
    """Across arbitrary seeded replica-loss schedules: every submitted
    request terminates exactly once, and every ok payload is bit-identical
    to the clean single-replica run."""
    clips = [_clip(5, seed=100 + i) for i in range(10)]
    ref = _clean_reference(clips)
    plan = FaultPlan(FaultSpec(replica_loss=0.2), seed=seed)
    sup = _fleet(3, faults=plan)
    reqs = [sup.submit(c) for c in clips]
    done = sup.run_until_drained(max_ticks=1200)
    # exactly-once: one terminal record per submission, no dups, no losses
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert sum(sup.status_counts().values()) == len(reqs)
    for r in done:
        if r.status == "ok":
            assert _payload_key(r) == ref[r.rid]


def test_same_seed_recovery_trace_is_deterministic():
    def run():
        plan = FaultPlan(FaultSpec(replica_loss=0.25), seed=17)
        sup = _fleet(3, faults=plan)
        reqs = [sup.submit(_clip(5, seed=i)) for i in range(8)]
        done = sup.run_until_drained(max_ticks=1200)
        return (tuple(sup.resil_log),
                tuple((e.tick, e.kind, e.slot) for e in plan.injected),
                tuple(sorted((r.rid, r.status, _payload_key(r))
                             for r in done)))

    assert run() == run()


def test_replica_loss_mid_chunked_admission_rewinds_and_recovers():
    """Hard-killing a replica while an LM request is mid-chunked-prefill
    must rewind the admission cursor to zero and front-requeue the request
    onto a survivor, where it re-admits from scratch and finishes with the
    same payload as a never-faulted run."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.admission import AdmissionConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config("tinyllama-1.1b-smoke")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0), tp=1)
    adm = AdmissionConfig(chunk_tokens=8, warmup=False)

    def build(mesh, rid):
        return ServeEngine(m, params, slots=1, max_len=64, seed=7,
                           admission=adm, emitter=False)

    sup = FleetSupervisor(build, 2, tp=1, policy=_policy(), rescale_ms=0.0)
    prompt = np.random.default_rng(5).integers(
        1, cfg.vocab, 40).astype(np.int32)
    req = sup.submit(prompt, 3)
    eng0 = sup.replicas[0].engine           # ties route to replica 0
    eng0.tick()                             # first chunk only
    assert req in eng0.slot_req
    assert 0 < req.cursor < req.payload_units - 1
    assert not eng0.workload.admit_complete(req)

    sup.kill(0)
    assert req.cursor == 0                  # rewound: fresh re-admission
    assert req in sup.replicas[1].engine.queue
    done = sup.run_until_drained()
    assert [r.rid for r in done] == [req.rid] and req.status == "ok"
    names = [n for _, n, _ in sup.resil_log]
    assert "replica_lost" in names and "rewind" in names

    ref_eng = ServeEngine(m, params, slots=1, max_len=64, seed=7,
                          admission=adm, emitter=False)
    ref = ref_eng.submit(prompt, 3)
    ref_eng.run_until_drained()
    assert req.out == ref.out               # recovery == clean run
