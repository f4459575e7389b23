"""Replica fleet supervision: serve through replica loss (DESIGN.md §14).

One engine is a blast radius.  :class:`FleetSupervisor` fronts N
data-parallel replica groups — each its own :class:`~jax.sharding.Mesh`
slice of ``tp`` devices running an independent serve engine — and owns the
story the single engine cannot tell: a whole replica dying mid-decode.

The failure arc, end to end:

  1. the fleet-level :class:`~repro.resil.FaultPlan` schedules a seeded
     ``replica_loss`` event (same determinism contract as SEU/latency
     faults: stateless per-tick draws, scripted mode for exact scenarios);
  2. the supervisor marks the victim dead (``repro_replica_up`` gauge to
     0), migrates its *queued* requests to survivors in order, and rewinds
     its *in-flight* requests through the same front-requeue machinery the
     per-slot quarantine uses — full rewind, capped backoff, ``failed``
     past ``max_retries`` — so exactly-once ``{ok,failed,shed,deadline}``
     accounting holds fleet-wide;
  3. :func:`repro.dist.elastic.plan_rescale` picks the survivor mesh
     (ragged counts degrade to a power-of-two subset + ``idle_devices``
     instead of crashing the recovery path) and the rescale duration —
     injectable through :class:`~repro.resil.VirtualClock` — lands in the
     ``repro_rescale_seconds`` histogram;
  4. serving resumes on the survivors; the capacity dip is absorbed by
     each engine's own brownout ladder (degrade approximation rungs)
     before anything sheds.

:func:`decommission` is the graceful twin: stop routing, drain the
decodable slots in place, then retire the replica — zero rewinds.

Every transition is written to the fleet ``resil_log`` (plain
``(tick, name, sorted-args)`` tuples, ``==``-comparable across runs) and
mirrored onto the ``fleet`` trace track; ``bench_elastic`` pins the whole
arc — goodput across the kill, zero lost/dup/corrupt payloads, same-seed
recovery trace — behind the ``_check_elastic`` regression gate.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import numpy as np

from repro.dist.elastic import RescalePlan, plan_rescale
from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry


def fleet_meshes(replicas: int, tp: int = 1) -> list:
    """One ``(1, tp)`` mesh per replica over disjoint device slices.

    With fewer than ``replicas * tp`` local devices, CPU hosts let every
    replica share the first ``tp`` devices (degenerate but correct — the
    CI fast suite runs whole fleets on one host CPU device this way).  On
    an accelerator that would stack every replica onto the same chips, so
    it raises instead."""
    devs = jax.devices()
    if replicas * tp > len(devs) and devs[0].platform != "cpu":
        raise ValueError(
            f"{replicas} replicas x tp={tp} need {replicas * tp} "
            f"{devs[0].platform} devices, have {len(devs)}")
    tp = min(tp, len(devs))
    meshes = []
    for r in range(replicas):
        lo = r * tp
        sub = devs[lo:lo + tp] if lo + tp <= len(devs) else devs[:tp]
        meshes.append(jax.sharding.Mesh(
            np.asarray(sub).reshape(1, tp), ("data", "model")))
    return meshes


@dataclass
class Replica:
    """One replica group: its mesh slice, its engine, and liveness."""

    rid: int
    mesh: object
    engine: object
    alive: bool = True
    #: fleet tick the replica died on (None while alive)
    died_at: Optional[int] = None


class FleetSupervisor:
    """Route requests across replica engines and survive losing one.

    ``build_engine(mesh, rid)`` constructs one replica's engine — the
    caller closes over shared pieces (model, params, QoS ladder, engine
    fault plans, the :class:`~repro.resil.VirtualClock`).  Engine-level
    fault plans must not carry ``replica_loss`` (engines ignore the kind;
    ``launch.serve`` zeroes it) — the fleet-level ``faults=`` plan is
    where replica deaths are drawn, bound here via ``bind_fleet``.

    ``policy`` governs the *fleet-level* rewind (retry cap + backoff for
    requests torn out of a dead replica's slots); per-engine policies keep
    governing their own queues.  ``rescale_ms`` is the modeled re-shard
    latency: charged to the injectable clock, observed into the
    ``repro_rescale_seconds`` histogram — deterministic on CI.
    """

    def __init__(self, build_engine: Callable, replicas: int, *,
                 tp: int = 1, clock=None, faults=None, policy=None,
                 registry: Optional[Registry] = None, tracer=None,
                 rescale_ms: float = 5.0,
                 target_global_batch: Optional[int] = None,
                 route_by: str = "slots"):
        if replicas < 1:
            raise ValueError("a fleet needs at least one replica")
        if route_by not in ("slots", "backlog"):
            raise ValueError("route_by must be 'slots' or 'backlog'")
        self.route_by = route_by
        self.tp = int(tp)
        self._clock = clock if clock is not None else time.time
        self._tracer = (tracer if tracer is not None
                        else obs_trace.get_tracer())
        self.faults = faults
        if faults is not None:
            faults.bind_fleet(replicas)
        if policy is None:
            from repro.resil import ServePolicy
            policy = ServePolicy()
        self.policy = policy
        self.rescale_ms = float(rescale_ms)
        self.registry = registry if registry is not None else Registry()
        self._g_up = self.registry.gauge(
            "repro_replica_up", "replica liveness (1 = serving)",
            labels=("replica",))
        self._h_rescale = self.registry.histogram(
            "repro_rescale_seconds", "elastic rescale duration")
        self._c_loss = self.registry.counter(
            "repro_replica_loss_total", "replica-loss events applied")
        self.replicas: list[Replica] = []
        meshes = fleet_meshes(replicas, tp)
        # one fleet-wide request-id counter: per-engine counters would
        # collide across replicas, making the recovery trace ambiguous
        # about which request a rewind/migrate names
        shared_rid = itertools.count()
        for rid, mesh in enumerate(meshes):
            eng = build_engine(mesh, rid)
            eng._rid = shared_rid
            self.replicas.append(Replica(rid, mesh, eng))
            self._g_up.labels(replica=str(rid)).set(1)
        # fleet-wide batch target for rescale planning: default the sum of
        # slot capacity (a serving fleet's "global batch" is its slots)
        self._tgb = (int(target_global_batch) if target_global_batch
                     else sum(r.engine.slots for r in self.replicas))
        self._ticks = 0
        #: fleet recovery trace — same tuple format as the engine logs
        self.resil_log: list = []
        #: requests terminated at fleet level (rewind exhausted retries)
        self._fleet_done: list = []
        #: the survivor-mesh plans, one per rescale, newest last
        self.rescales: list[RescalePlan] = []

    # -- liveness ---------------------------------------------------------

    @property
    def live(self) -> list[Replica]:
        return [r for r in self.replicas if r.alive]

    def _event(self, name: str, **args) -> None:
        self.resil_log.append((self._ticks, name,
                               tuple(sorted(args.items()))))
        self._tracer.event(name, track="fleet", tick=self._ticks, **args)

    # -- routing ----------------------------------------------------------

    def _route(self) -> Replica:
        """Least-loaded live replica, ties to the lowest rid (deterministic
        routing is part of the same-seed recovery-trace contract).

        ``route_by="slots"`` (default) counts requests: queued + in-slot.
        ``route_by="backlog"`` counts admission work instead — queued
        payload units plus the un-ingested remainder of every mid-admission
        slot — so a replica grinding through one long chunked prompt stops
        looking as cheap as one serving short decodes."""
        live = self.live
        if not live:
            raise RuntimeError("no live replicas")

        def load(r: Replica) -> tuple:
            eng = r.engine
            busy = sum(1 for q in eng.slot_req if q is not None)
            if self.route_by == "backlog":
                wl = eng.workload
                units = sum(q.payload_units for q in eng.queue)
                units += sum(max(q.payload_units - 1 - q.cursor, 0)
                             for q in eng.slot_req
                             if q is not None and not wl.admit_complete(q))
                return (units + busy, r.rid)
            return (len(eng.queue) + busy, r.rid)

        return min(live, key=load)

    def submit(self, payload, budget=None, **kw):
        """Enqueue one request on the least-loaded live replica; returns
        the live Request (the engine's own submit surface)."""
        return self._route().engine.submit(payload, budget, **kw)

    # -- failure path -----------------------------------------------------

    def _finish_fleet(self, req, status: str, now: float) -> None:
        req.status = status
        req.done = True
        req.t_done = now
        self._fleet_done.append(req)

    def _rewind(self, req, now: float) -> None:
        """Tear one in-flight request out of a dead replica: the same full
        rewind the per-slot quarantine performs (the retry must be
        indistinguishable from a fresh admission), front-requeued onto a
        survivor behind capped backoff, or failed past the retry cap."""
        req.retries += 1
        if req.retries > self.policy.max_retries:
            self._finish_fleet(req, "failed", now)
            self._event("request_failed", rid=req.rid, retries=req.retries)
            return
        req.out.clear()
        req.cursor = 0
        req.admitted_units = 0
        req.t_first_emit = 0.0
        req.degree_at_first_emit = None
        backoff = self.policy.backoff_s(req.retries)
        req.eligible_at = now + backoff
        target = self._route()
        target.engine.queue.appendleft(req)
        self._event("rewind", rid=req.rid, retries=req.retries,
                    to_replica=target.rid,
                    backoff_ms=round(backoff * 1e3, 3))

    def _migrate_queue(self, victim: Replica) -> int:
        """Move a dead/draining replica's *queued* (never-admitted)
        requests to survivors, FIFO order preserved — no rewind needed,
        nothing was decoded yet."""
        moved = 0
        while victim.engine.queue:
            req = victim.engine.queue.popleft()
            target = self._route()
            target.engine.queue.append(req)
            moved += 1
            self._event("migrate", rid=req.rid, to_replica=target.rid)
        return moved

    def _rescale(self, reason: str) -> RescalePlan:
        """Replan the survivor mesh and charge the re-shard latency to the
        (injectable) clock."""
        survivors = len(self.live)
        plan = plan_rescale(max(survivors, 1) * self.tp,
                            target_global_batch=self._tgb, tp=self.tp)
        seconds = self.rescale_ms / 1e3
        advance = getattr(self._clock, "advance", None)
        if advance is not None:
            advance(seconds)
        else:
            time.sleep(seconds)
        self._h_rescale.observe(seconds)
        self.rescales.append(plan)
        self._event("rescale", reason=reason, replicas=survivors,
                    data=plan.data, model=plan.model,
                    idle=plan.idle_devices, ms=round(seconds * 1e3, 3))
        return plan

    def kill(self, rid: int, reason: str = "fault") -> Optional[RescalePlan]:
        """Hard replica loss: mark dead, migrate its queue, rewind its
        in-flight slots onto survivors, replan the mesh.  The last live
        replica is never killed (a fleet of zero serves nobody — the event
        is logged and skipped; availability beats fidelity to the fault)."""
        victim = self.replicas[rid]
        if not victim.alive:
            return None
        if len(self.live) == 1:
            self._event("replica_loss_skipped", replica=rid,
                        why="last_live_replica")
            return None
        victim.alive = False
        victim.died_at = self._ticks
        self._g_up.labels(replica=str(rid)).set(0)
        self._c_loss.inc()
        now = self._clock()
        self._event("replica_lost", replica=rid, reason=reason)
        moved = self._migrate_queue(victim)
        eng = victim.engine
        rewound = 0
        for s in range(eng.slots):
            req = eng.slot_req[s]
            if req is None:
                continue
            eng.slot_req[s] = None
            self._rewind(req, now)
            rewound += 1
        self._event("replica_drained", replica=rid, migrated=moved,
                    rewound=rewound)
        return self._rescale(f"replica_loss:{rid}")

    def decommission(self, rid: int, max_ticks: int = 1000
                     ) -> Optional[RescalePlan]:
        """Graceful retirement: stop routing to the replica (migrate its
        queue), let its decodable in-flight slots drain in place, then
        mark it dead and replan — zero rewinds, zero retries."""
        victim = self.replicas[rid]
        if not victim.alive or len(self.live) == 1:
            return None
        self._event("decommission", replica=rid)
        self._migrate_queue(victim)
        ticks = 0
        while any(r is not None for r in victim.engine.slot_req) \
                and ticks < max_ticks:
            victim.engine.tick()
            self._migrate_queue(victim)   # quarantine requeues drain too
            ticks += 1
        victim.alive = False
        victim.died_at = self._ticks
        self._g_up.labels(replica=str(rid)).set(0)
        self._event("replica_drained", replica=rid, migrated=0, rewound=0)
        return self._rescale(f"decommission:{rid}")

    # -- the fleet loop ---------------------------------------------------

    def _apply_faults(self) -> None:
        for ev in self.faults.events_at(self._ticks):
            if ev.kind != "replica_loss":
                continue   # engine-level kinds belong to engine-level plans
            self.faults.record(ev)
            self.kill(ev.slot % len(self.replicas), reason="injected")

    def tick(self) -> int:
        """One fleet iteration: apply scheduled replica losses, then tick
        every live engine.  Returns total active slots fleet-wide."""
        if self.faults is not None:
            self._apply_faults()
        active = 0
        for r in self.live:
            active += r.engine.tick()
        self._ticks += 1
        return active

    def run_until_drained(self, max_ticks: int = 10_000) -> list:
        """Tick until every live queue and slot is empty (or the budget
        runs out); returns the fleet-wide done list."""
        ticks = 0
        while any(r.engine.queue or
                  any(q is not None for q in r.engine.slot_req)
                  for r in self.live) and ticks < max_ticks:
            self.tick()
            ticks += 1
        for r in self.live:
            if getattr(r.engine, "emitter", None) is not None:
                r.engine.emitter.flush()
        return self.done

    # -- accounting -------------------------------------------------------

    @property
    def done(self) -> list:
        """Every terminated request fleet-wide — dead replicas' histories
        included (their finished work still happened), plus requests the
        fleet itself failed out of the rewind path.  Exactly one entry per
        submitted request, whatever its fate."""
        out = []
        for r in self.replicas:
            out.extend(r.engine.done)
        out.extend(self._fleet_done)
        return out

    def status_counts(self) -> dict:
        """Fleet-wide ``{ok,failed,shed,deadline}`` tally."""
        counts: dict = {}
        for req in self.done:
            counts[req.status] = counts.get(req.status, 0) + 1
        return counts
