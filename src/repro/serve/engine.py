"""Workload-generic continuous-batching serve core: slot lifecycle + QoS.

Requests enter a FIFO queue; free slots are (re)filled on admission by the
workload's fused ingest call, which rewinds the slot's state region and
writes the payload prefix into it; every engine tick runs ONE fused,
jit-compiled step for all slots.  Free slots are masked out of the step —
their state never advances — so a freed slot can be handed to the next
request with no stale-state pollution: admission into a reused slot is
bit-identical to a solo run on a fresh engine.

The engine is generic over a :class:`~repro.serve.servable.ServableModel`
(DESIGN.md §12): everything workload-specific — what a unit of work is, how
a payload is ingested, what the fused step computes, when a request
finishes, even the vocabulary the trace events speak — lives behind that
protocol.  ``serve/lm.py`` adapts the language models (the historical
``ServeEngine`` surface, re-exported below unchanged); ``serve/stream.py``
serves the Ch. 7 approximate DSP/vision pipeline frame-by-frame through the
same slot lifecycle.

The fused step is a single compiled executable across the whole engine
lifetime: workload sampling/config is baked at construction, while the PRNG
key and the DyFXU approximation ``degree`` (Ch. 5 §5.2.3) are traced
operands — a global scalar or, under an
:class:`~repro.tune.plan.ApproxPlan`, a per-site degree *vector*
(models/degrees.py).  An optional :class:`~repro.core.dynamic.QoSController`
moves the degree with serving load — the dissertation's
runtime-configuration contract at system level: heavy load -> cheaper
arithmetic, idle -> exact.  With a plan the controller steps along the
plan's calibrated ladder (whole mixed per-site configurations, Pareto
points from ``repro.tune``) instead of rescaling one global knob; either
way the compiled executable never changes.

Resilience (``repro.resil``, DESIGN.md §13): ``faults=`` injects a seeded
:class:`~repro.resil.faults.FaultPlan` (SEU bit flips, NaN/Inf activations,
latency spikes, dropped ticks); ``guards=`` switches the engine onto the
workload's ``guarded_step`` — per-slot ok bits, quarantine through the
bit-identical slot reset, golden-param scrubbing, quality-tap sentinel;
``policy=`` adds deadlines, capped-backoff retry, backpressure, and
brownout-by-approximation (the QoS ladder degrades before anything sheds).
``clock=`` injects the engine's time source (``resil.policy.VirtualClock``
makes deadline/goodput behavior deterministic).  With all four at their
defaults the engine compiles and runs the exact legacy path.  Every request
terminates exactly once in ``done`` with a status in {ok, failed, shed,
deadline} — nothing is lost or double-charged — and ``resil_log`` records
the (tick, event, args) recovery trace the determinism tests assert on.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dynamic import QoSController, degree_operand
from repro.kernels import dispatch as kdispatch
from repro.obs import trace as obs_trace
from repro.serve.metrics import EngineStats
from repro.serve.servable import ServableModel

_DEFAULT_EBITS = 8


@dataclass
class Request:
    """One unit of serving work, workload-agnostic.  ``payload`` is what the
    workload ingests (LM prompt ids, stream frames), ``out`` what its steps
    emit; the LM adapter subclasses this with the historical field names as
    read-only views (``serve/lm.py``)."""

    rid: int
    payload: object
    budget: int = 32              # emission budget (units)
    payload_units: int = 0        # payload size in workload units
    out: list = field(default_factory=list)
    done: bool = False
    admitted_units: int = 0       # units ingested by the fused admit call
    cursor: int = 0               # workload read head into the payload
    t_enqueue: float = 0.0
    t_admitted: float = 0.0
    t_first_emit: float = 0.0
    t_done: float = 0.0
    # degree tuple that served the first emission (None until then, or
    # engine running without a traced degree): makes mid-run QoS rung moves
    # visible per request, not just the engine-final degree
    degree_at_first_emit: Optional[tuple] = None
    # -- resilience lifecycle (repro.resil; defaults = legacy behavior) --
    #: terminal disposition: ok | failed (retries spent) | shed | deadline
    status: str = "ok"
    #: guard-trip requeues so far
    retries: int = 0
    #: e2e / TTFT deadlines (seconds from t_enqueue; None = none)
    deadline_s: Optional[float] = None
    ttft_deadline_s: Optional[float] = None
    #: earliest admission time (retry backoff gate)
    eligible_at: float = 0.0

    # -- latency breakdown (valid once done) --
    @property
    def queue_time(self) -> float:
        return self.t_admitted - self.t_enqueue

    @property
    def ttft(self) -> float:
        return self.t_first_emit - self.t_enqueue

    @property
    def tpot(self) -> float:
        return (self.t_done - self.t_first_emit) / max(len(self.out) - 1, 1)

    @property
    def e2e(self) -> float:
        return self.t_done - self.t_enqueue


class ServeCore:
    """Continuous-batching engine over a fixed batch of ``slots``, generic
    over a :class:`~repro.serve.servable.ServableModel` workload.

    Construction compiles the workload's fused step once; afterwards
    ``submit`` enqueues requests and ``tick`` / ``run_until_drained``
    advance the batch.  ``qos`` drives the runtime approximation degree
    from load; ``plan`` replaces the controller's global-ebits ladder with
    the plan's calibrated per-site ladder (and supplies the initial degree
    vector), so QoS moves between whole tuned configurations.  ``degree``
    pins a static initial degree (scalar or per-site vector) without a
    controller.  ``prepack`` applies the workload's quantize-once weight
    residency at construction (DESIGN.md §9).

    Observability (DESIGN.md §11): every lifecycle edge — enqueue,
    admission/ingest, per-tick step, first emission, completion, QoS rung
    transitions (with the per-site degree vector attached) — is traced
    through ``tracer`` (the process-global :mod:`repro.obs.trace` tracer
    by default, whose spans also reach any JAX profiler session) under the
    *workload's* vocabulary, and every counter lives in ``stats.registry``
    (a fresh :class:`repro.obs.metrics.Registry`, or pass ``registry=`` to
    co-export with the dispatch counters).  ``quality_every=N`` samples the
    live-vs-exact output error every N ticks into a per-rung histogram
    (``obs/quality.py``) through the workload's quality tap.
    """

    def __init__(self, workload: ServableModel, params, *, slots: int = 8,
                 max_len: int = 512, seed: int = 0,
                 qos: Optional[QoSController] = None,
                 degree=None, prepack: bool = True, plan=None,
                 registry=None, tracer=None, quality_every: int = 0,
                 faults=None, guards=None, policy=None, clock=None,
                 emitter=None):
        self.workload = workload
        self.params = workload.prepack(params) if prepack else params
        self.slots = slots
        self.max_len = max_len
        self.qos = qos
        self._clock = clock if clock is not None else time.time
        self.state = workload.init_state(batch=slots, max_len=max_len)
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.slot_budget = np.zeros(slots, np.int32)
        self.queue: deque[Request] = deque()
        self.done: list[Request] = []
        self.stats = EngineStats(registry, unit=workload.unit,
                                 admit_name=workload.admit_span,
                                 step_name=workload.step_span)
        self._tracer = tracer if tracer is not None else obs_trace.get_tracer()
        self._feed = workload.init_feed(slots)
        self._rid = itertools.count()
        self._ticks = 0
        self._key = jax.random.PRNGKey(seed)
        # approximation plan: validate against the arch, and point the QoS
        # controller's ladder at the plan's calibrated per-site rungs
        cfg = workload.cfg
        self.plan = plan
        if plan is not None:
            plan.validate_for(cfg)
            if qos is not None:
                qos.ladder = plan.qos_ladder()
                qos.degree = min(qos.degree, len(qos.ladder) - 1)
        # degree is traced only when someone will drive it; None keeps the
        # static policy spec (and a leaner step signature).  With a plan (or
        # any ladder of per-site rungs) the traced operand is the degree
        # vector (models/degrees.py) — its shape is fixed by the arch, so
        # ladder moves never retrace.  The initial degree comes from the
        # controller's current rung so the first QoS update cannot change
        # the operand's shape (scalar -> vector would recompile).
        self._use_degree = (qos is not None or degree is not None
                            or plan is not None)
        if degree is not None:
            self._degree = jnp.asarray(degree, jnp.int32)
        elif qos is not None and qos.ladder:
            self._degree = degree_operand(qos.ladder[qos.degree])
        elif plan is not None:
            self._degree = jnp.asarray(plan.degrees(0), jnp.int32)
        else:
            self._degree = (jnp.asarray(_DEFAULT_EBITS, jnp.int32)
                            if self._use_degree else None)
        # plan site names label the repro_degree_ebits{site=..} gauge family
        # (and trace events); scalar degrees export as site="global"
        from repro.tune.plan import site_names as _site_names

        self._site_names = _site_names(cfg)
        self._degree_rec: Optional[tuple] = None
        if self._degree is not None:
            # the construction-time degree is served until the first QoS
            # update: record it so the history covers every degree used
            self._degree_rec = self.stats.record_degree(
                -1, self._degree, self._site_names)
        # per-rung online quality telemetry (obs/quality.py): compare the
        # live degree's outputs against the exact rung every N ticks
        self._tap = None
        if quality_every > 0:
            if self._degree is None:
                raise ValueError(
                    "quality_every needs a traced degree (pass degree=, "
                    "qos=, or plan=)")
            self._tap = workload.quality_tap(every=quality_every,
                                             registry=self.stats.registry,
                                             tracer=self._tracer)
        # resolved kernel backend for the per-tick route counters: captured
        # from dispatch.last_route after the first traced step/ingest
        self._route: dict = {}
        # -- resilience wiring (repro.resil, DESIGN.md §13) ---------------
        # faults imply guards (injected corruption must be catchable) and
        # guards imply a policy (something must own retry semantics); with
        # all three absent the compiled step is the exact legacy jaxpr.
        if faults is not None and guards is None:
            from repro.resil import GuardConfig
            guards = GuardConfig()
        if guards is not None and policy is None:
            from repro.resil import ServePolicy
            policy = ServePolicy()
        self.faults = faults
        self.guards = guards
        self.policy = policy
        #: (tick, event, sorted-args) recovery trace — the determinism
        #: contract: same fault seed + same traffic -> identical log
        self.resil_log: list = []
        self._golden = None
        self._sentinel = None
        self._fault_vec = np.zeros(slots, np.float32)
        if guards is not None:
            if guards.limit is not None:
                workload.guard_limit = guards.limit
            # golden copy for scrubbing: JAX immutability makes this a free
            # reference — prepacked weights are repaired by the same rebind
            self._golden = self.params
            self._slot_reset = jax.jit(workload.reset_slot)
            if guards.sentinel_threshold is not None:
                if self._tap is None:
                    raise ValueError(
                        "sentinel_threshold needs quality_every > 0 (the "
                        "sentinel watches the quality tap's samples)")
                self._sentinel = guards.sentinel()
            self._step = jax.jit(workload.guarded_step)
        else:
            self._step = jax.jit(workload.step)
        if faults is not None:
            faults.bind(self.state, self.params, slots)
        # -- admission pipeline (DESIGN.md §15): bucketed AOT prefill, ----
        # packed prompts, chunked prefill, async emit.  None = the legacy
        # exact-length admission path, bit-identical to prior engines.
        self._admission = getattr(workload, "admission", None)
        self.emitter = None
        if self._admission is not None and emitter is not False:
            from repro.serve.emitq import AsyncEmitter
            self.emitter = emitter if emitter is not None else AsyncEmitter()
        # warmup traces every admission executable + the fused step so no
        # request compiles after startup; ShardedServeCore defers it until
        # params/state carry their final shardings (a resharded arg would
        # otherwise retrace at first live call)
        if not getattr(self, "_defer_warmup", False):
            self._maybe_warmup()

    def _maybe_warmup(self) -> None:
        a = self._admission
        if a is None or not a.warmup:
            return
        with self._tracer.span("admission_warmup", track="engine",
                               buckets=list(a.buckets), pack=a.pack,
                               chunk=a.chunk_tokens):
            self.workload.warmup_admission(self.params, self.state,
                                           self._feed, self._degree)
            # the fused decode-step executable, with a throwaway key and an
            # all-free mask (state updates are masked out and discarded)
            mask = jnp.zeros(self.slots, bool)
            key = jax.random.PRNGKey(0)
            feed = jnp.asarray(self._feed)
            if self.guards is not None:
                out = self._step(self.params, self.state, feed, mask, key,
                                 self._degree, jnp.asarray(self._fault_vec))
            else:
                out = self._step(self.params, self.state, feed, mask, key,
                                 self._degree)
            jax.block_until_ready(out)
        self.stats.c_warmups.inc()

    # ------------------------------------------------------------------

    def submit(self, payload, budget: Optional[int] = None, *,
               deadline_ms: Optional[float] = None,
               ttft_deadline_ms: Optional[float] = None) -> Request:
        """Enqueue one request (FIFO).  Returns the live Request object —
        emissions appear in ``request.out`` as ticks produce them, and
        latency fields populate when it finishes.  The workload validates
        the payload here (raising at submit time — rejecting mid-tick
        would lose the request).  ``deadline_ms``/``ttft_deadline_ms``
        override the policy defaults per request (ignored without a
        policy — nothing would enforce them)."""
        wl = self.workload
        payload = wl.validate(payload)
        if budget is None:
            budget = wl.default_budget(payload)
        p = self.policy
        if p is not None:
            if deadline_ms is None:
                deadline_ms = p.deadline_ms
            if ttft_deadline_ms is None:
                ttft_deadline_ms = p.ttft_deadline_ms
        req = (wl.request_cls or Request)(
            rid=next(self._rid), payload=payload, budget=int(budget),
            payload_units=wl.payload_units(payload),
            t_enqueue=self._clock(),
            deadline_s=None if deadline_ms is None else deadline_ms / 1e3,
            ttft_deadline_s=(None if ttft_deadline_ms is None
                             else ttft_deadline_ms / 1e3))
        self.queue.append(req)
        self._tracer.event(
            "enqueue", track="engine", rid=req.rid,
            queue_depth=len(self.queue),
            **{wl.payload_arg: req.payload_units, wl.budget_arg: int(budget)})
        return req

    def _admit(self, slot: int, req: Request):
        """Reset the slot's state region and ingest the payload via the
        workload's fused admit; the first step input lands in the feed."""
        req.t_admitted = self._clock()
        wl = self.workload
        with self._tracer.span(wl.admit_span, track="engine", rid=req.rid,
                               slot=slot, requests=1,
                               waited_ms=req.queue_time * 1e3,
                               **{wl.payload_arg: req.payload_units}) as sp:
            self.state, ingested = wl.admit(self.params, self.state,
                                            self._feed, slot, req,
                                            self._degree)
            sp.set_metadata(**self._shape_args(int(ingested)))
        req.admitted_units = int(ingested)
        if req.admitted_units > 0:
            self.stats.c_admit_units.inc(req.admitted_units)
            self.stats.c_admit_calls.inc()
            if wl.admit_site:
                self._count_route(wl.admit_site)
        self.slot_req[slot] = req
        self.slot_budget[slot] = req.budget
        self.stats.c_admitted.inc()

    # ---- admission pipeline (DESIGN.md §15) ---------------------------

    def _chunk_call(self, slot: int, req: Request) -> None:
        """One chunked-prefill device call advancing ``req``'s admission."""
        wl = self.workload
        # a request counts in the call that starts its admission alone
        first = req.cursor == 0
        with self._tracer.span(wl.admit_span, track="engine", rid=req.rid,
                               slot=slot, chunk=True, cursor=req.cursor,
                               requests=int(first),
                               waited_ms=req.queue_time * 1e3 if first
                               else 0.0) as sp:
            self.state, n = wl.admit_chunk(self.params, self.state,
                                           self._feed, slot, req,
                                           self._degree)
            sp.set_metadata(**self._shape_args(int(n)))
        req.admitted_units += int(n)
        if n > 0:
            self.stats.c_admit_units.inc(int(n))
        self.stats.c_admit_calls.inc()
        self.stats.c_chunk_calls.inc()
        if wl.admit_site:
            self._count_route(wl.admit_site)

    def _flush_batch(self, pairs: list) -> None:
        """Admit up to ``pack`` requests in one bucketed prefill call."""
        if not pairs:
            return
        wl = self.workload
        with self._tracer.span(wl.admit_span, track="engine",
                               rid=pairs[0][1].rid, slot=pairs[0][0],
                               packed=len(pairs), requests=len(pairs),
                               waited_ms=sum(r.queue_time
                                             for _, r in pairs) * 1e3) as sp:
            self.state, ingested = wl.admit_batch(self.params, self.state,
                                                  self._feed, pairs,
                                                  self._degree)
            total = sum(int(n) for n in ingested)
            sp.set_metadata(**self._shape_args(total))
        for (_, req), n in zip(pairs, ingested):
            req.admitted_units = int(n)
        if total > 0:
            self.stats.c_admit_units.inc(total)
        self.stats.c_admit_calls.inc()
        if len(pairs) > 1:
            self.stats.c_packed_rows.inc(len(pairs))
        if wl.last_admit_shape is not None:
            self.stats.c_admit_bucket.labels(
                bucket=str(wl.last_admit_shape[0])).inc()
        if wl.admit_site:
            self._count_route(wl.admit_site)

    def _shape_args(self, ingested: int) -> dict:
        """Trace args of one admission call, known once it returns: the
        payload units it ingested (``tokens``), the positions its
        executable computes over all rows (``padded``) and the payload
        length it was built for (``bucket``)."""
        shape = self.workload.last_admit_shape
        bucket, padded = shape if shape is not None else (ingested, ingested)
        return {"tokens": ingested, "padded": padded, "bucket": bucket}

    def _admit_pipeline(self, now: float) -> None:
        """Bucketed/packed/chunked admission: first advance mid-admission
        chunked slots (bounded calls per tick, so long-prompt ingestion
        interleaves with decode instead of stalling short-request TTFT),
        then fill free slots — chunked requests take their slot alone,
        short ones pack into one bucketed prefill call."""
        wl = self.workload
        a = self._admission
        for s in range(self.slots):
            req = self.slot_req[s]
            if req is None or wl.admit_complete(req):
                continue
            for _ in range(a.chunk_calls_per_tick):
                self._chunk_call(s, req)
                if wl.admit_complete(req):
                    break
        batch: list = []
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                continue
            if self.policy is None:
                req = self.queue.popleft() if self.queue else None
            else:
                req = self._next_admittable(now)
            if req is None:
                break
            req.t_admitted = now
            self.slot_req[s] = req
            self.slot_budget[s] = req.budget
            self.stats.c_admitted.inc()
            if wl.wants_chunked(req):
                self._chunk_call(s, req)
            else:
                batch.append((s, req))
                if len(batch) >= a.pack:
                    self._flush_batch(batch)
                    batch = []
        self._flush_batch(batch)

    def _update_degree(self, n_active: int):
        """Feed the QoS controller a load-headroom signal: overload drives
        the approximation degree down the ladder (cheaper arithmetic), idle
        capacity drives it back to exact — at fixed compiled executable.
        Plan ladders step whole per-site degree vectors; the legacy global
        ladder steps one ebits scalar."""
        occupancy = (n_active + len(self.queue)) / self.slots
        headroom = max(0.0, 1.0 - occupancy)
        kw = self.qos.update(self._ticks, headroom)
        self._degree = degree_operand(kw)
        rec = self.stats.record_degree(self._ticks, self._degree,
                                       self._site_names)
        if rec != self._degree_rec:
            # QoS rung transition: the event carries the full per-site
            # degree vector so the trace shows WHICH arithmetic served
            # every span that follows
            self._tracer.event("qos_rung", track="engine", tick=self._ticks,
                               rung=self.qos.degree, degrees=list(rec),
                               headroom=round(headroom, 4))
            self._degree_rec = rec

    def _count_route(self, site: str) -> None:
        """Per-call kernel-route counter: the backend is read from
        ``dispatch.last_route`` (written at trace time of this engine's
        jitted step/admit) and cached — so the counters reflect what
        actually compiled, and `sum(route counters) == call count`."""
        backend = self._route.get(site)
        if backend is None:
            backend = kdispatch.last_route.get(site,
                                               kdispatch.resolved_backend())
            self._route[site] = backend
            self._tracer.event("kernel_route", track="engine", site=site,
                               backend=backend)
        self.stats.c_route_steps.labels(site=site, backend=backend).inc()

    # ---- resilience machinery (repro.resil, DESIGN.md §13) -------------

    def _resil_event(self, name: str, **args) -> None:
        """Record one recovery-trace entry + the matching obs trace event.
        The log entry is a plain (tick, name, sorted-args) tuple so two
        runs compare with ``==`` — the determinism contract's artifact."""
        self.resil_log.append((self._ticks, name, tuple(sorted(args.items()))))
        self._tracer.event(name, track="resil", tick=self._ticks, **args)

    def _finish(self, req: Request, status: str, now: float,
                slot: Optional[int] = None) -> None:
        """Terminate one request non-ok (failed/shed/deadline): exactly one
        ``done`` entry per submitted request, whatever its fate."""
        req.status = status
        req.done = True
        req.t_done = now
        self.done.append(req)
        if slot is not None:
            self.slot_req[slot] = None

    def _scrub(self, reason: str) -> None:
        """Restore the golden parameter tree (memory scrubbing): repairs
        any persistent seu_param corruption.  Free when already golden."""
        if self._golden is not None and self.params is not self._golden:
            self.params = self._golden
            self.stats.c_scrubs.inc()
            self._resil_event("param_scrub", reason=reason)

    def _quarantine(self, slot: int, now: float) -> None:
        """Per-slot guard trip: reset the slot through the bit-identical
        cache_ops reset, scrub, and requeue (rewound to a fresh admission,
        behind capped backoff) or fail the request per policy."""
        req = self.slot_req[slot]
        self.stats.c_guard_trips.labels(reason="slot").inc()
        self._resil_event("guard_tripped", reason="slot", rid=req.rid,
                          slot=slot)
        self.state = self._slot_reset(self.state, jnp.asarray(slot, jnp.int32))
        self.slot_req[slot] = None
        if self.guards.scrub_on_trip:
            self._scrub("guard_trip")
        req.retries += 1
        if req.retries > self.policy.max_retries:
            self._finish(req, "failed", now)
            self.stats.c_failed.inc()
            self._resil_event("request_failed", rid=req.rid,
                              retries=req.retries)
            return
        # full rewind: the retry must be indistinguishable from a fresh
        # admission (asserted bit-identical by the quarantine tests)
        req.out.clear()
        req.cursor = 0
        req.admitted_units = 0
        req.t_first_emit = 0.0
        req.degree_at_first_emit = None
        backoff = self.policy.backoff_s(req.retries)
        req.eligible_at = now + backoff
        self.queue.appendleft(req)
        self.stats.c_retries.inc()
        self._resil_event("retry", rid=req.rid, retries=req.retries,
                          backoff_ms=round(backoff * 1e3, 3))

    def _next_admittable(self, now: float) -> Optional[Request]:
        """Oldest queued request whose retry backoff has elapsed."""
        for req in self.queue:
            if req.eligible_at <= now:
                self.queue.remove(req)
                return req
        return None

    def _enforce_queue_policy(self, now: float) -> None:
        """Deadline-cull the queue, apply queue-age shedding, and resolve
        queue-length overload: brownout first (force the QoS controller one
        rung down the calibrated ladder), shed — newest first — only once
        the ladder is exhausted."""
        p = self.policy
        keep: deque[Request] = deque()
        for req in self.queue:
            age = now - req.t_enqueue
            if req.deadline_s is not None and age > req.deadline_s:
                self._finish(req, "deadline", now)
                self.stats.c_deadline_miss.labels(edge="queue").inc()
                self._resil_event("deadline_miss", edge="queue", rid=req.rid)
                continue
            if req.ttft_deadline_s is not None and req.t_first_emit == 0.0:
                # TTFT measures from ENQUEUE, so a queued request spends
                # its budget while waiting: past the deadline it can no
                # longer emit in time, and one whose remaining budget
                # cannot cover its admission call count (chunked prompts
                # need several device calls) is doomed — shed it now
                # instead of burning device time on a guaranteed miss
                if age > req.ttft_deadline_s:
                    self._finish(req, "deadline", now)
                    self.stats.c_deadline_miss.labels(edge="queue_ttft").inc()
                    self._resil_event("deadline_miss", edge="queue_ttft",
                                      rid=req.rid)
                    continue
                if p.admit_eta_ms is not None:
                    eta = (self.workload.admit_calls(req)
                           * p.admit_eta_ms / 1e3)
                    if age + eta > req.ttft_deadline_s:
                        self._finish(req, "shed", now)
                        self.stats.c_shed.labels(reason="doomed").inc()
                        self._resil_event("shed", reason="doomed",
                                          rid=req.rid)
                        continue
            if (p.max_queue_age_ms is not None
                    and age * 1e3 > p.max_queue_age_ms):
                self._finish(req, "shed", now)
                self.stats.c_shed.labels(reason="stale").inc()
                self._resil_event("shed", reason="stale", rid=req.rid)
                continue
            keep.append(req)
        self.queue = keep
        if p.max_queue is None or len(self.queue) <= p.max_queue:
            return
        qos = self.qos
        if (p.brownout and qos is not None and qos.ladder
                and qos.degree < len(qos.ladder) - 1):
            # graceful degradation: one rung per tick, with the controller's
            # own cooldown armed so it can't immediately climb back
            qos.degree += 1
            qos._cooldown = qos.cooldown_steps
            self.stats.c_brownout.inc()
            self._resil_event("brownout_rung", rung=qos.degree,
                              queued=len(self.queue))
            return
        while len(self.queue) > p.max_queue:
            victim = self.queue.pop()
            self._finish(victim, "shed", now)
            self.stats.c_shed.labels(reason="overload").inc()
            self._resil_event("shed", reason="overload", rid=victim.rid)

    def _enforce_active_deadlines(self, now: float) -> None:
        """Terminate in-slot requests past their e2e or TTFT deadline (the
        freed slot region is rewound by the next admission's reset)."""
        for s in range(self.slots):
            req = self.slot_req[s]
            if req is None:
                continue
            age = now - req.t_enqueue
            if req.deadline_s is not None and age > req.deadline_s:
                edge = "active"
            elif (req.ttft_deadline_s is not None and req.t_first_emit == 0.0
                    and age > req.ttft_deadline_s):
                edge = "ttft"
            else:
                continue
            self._finish(req, "deadline", now, slot=s)
            self.stats.c_deadline_miss.labels(edge=edge).inc()
            self._resil_event("deadline_miss", edge=edge, rid=req.rid, slot=s)

    def _stall(self, seconds: float) -> None:
        """Latency spike: advance an injectable clock, sleep a real one."""
        advance = getattr(self._clock, "advance", None)
        if advance is not None:
            advance(seconds)
        else:
            time.sleep(seconds)

    def _apply_faults(self) -> bool:
        """Apply this tick's scheduled faults; True = the step is dropped.
        State/param flips mutate the live trees (the golden copy is safe by
        immutability); activation faults arm the traced fault operand."""
        drop = False
        for ev in self.faults.events_at(self._ticks):
            self.faults.record(ev)
            self.stats.c_faults.labels(kind=ev.kind).inc()
            self._resil_event("fault_injected", **ev.args())
            if ev.kind == "seu_state":
                self.state = self.faults.apply_state(self.state, ev)
            elif ev.kind == "seu_param":
                self.params = self.faults.apply_params(self.params, ev)
            elif ev.kind == "nan":
                self._fault_vec[ev.slot] = ev.value
            elif ev.kind == "spike":
                self._stall(ev.value)
            elif ev.kind == "drop":
                drop = True
        return drop

    # ---------------------------------------------------------------

    def tick(self) -> int:
        """One engine iteration: admit queued requests into free slots
        (fused ingest per admission), update the QoS degree, run ONE fused
        step over all slots, and harvest emissions / finished requests.
        Returns the number of active slots (0 = idle).

        Its phases are sibling spans under ``tick``: ``admit``, the step's
        ``{step_span}_tick`` (holding ``dispatch`` and ``sync``) and
        ``harvest``, so a profiler trace charges each stretch the device
        waits through to the host work that caused it."""
        now = self._clock()
        with self._tracer.span("tick", track="engine", tick=self._ticks,
                               active=sum(r is not None
                                          for r in self.slot_req),
                               queued=len(self.queue)):
            return self._tick(now)

    def _admit_phase(self, now: float) -> None:
        """Queue policy, slot fill and every admission call of the tick."""
        st = self.stats
        admitted, calls = st.c_admitted.value, st.c_admit_calls.value
        with self._tracer.span("admit", track="engine") as sp:
            if self.policy is not None:
                self._enforce_queue_policy(now)
                self._enforce_active_deadlines(now)
            # FIFO admission into free slots
            if self._admission is None:
                for s in range(self.slots):
                    if self.slot_req[s] is None and self.queue:
                        if self.policy is None:
                            self._admit(s, self.queue.popleft())
                        else:
                            req = self._next_admittable(now)
                            if req is None:
                                break
                            self._admit(s, req)
            else:
                self._admit_pipeline(now)
            sp.set_metadata(requests=int(st.c_admitted.value - admitted),
                            calls=int(st.c_admit_calls.value - calls))

    def _tick(self, now: float) -> int:
        wl = self.workload
        self._admit_phase(now)
        if self.guards is not None and self.guards.scrub_every > 0 \
                and self._ticks and self._ticks % self.guards.scrub_every == 0:
            self._scrub("periodic")
        busy = [s for s in range(self.slots) if self.slot_req[s] is not None]
        if not busy:
            return 0
        # a slot mid-way through chunked admission holds a request but has
        # no decodable state yet: it stays out of the fused step's mask
        # until its payload is fully ingested
        active = [s for s in busy if wl.admit_complete(self.slot_req[s])]
        if not active:
            # admission-only tick: chunk calls progressed, nothing decodes
            self._ticks += 1
            return len(busy)
        if self.qos is not None:
            self._update_degree(len(active))
        # scheduled faults land before the step: state/param flips are what
        # the step consumes, the armed fault operand poisons its activations
        drop = self.faults is not None and self._apply_faults()
        mask = np.zeros(self.slots, bool)
        mask[active] = True
        if self._tap is not None and self._tap.due(self._ticks):
            # probe BEFORE the step: same inputs the fused step is about to
            # consume, state untouched (the tap discards its state updates)
            val = self._tap.sample(self._ticks, self.params, self.state,
                                   self._feed, mask, self._degree)
            if self._sentinel is not None and self._sentinel.observe(val):
                self.stats.c_guard_trips.labels(reason="quality").inc()
                self._resil_event("guard_tripped", reason="quality",
                                  sample=round(float(val), 6))
                if self.guards.scrub_on_trip:
                    self._scrub("sentinel")
        if drop:
            # dropped tick: the fused step never runs — no state advance,
            # no emission, no budget charge; an armed activation fault
            # evaporates with the skipped cycle
            self._fault_vec[:] = 0.0
            self._ticks += 1
            self.stats.c_dropped_ticks.inc()
            return len(active)
        tracer = self._tracer
        with tracer.span(f"{wl.step_span}_tick", track="engine",
                         tick=self._ticks, active=len(active),
                         queued=len(self.queue)):
            with tracer.span("dispatch", track="engine"):
                self._key, sub = jax.random.split(self._key)
                if self.guards is not None:
                    nxt, self.state, ok = self._step(
                        self.params, self.state, jnp.asarray(self._feed),
                        jnp.asarray(mask), sub, self._degree,
                        jnp.asarray(self._fault_vec))
                else:
                    nxt, self.state = self._step(self.params, self.state,
                                                 jnp.asarray(self._feed),
                                                 jnp.asarray(mask), sub,
                                                 self._degree)
                    ok = None
            with tracer.span("sync", track="engine"):
                nxt = np.asarray(nxt)
                if ok is not None:
                    ok = np.asarray(ok)
                    # cleared only once the step is done: the device may
                    # read the host buffer until then
                    self._fault_vec[:] = 0.0
        self._ticks += 1
        with tracer.span("harvest", track="engine") as sp:
            emitted, finished = self._harvest(active, nxt, ok)
            sp.set_metadata(emitted=emitted, finished=finished)
        return len(active)

    def _harvest(self, active: list, nxt: np.ndarray, ok) -> tuple[int, int]:
        """Bank the step's emissions: route and slot counters, per-slot
        harvest, the emitter, completions.  Returns (emitted, finished)."""
        wl = self.workload
        self.stats.c_steps.inc()
        self.stats.c_step_units.inc(len(active))
        for site in wl.step_sites:
            self._count_route(site)
        self._tracer.counter("slots", track="engine", active=len(active),
                             queued=len(self.queue))
        now = self._clock()
        n_emitted = n_finished = 0
        for s in active:
            req = self.slot_req[s]
            if ok is not None and not ok[s]:
                # corrupted emission: never banked — quarantine the slot
                self._quarantine(s, now)
                continue
            emitted, finished, info = wl.harvest(req, self._feed, s, nxt[s])
            if emitted:
                n_emitted += 1
                # a suppressed emission (e.g. an LM stop id) is neither
                # banked nor charged against the budget; a request that
                # finishes before emitting anything keeps t_first_emit == 0
                # (excluded from TTFT stats)
                if req.t_first_emit == 0.0:
                    req.t_first_emit = now
                    req.degree_at_first_emit = self._degree_rec
                    self._tracer.event(wl.first_event, track="engine",
                                       rid=req.rid, slot=s,
                                       ttft_ms=round(req.ttft * 1e3, 3))
                if self.emitter is not None:
                    # detokenize/deliver off-thread: harvest returns to the
                    # device step without waiting on host-side emit work
                    self.emitter.push(req, req.out[-1])
                self.slot_budget[s] -= 1
            if finished or self.slot_budget[s] <= 0:
                n_finished += 1
                req.done = True
                req.t_done = now
                self.done.append(req)
                self.slot_req[s] = None
                self.stats.record_completion(req)
                self._tracer.event("request_done", track="engine",
                                   rid=req.rid, slot=s,
                                   e2e_ms=round(req.e2e * 1e3, 3),
                                   **wl.done_args(req, info))
        return n_emitted, n_finished

    def run_until_drained(self, max_ticks: int = 10_000) -> list[Request]:
        """Tick until the queue and every slot are empty (or ``max_ticks``);
        returns all finished requests, completion order."""
        ticks = 0
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and ticks < max_ticks:
            self.tick()
            ticks += 1
        if self.emitter is not None:
            self.emitter.flush()
        return self.done


# The historical LM engine surface lives in serve/lm.py on top of ServeCore;
# re-exported here so every existing import path keeps working.  (Safe: by
# this line ServeCore/Request exist, which is all serve/lm.py needs.)
from repro.serve.lm import ServeEngine  # noqa: E402,F401
