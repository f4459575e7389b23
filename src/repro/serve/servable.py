"""ServableModel: the workload protocol the serve engine is generic over.

The engine (``serve/engine.py::ServeCore``) owns the *scheduling* machinery
— FIFO queue, fixed slot batch, free-slot masking, the QoS degree ladder,
tracing/metrics — and knows nothing about what flows through the slots.
Everything workload-specific (what a unit of work is, how a payload is
ingested into a slot, what one fused step computes, when a request
finishes) lives behind this protocol.  Two production workloads implement
it: the LM adapter (``serve/lm.py`` — sampling, EOS, KV caches) and the
streaming DSP/vision pipeline (``serve/stream.py`` — approximate FIR +
conv2d frames, Ch. 7 accelerators).

State contract: ``init_state`` returns a NamedTuple following the cache
layout convention of ``models/cache_ops.py`` — a ``length`` field of shape
(batch,) with batch at axis 0, every other field with batch at axis 1
(leading stack axis) — so the generic ``cache_reset_slot`` /
``cache_mask_update`` helpers apply unchanged, and a freed slot handed to
the next request is bit-identical to a fresh engine (the engine's
reuse-after-free guarantee holds per workload for free).

Vocabulary contract: the engine's trace events and summaries must speak the
workload's language ("prefill"/"first_token" for LMs, "admit"/"first_frame"
for streams), so the *names* are protocol attributes too — the engine never
hardcodes them.

Degree contract: ``admit``/``step`` receive the engine's traced degree
operand (None | scalar | per-site vector — models/degrees.py) and must
keep it traced (slice with ``dispatch.site_degree``, never ``int()``), so
QoS ladder moves stay zero-recompile for every workload.
"""

from __future__ import annotations

from typing import Optional


class ServableModel:
    """Base/protocol for engine workloads.  Subclasses override everything
    marked NotImplementedError; the attribute defaults are generic labels a
    workload usually re-brands."""

    # ---- vocabulary: how the engine narrates this workload ------------
    #: what one emitted unit is called (metric family names, summaries)
    unit: str = "items"
    #: trace-span name for slot admission/ingest
    admit_span: str = "admit"
    #: enqueue/admit trace arg naming the payload size
    payload_arg: str = "payload_items"
    #: enqueue trace arg naming the emission budget
    budget_arg: str = "budget"
    #: trace-event name for a request's first emission
    first_event: str = "first_emit"
    #: step vocabulary stem: the engine's tick span is "{step_span}_tick"
    #: and the step counter families are "repro_{step_span}_*"
    step_span: str = "step"
    #: Request subclass the engine constructs on submit (workloads may
    #: attach named read-only views of the generic fields)
    request_cls = None  # resolved to serve.engine.Request when None
    #: dispatch call-site counted per admission ingest (None = uncounted)
    admit_site: Optional[str] = "admit"
    #: dispatch call-sites counted per fused step
    step_sites: tuple = ()

    #: the underlying arch config (plan validation / degree site names);
    #: must expose ``name`` and ``n_layers`` at minimum
    cfg = None

    #: per-slot magnitude bound for the guarded step's sanity check (None =
    #: finite-only); workloads set the bound the clean pipeline can never
    #: leave (LM: a logit limit; stream: the Q-format range)
    guard_limit: Optional[float] = None

    #: admission pipeline config (serve/admission.py) — None keeps the
    #: legacy exact-length one-request-at-a-time admission path; the engine
    #: reads this to drive bucketed/packed/chunked admission
    admission = None

    #: ``(bucket, padded)`` of the last admission call, set by the workload
    #: as it returns: the payload length its executable was built for, and
    #: the positions it computed over all its rows, padding included (the
    #: engine's admit-span args and bucket counter); None where the
    #: workload does not say, and the engine takes the units ingested
    last_admit_shape: Optional[tuple] = None

    # ---- weights ------------------------------------------------------
    def prepack(self, params):
        """Quantize-once residency hook (DESIGN.md §9); identity by default."""
        return params

    # ---- slot state ---------------------------------------------------
    def init_state(self, *, batch: int, max_len: int):
        """Fresh per-slot stream state: a NamedTuple on the cache_ops layout
        (``length`` (batch,) at axis 0; other fields batch at axis 1)."""
        raise NotImplementedError

    def init_feed(self, slots: int):
        """Host-side (slots, ...) array the engine hands each fused step —
        the per-slot step input (next LM id, next stream frame)."""
        raise NotImplementedError

    def reset_slot(self, state, slot):
        """Rewind one slot's state region (jitted by the engine)."""
        raise NotImplementedError

    # ---- request validation ------------------------------------------
    def validate(self, payload):
        """Canonicalize a submitted payload (or raise ValueError at submit
        time — rejecting mid-tick would lose the request)."""
        raise NotImplementedError

    def payload_units(self, payload) -> int:
        """Payload size in this workload's units (trace/summary label)."""
        raise NotImplementedError

    def default_budget(self, payload) -> int:
        """Emission budget when the caller doesn't pass one."""
        raise NotImplementedError

    # ---- the three compute edges -------------------------------------
    def admit(self, params, state, feed, slot: int, req, degree):
        """Ingest ``req.payload`` into ``slot``: reset the slot region,
        consume any prefix that rides a fused ingest call, and write the
        first step input into ``feed``.  Returns ``(state, ingested)`` —
        ``ingested`` units count toward the admission counters (0 when the
        payload rides the step feed only)."""
        raise NotImplementedError

    # ---- budgeted admission (pipeline edge, DESIGN.md §15) ------------
    # The engine only calls these when :attr:`admission` is set; the
    # defaults preserve legacy single-call semantics so workloads opt in
    # incrementally.

    def admit_batch(self, params, state, feed, pairs, degree):
        """Admit several requests in one device call: ``pairs`` is a list of
        ``(slot, req)``.  Returns ``(state, ingested_list)``.  Default:
        sequential :meth:`admit` calls (no packing win, same semantics)."""
        ingested = []
        for slot, req in pairs:
            state, n = self.admit(params, state, feed, slot, req, degree)
            ingested.append(n)
        return state, ingested

    def admit_chunk(self, params, state, feed, slot: int, req, degree):
        """Advance one chunk of ``req``'s admission into ``slot`` (progress
        carried in ``req.cursor``; the engine's rewind path resets it).
        Returns ``(state, ingested)``."""
        raise NotImplementedError(f"{type(self).__name__} cannot chunk")

    def admit_complete(self, req) -> bool:
        """Whether ``req``'s payload is fully ingested — a slot only joins
        the fused decode batch once this holds."""
        return True

    def wants_chunked(self, req) -> bool:
        """Whether this request should admit via :meth:`admit_chunk`."""
        return False

    def admit_calls(self, req) -> int:
        """Device calls needed to admit ``req`` (doomed-shed estimate in
        resil.policy: calls x admit_eta_ms vs remaining TTFT budget)."""
        return 1

    def warmup_admission(self, params, state, feed, degree) -> None:
        """Trace every admission executable (bucket ladder, chunk size) with
        dummy rows so no request compiles after startup.  Must not mutate
        ``state``/``feed`` observably.  Default: nothing to warm."""

    def step(self, params, state, feed, active, key, degree):
        """ONE fused step over all slots (the engine jits this once):
        ``(emission, new_state)`` where emission is a (slots, ...) batch.
        Free slots must be masked via ``cache_mask_update`` so their state
        never advances."""
        raise NotImplementedError

    def guarded_step(self, params, state, feed, active, key, degree, fault):
        """Fault-aware twin of :meth:`step` (repro.resil, DESIGN.md §13):
        same contract plus a traced per-slot ``fault`` operand — a (slots,)
        float32 vector, 0.0 = clean, NaN/Inf = corrupt that slot's
        activations via ``dispatch.inject_fault`` — and a third output:
        per-slot ``ok`` bools from the jit-safe guard check
        (``resil.guards.slot_ok`` against :attr:`guard_limit`).  The engine
        never banks an emission whose ok bit is False; it quarantines the
        slot instead.  This default wraps :meth:`step` (inject + check on
        the emission); workloads override to place the injection/guard
        inside the pipeline (the LM adapter guards logits pre-sampling)."""
        from repro.kernels import dispatch as kdispatch
        from repro.resil import guards

        emission, new_state = self.step(params, state, feed, active, key,
                                        degree)
        emission = kdispatch.inject_fault(emission, fault)
        return emission, new_state, guards.slot_ok(emission,
                                                   limit=self.guard_limit)

    def harvest(self, req, feed, slot: int, emission):
        """Bank one slot's step emission into ``req.out`` and advance its
        feed.  Returns ``(emitted, finished, info)``: ``emitted`` False
        drops the emission (e.g. LM EOS — neither banked nor charged);
        ``finished`` ends the request regardless of remaining budget;
        ``info`` feeds :meth:`done_args`."""
        raise NotImplementedError

    def done_args(self, req, info: dict) -> dict:
        """Trace args for the request_done event (workload vocabulary)."""
        return {self.unit: len(req.out), **info}

    # ---- quality / calibration hooks ---------------------------------
    def quality_tap(self, *, every: int, registry, tracer):
        """Build the live-vs-exact quality sampler (obs/quality.py) for
        ``quality_every=N``; workloads without one raise."""
        raise NotImplementedError(
            f"{type(self).__name__} has no quality tap")

    def exact_model(self):
        """An exact-arithmetic twin for calibration references
        (tune.autotune probes); self if ``degree=None`` already means exact."""
        return self
