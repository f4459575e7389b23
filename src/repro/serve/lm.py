"""LM workload adapter: token decode on the generic serve core.

Everything token-specific that used to live inside the engine — sampling
(greedy / top-k, traced temperature), EOS stopping, the prompt-prefix fused
prefill, KV-cache init/reset, prompt-length bounds, the logit-RMS quality
tap — is an :class:`LMAdapter` implementing the
:class:`~repro.serve.servable.ServableModel` protocol.  The historical
:class:`ServeEngine` construction surface (and every attribute the tests,
benches and launchers read: ``cache``, ``eos_id``, ``submit(prompt,
max_new_tokens)``) is a thin facade over
:class:`~repro.serve.engine.ServeCore` — behavior through the adapter is
bit-identical to the pre-protocol engine (same jitted step jaxpr, same
admission arithmetic, same EOS/budget bookkeeping).

  eos_id semantics: ``-1`` (the default) disables EOS stopping — no vocab
  id compares equal.  When set, sampling ``eos_id`` finishes the request;
  the EOS token itself is neither emitted into ``out_tokens`` nor charged
  against ``max_new_tokens``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cache_ops import cache_mask_update
from repro.models.registry import Model
from repro.serve import engine as _engine
from repro.serve.admission import AdmissionConfig, bucket_for
from repro.serve.sampling import sample_tokens
from repro.serve.servable import ServableModel


class Request(_engine.Request):
    """Generic request with the historical LM field names as read-only
    views (``prompt``/``out_tokens``/``t_first_token``/...) — existing
    callers and the serve tests read these unchanged."""

    @property
    def prompt(self) -> np.ndarray:
        return self.payload

    @property
    def max_new_tokens(self) -> int:
        return self.budget

    @property
    def out_tokens(self) -> list:
        return self.out

    @property
    def prefill_tokens(self) -> int:
        return self.admitted_units

    @property
    def t_first_token(self) -> float:
        return self.t_first_emit

    @property
    def degree_at_first_token(self) -> Optional[tuple]:
        return self.degree_at_first_emit


class LMAdapter(ServableModel):
    """ServableModel over a :class:`~repro.models.registry.Model`: token
    units, fused-prefill admission, sample-and-feed-back decode steps."""

    unit = "tokens"
    admit_span = "prefill"
    step_span = "decode"
    payload_arg = "prompt_tokens"
    budget_arg = "max_new_tokens"
    first_event = "first_token"
    admit_site = "prefill"
    step_sites = ("decode",)
    request_cls = Request
    #: clean smoke-family logits sit well under this; a high-exponent SEU
    #: or NaN/Inf injection blows past it (resil.guards)
    guard_limit = 1e4

    def __init__(self, model: Model, *, tp: int = 1, eos_id: int = -1,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, max_len: int = 512,
                 admission: Optional[AdmissionConfig] = None):
        self.model = model
        self.cfg = model.cfg
        self.tp = tp
        self.eos_id = eos_id
        cfg = model.cfg
        # prompt-length bound: stateful families ingest unbounded prompts;
        # window caches ring-wrap only while window <= max_len (decode
        # saturates otherwise — attention.py); dense attention is bounded
        # by the cache capacity outright
        window = cfg.local_window if cfg.family == "hybrid" else cfg.swa_window
        if cfg.family == "ssm" or (window is not None and window <= max_len):
            self._max_prompt = None
        else:
            self._max_prompt = max_len
        vocab = cfg.vocab
        #: python-side executable census: each key counts TRACES (the
        #: counter lives inside the staged function body, so it bumps once
        #: per compilation, not per call) — the compile-count regression
        #: tests pin admission to the bucket ladder with this
        self.trace_counts = {"prefill": 0, "prefill_batch": 0,
                             "prefill_chunk": 0, "step": 0}

        def serve_step(p, cache, tokens, active, key, deg):
            self.trace_counts["step"] += 1
            logits, new_cache = model.decode_step(p, cache, tokens, tp=tp,
                                                  degree=deg, active=active)
            # free slots are masked out: length frozen, region unwritten
            new_cache = cache_mask_update(cache, new_cache, active)
            nxt = sample_tokens(logits[:, 0, :vocab], key, greedy=greedy,
                                temperature=temperature, top_k=top_k)
            return nxt, new_cache

        def guarded_serve_step(p, cache, tokens, active, key, deg, fault):
            # guard the *logits*, pre-sampling: the injection point is the
            # model's output activation (dispatch.inject_fault), the check
            # runs where corruption is still observable (sampling collapses
            # a poisoned distribution to a plausible-looking token id)
            from repro.kernels import dispatch as kdispatch
            from repro.resil import guards

            self.trace_counts["step"] += 1
            logits, new_cache = model.decode_step(p, cache, tokens, tp=tp,
                                                  degree=deg, active=active)
            new_cache = cache_mask_update(cache, new_cache, active)
            lv = kdispatch.inject_fault(logits[:, 0, :vocab], fault)
            ok = guards.slot_ok(lv, limit=self.guard_limit)
            # sampling must stay defined on quarantined slots (their token
            # is discarded, but NaN would poison the whole fused gather)
            safe = jnp.where(jnp.isfinite(lv), lv, 0.0)
            nxt = sample_tokens(safe, key, greedy=greedy,
                                temperature=temperature, top_k=top_k)
            return nxt, new_cache, ok

        self._serve_step = serve_step
        self._guarded_serve_step = guarded_serve_step

        def _prefill_impl(p, c, t, s, deg):
            self.trace_counts["prefill"] += 1
            return model.prefill(p, c, t, s, tp=tp, degree=deg)

        self._prefill = jax.jit(_prefill_impl)
        self._reset = jax.jit(model.reset_slot)

        # ---- bucketed/packed/chunked admission (DESIGN.md §15) --------
        self.admission = admission.resolved(max_len) if admission else None
        if self.admission is not None and getattr(cfg, "moe", None):
            # MoE capacity routing couples tokens ACROSS packed rows (the
            # per-expert capacity is computed over the whole call), so a
            # bucketed/packed prefill would not be bit-identical to
            # sequential admission — MoE keeps the exact-length path
            self.admission = None
        self._chunk_ok = False
        if self.admission is not None:
            import os

            def _prefill_batch_impl(p, c, t, s, ln, deg):
                self.trace_counts["prefill_batch"] += 1
                return model.prefill_batch(p, c, t, s, ln, tp=tp, degree=deg)

            self._prefill_batch = jax.jit(_prefill_batch_impl)
            self._chunk_ok = (self.admission.chunk_tokens > 0
                              and model.supports_chunked_prefill()
                              and os.environ.get("REPRO_KV_INT8", "0") != "1")
            if self._chunk_ok:
                def _prefill_chunk_impl(p, c, t, s, off, n, deg):
                    self.trace_counts["prefill_chunk"] += 1
                    return model.prefill_chunk(p, c, t, s, off, n, tp=tp,
                                               degree=deg)

                self._prefill_chunk = jax.jit(_prefill_chunk_impl)

    # ---- weights / slot state ----------------------------------------

    def prepack(self, params):
        return self.model.prepack(params)

    def init_state(self, *, batch: int, max_len: int):
        return self.model.init_cache(tp=self.tp, batch=batch,
                                     max_len=max_len)

    def init_feed(self, slots: int):
        # per-slot next-token feed for the fused decode step
        return np.zeros((slots, 1), np.int32)

    def reset_slot(self, state, slot):
        return self.model.reset_slot(state, slot)

    # ---- request validation ------------------------------------------

    def validate(self, prompt):
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if self._max_prompt is not None and prompt.size > self._max_prompt:
            raise ValueError(
                f"prompt length {prompt.size} exceeds cache capacity "
                f"{self._max_prompt} (max_len)")
        return prompt

    def payload_units(self, prompt) -> int:
        return int(prompt.size)

    def default_budget(self, prompt) -> int:
        return 32

    # ---- compute edges ------------------------------------------------

    def admit(self, params, cache, feed, slot, req, degree):
        """Ingest the prompt prefix with one fused prefill call; the final
        prompt token rides the next fused decode step (it produces the
        first generated token)."""
        prompt = req.payload
        sl = jnp.asarray(slot, jnp.int32)
        if prompt.size > 1:
            _, cache = self._prefill(params, cache, jnp.asarray(prompt[:-1]),
                                     sl, degree)
            ingested = int(prompt.size) - 1
        else:
            cache = self._reset(cache, sl)
            ingested = 0
        feed[slot, 0] = int(prompt[-1])
        req.cursor = ingested
        self.last_admit_shape = (ingested, ingested)
        return cache, ingested

    # ---- bucketed / packed / chunked admission ------------------------

    def admit_batch(self, params, cache, feed, pairs, degree):
        """Pack up to ``admission.pack`` prompt prefixes into ONE bucketed
        prefill call.  Calls are padded to exactly ``pack`` rows with
        dummies (slot = B, dropped out-of-bounds), so the executable set is
        one per bucket.  Prefixes longer than the largest bucket (unbounded
        window/SSM ingest) fall back to the exact-length path."""
        a = self.admission
        if a is None:
            return super().admit_batch(params, cache, feed, pairs, degree)
        B = feed.shape[0]
        ingested = {}
        bucketed = []
        bucket = padded = 0
        for slot, req in pairs:
            n = req.payload_units - 1
            if n > a.buckets[-1]:
                cache, ingested[id(req)] = self.admit(params, cache, feed,
                                                      slot, req, degree)
                bucket, padded = max(bucket, n), padded + n
            else:
                bucketed.append((slot, req))
        for i in range(0, len(bucketed), a.pack):
            group = bucketed[i:i + a.pack]
            lens = [r.payload_units - 1 for _, r in group]
            Pb = bucket_for(max(lens + [1]), a.buckets)
            toks = np.zeros((a.pack, Pb), np.int32)
            slots = np.full((a.pack,), B, np.int32)
            lengths = np.zeros((a.pack,), np.int32)
            for row, ((slot, req), n) in enumerate(zip(group, lens)):
                toks[row, :n] = req.payload[:-1]
                slots[row] = slot
                lengths[row] = n
                feed[slot, 0] = int(req.payload[-1])
                req.cursor = n
                ingested[id(req)] = n
            cache = self._prefill_batch(params, cache, jnp.asarray(toks),
                                        jnp.asarray(slots),
                                        jnp.asarray(lengths), degree)
            bucket, padded = max(bucket, Pb), padded + a.pack * Pb
        self.last_admit_shape = (bucket, padded)
        return cache, [ingested[id(r)] for _, r in pairs]

    def admit_chunk(self, params, cache, feed, slot, req, degree):
        """Advance one ``chunk_tokens`` chunk of ``req``'s prompt prefix;
        ``req.cursor`` carries progress (quarantine/rewind zero it).  The
        final prompt token rides the decode feed once the prefix lands."""
        a = self.admission
        C = a.chunk_tokens
        prompt = req.payload
        target = prompt.size - 1
        sl = jnp.asarray(slot, jnp.int32)
        if req.cursor == 0:
            cache = self._reset(cache, sl)
        take = min(C, target - req.cursor)
        toks = np.zeros((C,), np.int32)
        toks[:take] = prompt[req.cursor:req.cursor + take]
        cache = self._prefill_chunk(params, cache, jnp.asarray(toks), sl,
                                    jnp.asarray(req.cursor, jnp.int32),
                                    jnp.asarray(take, jnp.int32), degree)
        req.cursor += take
        if req.cursor >= target:
            feed[slot, 0] = int(prompt[-1])
        self.last_admit_shape = (C, C)
        return cache, take

    def admit_complete(self, req) -> bool:
        if self.admission is None:
            return True
        return req.cursor >= max(req.payload_units - 1, 0)

    def wants_chunked(self, req) -> bool:
        return (self._chunk_ok
                and req.payload_units - 1 > self.admission.chunk_tokens)

    def admit_calls(self, req) -> int:
        n = req.payload_units - 1
        if self.admission is not None and self.wants_chunked(req):
            return -(-n // self.admission.chunk_tokens)
        return 1

    def warmup_admission(self, params, cache, feed, degree) -> None:
        """Trace one executable per bucket (+ the chunk and slot-reset
        executables) with all-dummy rows: slot = B scatters are dropped, so
        the live state is untouched and the results are discarded."""
        a = self.admission
        if a is None:
            return
        B = feed.shape[0]
        dummy = jnp.asarray(B, jnp.int32)
        for Pb in a.buckets:
            out = self._prefill_batch(
                params, cache, jnp.zeros((a.pack, Pb), jnp.int32),
                jnp.full((a.pack,), B, jnp.int32),
                jnp.zeros((a.pack,), jnp.int32), degree)
            jax.block_until_ready(out)
        if self._chunk_ok:
            zero = jnp.asarray(0, jnp.int32)
            out = self._prefill_chunk(
                params, cache, jnp.zeros((a.chunk_tokens,), jnp.int32),
                dummy, zero, zero, degree)
            jax.block_until_ready(out)
        jax.block_until_ready(self._reset(cache, dummy))

    def step(self, params, cache, feed, active, key, degree):
        return self._serve_step(params, cache, feed, active, key, degree)

    def guarded_step(self, params, cache, feed, active, key, degree, fault):
        return self._guarded_serve_step(params, cache, feed, active, key,
                                        degree, fault)

    def harvest(self, req, feed, slot, emission):
        tok = int(emission)
        if self.eos_id >= 0 and tok == self.eos_id:
            return False, True, {"eos": True}
        req.out.append(tok)
        feed[slot, 0] = tok
        return True, False, {"eos": False}

    def done_args(self, req, info) -> dict:
        return {"eos": bool(info.get("eos", False)),
                "tokens": len(req.out)}

    # ---- quality ------------------------------------------------------

    def quality_tap(self, *, every, registry, tracer):
        from repro.obs.quality import QualityTap

        return QualityTap(self.model, tp=self.tp, every=every,
                          registry=registry, tracer=tracer)


class ServeEngine(_engine.ServeCore):
    """The historical LM serving engine: ``ServeCore`` specialized with an
    :class:`LMAdapter` — constructor signature, attribute surface
    (``cache``, ``_tokens``, sampling knobs) and behavior identical to the
    pre-protocol engine."""

    def __init__(self, model: Model, params, *, slots: int = 8,
                 max_len: int = 512, eos_id: int = -1, tp: int = 1,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, seed: int = 0, qos=None, degree=None,
                 prepack: bool = True, plan=None, registry=None,
                 tracer=None, quality_every: int = 0,
                 admission: Optional[AdmissionConfig] = None, **resil_kw):
        workload = LMAdapter(model, tp=tp, eos_id=eos_id, greedy=greedy,
                             temperature=temperature, top_k=top_k,
                             max_len=max_len, admission=admission)
        super().__init__(workload, params, slots=slots, max_len=max_len,
                         seed=seed, qos=qos, degree=degree, prepack=prepack,
                         plan=plan, registry=registry, tracer=tracer,
                         quality_every=quality_every, **resil_kw)
        self.model = model
        self.eos_id = eos_id
        self.tp = tp
        self.greedy = greedy
        self.temperature = temperature
        self.top_k = top_k

    # historical attribute views over the generic core state
    @property
    def cache(self):
        return self.state

    @cache.setter
    def cache(self, value):
        self.state = value

    @property
    def _tokens(self):
        return self._feed

    def submit(self, prompt, max_new_tokens: int = 32, **kw) -> Request:
        """Enqueue one request (FIFO).  Returns the live Request — tokens
        appear in ``request.out_tokens`` as ticks generate them."""
        return super().submit(prompt, max_new_tokens, **kw)
