"""Kernel backend dispatch: the single routing point between the model
attention call sites and the Pallas kernels (DESIGN.md §8).

Backend selection — ``REPRO_KERNELS`` env var, overridable per-process via
:func:`set_backend` (``launch.serve``/``launch.train`` ``--kernels`` flag,
``bench_serving`` A/B):

  pallas   always route qualifying shapes through the Pallas kernels
           (interpret-mode emulation off-TPU: correctness/step-count work)
  xla      always the pure-jnp paths (models/attention.py)
  auto     pallas on TPU, xla elsewhere (default — CPU CI stays on the
           fast jnp paths, TPU gets the kernels with ``interpret=False``)

``interpret`` is resolved per backend (``jax.default_backend() != "tpu"``)
instead of the old hardcoded ``True``.

Routing contract:

  * :func:`prefill_attention` — the model-layout (B, S, H, D) GQA entry for
    full-sequence attention (train forward, fused serve prefill).  Qualifies
    when causal or un-windowed (the kernel's grids); GQA is flattened to the
    kernel's (BH, S, D) layout (kv heads repeated — the kernel layout
    contract; the jnp fallback keeps the grouped never-materialized form).
    Differentiable: routes through ``flash_attention_vjp``.
  * :func:`decode_attention` — single-token decode against a KVCache /
    QuantKVCache, routed to kernels/flash_decode.py with free-slot masking
    and the runtime ebits degree; falls back to decode_attn(_quant).
  * :func:`axq_matmul` / :func:`axq_gated` — the GEMM-side twin (DESIGN.md
    §9): AXQ projections route to the axqmm Pallas kernels (fused epilogues,
    prepacked-weight residency) or the pure-jnp qmm refs.  Float weights go
    through a custom-VJP (kernel fwd, ``qmm_ref``-oracle bwd — or an STE
    exact-matmul bwd for the MoE experts) so ``--kernels pallas`` training
    routes AXQ too; :class:`~repro.kernels.qstore.PackedQWeight` operands
    take the quantize-once inference path.

``last_route`` records the decision per call site — keys ``"prefill"`` /
``"decode"`` (attention) and ``"gemm"`` / ``"gated"`` (AXQ projections) —
for tests and benchmarks.  Every decision is also published through
``repro.obs`` (DESIGN.md §11): a ``repro_kernel_route_trace_total{site=..,
backend=..}`` counter on the process-global metrics registry plus a
``kernel_route`` trace event.  Routers run at *trace* time (inside jit
tracing), so these count compilations — the serve engine's
``repro_kernel_route_steps_total`` counts executed steps per backend.

Runtime degree contract: every router takes the DyFXU degree as a *traced*
scalar (``ebits`` / ``degree``), so moving it never recompiles.  Per-layer
plans (repro.tune, models/degrees.py) keep that contract by slicing their
(n_layers + 1,) degree vector down to this layer's scalar before the call —
inside ``lax.scan`` the slice is automatic (the vector rides the scan xs);
unrolled call sites (e.g. the hybrid tail blocks in models/rglru.py) use
:func:`site_degree`.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import (qmm_gated_packed_ref, qmm_gated_ref,
                                     qmm_packed_ref, qmm_ref)
from repro.kernels import axqmm as _axq
from repro.kernels.flash_attention import flash_attention_vjp
from repro.kernels.flash_decode import decode_attn_flash
from repro.kernels.qstore import PackedQWeight, resolve_block

Array = jnp.ndarray

_VALID = ("auto", "pallas", "xla")

_override: Optional[str] = None

#: last routing decision per call site ("prefill" / "decode" attention,
#: "gemm" / "gated" AXQ projections) — debug aid for tests and benchmarks,
#: written at trace time.
last_route: dict = {}


def _record_route(site: str, backend: str) -> None:
    """Publish one routing decision: ``last_route`` (tests), the global
    metrics registry (counter by site x backend), and a trace event.
    Called at trace time — counts reflect compilations, not executions."""
    last_route[site] = backend
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    obs_metrics.get_registry().counter(
        "repro_kernel_route_trace_total",
        "kernel routing decisions at trace time, by call site and backend",
        labels=("site", "backend")).labels(site=site, backend=backend).inc()
    obs_trace.event("kernel_route_trace", track="dispatch", site=site,
                    backend=backend)


def set_backend(name: Optional[str]) -> None:
    """Process-wide override of ``REPRO_KERNELS`` (None -> back to env).
    Takes effect for functions traced afterwards (the serve engine traces
    its fused step at construction, so build engines after switching)."""
    global _override
    if name is not None and name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _override = name


def backend_setting() -> str:
    setting = _override or os.environ.get("REPRO_KERNELS", "auto")
    if setting not in _VALID:
        raise ValueError(
            f"REPRO_KERNELS must be one of {_VALID}, got {setting!r}")
    return setting


def resolved_backend() -> str:
    """'pallas' or 'xla' after resolving 'auto' against the live platform."""
    setting = backend_setting()
    if setting == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return setting


def use_pallas() -> bool:
    return resolved_backend() == "pallas"


def interpret_mode() -> bool:
    """Pallas interpret flag for the current platform (auto, not hardcoded).
    Single source of truth: flash_attention._resolve_interpret (shared by
    both kernels)."""
    from repro.kernels.flash_attention import _resolve_interpret

    return _resolve_interpret(None)


def site_degree(degree, site: int):
    """Index a per-layer degree vector down to one site's scalar knob.

    ``degree`` may be None (static spec), a traced scalar (global DyFXU
    degree — passes through), or a per-site vector (an ApproxPlan rung);
    ``site`` is the layer id (or ``n_layers`` for the head site).  The
    returned scalar is what the kernels scalar-prefetch — indexing a traced
    vector keeps the zero-recompile contract."""
    if degree is None:
        return None
    d = jnp.asarray(degree)
    return d[site] if d.ndim else d


def inject_fault(x, fault):
    """Resilience fault hook (repro.resil, DESIGN.md §13): corrupt a batch
    activation ``x`` (slots leading axis) with a traced per-slot ``fault``
    operand — a (slots,) float32 vector where 0.0 means clean and NaN/Inf
    marks the slot for corruption.  Float activations take ``x + fault``
    (exact identity for clean slots, NaN/Inf poisoning for marked ones);
    integer activations flip the high magnitude bit on marked slots
    (SEU-style — NaN compares unordered so ``fault != 0`` is True for it).
    ``fault=None`` is the no-resilience path: returns ``x`` untouched with
    zero trace footprint."""
    if fault is None:
        return x
    f = jnp.asarray(fault, jnp.float32).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 1))
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x + f.astype(x.dtype)
    mask = jnp.asarray(1 << (8 * x.dtype.itemsize - 2), x.dtype)
    return jnp.where(f != 0.0, x ^ mask, x)


# ---------------------------------------------------------------------------
# call-site routers
# ---------------------------------------------------------------------------


def _head_sharded(fn, *args):
    """Run ``fn(*args)`` per device when a mesh wider than one device is
    active.

    The chip's compiler cannot partition a Mosaic kernel, so the attention
    kernels run under ``shard_map``.  Every argument and result of rank 3 or
    more carries its head axis at dim 2 and is split over ``"model"``; it and
    every (B,) vector split the batch over the data axes where it divides
    them; scalars are replicated.  Attention is independent per (slot,
    head), so the per-device calls compute exactly what one device would."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import meshctx

    mesh = meshctx.get_mesh()
    if mesh.size == 1:
        return fn(*args)
    bd = meshctx.batch_axes(mesh)
    width = int(np.prod([mesh.shape[a] for a in bd])) if bd else 1
    b = tuple(bd) if bd and args[0].shape[0] % width == 0 else None

    def spec(a):
        nd = jnp.ndim(a)
        if nd == 0:
            return P()
        if nd == 1:
            return P(b)
        return P(b, None, "model", *([None] * (nd - 3)))

    return jax.shard_map(
        fn, mesh=mesh, in_specs=jax.tree.map(spec, args),
        out_specs=jax.tree.map(spec, jax.eval_shape(fn, *args)),
        check_vma=False)(*args)


def prefill_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      window: Optional[int] = None) -> Array:
    """Full-sequence GQA attention, model layout: q (B, S, H, D),
    k/v (B, S, KVr, D) -> (B, S, H, D)."""
    from repro.models import attention as attn  # lazy: kernels<->models layering

    qualifies = use_pallas() and q.shape[1] > 1 and (causal or window is None)
    _record_route("prefill", "pallas" if qualifies else "xla")
    if not qualifies:
        return attn.attn_blockwise(q, k, v, causal=causal, window=window)

    def run(q, k, v):
        b, s, h, d = q.shape
        kf = attn.repeat_kv(k, h)
        vf = attn.repeat_kv(v, h)

        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

        o = flash_attention_vjp(flat(q), flat(kf), flat(vf), causal, window)
        return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return _head_sharded(run, q, k, v)


def decode_attention(q1: Array, knew: Array, vnew: Array, cache, *,
                     window: Optional[int] = None, degree=None, active=None):
    """Single-token decode against the cache: q1 (B, 1, H, D),
    knew/vnew (B, 1, KVr, D) -> (out (B, 1, H, D), advanced cache).

    ``degree``: runtime ebits knob (int8 cache dequant degrade on the pallas
    path; the jnp path dequantizes exactly).  ``active``: (B,) bool free-slot
    mask (pallas path zeroes masked outputs; the jnp path computes and lets
    the engine discard them).
    """
    from repro.models import attention as attn

    if use_pallas():
        _record_route("decode", "pallas")

        def run(q1, knew, vnew, cache, active, degree):
            return decode_attn_flash(q1, knew, vnew, cache, window=window,
                                     active=active, degree=degree)

        return _head_sharded(run, q1, knew, vnew, cache, active, degree)
    _record_route("decode", "xla")
    if isinstance(cache, attn.QuantKVCache):
        return attn.decode_attn_quant(q1, knew, vnew, cache, window=window)
    return attn.decode_attn(q1, knew, vnew, cache, window=window)


# ---------------------------------------------------------------------------
# GEMM routing (AXQ projections — DESIGN.md §9)
# ---------------------------------------------------------------------------

# legacy pre-dispatch escape hatch: force the Pallas GEMM regardless of the
# attention backend setting (kept for parity with the seed's ops.py knob)
_GEMM_FORCE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def _gemm_route() -> str:
    return "pallas" if (use_pallas() or _GEMM_FORCE_PALLAS) else "xla"


def _float0(a):
    return np.zeros(np.shape(a), jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _axq_core(block: int, route: str, ste: bool):
    """Differentiable AXQ matmul core for *float* weights, cached per
    (block, backend, bwd-flavor).  Forward runs the Pallas kernel (or the
    jnp ref); backward differentiates the ``qmm_ref`` oracle — both backends
    therefore produce identical gradients, so AXQ training no longer
    silently requires the jnp reference path.  ``ste=True`` swaps in a
    straight-through exact-matmul backward (quantization is
    piecewise-constant; the MoE experts train through this)."""

    def run(x, w, e):
        if route == "pallas":
            return _axq.axqmm(x, w, block=block, ebits=e)
        return qmm_ref(x, w, block=block, ebits=e)

    core = jax.custom_vjp(run)

    def fwd(x, w, e):
        return run(x, w, e), (x, w, e)

    def bwd(res, g):
        x, w, e = res
        if ste:
            g16 = g.astype(jnp.bfloat16)
            dx = jnp.matmul(g16, w.astype(jnp.bfloat16).T,
                            preferred_element_type=jnp.float32).astype(x.dtype)
            dw = jnp.matmul(x.astype(jnp.bfloat16).T, g16,
                            preferred_element_type=jnp.float32).astype(w.dtype)
        else:
            _, vjp = jax.vjp(
                lambda xx, ww: qmm_ref(xx, ww, block=block, ebits=e), x, w)
            dx, dw = vjp(g)
        return dx, dw, _float0(e)

    core.defvjp(fwd, bwd)
    return core


@functools.lru_cache(maxsize=None)
def _axq_gated_core(block: int, route: str, act: str, ste: bool):
    """Differentiable fused gated core (float weights): kernel fwd,
    oracle bwd — see :func:`_axq_core`."""
    actf = _axq._ACTS[act]

    def run(x, wu, wg, e):
        if route == "pallas":
            return _axq.axqmm_gated(x, wu, wg, block=block, ebits=e, act=act)
        return qmm_gated_ref(x, wu, wg, actf, block=block, ebits=e)

    core = jax.custom_vjp(run)

    def fwd(x, wu, wg, e):
        return run(x, wu, wg, e), (x, wu, wg, e)

    def bwd(res, g):
        x, wu, wg, e = res
        if ste:
            def exact(xx, wuu, wgg):
                u = jnp.matmul(xx, wuu, preferred_element_type=jnp.float32)
                t = jnp.matmul(xx, wgg, preferred_element_type=jnp.float32)
                return actf(t) * u
            _, vjp = jax.vjp(exact, x, wu, wg)
        else:
            _, vjp = jax.vjp(
                lambda xx, wuu, wgg: qmm_gated_ref(
                    xx, wuu, wgg, actf, block=block, ebits=e), x, wu, wg)
        dx, dwu, dwg = vjp(g)
        return (dx.astype(x.dtype), dwu.astype(wu.dtype),
                dwg.astype(wg.dtype), _float0(e))

    core.defvjp(fwd, bwd)
    return core


def axq_matmul(x2: Array, w, *, block: int = 256, ebits=8,
               bias: Optional[Array] = None, residual: Optional[Array] = None,
               ste: bool = False) -> Array:
    """AXQ GEMM router: x2 (M, K) @ w -> (M, N) f32.

    ``w`` is either a float (K, N) array (trainable: quantized on the fly
    inside a custom-VJP) or a :class:`PackedQWeight` (quantize-once
    residency: per-call work is activation quantization only; inference).
    ``bias`` (N,) / ``residual`` (M, N) fuse into the kernel's f32 writeback
    only on the *packed* pallas route (the inference hot path); the float
    (training) route and the jnp fallback apply them as the same-ordered f32
    adds after the matmul, so every route computes identical values."""
    route = _gemm_route()
    _record_route("gemm", route)
    e = jnp.asarray(ebits, jnp.int32)
    x2 = x2.astype(jnp.float32)
    if isinstance(w, PackedQWeight):
        if route == "pallas":
            return _axq.axqmm_packed(x2, w, e, bias=bias, residual=residual)
        y = qmm_packed_ref(x2, w.qw, w.scales, e)
        if bias is not None:
            y = y + bias.astype(jnp.float32)[None, :]
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        return y
    blk = resolve_block(x2.shape[-1], block)
    y = _axq_core(blk, route, ste)(x2, w.astype(jnp.float32), e)
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :]
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return y


def axq_gated(x2: Array, w_up, w_gate, *, act: str = "silu",
              block: int = 256, ebits=8, ste: bool = False) -> Array:
    """Fused gated-MLP first-half router: ``act(x@w_gate) * (x@w_up)``.
    Same float-vs-packed contract as :func:`axq_matmul`; the pallas route
    streams one shared x tile through both GEMMs and gates in-VMEM."""
    route = _gemm_route()
    _record_route("gated", route)
    e = jnp.asarray(ebits, jnp.int32)
    x2 = x2.astype(jnp.float32)
    if isinstance(w_up, PackedQWeight):
        if route == "pallas":
            return _axq.axqmm_gated_packed(x2, w_up, w_gate, e, act=act)
        return qmm_gated_packed_ref(x2, w_up.qw, w_up.scales, w_gate.qw,
                                    w_gate.scales, _axq._ACTS[act], e)
    blk = resolve_block(x2.shape[-1], block)
    return _axq_gated_core(blk, route, act, ste)(
        x2, w_up.astype(jnp.float32), w_gate.astype(jnp.float32), e)


# ---------------------------------------------------------------------------
# DSP routing (approximate FIR / conv2d — the Ch. 7 accelerators)
# ---------------------------------------------------------------------------


def _pr_knobs(degree, p, r):
    """Resolve the PR knobs: either a ladder ``degree`` (effective bits,
    mapped via ``dsp.degree_to_pr``) or explicit raw (p, r) — not both."""
    from repro.kernels import dsp as _dsp

    if degree is not None:
        if p is not None or r is not None:
            raise ValueError("pass either degree= or explicit p=/r=, not both")
        return _dsp.degree_to_pr(degree)
    return (jnp.int32(0) if p is None else jnp.asarray(p, jnp.int32),
            jnp.int32(0) if r is None else jnp.asarray(r, jnp.int32))


def fir(x, taps, *, tail=None, degree=None, p=None, r=None, n: int = 16,
        shift: int = 0):
    """Approximate-FIR router (DyFXU PR datapath): pallas kernel vs the
    bit-identical jnp ref, selected like every other site (``REPRO_KERNELS``
    / :func:`set_backend`), recorded under ``last_route["fir"]``.

    Two call modes (int32 operands; the float/differentiable entry is
    :func:`fir_approx`):

    * offline / valid-mode (``tail=None``): ``x`` is a whole (L,) signal;
      host-side int64 accumulation (arbitrary Q14 operands), returns a
      numpy (L - T,) array.  Benchmarks and examples.
    * streaming (``tail`` given): ``x`` (B, L) frame batch, ``tail``
      (B, T-1) carried history; jit-safe int32 accumulation (taps l1 norm
      <= ``2**shift``), returns ``(y, new_tail)``.  The serve engine.

    ``degree`` is the ladder knob (None = exact, traced scalar = runtime
    rung); raw (p, r) may be passed instead for sweep-style benches."""
    from repro.kernels import dsp as _dsp

    backend = "pallas" if use_pallas() else "xla"
    _record_route("fir", backend)
    pk, rk = _pr_knobs(degree, p, r)
    interp = interpret_mode()
    if tail is None:
        return _dsp.fir_valid(x, taps, pk, rk, n=n, backend=backend,
                              interpret=interp)
    return _dsp.fir_frames(x, tail, taps, pk, rk, n=n, shift=shift,
                           backend=backend, interpret=interp)


def conv2d(img, kern, *, degree=None, p=None, r=None, n: int = 16,
           shift: int = 0, pad: str = "zero"):
    """Approximate-conv2d router (same-size 2D correlation on the PR
    datapath): img (B, H, W) int32, kern (kh, kw) int32 with l1 norm <=
    ``2**shift``; jit-safe, recorded under ``last_route["conv2d"]``.  Same
    degree/knob contract as :func:`fir`."""
    from repro.kernels import dsp as _dsp

    backend = "pallas" if use_pallas() else "xla"
    _record_route("conv2d", backend)
    pk, rk = _pr_knobs(degree, p, r)
    return _dsp.conv2d_pr(img, kern, pk, rk, n=n, shift=shift, pad=pad,
                          backend=backend, interpret=interpret_mode())


@functools.lru_cache(maxsize=None)
def _fir_core(T: int, q: int, n: int, route: str, interp: bool):
    """Differentiable float FIR core, cached per (taps, Q format, backend):
    quantize -> PR streaming kernel -> dequantize forward; exact-correlation
    STE backward (the PR bit surgery is piecewise-constant), ``_float0``
    cotangents for the integer knobs — the GEMM ``_axq_core`` pattern."""
    from repro.kernels import dsp as _dsp

    scale = float(1 << q)
    lim = float((1 << (n - 1)) - 1)

    def run(x, t, pk, rk):
        xq = jnp.clip(jnp.round(x * scale), -lim, lim).astype(jnp.int32)
        tq = jnp.clip(jnp.round(t * scale), -lim, lim).astype(jnp.int32)
        tail = jnp.zeros((x.shape[0], T - 1), jnp.int32)
        y, _ = _dsp.fir_frames(xq, tail, tq, pk, rk, n=n, shift=0,
                               backend=route, interpret=interp)
        return y.astype(jnp.float32) / (scale * scale)

    core = jax.custom_vjp(run)

    def exact(x, t):
        ext = jnp.concatenate(
            [jnp.zeros((x.shape[0], T - 1), x.dtype), x], axis=1)
        win = jnp.stack([ext[:, i:i + x.shape[1]] for i in range(T)])
        return jnp.einsum("i,ibl->bl", t, win)

    def fwd(x, t, pk, rk):
        return run(x, t, pk, rk), (x, t)

    def bwd(res, g):
        x, t = res
        _, vjp = jax.vjp(exact, x, t)
        dx, dt = vjp(g)
        return dx, dt, _float0(jnp.int32(0)), _float0(jnp.int32(0))

    core.defvjp(fwd, bwd)
    return core


def fir_approx(x: Array, taps: Array, *, degree=None, q: int = 12,
               n: int = 16) -> Array:
    """Differentiable float FIR entry (custom-VJP like the GEMM routes):
    x (B, L) f32 in ~[-1, 1], taps (T,) f32 with |l1| <~ 1 (so Q-``q``
    products fit int32 lanes).  Zero-history causal filtering; forward runs
    the int PR datapath, backward is the exact correlation (STE)."""
    route = "pallas" if use_pallas() else "xla"
    _record_route("fir", route)
    pk, rk = _pr_knobs(degree, None, None)
    return _fir_core(int(taps.shape[0]), q, n, route, interpret_mode())(
        x.astype(jnp.float32), taps.astype(jnp.float32), pk, rk)
