"""Fused single-token decode attention against the serving KV cache.

Replaces the pure-jnp ``decode_attn`` / ``decode_attn_quant`` full-``T_max``
einsum in the engine's fused step (kernels/dispatch.py routes the call).

Grid (B, n_t): t walks the slot's cache region in ``bt``-sized tiles; each
tile spans every kv head, (1, bt, KVr, D), and the kernel loops over the
heads with online-softmax scratch per (kv-head, group).  The q block is the
slot's whole (KVr, G, D) query set, so grouped query heads share each loaded
kv tile.
Tiles that start beyond the slot's valid length are *skipped at runtime*
(``pl.when`` on the scalar-prefetched length — the dissertation's
computation-skipping pillar keyed on per-slot serving state, not a static
shape).  Block specs read the cache natively as (B, T, KVr, D); no transpose
or repeat_kv materialization on the decode path.

The int8 variant dequantizes tiles in-kernel — HBM holds int8, the
per-(token, head) scales ride along D x smaller — and first applies the
runtime effective-bits degrade to the integer mantissas: ``axqmm``'s DyFXU
scalar-prefetch knob (``ebits``) at the attention operand, so the QoS
controller's degree ladder reaches the decode hot loop with zero recompiles.

Slot semantics mirror ``models.attention.decode_attn``: the (ring-)buffer
write of the new token happens *outside* the kernel (a cheap scatter);
``nvalid = min(length + 1, T)`` already counts the just-written token, and
softmax over the valid set is permutation-invariant, so ring wraparound
order never matters.  Free slots (``active == 0``) produce exact-zero
outputs — the engine discards them, but they can never leak NaNs from an
uninitialized output block.

Validated vs decode_attn/decode_attn_quant incl. ring wraparound and
freed-slot masking (tests/test_flash_decode.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.axqmm import _degrade_tile
from repro.kernels.flash_attention import NEG_INF, _resolve_interpret

Array = jnp.ndarray


def _tiles(T: int, bt: int) -> tuple[int, int]:
    """(bt, n_t) with a ragged final tile when bt does not divide T — the
    cache is never padded or re-tiled per step; out-of-bounds lanes of the
    last tile are masked in-kernel (``cols < nvalid`` plus the v sanitize),
    so an odd cache capacity keeps full-width tiles instead of degrading
    toward 1-token tiles."""
    bt = min(bt, T)
    return bt, -(-T // bt)


def _online_block(s, v, acc_ref, m_ref, l_ref, h: int):
    """One online-softmax accumulation step for kv head ``h``; s (G, bt)
    pre-masked."""
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[h] = m_new
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finish(o_ref, acc_ref, l_ref, active_ref, b):
    act = (active_ref[b] > 0).astype(jnp.float32)
    o_ref[0] = (act * acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _head_scores(q_ref, k, h: int, t, bt: int, nv, scale: float):
    """Masked scores (G, bt) of kv head ``h``'s query group against one
    cache tile; also returns the (bt, 1) valid-row mask for v."""
    q = q_ref[0, h].astype(jnp.float32) * scale                   # (G, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (G, bt)
    cols = t * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    rows = t * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    return jnp.where(cols < nv, s, NEG_INF), rows < nv


def _decode_kernel(nvalid_ref, active_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, n_t: int, bt: int, kvr: int,
                   scale: float):
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    nv = nvalid_ref[b]

    @pl.when(t * bt < nv)          # runtime skip: tile wholly past the length
    def _compute():
        for h in range(kvr):       # every kv head of the tile, one DMA
            k = k_ref[0, :, h, :].astype(jnp.float32)             # (bt, D)
            s, valid = _head_scores(q_ref, k, h, t, bt, nv, scale)
            # sanitize past-length rows: a ragged final tile reads out of
            # bounds (undefined lanes) and 0 * NaN would poison p @ v
            v = jnp.where(valid, v_ref[0, :, h, :].astype(jnp.float32), 0.0)
            _online_block(s, v, acc_ref, m_ref, l_ref, h)

    @pl.when(t == n_t - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref, active_ref, b)


def _decode_kernel_quant(ebits_ref, nvalid_ref, active_ref, q_ref,
                         k_ref, ks_ref, v_ref, vs_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, n_t: int, bt: int,
                         kvr: int, scale: float):
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    nv = nvalid_ref[b]

    @pl.when(t * bt < nv)
    def _compute():
        shift = jnp.maximum(8 - ebits_ref[0], 0)
        ks, vs = ks_ref[0], vs_ref[0]                             # (bt, KVr)
        for h in range(kvr):
            kq = _degrade_tile(k_ref[0, :, h, :].astype(jnp.int32), shift)
            k = kq.astype(jnp.float32) * ks[:, h:h + 1]           # (bt, D)
            s, valid = _head_scores(q_ref, k, h, t, bt, nv, scale)
            vq = _degrade_tile(v_ref[0, :, h, :].astype(jnp.int32), shift)
            v = vq.astype(jnp.float32) * vs[:, h:h + 1]
            # sanitize past-length rows (ragged final tile: undefined lanes)
            v = jnp.where(valid, v, 0.0)
            _online_block(s, v, acc_ref, m_ref, l_ref, h)

    @pl.when(t == n_t - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref, active_ref, b)


def _grid_spec(n_prefetch: int, B: int, n_t: int, bt: int, KVr: int,
               G: int, D: int, *, quant: bool):
    """Grid (B, n_t): each step DMAs one cache tile across ALL kv heads,
    (1, bt, KVr, D) — the chip's tiling rules refuse a block of 1 against
    the KVr axis, which sits second-minor in the (B, T, KVr, D) cache."""
    kv = pl.BlockSpec((1, bt, KVr, D), lambda b, t, *pf: (b, t, 0, 0))
    sc = pl.BlockSpec((1, bt, KVr), lambda b, t, *pf: (b, t, 0))
    q = pl.BlockSpec((1, KVr, G, D), lambda b, t, *pf: (b, 0, 0, 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, n_t),
        in_specs=[q, kv, sc, kv, sc] if quant else [q, kv, kv],
        out_specs=pl.BlockSpec((1, KVr, G, D), lambda b, t, *pf: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVr, G, D), jnp.float32),
            pltpu.VMEM((KVr, G, 1), jnp.float32),
            pltpu.VMEM((KVr, G, 1), jnp.float32),
        ],
    )


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def flash_decode(qg: Array, k: Array, v: Array, nvalid: Array, active: Array,
                 *, bt: int = 128, interpret: Optional[bool] = None) -> Array:
    """qg: (B, KVr, G, D) grouped queries; k/v: (B, T, KVr, D) cache
    (new token already written); nvalid/active: (B,) int32.
    Returns (B, KVr, G, D) f32."""
    interpret = _resolve_interpret(interpret)
    B, KVr, G, D = qg.shape
    T = k.shape[1]
    bt, n_t = _tiles(T, bt)
    kern = functools.partial(_decode_kernel, n_t=n_t, bt=bt, kvr=KVr,
                             scale=1.0 / math.sqrt(D))
    return pl.pallas_call(
        kern,
        grid_spec=_grid_spec(2, B, n_t, bt, KVr, G, D, quant=False),
        out_shape=jax.ShapeDtypeStruct((B, KVr, G, D), jnp.float32),
        interpret=interpret,
    )(nvalid.astype(jnp.int32), active.astype(jnp.int32), qg, k, v)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def flash_decode_quant(qg: Array, k: Array, ks: Array, v: Array, vs: Array,
                       nvalid: Array, active: Array, ebits: Array,
                       *, bt: int = 128,
                       interpret: Optional[bool] = None) -> Array:
    """int8 cache variant: k/v (B, T, KVr, D) int8, ks/vs (B, T, KVr) f32
    scales, ebits (1,) int32 runtime degree (8 = exact dequant)."""
    interpret = _resolve_interpret(interpret)
    B, KVr, G, D = qg.shape
    T = k.shape[1]
    bt, n_t = _tiles(T, bt)
    kern = functools.partial(_decode_kernel_quant, n_t=n_t, bt=bt, kvr=KVr,
                             scale=1.0 / math.sqrt(D))
    return pl.pallas_call(
        kern,
        grid_spec=_grid_spec(3, B, n_t, bt, KVr, G, D, quant=True),
        out_shape=jax.ShapeDtypeStruct((B, KVr, G, D), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(ebits, jnp.int32).reshape(1), nvalid.astype(jnp.int32),
      active.astype(jnp.int32), qg, k, ks, v, vs)


def decode_attn_flash(q1: Array, knew: Array, vnew: Array, cache, *,
                      window: Optional[int] = None, active=None, degree=None,
                      interpret: Optional[bool] = None):
    """Drop-in for ``models.attention.decode_attn`` / ``decode_attn_quant``
    through the fused kernel.

    q1: (B, 1, H, D); knew/vnew: (B, 1, KVr, D); cache: KVCache or
    QuantKVCache.  ``active`` (B,) bool masks freed slots to zero output;
    ``degree`` is the runtime ebits knob (quant cache only).  Returns
    (out (B, 1, H, D), advanced cache) — same slot/ring math, same length
    semantics as the jnp paths.
    """
    from repro.models import attention as attn  # lazy: kernels<->models layering

    B, _, H, D = q1.shape
    T = cache.k.shape[1]
    kvh = cache.k.shape[2]
    pos = cache.length
    ring = window is not None and window <= T
    slot = jnp.mod(pos, T) if ring else jnp.minimum(pos, T - 1)
    bidx = jnp.arange(B)
    quant = isinstance(cache, attn.QuantKVCache)
    if quant:
        kq, ksn = attn._q8(knew)
        vq, vsn = attn._q8(vnew)
        k = cache.k.at[bidx, slot].set(kq[:, 0])
        v = cache.v.at[bidx, slot].set(vq[:, 0])
        ks = cache.ks.at[bidx, slot].set(ksn[:, 0])
        vs = cache.vs.at[bidx, slot].set(vsn[:, 0])
        new_cache = attn.QuantKVCache(k, v, ks, vs, pos + 1)
    else:
        k = cache.k.at[bidx, slot].set(knew[:, 0].astype(cache.k.dtype))
        v = cache.v.at[bidx, slot].set(vnew[:, 0].astype(cache.v.dtype))
        new_cache = attn.KVCache(k=k, v=v, length=pos + 1)
    qg = attn._group_q(q1, kvh)[:, 0]             # (B, KVr, G, D)
    nvalid = jnp.minimum(pos + 1, T)
    act = (jnp.ones((B,), jnp.int32) if active is None
           else jnp.asarray(active).astype(jnp.int32))
    if quant:
        ebits = jnp.asarray(8 if degree is None else degree, jnp.int32)
        out = flash_decode_quant(qg, k, ks, v, vs, nvalid, act, ebits,
                                 interpret=interpret)
    else:
        out = flash_decode(qg, k, v, nvalid, act, interpret=interpret)
    return out.reshape(B, 1, H, D).astype(q1.dtype), new_cache
