"""Approximate DSP compute cores on the PR multiplier (Ch. 7 accelerators).

The dissertation's DSP accelerators — 1D FIR filtering and 2D convolution —
are product-sum pipelines over the Ch. 5 PR (perforation + rounding)
multiplier.  This module holds the *compute cores* behind the
``kernels.dispatch.fir`` / ``dispatch.conv2d`` routers: the batched operand
layout (all taps / all kernel offsets stacked into ONE elementwise PR call),
the pad-to-block plumbing the Pallas kernel requires, and a pure-jnp mirror
of the kernel's bit math so the ``xla`` backend is bit-identical to the
``pallas`` one (the same oracle contract the AXQ GEMMs satisfy).

Operand convention (weight-stationary accelerator): the *weights* (FIR taps,
conv kernel) are the rounded operand A, the *samples* (signal, pixels) the
perforated operand B — matching ``axmult_elem._pr_kernel``'s (a, b) roles
and the Ch. 7 datapath, where the configuration registers degrade the
stationary operand path and the streaming operand path independently.

Fixed-point safety: accumulation stays in int32 lanes (TPU-native), so
streaming entry points require the weight vector's l1 norm to fit
``2**shift`` — quantizing weights with ``quantize_weights`` guarantees
``|sum_i w_i * x_i| <= 2**shift * max|x|`` and the post-sum ``>> shift``
returns the result to the input's Q format.  The offline ``fir_valid`` entry
(benchmarks, arbitrary Q14 operands) accumulates host-side in int64 instead.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.axmult_elem import pr_multiply

Array = jnp.ndarray

#: the Pallas kernel's flat block size (axmult_elem contract: total % block == 0)
PR_BLOCK = 2048


def degree_to_pr(degree, n: int = 16):
    """Map an effective-bits degree (8 = exact, down the QoS ladder) to the
    DyFXU (p, r) configuration registers: each lost bit costs two rounding
    bits and every second lost bit one perforation step —
    ``e=8 -> (0,0), 7 -> (0,2), 6 -> (1,4), 5 -> (1,6), 4 -> (2,8)``.
    ``degree`` may be None (exact) or a traced int32 scalar (zero-recompile
    contract); returns traced (p, r) int32 scalars."""
    if degree is None:
        return jnp.int32(0), jnp.int32(0)
    d = jnp.maximum(8 - jnp.asarray(degree, jnp.int32), 0)
    return d // 2, 2 * d


def quantize_weights(w, shift: int):
    """Quantize a float weight vector/kernel so its l1 norm is <= 2**shift
    (int32-safe accumulation for Q-``shift`` samples): returns int32 weights
    whose product-sum dequantizes via ``>> shift``."""
    w = np.asarray(w, np.float64)
    scale = float(1 << shift) / max(float(np.abs(w).sum()), 1e-30)
    return np.round(w * scale).astype(np.int32)


def pr_multiply_ref(a: Array, b: Array, p, r, *, n: int = 16) -> Array:
    """Pure-jnp mirror of ``axmult_elem._pr_kernel`` — the xla-route twin,
    bit-identical to the Pallas kernel (integer bit math has no tolerance)."""
    p = jnp.asarray(p, jnp.int32)
    r = jnp.asarray(r, jnp.int32)
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    # rounding: A_r = (floor(A / 2^r) + a_{r-1}) * 2^r  (r = 0 -> identity)
    rbit = jnp.where(r > 0,
                     jnp.bitwise_and(jnp.right_shift(a, jnp.maximum(r - 1, 0)), 1),
                     0)
    a_r = jnp.where(r > 0, jnp.left_shift(jnp.right_shift(a, r) + rbit, r), a)
    # perforation: B' = B - (B mod 2^{2p}) + 2^{2p} * b_{2p-1}
    u = jnp.bitwise_and(b, (1 << n) - 1)
    two_p = jnp.left_shift(jnp.int32(1), 2 * p)
    low = jnp.bitwise_and(u, two_p - 1)
    cbit = jnp.bitwise_and(jnp.right_shift(u, jnp.maximum(2 * p - 1, 0)), 1)
    b_p = jnp.where(p > 0, b - low + cbit * two_p, b)
    return a_r * b_p


def pr_product(a: Array, b: Array, p, r, *, n: int = 16,
               backend: str = "xla", interpret: bool | None = None) -> Array:
    """One elementwise PR product through the selected backend.  Pallas route:
    flatten + zero-pad to the kernel's block multiple (zeros multiply to
    zeros, so padding never pollutes); jnp route: the bit-identical ref."""
    if backend != "pallas":
        return pr_multiply_ref(a, b, p, r, n=n)
    flat_a = jnp.asarray(a, jnp.int32).reshape(-1)
    flat_b = jnp.asarray(b, jnp.int32).reshape(-1)
    size = flat_a.shape[0]
    pad = (-size) % PR_BLOCK
    if pad:
        z = jnp.zeros((pad,), jnp.int32)
        flat_a = jnp.concatenate([flat_a, z])
        flat_b = jnp.concatenate([flat_b, z])
    out = pr_multiply(flat_a, flat_b, p, r, n=n, block=PR_BLOCK,
                      interpret=interpret)
    return out[:size].reshape(a.shape)


def fir_valid(sig, taps, p, r, *, n: int = 16, backend: str = "xla",
              interpret: bool | None = None) -> np.ndarray:
    """Valid-mode batched FIR (the Ch. 7 Tables 7.1/7.2 bench layout):
    ``y[j] = sum_i taps[i] * sig[i + j]`` for ``j < len(sig) - len(taps)``.

    All taps ride ONE PR call as stacked (T, L) operand planes; accumulation
    is host-side int64 (unbounded Q14 operands overflow int32 lanes).  NOT
    jit-traceable — the streaming/jit path is :func:`fir_frames`."""
    sig = np.asarray(sig, np.int32)
    taps = np.asarray(taps, np.int32)
    T = len(taps)
    L = len(sig) - T
    a = np.ascontiguousarray(np.broadcast_to(taps[:, None], (T, L)))
    b = np.ascontiguousarray(np.lib.stride_tricks.sliding_window_view(sig, L)[:T])
    prod = np.asarray(pr_product(jnp.asarray(a), jnp.asarray(b), p, r, n=n,
                                 backend=backend, interpret=interpret))
    return prod.astype(np.int64).sum(axis=0)


def fir_frames(frames: Array, tail: Array, taps: Array, p, r, *, n: int = 16,
               shift: int = 0, backend: str = "xla",
               interpret: bool | None = None):
    """Streaming FIR over one frame batch (jit-safe; the serve-engine step).

    frames (B, L) int32 samples, tail (B, T-1) the previous frame's carried
    history (zeros at stream start), taps (T,) int32 with l1 norm <=
    ``2**shift`` (int32-safe accumulation — see :func:`quantize_weights`).
    Returns ``(y (B, L) int32 >> shift, new_tail (B, T-1))`` — outputs are
    continuous across frames: frame-by-frame equals one whole-signal pass.
    """
    B, L = frames.shape
    T = taps.shape[0]
    ext = jnp.concatenate([jnp.asarray(tail, jnp.int32),
                           jnp.asarray(frames, jnp.int32)], axis=1)
    # static window slices (T is static): (T, B, L) operand planes
    win = jnp.stack([ext[:, i:i + L] for i in range(T)])
    a = jnp.broadcast_to(taps.astype(jnp.int32)[:, None, None], win.shape)
    prod = pr_product(a, win, p, r, n=n, backend=backend, interpret=interpret)
    acc = jnp.sum(prod, axis=0)
    y = jnp.right_shift(acc, shift) if shift else acc
    return y, ext[:, L:]


def conv2d_pr(img: Array, kern: Array, p, r, *, n: int = 16, shift: int = 0,
              pad: str = "zero", backend: str = "xla",
              interpret: bool | None = None) -> Array:
    """Same-size 2D correlation through the PR datapath (jit-safe).

    img (B, H, W) int32 pixels, kern (kh, kw) int32 weights with l1 norm <=
    ``2**shift``; all kh*kw offsets ride ONE PR call as stacked patch planes.
    ``pad``: "zero" | "edge" border handling.  Returns (B, H, W) int32
    ``>> shift``."""
    B, H, W = img.shape
    kh, kw = kern.shape
    ph, pw = kh // 2, kw // 2
    mode = "edge" if pad == "edge" else "constant"
    ext = jnp.pad(jnp.asarray(img, jnp.int32),
                  ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw)), mode=mode)
    patches = jnp.stack([ext[:, dy:dy + H, dx:dx + W]
                         for dy in range(kh) for dx in range(kw)])
    a = jnp.broadcast_to(kern.astype(jnp.int32).reshape(-1)[:, None, None, None],
                         patches.shape)
    prod = pr_product(a, patches, p, r, n=n, backend=backend,
                      interpret=interpret)
    acc = jnp.sum(prod, axis=0)
    return jnp.right_shift(acc, shift) if shift else acc
