"""axmult_elem — the dissertation's PR (perforation+rounding) multiplier as a
vectorized Pallas kernel.

This is the Ch. 5 circuit itself, one lane per element: given int operand
arrays A, B (n-bit values in int32 lanes), compute
    round_r(A) * perforate_p(B)
entirely with the bit manipulations of the hardware (shift/mask/add), with
(p, r) as *runtime* scalar-prefetch arguments — the DyFXU configuration
registers.  Used by the approximate DSP accelerators (FIR / conv) benchmarks
to run the paper's arithmetic at array scale.

VPU mapping: pure element-wise integer ops on (8,128)-aligned tiles; VMEM
block of 16K lanes x 4 B x 2 operands = 128 KiB per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import _resolve_interpret

Array = jnp.ndarray


def _pr_kernel(pr_ref, a_ref, b_ref, out_ref, *, n: int):
    p = pr_ref[0]
    r = pr_ref[1]
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
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
    out_ref[...] = a_r * b_p


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def pr_multiply(a: Array, b: Array, p: Array | int, r: Array | int,
                *, n: int = 16, block: int = 2048,
                interpret: bool | None = None) -> Array:
    """Elementwise DyFXU product of int32 operand arrays (n-bit values).

    a, b: same shape, total size % block == 0 (callers pad); p, r runtime
    scalars.  N-D operands (e.g. a stacked (taps, L) FIR batch) are flattened
    for the kernel and restored on return.  ``interpret=None`` compiles the
    kernel on a TPU and interprets it elsewhere.
    """
    shape = a.shape
    assert b.shape == shape, (shape, b.shape)
    a = a.reshape(-1)
    b = b.reshape(-1)
    (L,) = a.shape
    assert L % block == 0, (L, block)
    pr = jnp.stack([jnp.asarray(p, jnp.int32), jnp.asarray(r, jnp.int32)])
    grid = (L // block,)
    lanes = 128
    rows = block // lanes
    a2 = a.reshape(-1, lanes)
    b2 = b.reshape(-1, lanes)
    out = pl.pallas_call(
        functools.partial(_pr_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rows, lanes), lambda i, *_: (i, 0)),
                pl.BlockSpec((rows, lanes), lambda i, *_: (i, 0)),
            ],
            out_specs=pl.BlockSpec((rows, lanes), lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(a2.shape, jnp.int32),
        interpret=_resolve_interpret(interpret),
    )(pr, a2, b2)
    return out.reshape(shape)
