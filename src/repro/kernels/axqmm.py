"""axqmm — block-quantized, effective-bits, runtime-degradable GEMM.

The TPU-native embodiment of the dissertation's perforation+rounding
multiplier (DESIGN.md §2.1): int8 operands with per-(row, k-block) scales; a
*runtime* effective-bits degree e <= 8 drops low operand bits by
round-and-shift exactly like DyFXU's runtime perforation registers — no
recompile, the degree is a scalar-prefetch argument (SMEM).

TPU mapping (VMEM/MXU co-design, the Ch. 9 scratchpad-scheduling insight):
  * tiles (bm, bk) x (bn, bk) -> (bm, bn), multiples of 128 so the MXU
    systolic array is fully utilized and int8 ingestion is 2x bf16 rate;
  * quantization block == bk so each grid step consumes exactly one scale
    column: the whole (bm, K//bk) / (K//bk, bn) scale blocks ride along in
    VMEM (the chip refuses a (bm, 1) block) and the kernel selects column k;
  * the perforated tiles are cast back to int8 (lossless: they stay in
    [-127, 127]) so the MXU runs s8 x s8 -> s32;
  * f32 accumulator tile lives in a VMEM scratch across the K grid walk
    (output tile revisited over k), written back once on the last k step;
  * working set per step: bm*bk + bn*bk int8 + 2*bm*bn f32
    = 2*128*512 + 2*128*128*4 bytes ~ 260 KiB << 16 MiB VMEM.

Weight residency (DESIGN.md §9): the weight operand arrives *prepacked* as a
:class:`~repro.kernels.qstore.PackedQWeight` — ``(N, K)`` int8 K-major plus
``(N, K//bk)`` f32 scales, quantized once at load time — so the per-call work
is activation quantization only.  The float-``w`` wrappers below pack
on-the-fly through the same code path (bit-identical by construction).

Fused epilogues ride the last k grid step while the output tile is still in
VMEM:
  * :func:`axqmm_packed` — optional bias (+b) and residual (+r) added in f32
    before the single writeback (down/out projections fuse the residual add);
  * :func:`axqmm_gated` / :func:`axqmm_gated_packed` — the gated-MLP first
    half ``act(x@w_gate) * (x@w_up)``: both GEMMs stream the *same* x tile
    (quantized and degraded once per step), keep two accumulators, and apply
    the gate in-VMEM — one HBM roundtrip instead of three.

Validated against core.quantization.qmm_packed_ref / qmm_gated_packed_ref
(pure-jnp oracles) in interpret mode on CPU (tests/test_kernels.py,
tests/test_qstore.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import quantize_block
from repro.kernels.flash_attention import _resolve_interpret
from repro.kernels.qstore import PackedQWeight, prepack_weight, resolve_block

Array = jnp.ndarray

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _degrade_tile(q: Array, shift: Array) -> Array:
    """Round-to-nearest drop of `shift` low bits (int32 lanes), saturating —
    the runtime perforation knob.  shift is a traced int32 scalar."""
    half = jnp.where(shift > 0, jnp.left_shift(1, jnp.maximum(shift - 1, 0)), 0)
    down = jnp.right_shift(q + half, shift)
    out = jnp.left_shift(down, shift)
    return jnp.clip(out, -127, 127)


def _degrade_s8(q_ref, shift: Array) -> Array:
    """Load an int8 operand tile and degrade it to the runtime effective
    bits.  The degrade runs in int32 lanes and lands back in [-127, 127],
    so the cast back to int8 is lossless and the MXU sees s8 operands."""
    return _degrade_tile(q_ref[...].astype(jnp.int32), shift).astype(jnp.int8)


def _s8_dot(qx: Array, qw: Array) -> Array:
    """s8 x s8 -> s32 MXU dot of (bm, bk) by (bn, bk): contract both on k."""
    return jax.lax.dot_general(qx, qw,
                               dimension_numbers=(((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _scale_at(s_ref, k, axis: int) -> Array:
    """Entry ``k`` along ``axis`` of a scale block, the axis kept at size 1:
    column k of the (rows, n_k) activation scales (axis 1), row k of the
    transposed (n_k, cols) weight scales (axis 0).  A one-hot
    select-and-sum: exact (one nonzero term), and it keeps every block the
    whole scale array along k, which the chip's tiling rules accept where a
    block of 1 is refused."""
    s = s_ref[...]
    hit = jax.lax.broadcasted_iota(jnp.int32, s.shape, axis) == k
    return jnp.sum(jnp.where(hit, s, 0.0), axis=axis, keepdims=True)


def _step_dot(ebits_ref, qx_ref, qw_ref, sx_ref, swT_ref, k):
    """One k-step partial product: degrade both int tiles to the runtime
    effective bits, s8 x s8 -> s32 dot, scale by the block scales."""
    shift = jnp.maximum(8 - ebits_ref[0], 0)
    acc = _s8_dot(_degrade_s8(qx_ref, shift), _degrade_s8(qw_ref, shift))
    scale = _scale_at(sx_ref, k, 1) * _scale_at(swT_ref, k, 0)  # (bm, bn)
    return acc.astype(jnp.float32) * scale


def _axqmm_kernel(ebits_ref, qx_ref, sx_ref, qw_ref, swT_ref, *rest,
                  n_k: int, has_bias: bool, has_res: bool):
    idx = 0
    bias_ref = rest[idx] if has_bias else None
    idx += int(has_bias)
    res_ref = rest[idx] if has_res else None
    idx += int(has_res)
    out_ref, acc_ref = rest[idx], rest[idx + 1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _step_dot(ebits_ref, qx_ref, qw_ref, sx_ref, swT_ref, k)

    @pl.when(k == n_k - 1)
    def _done():
        # fused epilogue: the output tile is still in VMEM — bias and
        # residual are added in f32 before the one writeback
        y = acc_ref[...]
        if has_bias:
            y = y + bias_ref[...]                # (1,bn) broadcasts over bm
        if has_res:
            y = y + res_ref[...]
        out_ref[...] = y


def _axqmm_gated_kernel(ebits_ref, qx_ref, sx_ref, qu_ref, suT_ref,
                        qg_ref, sgT_ref, out_ref, accu_ref, accg_ref,
                        *, n_k: int, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accu_ref[...] = jnp.zeros_like(accu_ref)
        accg_ref[...] = jnp.zeros_like(accg_ref)

    # the x tile is streamed (and degraded) ONCE per step for both GEMMs
    shift = jnp.maximum(8 - ebits_ref[0], 0)
    qx = _degrade_s8(qx_ref, shift)
    up = _s8_dot(qx, _degrade_s8(qu_ref, shift))
    gt = _s8_dot(qx, _degrade_s8(qg_ref, shift))
    sx = _scale_at(sx_ref, k, 1)
    accu_ref[...] += up.astype(jnp.float32) * (sx * _scale_at(suT_ref, k, 0))
    accg_ref[...] += gt.astype(jnp.float32) * (sx * _scale_at(sgT_ref, k, 0))

    @pl.when(k == n_k - 1)
    def _done():
        # in-VMEM gate: act(gate) * up written back once — the intermediate
        # up/gate tensors never round-trip through HBM
        out_ref[...] = _ACTS[act](accg_ref[...]) * accu_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def axqmm_quantized(qx: Array, sx: Array, qwT: Array, sw: Array,
                    ebits: Array | int = 8, *, bm: int = 128, bn: int = 128,
                    bk: int = 512, interpret: bool | None = None) -> Array:
    """qx: (M, K) int8; sx: (M, K//bk) f32; qwT: (N, K) int8;
    sw: (N, K//bk) f32; ebits: runtime scalar.  Returns (M, N) f32."""
    return _axqmm_call(qx, sx, qwT, sw, ebits, None, None, bm=bm, bn=bn,
                       bk=bk, interpret=_resolve_interpret(interpret))


def _axqmm_call(qx, sx, qwT, sw, ebits, bias, residual, *, bm, bn, bk,
                interpret):
    M, K = qx.shape
    N = qwT.shape[0]
    assert K % bk == 0 and M % bm == 0 and N % bn == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    ebits_arr = jnp.asarray(ebits, jnp.int32).reshape(1)
    grid = (M // bm, N // bn, n_k)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, *prefetch: (i, k)),    # qx
        pl.BlockSpec((bm, n_k), lambda i, j, k, *prefetch: (i, 0)),   # sx
        pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),    # qwT
        pl.BlockSpec((n_k, bn), lambda i, j, k, *prefetch: (0, j)),   # sw.T
    ]
    args = [qx, sx, qwT, sw.T]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k, *prefetch: (0, j)))
        args.append(bias.reshape(1, N).astype(jnp.float32))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)))
        args.append(residual.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_axqmm_kernel, n_k=n_k, has_bias=bias is not None,
                          has_res=residual is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(ebits_arr, *args)


def _axqmm_gated_call(qx, sx, qu, su, qg, sg, ebits, *, act, bm, bn, bk,
                      interpret):
    M, K = qx.shape
    N = qu.shape[0]
    assert K % bk == 0 and M % bm == 0 and N % bn == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    ebits_arr = jnp.asarray(ebits, jnp.int32).reshape(1)
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_axqmm_gated_kernel, n_k=n_k, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, *prefetch: (i, k)),   # qx
                pl.BlockSpec((bm, n_k), lambda i, j, k, *prefetch: (i, 0)),  # sx
                pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),   # qu
                pl.BlockSpec((n_k, bn), lambda i, j, k, *prefetch: (0, j)),  # su.T
                pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),   # qg
                pl.BlockSpec((n_k, bn), lambda i, j, k, *prefetch: (0, j)),  # sg.T
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(ebits_arr, qx, sx, qu, su.T, qg, sg.T)


def quantize_for_axqmm(x: Array, bk: int = 512):
    """Per-(row, k-block) symmetric int8 quantization. x: (M, K) float.
    Thin view over core.quantization.quantize_block — ONE quantizer shared by
    kernel, jnp oracle, and the prepack pass (the bit-identity contract)."""
    qt = quantize_block(x.astype(jnp.float32), bk)
    return qt.values, qt.scales


def _tile(dim: int) -> int:
    """Row tile for M.  N always tiles at 128 (padded up): the output and
    weight-scale blocks put N on the lane axis, where the chip accepts only
    multiples of 128 short of the whole array."""
    return 128 if dim % 128 == 0 else (64 if dim % 64 == 0 else 8)


def _pad0(a: Array, to: int) -> Array:
    return jnp.pad(a, ((0, to - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def axqmm_packed(x: Array, pw: PackedQWeight, ebits: Array | int = 8, *,
                 bias: Array | None = None, residual: Array | None = None,
                 interpret: bool | None = None) -> Array:
    """float x (M, K) @ prepacked weight through the quantized kernel.

    Per-call work is activation quantization only — the weight was encoded
    at load time (qstore).  M/N are zero-padded up to the tile multiple and
    the result sliced back, so decode-shaped inputs (M = serve slots) take
    the Pallas path.  Padding happens *after* quantization: scales are
    per-row, so real rows are unchanged and padded rows (zero operands)
    contribute exact zeros that the slice drops.

    ``bias`` (N,) and ``residual`` (M, N) fuse into the f32 epilogue on the
    last k step: ``out = acc + bias + residual`` before the one writeback.
    """
    M, K = x.shape
    N, bk = pw.n, pw.block
    assert pw.k == K, (pw.k, K)
    qx, sx = quantize_for_axqmm(x, bk)
    qw, sw = pw.qw, pw.scales
    bm, bn = _tile(M), 128
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    if Mp != M:
        qx, sx = _pad0(qx, Mp), _pad0(sx, Mp)
        if residual is not None:
            residual = _pad0(residual, Mp)
    if Np != N:
        qw, sw = _pad0(qw, Np), _pad0(sw, Np)
        if bias is not None:
            bias = jnp.pad(bias, (0, Np - N))
        if residual is not None:
            residual = jnp.pad(residual, ((0, 0), (0, Np - N)))
    y = _axqmm_call(qx, sx, qw, sw, ebits, bias, residual, bm=bm, bn=bn,
                    bk=bk, interpret=_resolve_interpret(interpret))
    return y[:M, :N] if (Mp != M or Np != N) else y


def axqmm_gated_packed(x: Array, pw_up: PackedQWeight, pw_gate: PackedQWeight,
                       ebits: Array | int = 8, *, act: str = "silu",
                       interpret: bool | None = None) -> Array:
    """Fused gated-MLP first half against prepacked weights:
    ``act(x @ w_gate) * (x @ w_up)`` in one kernel — the shared x tile is
    quantized/degraded once per step, and the up/gate intermediates never
    leave VMEM (one HBM roundtrip instead of three)."""
    M, K = x.shape
    N, bk = pw_up.n, pw_up.block
    assert pw_up.k == K and pw_gate.k == K, (pw_up.k, pw_gate.k, K)
    assert pw_gate.n == N and pw_gate.block == bk, "up/gate packs must agree"
    qx, sx = quantize_for_axqmm(x, bk)
    qu, su = pw_up.qw, pw_up.scales
    qg, sg = pw_gate.qw, pw_gate.scales
    bm, bn = _tile(M), 128
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    if Mp != M:
        qx, sx = _pad0(qx, Mp), _pad0(sx, Mp)
    if Np != N:
        qu, su = _pad0(qu, Np), _pad0(su, Np)
        qg, sg = _pad0(qg, Np), _pad0(sg, Np)
    y = _axqmm_gated_call(qx, sx, qu, su, qg, sg, ebits, act=act, bm=bm,
                          bn=bn, bk=bk, interpret=_resolve_interpret(interpret))
    return y[:M, :N] if (Mp != M or Np != N) else y


def axqmm(x: Array, w: Array, *, block: int = 512, ebits: Array | int = 8,
          interpret: bool | None = None, bias: Array | None = None,
          residual: Array | None = None) -> Array:
    """float x (M,K) @ float w (K,N): packs the weight on the fly (same
    quantizer as the prepack pass) and defers to :func:`axqmm_packed` —
    prepacked and on-the-fly execution share one kernel graph from the
    quantized operands on."""
    bk = resolve_block(x.shape[-1], block)
    return axqmm_packed(x, prepack_weight(w, bk), ebits, bias=bias,
                        residual=residual, interpret=interpret)


def axqmm_gated(x: Array, w_up: Array, w_gate: Array, *, block: int = 512,
                ebits: Array | int = 8, act: str = "silu",
                interpret: bool | None = None) -> Array:
    """On-the-fly-packed variant of :func:`axqmm_gated_packed`."""
    bk = resolve_block(x.shape[-1], block)
    return axqmm_gated_packed(x, prepack_weight(w_up, bk),
                              prepack_weight(w_gate, bk), ebits, act=act,
                              interpret=interpret)
