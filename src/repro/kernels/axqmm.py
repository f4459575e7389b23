"""axqmm — block-quantized, effective-bits, runtime-degradable GEMM.

The TPU-native embodiment of the dissertation's perforation+rounding
multiplier (DESIGN.md §2.1): int8 operands with per-(row, k-block) scales; a
*runtime* effective-bits degree e <= 8 drops low operand bits by
round-and-shift exactly like DyFXU's runtime perforation registers — no
recompile, the degree is a scalar-prefetch argument (SMEM).

TPU mapping (VMEM/MXU co-design, the Ch. 9 scratchpad-scheduling insight):
  * the tiles follow the call's shapes (:func:`axq_tiles`).  A row block
    holds all of M up to 1024 rows (decode's slots, most prefills), so
    every weight tile crosses HBM once per call.  A column block is 1024
    wide on the lane axis, or all of N when N is narrower.  The k tile is
    the whole contraction wherever it fits, so the grid walks N and little
    else: a Qwen2.5-3B decode step takes 833 grid steps, against ~139K at
    the old 8 x 128 x 256 tiles.  Neither M nor N need divide: a ragged
    last block computes rows or columns that are never written back, so
    nothing is padded per call; K always divides;
  * inside a step the kernel walks the k tile's quantization blocks in k
    order, two to a turn of the loop so one block's degrade can overlap
    the other's dot: per block one s8 x s8 -> s32 MXU dot, scaled by that
    block's activation and weight scales, added to the f32 accumulator.
    These are the adds, in the order, of one grid step per block, so the
    result does not depend on the tiling.  The scale blocks ride along
    in VMEM whole along k (the chip refuses a (bm, 1) block);
  * the perforated tiles are cast back to int8 (lossless: they stay in
    [-127, 127]) so the MXU runs s8 x s8 -> s32;
  * VMEM: a step holds the double-buffered int8 tiles and their scales,
    one quantization block of each upcast to int32, the output, residual
    and accumulator blocks and the dot's temporaries.  Tiles shrink
    (columns, then rows, then the k tile) until that fits 24 MiB, under a
    48 MiB scoped limit (a v5e core has 128 MiB).  Decode's down
    projection, 16 x 11008 by 1024 x 11008, comes closest at ~24 MiB.

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
    (quantized and degraded once per block), keep two accumulators, and apply
    the gate in-VMEM — one HBM roundtrip instead of three.

Both ``pallas_call``s carry a name (``axqmm``, ``axqmm_gated``), which the
compiled program gives their custom-call ops (``axqmm.7``): a profiler
trace lists them by it, not by whatever encloses them (``closed_call.41``).

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


def _degrade_s8(q: Array, shift: Array) -> Array:
    """Degrade an int8 operand tile to the runtime effective bits.  The
    degrade runs in int32 lanes and lands back in [-127, 127], so the cast
    back to int8 is lossless and the MXU sees s8 operands."""
    return _degrade_tile(q.astype(jnp.int32), shift).astype(jnp.int8)


def _s8_dot(qx: Array, qw: Array) -> Array:
    """s8 x s8 -> s32 MXU dot of (bm, bk) by (bn, bk): contract both on k."""
    return jax.lax.dot_general(qx, qw,
                               dimension_numbers=(((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _column(s: Array, k) -> Array:
    """Column ``k`` (traced) of the (rows, n_blocks) activation scales, kept
    (rows, 1): a one-hot select-and-sum, exact (one nonzero term), since
    the chip loads at a traced lane offset only in multiples of 128."""
    hit = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == k
    return jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)


def _accumulate(ebits_ref, qx_ref, sx_ref, w_refs, accs, *, block: int):
    """Add one grid step's k tile to the f32 accumulators ``accs``, one
    quantization block at a time in k order: per block, degrade the x tile
    and each weight tile of ``w_refs`` ((weight, transposed scales) ref
    pairs) to the runtime effective bits, s8 x s8 -> s32 dot, scale by the
    block's activation and weight scales, add."""
    shift = jnp.maximum(8 - ebits_ref[0], 0)
    c = qx_ref.shape[1] // block
    k0 = pl.program_id(2) * c
    sx = sx_ref[...]

    def add_block(b, accs):
        cols = pl.ds(pl.multiple_of(b * block, block), block)
        qx = _degrade_s8(qx_ref[:, cols], shift)
        sx_b = _column(sx, k0 + b)
        return tuple(
            acc + _s8_dot(qx, _degrade_s8(qw_ref[:, cols], shift))
            .astype(jnp.float32) * (sx_b * swT_ref[pl.ds(k0 + b, 1), :])
            for acc, (qw_ref, swT_ref) in zip(accs, w_refs))

    def add_turn(t, accs):
        for u in range(_UNROLL):
            accs = add_block(t * _UNROLL + u, accs)
        return accs

    turns = c // _UNROLL
    accs = jax.lax.fori_loop(0, turns, add_turn, tuple(accs))
    for b in range(turns * _UNROLL, c):
        accs = add_block(b, accs)
    return accs


def _axqmm_kernel(ebits_ref, qx_ref, sx_ref, qw_ref, swT_ref, *rest,
                  n_k: int, block: int, has_bias: bool, has_res: bool):
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

    acc_ref[...], = _accumulate(ebits_ref, qx_ref, sx_ref,
                                [(qw_ref, swT_ref)], [acc_ref[...]],
                                block=block)

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
                        *, n_k: int, block: int, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accu_ref[...] = jnp.zeros_like(accu_ref)
        accg_ref[...] = jnp.zeros_like(accg_ref)

    # the x tile is streamed (and degraded) ONCE per block for both GEMMs
    accu_ref[...], accg_ref[...] = _accumulate(
        ebits_ref, qx_ref, sx_ref, [(qu_ref, suT_ref), (qg_ref, sgT_ref)],
        [accu_ref[...], accg_ref[...]], block=block)

    @pl.when(k == n_k - 1)
    def _done():
        # in-VMEM gate: act(gate) * up written back once — the intermediate
        # up/gate tensors never round-trip through HBM
        out_ref[...] = _ACTS[act](accg_ref[...]) * accu_ref[...]


#: row and column tiles, largest first: a row block is all of M up to the
#: first, a column block all of N up to the first; VMEM shrinks them
_TILES = (1024, 512, 256, 128)
#: quantization blocks per unrolled turn of the loop over a k tile, so the
#: scheduler can overlap one block's degrade with another's dot
_UNROLL = 2
#: the working set the tiles are sized to, and the scoped VMEM the kernel
#: may use (a v5e core has 128 MiB)
_VMEM_BUDGET, _VMEM_LIMIT = 24 << 20, 48 << 20


def _vmem_bytes(bm: int, bn: int, bk: int, K: int, block: int,
                n_w: int) -> int:
    """Working set of one grid step: double-buffered int8 operand tiles and
    their f32 scales, one quantization block of each upcast to int32, and
    per output element the double-buffered output and residual, the
    accumulators and the dot's s32 and f32 temporaries."""
    rows = bm + n_w * bn
    return (2 * rows * bk + 4 * rows * (2 * K // block + block)
            + 4 * bm * bn * (4 + 3 * n_w))


def axq_tiles(M: int, N: int, K: int, block: int,
              n_w: int = 1) -> tuple[int, int, int]:
    """(bm, bn, bk) for an AXQ GEMM of (M, K) by (K, N) with quantization
    block ``block``; ``n_w`` weights share the x tile (2 for the gated
    kernel).  Rows and columns start whole up to the largest tile (one
    row block: every weight tile crosses HBM once), the k tile whole;
    under the VMEM budget columns shrink first, then rows, then the k
    tile.  Blocks need not divide M or N (the grid is ragged there); bk
    divides K."""
    assert K % block == 0, (K, block)
    bms = [M] * (M <= _TILES[0]) + [b for b in _TILES if b < M]
    bns = [N] * (N <= _TILES[0]) + [b for b in _TILES if b < N]
    n_blocks = K // block
    bks = [c * block for c in range(n_blocks, 0, -1) if n_blocks % c == 0]
    for bk in bks:
        for bm in bms:
            for bn in bns:
                if _vmem_bytes(bm, bn, bk, K, block, n_w) <= _VMEM_BUDGET:
                    return bm, bn, bk
    return bms[-1], bns[-1], block


def _interpreter(interpret: bool):
    """Interpret mode runs the TPU interpreter, which keeps the kernel's
    refs in simulated VMEM.  The plain one lowers the kernel to one XLA
    program, whose CPU compiler folds a one-step grid's loop away and then
    fuses the last block's scale multiply with the epilogue's bias add into
    an FMA: one rounding fewer than the kernel states."""
    return pltpu.InterpretParams() if interpret else False


def _grid(M: int, N: int, K: int, tiles) -> tuple[int, int, int]:
    bm, bn, bk = tiles
    assert K % bk == 0, (K, bk)
    return pl.cdiv(M, bm), pl.cdiv(N, bn), K // bk


def _axqmm_call(qx, sx, qwT, sw, ebits, bias, residual, *, tiles,
                interpret):
    """qx: (M, K) int8; sx: (M, K//block) f32; qwT: (N, K) int8;
    sw: (N, K//block) f32; ebits: runtime scalar; ``tiles`` (bm, bn, bk),
    bk a multiple of the quantization block.  Returns (M, N) f32."""
    M, K = qx.shape
    N, n_blocks = sw.shape
    bm, bn, bk = tiles
    grid = _grid(M, N, K, tiles)
    ebits_arr = jnp.asarray(ebits, jnp.int32).reshape(1)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, *prefetch: (i, k)),        # qx
        pl.BlockSpec((bm, n_blocks), lambda i, j, k, *prefetch: (i, 0)),  # sx
        pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),        # qwT
        pl.BlockSpec((n_blocks, bn), lambda i, j, k, *prefetch: (0, j)),  # sw.T
    ]
    args = [qx, sx, qwT, sw.T]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k, *prefetch: (0, j)))
        args.append(bias.reshape(1, N).astype(jnp.float32))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)))
        args.append(residual.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_axqmm_kernel, n_k=grid[2], block=K // n_blocks,
                          has_bias=bias is not None,
                          has_res=residual is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpreter(interpret),
        name="axqmm",
    )(ebits_arr, *args)


def _axqmm_gated_call(qx, sx, qu, su, qg, sg, ebits, *, act, tiles,
                      interpret):
    M, K = qx.shape
    N, n_blocks = su.shape
    bm, bn, bk = tiles
    grid = _grid(M, N, K, tiles)
    ebits_arr = jnp.asarray(ebits, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_axqmm_gated_kernel, n_k=grid[2],
                          block=K // n_blocks, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, *prefetch: (i, k)),        # qx
                pl.BlockSpec((bm, n_blocks), lambda i, j, k, *prefetch: (i, 0)),  # sx
                pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),        # qu
                pl.BlockSpec((n_blocks, bn), lambda i, j, k, *prefetch: (0, j)),  # su.T
                pl.BlockSpec((bn, bk), lambda i, j, k, *prefetch: (j, k)),        # qg
                pl.BlockSpec((n_blocks, bn), lambda i, j, k, *prefetch: (0, j)),  # sg.T
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *prefetch: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpreter(interpret),
        name="axqmm_gated",
    )(ebits_arr, qx, sx, qu, su.T, qg, sg.T)


def quantize_for_axqmm(x: Array, bk: int = 512):
    """Per-(row, k-block) symmetric int8 quantization. x: (M, K) float.
    Thin view over core.quantization.quantize_block — ONE quantizer shared by
    kernel, jnp oracle, and the prepack pass (the bit-identity contract)."""
    qt = quantize_block(x.astype(jnp.float32), bk)
    return qt.values, qt.scales


def axqmm_packed(x: Array, pw: PackedQWeight, ebits: Array | int = 8, *,
                 bias: Array | None = None, residual: Array | None = None,
                 interpret: bool | None = None) -> Array:
    """float x (M, K) @ prepacked weight through the quantized kernel.

    Per-call work is activation quantization only — the weight was encoded
    at load time (qstore).  Tiles follow the shapes (:func:`axq_tiles`);
    nothing is padded: a block that overhangs M or N computes rows or
    columns that are never written back.

    ``bias`` (N,) and ``residual`` (M, N) fuse into the f32 epilogue on the
    last k step: ``out = acc + bias + residual`` before the one writeback.
    """
    M, K = x.shape
    assert pw.k == K, (pw.k, K)
    qx, sx = quantize_for_axqmm(x, pw.block)
    return _axqmm_call(qx, sx, pw.qw, pw.scales, ebits, bias, residual,
                       tiles=axq_tiles(M, pw.n, K, pw.block),
                       interpret=_resolve_interpret(interpret))


def axqmm_gated_packed(x: Array, pw_up: PackedQWeight, pw_gate: PackedQWeight,
                       ebits: Array | int = 8, *, act: str = "silu",
                       interpret: bool | None = None) -> Array:
    """Fused gated-MLP first half against prepacked weights:
    ``act(x @ w_gate) * (x @ w_up)`` in one kernel — the shared x tile is
    quantized/degraded once per step, and the up/gate intermediates never
    leave VMEM (one HBM roundtrip instead of three)."""
    M, K = x.shape
    N, block = pw_up.n, pw_up.block
    assert pw_up.k == K and pw_gate.k == K, (pw_up.k, pw_gate.k, K)
    assert pw_gate.n == N and pw_gate.block == block, "up/gate packs must agree"
    qx, sx = quantize_for_axqmm(x, block)
    return _axqmm_gated_call(qx, sx, pw_up.qw, pw_up.scales, pw_gate.qw,
                             pw_gate.scales, ebits, act=act,
                             tiles=axq_tiles(M, N, K, block, n_w=2),
                             interpret=_resolve_interpret(interpret))


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
