"""Per-kernel shape/dtype/degree sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis wheel
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ref
from repro.kernels.axmult_elem import pr_multiply
from repro.kernels.axqmm import axqmm


@pytest.mark.parametrize("shape", [(128, 512, 128), (256, 1024, 384),
                                   (64, 512, 256)])
@pytest.mark.parametrize("e", [8, 5])
def test_axqmm_matches_ref(shape, e):
    M, K, N = shape
    k = jax.random.PRNGKey(M + K + N + e)
    x = jax.random.normal(k, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (K, N), jnp.float32)
    y = axqmm(x, w, block=512, ebits=e)
    yr = ref.axqmm_ref(x, w, block=512 if K % 512 == 0 else 256, ebits=e)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("shape", [(4, 256, 96), (3, 512, 130), (1, 256, 64)])
def test_axqmm_decode_shapes_pad_to_tile(shape):
    """Serving-shaped inputs (M = slots, ragged N) must pad to the tile
    multiple and slice back instead of raising 'shape not tileable'."""
    M, K, N = shape
    k = jax.random.PRNGKey(M + K + N)
    x = jax.random.normal(k, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (K, N), jnp.float32)
    y = axqmm(x, w, block=256)
    assert y.shape == (M, N)
    yr = ref.axqmm_ref(x, w, block=256, ebits=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-4)


def test_axqmm_dynamic_degree_single_executable():
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (128, 512), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (512, 128), jnp.float32)
    f = jax.jit(lambda x, w, e: axqmm(x, w, ebits=e))
    y8, y4 = f(x, w, jnp.int32(8)), f(x, w, jnp.int32(4))
    exact = x @ w
    assert float(jnp.abs(y8 - exact).mean()) < float(jnp.abs(y4 - exact).mean())


def _old_tiles(M, block):
    """The tiling before tiles followed the call's shapes: rows 128 / 64 /
    8, columns 128, one quantization block per k step."""
    return (128 if M % 128 == 0 else 64 if M % 64 == 0 else 8), 128, block


@pytest.mark.parametrize("e", [8, 5])
@pytest.mark.parametrize("M", [16, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("kind", ["plain", "bias_residual", "gated"])
def test_axqmm_shape_tiles_bit_identical_to_old_tiling(kind, M, e):
    """The shape-chosen tiles (one row block at decode, a k tile over all
    five quantization blocks, a ragged last column block: N / 128 = 11)
    give the same bits as the old tiling through the same kernel."""
    from repro.kernels import axqmm as axq
    from repro.kernels.qstore import prepack_weight

    K, N, block = 5 * 256, 11 * 128, 256
    k = jax.random.PRNGKey(M + e)
    x = jax.random.normal(k, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (K, N), jnp.float32)
    pw = prepack_weight(w, block)
    qx, sx = axq.quantize_for_axqmm(x, block)
    old = _old_tiles(M, block)
    if kind == "gated":
        pg = prepack_weight(jax.random.normal(jax.random.fold_in(k, 2),
                                              (K, N), jnp.float32), block)
        new = axq.axqmm_gated_packed(x, pw, pg, e)
        ref_ = axq._axqmm_gated_call(qx, sx, pw.qw, pw.scales, pg.qw,
                                     pg.scales, e, act="silu", tiles=old,
                                     interpret=True)
        tiles = axq.axq_tiles(M, N, K, block, n_w=2)
    else:
        b = r = None
        if kind == "bias_residual":
            b = jax.random.normal(jax.random.fold_in(k, 3), (N,))
            r = jax.random.normal(jax.random.fold_in(k, 4), (M, N))
        new = axq.axqmm_packed(x, pw, e, bias=b, residual=r)
        ref_ = axq._axqmm_call(qx, sx, pw.qw, pw.scales, e, b, r, tiles=old,
                               interpret=True)
        tiles = axq.axq_tiles(M, N, K, block)
    assert tiles[2] == K and tiles[0] == min(M, axq._TILES[0])
    assert N % tiles[1]
    assert new.shape == (M, N)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(ref_))


@pytest.mark.parametrize("M,N,K,n_w", [
    (16, 2048, 2048, 1), (16, 11008, 2048, 2), (16, 2048, 11008, 1),
    (16, 151936, 2048, 1), (3072, 2048, 11008, 1), (3072, 11008, 2048, 2),
    (600, 96, 256, 1), (16, 128, 1 << 17, 1)])
def test_axq_tiles_follow_the_shapes(M, N, K, n_w):
    """Legal blocks (whole dims, or multiples of 32 rows and 128 columns),
    a k tile of whole quantization blocks dividing K, the VMEM budget kept
    wherever some tiling keeps it, and one row block up to the largest
    row tile."""
    from repro.kernels import axqmm as axq

    bm, bn, bk = axq.axq_tiles(M, N, K, 256, n_w)
    top = axq._TILES[0]
    assert bm == M if M <= top else bm % 32 == 0
    assert bn == N if N <= top else bn % 128 == 0
    assert bk % 256 == 0 and K % bk == 0
    assert axq._vmem_bytes(bm, bn, bk, K, 256, n_w) <= axq._VMEM_BUDGET
    if K <= 11008:
        assert bk == K


@pytest.mark.parametrize("p,r", [(0, 0), (1, 2), (2, 4), (4, 8)])
def test_pr_multiply_kernel_bit_exact(p, r):
    rng = np.random.default_rng(p * 10 + r)
    a = jnp.asarray(rng.integers(-2**15, 2**15, 4096), jnp.int32)
    b = jnp.asarray(rng.integers(-2**15, 2**15, 4096), jnp.int32)
    y = pr_multiply(a, b, p, r, n=16)
    yr = ref.pr_multiply_ref(a, b, p, r, n=16)
    assert (np.asarray(y) == np.asarray(yr)).all()


@given(st.integers(0, 4), st.integers(0, 8))
@settings(max_examples=12, deadline=None)
def test_pr_multiply_kernel_property(p, r):
    rng = np.random.default_rng(42)
    a = jnp.asarray(rng.integers(-2**15, 2**15, 2048), jnp.int32)
    b = jnp.asarray(rng.integers(-2**15, 2**15, 2048), jnp.int32)
    y = pr_multiply(a, b, p, r, n=16)
    yr = ref.pr_multiply_ref(a, b, p, r, n=16)
    assert (np.asarray(y) == np.asarray(yr)).all()


@pytest.mark.parametrize("shape", [(4, 256, 64), (2, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, causal):
    from repro.kernels.flash_attention import flash_attention, flash_attention_ref

    BH, S, D = shape
    k = jax.random.PRNGKey(S + D)
    q = jax.random.normal(k, (BH, S, D), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (BH, S, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (BH, S, D), jnp.float32)
    y = flash_attention(q, kk, v, causal=causal, bq=128, bk=128)
    yr = flash_attention_ref(q, kk, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5)


def test_flash_attention_odd_blocks():
    from repro.kernels.flash_attention import flash_attention, flash_attention_ref

    k = jax.random.PRNGKey(7)
    q = jax.random.normal(k, (2, 192, 64), jnp.float32)   # S not /128 -> bq 64
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 192, 64), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 192, 64), jnp.float32)
    y = flash_attention(q, kk, v, causal=True)
    yr = flash_attention_ref(q, kk, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5)


@pytest.mark.parametrize("window", [16, 40, 500])
def test_flash_attention_window_matches_ref(window):
    from repro.kernels.flash_attention import flash_attention, flash_attention_ref

    k = jax.random.PRNGKey(window)
    q = jax.random.normal(k, (2, 128, 32), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 128, 32), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 128, 32), jnp.float32)
    y = flash_attention(q, kk, v, causal=True, window=window, bq=32, bk=32)
    yr = flash_attention_ref(q, kk, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5)


@pytest.mark.parametrize("shape,causal", [((2, 100, 16), True),
                                          ((3, 130, 16), False),
                                          ((1, 1, 8), True)])
def test_flash_attention_nonpow2_seq_pads_to_tile(shape, causal):
    """Non-power-of-two S must pad to the block multiple and slice back
    (the seed's bq //= 2 loop degraded to degenerate tiles instead)."""
    from repro.kernels.flash_attention import flash_attention, flash_attention_ref

    BH, S, D = shape
    k = jax.random.PRNGKey(S)
    q = jax.random.normal(k, shape, jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), shape, jnp.float32)
    y = flash_attention(q, kk, v, causal=causal)
    assert y.shape == shape
    yr = flash_attention_ref(q, kk, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5)


def test_flash_causal_skip_grid_steps_and_bit_identity():
    """The causal grid must *execute* <= n(n+1)/2 block-steps per BH (vs n^2
    dense) — asserted on the in-kernel counter, not the plan — with output
    bit-identical to the dense grid."""
    from repro.kernels.flash_attention import flash_attention, planned_grid_steps

    BH, S, D, blk = 2, 256, 16, 32
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (BH, S, D), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (BH, S, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (BH, S, D), jnp.float32)
    y_skip, st_skip = flash_attention(q, kk, v, causal=True, bq=blk, bk=blk,
                                      return_steps=True)
    y_dense, st_dense = flash_attention(q, kk, v, causal=True, bq=blk, bk=blk,
                                        skip_grid=False, return_steps=True)
    n = S // blk
    assert int(st_skip) == BH * n * (n + 1) // 2 == planned_grid_steps(
        BH, S, causal=True, bq=blk, bk=blk)
    assert int(st_dense) == BH * n * n
    assert (np.asarray(y_skip) == np.asarray(y_dense)).all()


def test_flash_banded_grid_steps():
    """Sliding-window layers must execute O(S*W) block-steps."""
    from repro.kernels.flash_attention import flash_attention, planned_grid_steps

    BH, S, D, blk, w = 2, 256, 16, 32, 40
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (BH, S, D), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (BH, S, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (BH, S, D), jnp.float32)
    y, steps = flash_attention(q, kk, v, causal=True, window=w, bq=blk,
                               bk=blk, return_steps=True)
    n = S // blk
    band = (w - 1 + blk - 1) // blk + 1
    assert int(steps) == BH * n * band == planned_grid_steps(
        BH, S, causal=True, window=w, bq=blk, bk=blk)
    assert int(steps) < BH * n * (n + 1) // 2  # beats the triangular walk too
    y_dense = flash_attention(q, kk, v, causal=True, window=w, bq=blk, bk=blk,
                              skip_grid=False)
    assert (np.asarray(y) == np.asarray(y_dense)).all()


def test_flash_attention_vjp_grad_matches_ref():
    from repro.kernels.flash_attention import (flash_attention_ref,
                                               flash_attention_vjp)

    k = jax.random.PRNGKey(3)
    q = jax.random.normal(k, (2, 64, 16), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 64, 16), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 64, 16), jnp.float32)
    g1 = jax.grad(lambda q, kk, v: flash_attention_vjp(
        q, kk, v, True, None).sum(), argnums=(0, 1, 2))(q, kk, v)
    g2 = jax.grad(lambda q, kk, v: flash_attention_ref(
        q, kk, v, causal=True).sum(), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
