"""Compile the serving path's Pallas kernels for a TPU v5e that is described,
not attached: at TinyLlama-1.1B widths (d_model 2048, d_ff 5632, 32 heads
over 4 kv heads of 64, vocab 32000), with decode M equal to the slot count.

Interpret mode accepts block shapes and operand types the chip's compiler
refuses; these compiles catch that here.  Each test checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``) and prints
its memory analysis.  Nothing runs: results and times need the chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.axqmm import axqmm_gated_packed, axqmm_packed
from repro.kernels.dsp import pr_product
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_quant
from repro.kernels.qstore import PackedQWeight

D_MODEL, D_FF, KV_HEADS, HEAD_DIM, HEADS, VOCAB = 2048, 5632, 4, 64, 32, 32000
BLOCK = 256            # ApproxSpec.block: the AXQ quantization block
SLOTS, MAX_LEN = 4, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


def _packed(sds, n, k):
    return PackedQWeight(sds((n, k), jnp.int8),
                         sds((n, k // BLOCK), jnp.float32))


@pytest.mark.parametrize("m", [128, SLOTS], ids=["prefill", "decode"])
@pytest.mark.parametrize("k,n", [
    (D_MODEL, HEADS * HEAD_DIM),        # wq / wo
    (D_MODEL, KV_HEADS * HEAD_DIM),     # wk / wv
    (D_FF, D_MODEL),                    # mlp down
    (D_MODEL, VOCAB),                   # unembed
], ids=["wq", "wk", "down", "unembed"])
def test_axqmm_packed_compiles(sds, m, k, n):
    _compile(lambda x, w, e: axqmm_packed(x, w, e, interpret=False),
             sds((m, k), jnp.float32), _packed(sds, n, k),
             sds((), jnp.int32))


@pytest.mark.parametrize("m", [128, SLOTS], ids=["prefill", "decode"])
def test_axqmm_gated_packed_compiles(sds, m):
    up = _packed(sds, D_FF, D_MODEL)
    _compile(lambda x, u, g, e: axqmm_gated_packed(x, u, g, e,
                                                   interpret=False),
             sds((m, D_MODEL), jnp.float32), up, up, sds((), jnp.int32))


def test_flash_attention_causal_compiles(sds):
    qkv = sds((HEADS, 256, HEAD_DIM), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False), qkv, qkv, qkv)


def _decode_args(sds):
    G = HEADS // KV_HEADS
    return (sds((SLOTS, KV_HEADS, G, HEAD_DIM), jnp.bfloat16),
            sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.int32))


def test_flash_decode_compiles(sds):
    qg, nvalid, active = _decode_args(sds)
    kv = sds((SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    _compile(lambda q, k, v, n, a: flash_decode(q, k, v, n, a,
                                                interpret=False),
             qg, kv, kv, nvalid, active)


def test_flash_decode_quant_compiles(sds):
    qg, nvalid, active = _decode_args(sds)
    kv = sds((SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM), jnp.int8)
    sc = sds((SLOTS, MAX_LEN, KV_HEADS), jnp.float32)
    _compile(lambda q, k, ks, v, vs, n, a, e: flash_decode_quant(
        q, k, ks, v, vs, n, a, e, interpret=False),
        qg, kv, sc, kv, sc, nvalid, active, sds((1,), jnp.int32))


def test_pr_product_compiles(sds):
    # the stream workload's FIR operand planes: (taps, frames, frame length)
    plane = sds((16, 8, 256), jnp.int32)
    _compile(lambda a, b, p, r: pr_product(a, b, p, r, backend="pallas",
                                           interpret=False),
             plane, plane, sds((), jnp.int32), sds((), jnp.int32))


@pytest.mark.parametrize("site", ["prefill", "decode"])
def test_attention_compiles_tensor_parallel_on_four_chips(topo, monkeypatch,
                                                          site):
    """At tp=4 the chip's compiler refuses to partition a Mosaic kernel;
    the dispatch routers run the attention kernels under shard_map."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.dist import meshctx
    from repro.kernels import dispatch
    from repro.models import attention as attn

    # steer the routers as on a TPU host: pallas route, compiled kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "_override", None)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    heads = P(None, None, "model", None)
    with meshctx.use_mesh(mesh):
        if site == "prefill":
            q = sds((1, 256, HEADS, HEAD_DIM), jnp.bfloat16, heads)
            kv = sds((1, 256, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads)
            _compile(lambda q, k, v: dispatch.prefill_attention(
                q, k, v, causal=True), q, kv, kv)
        else:
            q = sds((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16, heads)
            new = sds((SLOTS, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads)
            kv = sds((SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM), jnp.bfloat16,
                     heads)
            vec = sds((SLOTS,), jnp.int32, P(None))

            def step(q, kn, vn, k, v, length):
                cache = attn.KVCache(k, v, length)
                return dispatch.decode_attention(
                    q, kn, vn, cache, active=length >= 0)

            _compile(step, q, new, new, kv, kv, vec)
