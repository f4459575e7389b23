"""Approximation-aware building blocks (pure-JAX, param-dict style).

Parameters are nested dicts of jnp arrays; ``init_*`` builds them, ``*_apply``
consumes them.  Every matmul goes through :func:`repro.kernels.ops.approx_matmul`
with the ApproxSpec resolved from the model's ApproxPolicy by parameter path —
the MAx-DNN-style fine-grained approximation hook (DESIGN.md §2.3).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.approx import ApproxMode, ApproxPolicy, ApproxSpec
from repro.kernels.ops import approx_gated_matmul, approx_matmul

Array = jnp.ndarray


def truncated_normal(key, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, bias: bool = False, scale: float | None = None):
    w_key, _ = jax.random.split(key)
    stddev = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal(w_key, (d_in, d_out), stddev)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense_apply(p, x: Array, policy: ApproxPolicy, path: str,
                degree: Optional[Array] = None,
                residual: Optional[Array] = None) -> Array:
    """``x @ w (+ b) (+ residual)``.  On the AXQ route bias and residual ride
    the kernel's fused f32 epilogue (one writeback, DESIGN.md §9); elsewhere
    they are the same post-cast adds the call sites used to do inline."""
    spec = policy.spec_for(path)
    if spec.mode == ApproxMode.AXQ:
        return approx_matmul(x, p["w"], spec, degree=degree, out_dtype=x.dtype,
                             path=path, bias=p.get("b"), residual=residual)
    y = approx_matmul(x, p["w"], spec, degree=degree, out_dtype=x.dtype,
                      path=path)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    if residual is not None:
        y = residual + y
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(p, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(dt)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm_apply(p, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int):
    # 1/sqrt(d) keeps tied-unembedding logits at unit variance
    return {"emb": truncated_normal(key, (vocab, d), 1.0 / math.sqrt(d))}


def embed_apply(p, tokens: Array, dtype=jnp.bfloat16) -> Array:
    return jnp.take(p["emb"], tokens, axis=0).astype(dtype)


def unembed_apply(p, x: Array, policy: ApproxPolicy, path: str,
                  degree=None) -> Array:
    """logits = x @ emb.T (tied) — routed through the approx dispatch.
    A prepacked tied unembedding rides the embed dict as ``unembed_q``
    (kernels/qstore.py); the token-lookup ``emb`` stays float."""
    spec = policy.spec_for(path)
    w = p.get("unembed_q")
    if w is None:
        w = p["emb"].T
    return approx_matmul(x, w, spec, degree=degree, out_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_gated_mlp(key, d: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "up": init_dense(k1, d, d_ff),
        "gate": init_dense(k2, d, d_ff),
        "down": init_dense(k3, d_ff, d, scale=1.0 / math.sqrt(d_ff)),
    }


def gated_mlp_apply(p, x: Array, policy: ApproxPolicy, path: str, act: str = "silu",
                    degree=None, residual: Optional[Array] = None) -> Array:
    """up/gate/act(gate)*up/down.  When up and gate share one AXQ spec the
    first half runs as ONE fused kernel (shared x stream, gate applied
    in-VMEM — one HBM roundtrip instead of three); the down projection fuses
    ``residual`` into its epilogue (DESIGN.md §9)."""
    spec_up = policy.spec_for(path + "/up")
    spec_gate = policy.spec_for(path + "/gate")
    if (spec_up.mode == ApproxMode.AXQ and spec_gate == spec_up
            and "b" not in p["up"] and "b" not in p["gate"]):
        h = approx_gated_matmul(x, p["up"]["w"], p["gate"]["w"], spec_up,
                                act=act, degree=degree, out_dtype=x.dtype)
    else:
        up = dense_apply(p["up"], x, policy, path + "/up", degree)
        gate = dense_apply(p["gate"], x, policy, path + "/gate", degree)
        h = act_fn(act)(gate) * up
    return dense_apply(p["down"], h, policy, path + "/down", degree,
                       residual=residual)


# ---------------------------------------------------------------------------
# Causal depthwise conv1d (RG-LRU / Mamba front conv)
# ---------------------------------------------------------------------------


def init_conv1d(key, channels: int, width: int):
    return {"w": truncated_normal(key, (width, channels), 1.0 / math.sqrt(width)),
            "b": jnp.zeros((channels,), jnp.float32)}


def conv1d_apply(p, x: Array, state: Optional[Array] = None):
    """Causal depthwise conv. x: (B, S, C).  If `state` (B, width-1, C) is
    given (decode), it is prepended and the new state returned."""
    width = p["w"].shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        out = out + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * p["w"][i]
    out = (out + p["b"]).astype(x.dtype)
    new_state = xp[:, -(width - 1):, :] if width > 1 else pad
    return out, new_state


# ---------------------------------------------------------------------------
# Sharding-constraint helper (activation partitioning)
# ---------------------------------------------------------------------------


def shard_activation(x: Array, spec) -> Array:
    """Constrain ``x`` to ``spec`` on the active mesh; a no-op when there is
    no mesh wider than one device.  Any other failure is raised: dropping
    the constraint silently would hide a wrong layout on the chip."""
    from jax.sharding import NamedSharding

    from repro.dist.meshctx import get_mesh

    mesh = get_mesh()
    if mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
