"""Plain float32 reference of the dense decoder family the configurations use.

Llama/Qwen2/Mistral-style blocks as published: pre-RMSNorm, grouped-query
attention with rotary positions (rotate-half form, inverse frequencies
``theta ** (-2i / head_dim)``), optional biases on q/k/v, an optional
sliding window (position i sees j with ``i - window < j <= i``), SwiGLU
MLP ``down(silu(gate(x)) * up(x))``, a final RMSNorm and an untied or tied
unembedding.  It reads the weights of ``weights.make`` and nothing of the
program: no cache, no batching, no kernels, every matmul in float32 at
``highest`` precision.  Layers run one at a time under a scan, queries in
blocks and logits in blocks of rows, so a 4k-token sequence fits beside
the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512
ROW_BLOCK = 512


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, D); pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q (S, H, D), k/v (S, KV, D): causal (and windowed) GQA, queries in
    blocks of Q_BLOCK."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, KV, G, D)
    j = jnp.arange(S)

    def block(args):
        qi, b = args
        i = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(D)
        mask = j[None, :] <= i[:, None]
        if window is not None:
            mask &= j[None, :] > i[:, None] - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    return o.reshape(S, H * D)


def _block(x, ly, arch, pos):
    f32 = lambda a: a.astype(jnp.float32)
    H, KV, D = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    S = x.shape[0]
    h = _rmsnorm(x, ly["attn_norm"], arch["norm_eps"])
    q, k, v = h @ f32(ly["wq"]), h @ f32(ly["wk"]), h @ f32(ly["wv"])
    if arch["qkv_bias"]:
        q, k, v = q + f32(ly["bq"]), k + f32(ly["bk"]), v + f32(ly["bv"])
    q = _rope(q.reshape(S, H, D), pos, arch["rope_theta"])
    k = _rope(k.reshape(S, KV, D), pos, arch["rope_theta"])
    v = v.reshape(S, KV, D)
    x = x + _attention(q, k, v, arch["swa_window"]) @ f32(ly["wo"])
    h = _rmsnorm(x, ly["mlp_norm"], arch["norm_eps"])
    g = jax.nn.silu(h @ f32(ly["w_gate"])) * (h @ f32(ly["w_up"]))
    return x + g @ f32(ly["w_down"])


def _gaps(w, arch, tokens, targets):
    """Per position: the reference's best logit minus its logit of
    ``targets`` (-1 = not compared, gap 0)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = w["embed"][tokens].astype(jnp.float32)

        def layer(x, ly):
            return _block(x, ly, arch, pos), None

        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = _rmsnorm(x, w["final_norm"], arch["norm_eps"])
        unembed = (w["embed"].T if arch["tie_embeddings"]
                   else w["unembed"])
        nb = x.shape[0] // ROW_BLOCK

        def block(args):
            xb, tb = args
            logits = xb @ unembed.astype(jnp.float32)
            best = jnp.max(logits, axis=-1)
            mine = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None],
                                       axis=-1)[:, 0]
            return jnp.where(tb >= 0, best - mine, 0.0)

        g = jax.lax.map(block, (x.reshape(nb, ROW_BLOCK, -1),
                                targets.reshape(nb, ROW_BLOCK)))
        return g.reshape(-1)


_GAPS = {}


def gaps(w, arch: dict, tokens, targets):
    """``_gaps`` compiled once per padded length (a multiple of Q_BLOCK)."""
    key = tuple(sorted((k, v) for k, v in arch.items()
                       if not isinstance(v, (dict, list))))
    fn = _GAPS.get(key)
    if fn is None:
        fn = _GAPS[key] = jax.jit(lambda w, t, g: _gaps(w, arch, t, g))
    return fn(w, tokens, targets)


def padded_len(n: int) -> int:
    """The next power of two times Q_BLOCK that holds ``n`` positions, so a
    run compiles the reference for a handful of lengths at most."""
    blocks = 1
    while blocks * Q_BLOCK < n:
        blocks *= 2
    return blocks * Q_BLOCK
