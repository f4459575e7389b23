"""The dense decoder family (``arch.family == "dense"``): its weights, its
plain float32 reference and its work counts.

Llama/Qwen2/Mistral-style blocks as published: pre-RMSNorm, grouped-query
attention with rotary positions (rotate-half form, inverse frequencies
``theta ** (-2i / head_dim)``), optional biases on q/k/v, an optional
sliding window (position i sees j with ``i - window < j <= i``), SwiGLU
MLP ``down(silu(gate(x)) * up(x))``, a final RMSNorm and an untied or tied
unembedding.

Weights (layers stacked on a leading axis of ``n_layers``):
  embed (V, d); unembed (d, V) unless tied; final_norm (d,)
  layers: attn_norm, mlp_norm (L, d); wq (L, d, H*D); wk, wv (L, d, KV*D);
          bq (L, H*D), bk, bv (L, KV*D) when the q/k/v projections have
          biases; wo (L, H*D, d); w_gate, w_up (L, d, F); w_down (L, F, d)

The reference reads the weights of ``make`` and nothing of the program: no
cache, no batching, no kernels, every matmul in float32 at ``highest``
precision.  Layers run one at a time under a scan, queries in blocks and
logits in blocks of rows, so a 4k-token sequence fits beside the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights
import workcount as wc

BIAS_STD = 0.1
NORM_STD = 0.05
Q_BLOCK = 512
ROW_BLOCK = 512


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def shapes(arch: dict) -> dict:
    L, d, H, KV, D, F, V = (arch[k] for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab"))
    layers = {
        "attn_norm": (L, d), "mlp_norm": (L, d),
        "wq": (L, d, H * D), "wk": (L, d, KV * D), "wv": (L, d, KV * D),
        "wo": (L, H * D, d),
        "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
    }
    if arch["qkv_bias"]:
        layers.update(bq=(L, H * D), bk=(L, KV * D), bv=(L, KV * D))
    out = {"embed": (V, d), "final_norm": (d,), "layers": layers}
    if not arch["tie_embeddings"]:
        out["unembed"] = (d, V)
    return out


def _scale(name: str, shape: tuple) -> tuple[float, float]:
    """(mean, std) of a leaf."""
    if name.endswith("norm"):
        return 1.0, NORM_STD
    if name in ("bq", "bk", "bv"):
        return 0.0, BIAS_STD
    if name == "embed":
        return 0.0, 1.0 / math.sqrt(shape[-1])
    return 0.0, 1.0 / math.sqrt(shape[-2])        # fan-in of (.., K, N)


def make(arch: dict, seed: int, mesh, dtype=jnp.bfloat16) -> dict:
    """All leaves for ``arch`` from ``seed``, in ``dtype``, in one jitted
    call, each leaf placed on ``mesh`` as the program places it."""
    tree = shapes(arch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            mean, std = _scale(name, shape)
            x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            leaves.append(x * jnp.asarray(std, dtype) + jnp.asarray(mean, dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return weights.placed(build, weights.key_from_seed(seed),
                          lambda w: to_program(w, arch), mesh)


def to_program(w: dict, arch: dict) -> dict:
    """The same arrays in the parameter layout of
    ``repro.models.transformer.init_lm`` (no copies)."""
    ly = w["layers"]

    def dense(name, bias=None):
        p = {"w": ly[name]}
        if bias is not None and bias in ly:
            p["b"] = ly[bias]
        return p

    layers = {
        "ln1": {"scale": ly["attn_norm"]}, "ln2": {"scale": ly["mlp_norm"]},
        "wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
        "wv": dense("wv", "bv"), "wo": dense("wo"),
        "mlp": {"up": dense("w_up"), "gate": dense("w_gate"),
                "down": dense("w_down")},
    }
    out = {"embed": {"emb": w["embed"]}, "layers": layers,
           "ln_f": {"scale": w["final_norm"]}}
    if not arch["tie_embeddings"]:
        out["unembed"] = {"w": w["unembed"]}
    return out


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------


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


def _mm(h, w):
    return h @ w.astype(jnp.float32)


def _block(x, ly, arch, pos, mm):
    f32 = lambda a: a.astype(jnp.float32)
    H, KV, D = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    S = x.shape[0]
    h = _rmsnorm(x, ly["attn_norm"], arch["norm_eps"])
    q, k, v = mm(h, ly["wq"]), mm(h, ly["wk"]), mm(h, ly["wv"])
    if arch["qkv_bias"]:
        q, k, v = q + f32(ly["bq"]), k + f32(ly["bk"]), v + f32(ly["bv"])
    q = _rope(q.reshape(S, H, D), pos, arch["rope_theta"])
    k = _rope(k.reshape(S, KV, D), pos, arch["rope_theta"])
    v = v.reshape(S, KV, D)
    x = x + mm(_attention(q, k, v, arch["swa_window"]), ly["wo"])
    h = _rmsnorm(x, ly["mlp_norm"], arch["norm_eps"])
    g = jax.nn.silu(mm(h, ly["w_gate"])) * mm(h, ly["w_up"])
    return x + mm(g, ly["w_down"])


def _hidden(w, arch, tokens, mm):
    """The final normed hidden state of every position, each projection
    computed by ``mm``."""
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, ly):
        return _block(x, ly, arch, pos, mm), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rmsnorm(x, w["final_norm"], arch["norm_eps"])


def _unembed(w, arch):
    return w["embed"].T if arch["tie_embeddings"] else w["unembed"]


def _gaps(w, arch, tokens, targets):
    """Per position: the reference's best logit minus its logit of
    ``targets`` (-1 = not compared, gap 0)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, arch, tokens, _mm)
        unembed = _unembed(w, arch)
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


_JITTED = {}


def _jitted(fn, arch: dict):
    """``fn`` for ``arch``, compiled once per padded length (a multiple of
    Q_BLOCK).  On weights placed over several chips GSPMD partitions it as
    the weights lie."""
    key = (fn.__name__,) + tuple(sorted(
        (k, v) for k, v in arch.items() if not isinstance(v, (dict, list))))
    jitted = _JITTED.get(key)
    if jitted is None:
        jitted = _JITTED[key] = jax.jit(lambda w, t, g: fn(w, arch, t, g))
    return jitted


def gaps(w, arch: dict, tokens, targets):
    """``_gaps``, jitted."""
    return _jitted(_gaps, arch)(w, tokens, targets)


def padded_len(n: int) -> int:
    """The next power of two times Q_BLOCK that holds ``n`` positions, so a
    run compiles the reference for a handful of lengths at most."""
    blocks = 1
    while blocks * Q_BLOCK < n:
        blocks *= 2
    return blocks * Q_BLOCK


# ---------------------------------------------------------------------------
# work counts (the whole cell's; see workcount.py for a chip's share)
# ---------------------------------------------------------------------------


def projections(arch: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one layer's GEMMs."""
    d, H, KV, D, F = (arch[k] for k in (
        "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff"))
    return [("q", d, H * D), ("k", d, KV * D), ("v", d, KV * D),
            ("o", H * D, d), ("gate", d, F), ("up", d, F), ("down", F, d)]


def layer_params(arch: dict) -> int:
    return sum(k * n for _, k, n in projections(arch))


def attended(arch: dict, pos: int) -> int:
    """Positions a query at index ``pos`` attends to (itself included)."""
    w = arch.get("swa_window")
    return min(pos + 1, w) if w else pos + 1


def prefill_flops(arch: dict, n: int) -> float:
    """Model FLOPs of prefilling ``n`` prompt tokens (no logits: admission
    feeds the last prompt token through the decode step)."""
    H, D, L = arch["n_heads"], arch["head_dim"], arch["n_layers"]
    attn = sum(4 * H * D * attended(arch, i) for i in range(n))
    return L * (2.0 * n * layer_params(arch) + attn)


def decode_flops(arch: dict, positions: list[int]) -> float:
    """Model FLOPs of one decode step; ``positions`` holds, per active slot,
    the index of the token being decoded."""
    H, D, L = arch["n_heads"], arch["head_dim"], arch["n_layers"]
    head = 2.0 * arch["d_model"] * arch["vocab"]
    per_tok = L * 2.0 * layer_params(arch) + head
    attn = L * sum(4 * H * D * attended(arch, p) for p in positions)
    return len(positions) * per_tok + attn


def axq_call_least_s(arch: dict, M: int, peaks: dict, unembed: bool) -> float:
    """Least time of every AXQ GEMM kernel call one model pass makes for M
    rows: per layer q, k, v, o, the fused gate/up, down; plus the
    unembedding when the pass computes logits."""
    by = {name: (k, n) for name, k, n in projections(arch)}
    per_layer = sum(wc.gemm_least_s(M, *by[p], peaks, "int8")
                    for p in ("q", "k", "v", "o", "down"))
    per_layer += wc.gemm_least_s(M, *by["gate"], peaks, "int8", gated=True)
    total = arch["n_layers"] * per_layer
    if unembed:
        total += wc.gemm_least_s(M, arch["d_model"], arch["vocab"], peaks,
                                 "int8")
    return total


def decode_attn_least_s(arch: dict, positions: list[int], peaks: dict,
                        kv_dtype: str) -> float:
    """Least time of one layer's decode attention over the active slots:
    read each slot's live keys and values once, plus the queries and the
    float32 outputs, or do 4*H*D operations per attended position at the
    bfloat16 peak, whichever takes longer."""
    H, KV, D = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    kvb = wc.DTYPE_BYTES[kv_dtype]
    live = sum(attended(arch, p) for p in positions)
    bytes_ = 2 * live * KV * D * kvb + len(positions) * H * D * (2 + 4)
    ops = 4.0 * H * D * live
    return max(ops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
