"""Random weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them in its
own parameter layout (``to_program``), and the plain reference makes them
again from the same seed after the program's state is freed.  Every leaf
is drawn directly in the configuration's dtype; no float32 copy is made.

Layout (layers stacked on a leading axis of ``n_layers``):
  embed (V, d); unembed (d, V) unless tied; final_norm (d,)
  layers: attn_norm, mlp_norm (L, d); wq (L, d, H*D); wk, wv (L, d, KV*D);
          bq (L, H*D), bk, bv (L, KV*D) when the q/k/v projections have
          biases; wo (L, H*D, d); w_gate, w_up (L, d, F); w_down (L, F, d)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1
NORM_STD = 0.05


def key_from_seed(seed: int):
    """A JAX key from a seed of any size (run seeds exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), 0]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


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


def make(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All leaves for ``arch`` from ``seed``, in ``dtype``, on the default
    device, in one jitted call."""
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

    return jax.jit(build)(key_from_seed(seed))


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
