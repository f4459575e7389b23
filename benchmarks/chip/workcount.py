"""Operations and bytes that the work needs, from the cell's shapes alone.

The yardstick for every roofline and utilization share.  Counts come from
the configuration's published shapes and stated precisions, never from the
program's arrays or its HLO, so a later change to the implementation leaves
them where they are:

  * M is the rows that carry a token (active slots, real prompt tokens),
    never a padded tile;
  * KV bytes are the live positions only, at the cache dtype;
  * weight bytes are int8 values plus their float32 block scales under an
    int8 GEMM precision, bfloat16 values otherwise.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
#: values per float32 scale in the AXQ block quantization (the kernel's
#: contraction block, ``ApproxSpec.block``)
QBLOCK = 256


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


def gemm_least_s(M: int, K: int, N: int, peaks: dict, precision: str,
                 gated: bool = False) -> float:
    """Least time of one GEMM of M real rows: the larger of its operations
    over the peak rate and its unavoidable bytes (weights with their scales,
    the quantized activations, the float32 output) over HBM bandwidth.
    ``gated``: two weights of (K, N) share one activation stream."""
    nw = 2 if gated else 1
    if precision == "int8":
        rate = peaks["int8_ops_per_s"]
        w_bytes = nw * (K * N + 4 * N * (K // QBLOCK))
        x_bytes = M * K + 4 * M * (K // QBLOCK)
    else:
        rate = peaks["bf16_flops_per_s"]
        w_bytes = nw * 2 * K * N
        x_bytes = 2 * M * K
    ops = nw * 2.0 * M * K * N
    out_bytes = 4 * M * N
    return max(ops / rate, (w_bytes + x_bytes + out_bytes)
               / peaks["hbm_bytes_per_s"])


def axq_call_least_s(arch: dict, M: int, peaks: dict, unembed: bool) -> float:
    """Least time of every AXQ GEMM kernel call one model pass makes for M
    rows: per layer q, k, v, o, the fused gate/up, down; plus the
    unembedding when the pass computes logits."""
    by = {name: (k, n) for name, k, n in projections(arch)}
    per_layer = sum(gemm_least_s(M, *by[p], peaks, "int8")
                    for p in ("q", "k", "v", "o", "down"))
    per_layer += gemm_least_s(M, *by["gate"], peaks, "int8", gated=True)
    total = arch["n_layers"] * per_layer
    if unembed:
        total += gemm_least_s(M, arch["d_model"], arch["vocab"], peaks, "int8")
    return total


def decode_attn_least_s(arch: dict, positions: list[int], peaks: dict,
                        kv_dtype: str) -> float:
    """Least time of one layer's decode attention over the active slots:
    read each slot's live keys and values once, plus the queries and the
    float32 outputs, or do 4*H*D operations per attended position at the
    bfloat16 peak, whichever takes longer."""
    H, KV, D = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    kvb = DTYPE_BYTES[kv_dtype]
    live = sum(attended(arch, p) for p in positions)
    bytes_ = 2 * live * KV * D * kvb + len(positions) * H * D * (2 + 4)
    ops = 4.0 * H * D * live
    return max(ops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def peak_rate(peaks: dict, precision: str) -> float:
    """The peak of the configuration's GEMM precision."""
    return peaks["int8_ops_per_s" if precision == "int8" else
                 "bf16_flops_per_s"]
