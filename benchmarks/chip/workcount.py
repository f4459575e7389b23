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

What a layer computes belongs to its model family: each
``families/<family>.py`` counts its own GEMMs as (name, K, N), the FLOPs of
a prefilled and of a decoded token, the positions a query attends to and
the bytes of decode attention, for the whole cell.  This module holds the
arithmetic every family shares.

**A chip's share.**  A per-layer reader compares chip 0's device time with
chip 0's share of the work.  Under the tensor parallelism of a cell on
several chips, heads, feed-forward columns and the vocabulary are split
evenly over the ``model`` axis, so that share is the whole cell's count
divided by ``chips``.  The readers take it so by reading the whole cell's
count against ``cell_peaks``: each rate of one chip times ``chips``, which
is the same quotient.  The activations that every chip reads in full are
then counted once over all chips rather than once per chip: a few bytes
against each weight matrix.  At one chip every share is unchanged.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
#: values per float32 scale in the AXQ block quantization (the kernel's
#: contraction block, ``ApproxSpec.block``)
QBLOCK = 256
RATES = ("bf16_flops_per_s", "int8_ops_per_s", "hbm_bytes_per_s")


def cell_peaks(peaks: dict, chips: int) -> dict:
    """One chip's ``peaks`` with every rate times ``chips`` (see the module
    docstring)."""
    return {k: v * chips if k in RATES else v for k, v in peaks.items()}


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


def peak_rate(peaks: dict, precision: str) -> float:
    """The peak of the configuration's GEMM precision."""
    return peaks["int8_ops_per_s" if precision == "int8" else
                 "bf16_flops_per_s"]
