"""One general generator of open-loop request schedules.

A traffic file gives the parameters; the cell gives the offered rate; the
run gives the window length and the seed.  A window of ``seconds`` at
``rate_rps`` holds ``N = round(rate_rps * seconds)`` requests, so every
seed offers the same amount of work:

  arrivals  ``poisson``: the N gaps between arrivals are the
            ``(i + 1/2) / N`` quantiles of an exponential, in a drawn order,
            scaled to span the window: the bursts and lulls of a Poisson
            process, with the same multiset of gaps in every run.
            ``even``: due at ``(i + 1/2) / rate_rps``.
  lengths   ``lognormal``: the N quantiles ``(i + 1/2) / N`` of a lognormal
            with the given median and sigma, rounded and clipped.
            ``log_ladder`` / ``linear_ladder``: N lengths spaced evenly in
            log or linear scale over [min, max].  So the multiset of
            lengths is fixed by N alone.

The orders of gaps, prompt lengths and output lengths are drawn from the
traffic file's ``shuffle_seed``, so every run replays one schedule; the
run's seed makes only the token ids.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    due_s: float
    prompt: np.ndarray    # int32 token ids
    out_len: int


def n_requests(rate_rps: float, seconds: float) -> int:
    return max(1, round(rate_rps * seconds))


def lengths(spec: dict, n: int) -> np.ndarray:
    kind = spec["kind"]
    lo, hi = spec["min"], spec["max"]
    if kind == "lognormal":
        nd = NormalDist()
        q = [(i + 0.5) / n for i in range(n)]
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(p))
                for p in q]
    elif kind == "log_ladder":
        vals = np.exp(np.linspace(math.log(lo), math.log(hi), n))
    elif kind == "linear_ladder":
        vals = np.linspace(lo, hi, n)
    else:
        raise ValueError(f"unknown length kind {kind!r}")
    return np.clip(np.rint(np.asarray(vals, float)), lo, hi).astype(np.int64)


def schedule(traffic: dict, rate_rps: float, seconds: float, seed: int,
             vocab: int) -> list[Request]:
    n = n_requests(rate_rps, seconds)
    order = np.random.default_rng(int(traffic["shuffle_seed"]))
    prompts = order.permutation(lengths(traffic["prompt"], n))
    outs = order.permutation(lengths(traffic["output"], n))
    kind = traffic["arrivals"]["kind"]
    if kind == "poisson":
        q = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-q))
        ends = np.cumsum(gaps)
        due = (ends - gaps[0] / 2) / ends[-1] * seconds
    elif kind == "even":
        due = (np.arange(n) + 0.5) / rate_rps
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    # the token ids come from the second child of the seed's sequence, as in
    # every reading PERF.md gives
    tok = np.random.default_rng(np.random.SeedSequence(int(seed)).spawn(2)[1])
    return [Request(float(d), tok.integers(0, vocab, int(p), dtype=np.int32),
                    int(o)) for d, p, o in zip(due, prompts, outs)]
