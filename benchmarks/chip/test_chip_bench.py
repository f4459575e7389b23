"""CPU checks of the chip benchmark's yardstick.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

Traffic generation, the work counts behind every roofline and utilization
share, the peaks table, the reduction from a recorded trace, the
configuration files against their published sources, and the correctness
comparison: a sound run at a tiny size passes it, while the program's
lower-precision path (the control) and a served token altered where it is
produced both fail it.  Nothing here needs a chip.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import arrivals  # noqa: E402
import spec  # noqa: E402
import tracereduce as tr  # noqa: E402
import workcount as wc  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEEDS = (0, 1, 2 ** 31 + 11, 3_000_000_017)


def cell_of(name):
    return spec.find_cell(name, BENCH)


# ---------------------------------------------------------------------------
# the files BENCHMARK.json points at
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    c = cell_of(name)
    assert c.rate_rps > 0
    assert c.cell["limits"]["request_mean_logit_gap"] > 0
    for m in c.per_layer:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}


def test_every_config_used_and_its_file_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for conf in BENCH["configs"]:
        assert conf["name"] in used
        assert conf["file"].startswith(BENCH["paths"][0] + "/")
        assert spec.load_json(spec.ROOT / conf["file"])["name"] == conf["name"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_matches_published(conf):
    """Every size the program runs is the published one; what differs from
    the repo's registry is listed as a correction."""
    c = spec.load_json(spec.ROOT / conf["file"])
    pub, arch = c["published"], c["arch"]
    assert conf["reduced"] == c["reduced"] == []
    pairs = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tie_embeddings"}
    for k, a in pairs.items():
        assert pub[k] == arch[a], k
    assert arch["head_dim"] == pub.get("head_dim",
                                       pub["hidden_size"]
                                       // pub["num_attention_heads"])
    window = (None if pub.get("use_sliding_window") is False
              else pub.get("sliding_window"))
    assert arch["swa_window"] == window
    assert arch["dtype"] == pub["torch_dtype"] == c["weights_dtype"]
    from repro.configs.base import get_config

    reg = get_config(arch["name"])
    for key, why in c["corrections"].items():
        assert getattr(reg, key) != arch[key] and why
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "qkv_bias", "swa_window"):
        assert getattr(reg, key) == arch[key], key


def test_qwen_ties_embeddings_from_source():
    c = spec.load_json(HERE / "configs" / "qwen2.5-3b-axq8.json")
    assert c["published"]["tie_word_embeddings"] is True
    assert c["arch"]["tie_embeddings"] is True
    assert "tie_embeddings" in c["corrections"]


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def sched(name, seed, seconds=40.0):
    c = cell_of(name)
    return arrivals.schedule(c.traffic, c.rate_rps, seconds, seed,
                             c.config["arch"]["vocab"])


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_schedule(name):
    a, b = sched(name, 12345), sched(name, 12345)
    assert [(r.due_s, r.out_len) for r in a] == [(r.due_s, r.out_len)
                                                 for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", CELLS)
def test_replay_ignores_seed(name):
    """Due times and lengths are a function of the cell alone; the seed
    makes only the token ids."""
    runs = [sched(name, s) for s in SEEDS]
    first = [(r.due_s, len(r.prompt), r.out_len) for r in runs[0]]
    for other in runs[1:]:
        assert [(r.due_s, len(r.prompt), r.out_len) for r in other] == first
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(runs[0], other))


def test_longctx_arrivals_even():
    dues = [r.due_s for r in sched("h2o-danube-1.8b-bf16.longctx", 7)]
    assert np.allclose(np.diff(dues), dues[1] - dues[0])


def test_chat_arrival_gaps():
    """The gaps between arrivals are the exponential's quantiles, scaled
    to span the window."""
    run = sched("qwen2.5-3b-axq8.chat", 3)
    n = len(run)
    want = -np.log1p(-(np.arange(n) + 0.5) / n)
    d = np.diff([r.due_s for r in run]) / (40.0 / want.sum())
    assert np.abs(d[:, None] - want[None, :]).min(axis=1).max() < 1e-9
    assert 0 <= run[0].due_s and run[-1].due_s < 40.0


def test_lognormal_quantiles():
    spec_ = {"kind": "lognormal", "median": 256, "sigma": 1.0, "min": 16,
             "max": 1536}
    x = arrivals.lengths(spec_, 101)
    assert x[50] == 256 and x.min() >= 16 and x.max() <= 1536
    assert list(x) == sorted(x)


@pytest.mark.parametrize("name", CELLS)
def test_requests_fit_cache_and_ladder(name):
    c = cell_of(name)
    tr_ = c.traffic
    for seed in SEEDS:
        for r in sched(name, seed, seconds=BENCH["run_seconds"]):
            assert len(r.prompt) + r.out_len <= tr_["max_len"]
            assert len(r.prompt) - 1 <= tr_["buckets"][-1]
            assert r.prompt.max() < c.config["arch"]["vocab"]


def test_window_peak_leaves_out_setup():
    """Where the window did not raise the process's peak of device memory,
    the peak reported is the most in use at its tick boundaries."""
    import run

    assert run.window_peak(setup_peak=9, process_peak=9, tick_max=5) == 5
    assert run.window_peak(setup_peak=9, process_peak=12, tick_max=5) == 12


# ---------------------------------------------------------------------------
# work counts and peaks
# ---------------------------------------------------------------------------

ARCH = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab": 10, "swa_window": None}
PEAKS = {"bf16_flops_per_s": 100.0, "int8_ops_per_s": 200.0,
         "hbm_bytes_per_s": 10.0}


def test_layer_params_and_flops():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    assert wc.layer_params(ARCH) == 64 + 32 + 32 + 64 + 128 * 3
    # one token at index 0: matmuls + attention over 1 position
    assert wc.prefill_flops(ARCH, 1) == 2 * (2 * 576 + 4 * 2 * 4 * 1)
    # 3 tokens attend 1 + 2 + 3 positions
    assert wc.prefill_flops(ARCH, 3) == 2 * (2 * 3 * 576 + 32 * 6)
    # one slot decoding index 4 (5 positions), plus the unembedding
    assert wc.decode_flops(ARCH, [4]) == 2 * 2 * 576 + 2 * 8 * 10 + 2 * 32 * 5


def test_window_caps_attended_positions():
    a = dict(ARCH, swa_window=3)
    assert [wc.attended(a, p) for p in range(5)] == [1, 2, 3, 3, 3]


def test_gemm_least_time():
    # int8: 2*M*K*N ops at 200; bytes = weights K*N + scales, x + scales,
    # f32 out; here bytes bound
    t = wc.gemm_least_s(4, 512, 8, PEAKS, "int8")
    w_bytes = 512 * 8 + 4 * 8 * 2
    x_bytes = 4 * 512 + 4 * 4 * 2
    assert t == pytest.approx(max(2 * 4 * 512 * 8 / 200,
                                  (w_bytes + x_bytes + 4 * 4 * 8) / 10))
    # bf16 weights are two bytes each
    t = wc.gemm_least_s(1, 4, 4, PEAKS, "bf16")
    assert t == pytest.approx((2 * 16 + 2 * 4 + 4 * 4) / 10)
    g = wc.gemm_least_s(1, 4, 4, PEAKS, "bf16", gated=True)
    assert g == pytest.approx((2 * 2 * 16 + 2 * 4 + 4 * 4) / 10)


def test_decode_attention_least_time():
    # 2 slots at indices 1 and 2 attend 2 + 3 = 5 positions: k and v of 1
    # head of 4 dims in bf16, q in bf16 and out in f32 for 2 heads each
    t = wc.decode_attn_least_s(ARCH, [1, 2], PEAKS, "bfloat16")
    bytes_ = 2 * 5 * 1 * 4 * 2 + 2 * 2 * 4 * 6
    assert t == pytest.approx(max(4 * 2 * 4 * 5 / 100, bytes_ / 10))


def test_peaks_lookup():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert wc.peak_rate(p, "int8") == 393e12
    assert wc.peak_rate(p, "bf16") == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def test_busy_union_and_self_time():
    evs = [("while.1", 0, 100), ("fusion.2", 10, 30), ("flash_decode.3", 30,
                                                        60),
           ("copy.4", 120, 150), ("copy.4", 140, 160)]
    assert tr.busy_ns(evs, 0, 200) == 100 + 40
    assert tr.busy_ns(evs, 50, 130) == 50 + 10
    st = tr.self_times(evs)
    assert st["while.1"] == 100 - 20 - 30
    assert st["fusion.2"] == 20 and st["flash_decode.3"] == 30
    assert tr.time_matching(evs, r"flash_decode") == (30, 1)
    assert tr.time_matching(evs, r"^copy") == (50, 2)


def test_idle_charged_to_innermost_span():
    ops = [("a", 10, 20), ("b", 40, 50)]
    spans = [("bench.traced", 0, 100), ("bench.tick", 5, 45),
             ("bench.wait", 60, 80)]
    idle = tr.idle_by_span(ops, spans, 0, 100)
    # idle: [0,10) [20,40) [50,100); tick covers 5..45, wait 60..80
    assert idle == {"bench.traced": 5 + 10 + 20, "bench.tick": 5 + 20,
                    "bench.wait": 20}
    assert tr.idle_inside(ops, spans, "bench.tick", 0, 100) == 25
    assert tr.in_spans(spans, "bench.tick", 0, 100) == 1


RECORDED = HERE / "testdata" / "trace_small.json"


def test_recorded_trace():
    """Two engine ticks cut from a chip trace of ``qwen2.5-3b-axq8.chat``
    by ``tracereduce.load``: the reduction gives the numbers recorded
    beside them, and the numbers hang together."""
    rec = json.loads(RECORDED.read_text())
    ops, kers, mods, spans = ([tuple(e) for e in rec[k]] for k in
                              ("ops", "kernels", "modules", "spans"))
    lo, hi = rec["window"]
    want = rec["expect"]
    busy = tr.busy_ns(ops, lo, hi)
    assert busy == want["busy_ns"] and 0 < busy <= hi - lo
    step = tr.time_matching(mods, r"^jit_step\b")
    fd = tr.time_matching(kers, r"^flash_decode$")
    axq = tr.time_matching(kers, r"^axqmm$")
    assert step == tuple(want["step"]) and step[1] == 2
    assert fd == tuple(want["flash_decode"]) and fd[1] == 2 * 36
    # per step: q, k, v, o, the fused gate/up, down in each of 36 layers,
    # and the tied unembedding
    assert axq == tuple(want["axqmm"]) and axq[1] == 2 * (36 * 6 + 1)
    # kernels run inside the step executables, and are ops of their own
    assert fd[0] + axq[0] < step[0] <= busy
    assert set(tr.self_times(kers)) <= {"flash_decode", "axqmm",
                                        "flash_attention"}
    idle = tr.idle_inside(ops, spans, "bench.tick", lo, hi)
    assert idle == want["tick_idle_ns"]
    by_span = tr.idle_by_span(ops, spans, lo, hi)
    assert sum(by_span.values()) == (hi - lo) - busy
    assert by_span.get("bench.tick", 0) <= idle


# ---------------------------------------------------------------------------
# the correctness comparison, at a tiny size on the CPU
# ---------------------------------------------------------------------------

TINY_ARCH = dict(name="tiny", family="dense", n_layers=2, d_model=256,
                 n_heads=4, n_kv_heads=2, head_dim=64, d_ff=512, vocab=1024,
                 rope_theta=10000.0, qkv_bias=True, swa_window=None,
                 norm_eps=1e-6, tie_embeddings=True, act="silu",
                 dtype="bfloat16", source="test")
TINY_TRAFFIC = {
    "name": "tiny", "arrivals": {"kind": "poisson"}, "shuffle_seed": 5,
    "prompt": {"kind": "lognormal", "median": 32, "sigma": 1.0, "min": 8,
               "max": 100},
    "output": {"kind": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64},
    "slots": 4, "max_len": 256, "buckets": [16, 32, 64, 128], "pack": 2}
#: the tiny cells' limits on the mean gap over requests, set like the chip
#: cells' from the program's readings and its next precision down, at this
#: size on the CPU over the tests' seed and three others: axq8 reads
#: 0.0008-0.0033 against 0.39-0.54 for axq4; the exact path reads
#: 0.00007-0.00059 against axq8's 0.0008-0.0033 (0.0028 on the tests' seed),
#: which at this size lies close
TINY = {"axq8": ("axq4", 0.05), "exact": ("axq8", 0.0012)}


def tiny_cell(approx):
    return spec.Cell(
        name="tiny.chat", chips=1,
        config={"name": "tiny", "arch": TINY_ARCH, "approx": approx,
                "gemm_precision": "int8" if approx != "exact" else "bf16",
                "kv_cache_dtype": "bfloat16"},
        traffic=TINY_TRAFFIC,
        cell={"rate_rps": 4.0,
              "limits": {"request_mean_logit_gap": TINY[approx][1],
                         "tokens_compared": 20}},
        end_to_end=BENCH["end_to_end"], per_layer=[])


def tiny_run(config_approx, **kw):
    import run

    return run.run_cell(tiny_cell(config_approx), 2 ** 33 + 5, 2.0, False,
                        **kw)


@pytest.mark.parametrize("approx", sorted(TINY))
def test_sound_run_is_correct(approx):
    res = tiny_run(approx)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 8
    assert list(res["checks"])[0] == "request_mean_logit_gap"
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("approx", sorted(TINY))
def test_control_fails(approx):
    """The program's next precision down (axq4 for axq8, axq8 for bf16)
    in the program's place reads above the limit."""
    res = tiny_run(approx, approx=TINY[approx][0])
    assert not res["correct"], res["checks"]


def test_altered_token_fails(monkeypatch):
    """A served token altered where it is produced fails the comparison."""
    import repro.serve.engine  # noqa: F401  (serve.lm imports through it)
    from repro.serve import lm

    harvest = lm.LMAdapter.harvest

    def altered(self, req, feed, slot, emission):
        return harvest(self, req, feed, slot,
                       (int(emission) + 1) % self.cfg.vocab)

    monkeypatch.setattr(lm.LMAdapter, "harvest", altered)
    res = tiny_run("axq8")
    assert not res["correct"]
    assert res["compared"]["max_logit_gap"] > 1.0
