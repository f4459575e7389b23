"""CPU checks of the model families and of cells on several chips.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

The dense family keeps the weights and the work counts that the harness
had before they moved into ``families/dense.py``; a family is found by a
configuration's ``arch.family`` alone; a tiny dense cell served over a
four-device host mesh (``ShardedServeEngine``) agrees with the reference
on that mesh, and the reference there agrees with the one-device
reference on the same tokens; the program's own int8 path (axq8, the
control) and a step whose row-parallel products skip the exchange
between chips both fail the comparison.  Nothing here needs a chip.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
import spec  # noqa: E402
import test_chip_bench as tcb  # noqa: E402
import workcount as wc  # noqa: E402

DENSE = spec.family("dense")
SEED = 2 ** 33 + 5
#: sha256 over each leaf's path and bfloat16 bits, as the harness made the
#: weights before the move (``weights.make``), for the tiny configuration
#: with tied embeddings and q/k/v biases, and with neither
WEIGHTS_SHA256 = {
    True: "66b59d1db4ff9ae48bea84254ad7b96abacc2688664651db7571da9171b265d6",
    False: "cf384b5680af3fdf5c41fd0e56efbe63314c8470e494fdff325428f022b23447",
}
COUNTS = json.loads((HERE / "testdata" / "dense_counts.json").read_text())
API = ("make", "to_program", "gaps", "padded_len",
       "projections", "attended", "prefill_flops", "decode_flops",
       "axq_call_least_s", "decode_attn_least_s")


def one_chip_mesh():
    from repro.dist import meshctx

    return meshctx.make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# the dense family against the harness before the move
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tied", [True, False])
def test_dense_weights_bit_identical_at_one_chip(tied):
    arch = dict(tcb.TINY_ARCH, tie_embeddings=tied, qkv_bias=tied)
    w = DENSE.make(arch, SEED, one_chip_mesh())
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[tied]


@pytest.mark.parametrize("name", sorted(COUNTS["configs"]))
def test_dense_counts_unchanged(name):
    arch = spec.load_json(HERE / "configs" / f"{name}.json")["arch"]
    want = COUNTS["configs"][name]
    pos = COUNTS["positions"]
    peaks = spec.peaks("TPU v5 lite")
    assert [list(p) for p in DENSE.projections(arch)] == want["projections"]
    assert DENSE.layer_params(arch) == want["layer_params"]
    assert [DENSE.attended(arch, p) for p in pos] == want["attended"]
    assert [DENSE.prefill_flops(arch, n)
            for n in COUNTS["prefill_tokens"]] == want["prefill_flops"]
    assert DENSE.decode_flops(arch, pos) == want["decode_flops"]
    assert [DENSE.axq_call_least_s(arch, m, peaks, u)
            for m, u in COUNTS["axq_calls"]] == want["axq_call_least_s"]
    assert DENSE.decode_attn_least_s(arch, pos, peaks, "bfloat16") == \
        want["decode_attn_least_s"]


# ---------------------------------------------------------------------------
# families by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("conf", spec.benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_every_config_finds_its_family(conf):
    arch = spec.load_json(spec.ROOT / conf["file"])["arch"]
    fam = spec.family(arch["family"])
    assert all(callable(getattr(fam, f)) for f in API)


def test_unknown_family_is_an_error():
    with pytest.raises(KeyError):
        spec.family("no-such-family")


@pytest.fixture
def stub(monkeypatch):
    monkeypatch.setattr(spec, "FAMILY_DIRS",
                        (HERE / "testdata" / "families",) + spec.FAMILY_DIRS)
    arch = dict(tcb.TINY_ARCH, family="stub")
    cell = tcb.tiny_cell("exact")
    cell.config = dict(cell.config, arch=arch)
    return cell


def test_stub_family_found_by_arch_family(stub):
    """A family is a new file plus a configuration that names it: the
    harness's comparison and readers run the stub's code, with no edit to
    ``run.py`` or to a reader."""
    fam = stub.family
    assert fam.__file__.endswith(os.path.join("testdata", "families",
                                              "stub.py"))
    handle = SimpleNamespace(out=[1, 2, 3], done=True, status="ok")
    rec = SimpleNamespace(prompt=np.arange(4, dtype=np.int32), out_len=3,
                          handle=handle)
    cmp = run.compare(stub, SEED, [rec])
    assert cmp["request_mean_logit_gap"] == pytest.approx(fam.GAP)
    ctx = SimpleNamespace(
        arch=stub.config["arch"], config=stub.config,
        peaks=wc.cell_peaks(tcb.PEAKS, 1),
        modules=[("jit_step(1)", 0, 10 ** 9)],
        work=SimpleNamespace(steps=[[3, 4], [5, 6, 7]], prefills=[]))
    mfu = run.load_reader("decode.mfu")(ctx)
    assert mfu == pytest.approx(100 * 5 * fam.FLOPS / tcb.PEAKS[
        "bf16_flops_per_s"])


# ---------------------------------------------------------------------------
# a chip's share of the work
# ---------------------------------------------------------------------------


def test_cell_peaks_give_chip_zeros_share():
    """Against the peaks of four chips, the whole cell's count over chip
    0's time is chip 0's quarter of the count over that time at one chip's
    peaks; at one chip nothing changes."""
    p1, p4 = wc.cell_peaks(tcb.PEAKS, 1), wc.cell_peaks(tcb.PEAKS, 4)
    assert p1 == tcb.PEAKS
    assert p4["bf16_flops_per_s"] == 4 * tcb.PEAKS["bf16_flops_per_s"]
    assert p4["hbm_bytes_per_s"] == 4 * tcb.PEAKS["hbm_bytes_per_s"]
    arch, pos = tcb.ARCH, [3, 9]
    whole = DENSE.decode_attn_least_s(arch, pos, p4, "bfloat16")
    assert whole == pytest.approx(
        DENSE.decode_attn_least_s(arch, pos, tcb.PEAKS, "bfloat16") / 4)
    assert wc.gemm_least_s(16, 512, 64, p4, "int8") == pytest.approx(
        wc.gemm_least_s(16, 512, 64, tcb.PEAKS, "int8") / 4)


# ---------------------------------------------------------------------------
# the collectives' share of the step
# ---------------------------------------------------------------------------

CUT = HERE / "testdata" / "trace_tp4_cut.json"


def test_collective_share_on_recorded_tp4_trace():
    """Two engine ticks of chip 0 cut from a ``--trace 1`` run of
    ``mistral-nemo-12b-bf16.chat-tp4``: the pattern finds the step's
    collectives by the names the chip gives them, two all-reduces a layer
    over 40 layers plus one, and two all-gathers, in each of the two
    steps."""
    import re

    rec = json.loads(CUT.read_text())
    ctx = SimpleNamespace(modules=[tuple(e) for e in rec["modules"]],
                          ops=[tuple(e) for e in rec["ops"]])
    read = run.load_reader("decode.collective_share")
    coll = re.compile(r"^(all-reduce|all-gather|reduce-scatter)\b")
    names = [n for n, _, _ in ctx.ops if coll.match(n)]
    assert sum(n.startswith("all-reduce.") for n in names) == 2 * (2 * 40 + 1)
    assert sum(n.startswith("all-gather.") for n in names) == 2 * 2
    assert read(ctx) == pytest.approx(5.903249865759175, rel=1e-12)


def test_collective_share_pattern():
    """Collectives by op name, their async halves included, and only inside
    the step's executables; other ops and other executables do not count."""
    read = run.load_reader("decode.collective_share")
    ctx = SimpleNamespace(
        modules=[("jit_step(7)", 0, 100), ("jit__prefill_batch_impl(3)",
                                           200, 300),
                 ("jit_step(7)", 400, 500)],
        ops=[("while.1", 0, 100), ("all-reduce.5", 10, 20),
             ("all-reduce-start.2", 30, 31), ("all-reduce-done.2", 31, 40),
             ("all-gather.3", 410, 415), ("reduce-scatter.1", 420, 425),
             ("all-reduce.1", 250, 260), ("fusion.2", 40, 60),
             ("my-all-reduce-like.4", 60, 70)])
    assert read(ctx) == pytest.approx(100 * (10 + 1 + 9 + 5 + 5) / 200)
    ctx.ops = [op for op in ctx.ops if not op[0].startswith(("all", "red"))]
    assert read(ctx) is None


# ---------------------------------------------------------------------------
# a tiny dense cell on a four-device host mesh
# ---------------------------------------------------------------------------


def _cell(chips):
    cell = tcb.tiny_cell("exact")
    cell.chips = chips
    return cell


def _serve(cell, seed):
    """The cell's engine from ``build``, a fixed set of requests served to
    the end, and the engine freed: the records ``compare`` reads."""
    import arrivals
    import serveloop

    eng = run.build(cell, seed, parts={})
    recs = [serveloop.Record(due=q.due_s, prompt=q.prompt, out_len=q.out_len)
            for q in arrivals.schedule(cell.traffic, cell.rate_rps, 2.0, seed,
                                       cell.config["arch"]["vocab"])]
    for r in recs:
        r.handle = eng.submit(r.prompt, r.out_len)
    eng.run_until_drained()
    run.free(eng)
    return recs


def _exchange_left_out():
    """Each row-parallel product (``wo``, ``down``) reduced over the first
    quarter of its contraction only: chip 0's partial sum, used as if the
    all-reduce between the chips had run."""
    import jax.numpy as jnp

    from repro.models import layers

    dense = layers.dense_apply

    def partial(p, x, policy, path, *a, **k):
        if path.endswith(("/wo", "/down")):
            keep = jnp.arange(x.shape[-1]) < x.shape[-1] // 4
            x = jnp.where(keep, x, jnp.zeros_like(x))
        return dense(p, x, policy, path, *a, **k)

    layers.dense_apply = partial


def tp4_child() -> dict:
    """Runs in a process with four host devices (``tp4``)."""
    import repro.serve.engine  # noqa: F401  (serve.lm imports through it)

    one, four = _cell(1), _cell(4)
    out = {"devices": len(jax.devices())}
    recs1 = _serve(one, SEED)
    out["one"] = run.compare(one, SEED, recs1)
    out["one_on_mesh"] = run.compare(four, SEED, recs1)
    recs4 = _serve(four, SEED)
    out["four"] = run.compare(four, SEED, recs4)
    res = run.run_cell(four, SEED + 1, 2.0, False)
    out["run"] = {k: res[k] for k in ("correct", "checks", "device")}
    res = run.run_cell(four, SEED + 1, 2.0, False, approx="axq8")
    out["control"] = {k: res[k] for k in ("correct", "checks")}
    _exchange_left_out()
    res = run.run_cell(four, SEED + 1, 2.0, False)
    out["no_exchange"] = {k: res[k] for k in ("correct", "checks")}
    return out


@pytest.fixture(scope="module")
def tp4():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = (f"import sys, json; sys.path.insert(0, {str(HERE)!r}); "
            "import test_families as t; print(json.dumps(t.tp4_child()))")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=HERE,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tp4_runs_on_four_devices(tp4):
    assert tp4["devices"] == 4
    assert tp4["run"]["device"]["count"] == 4
    assert tp4["run"]["correct"], tp4["run"]["checks"]
    assert tp4["four"]["tokens_compared"] == tp4["one"]["tokens_compared"]
    assert tp4["four"]["request_mean_logit_gap"] < \
        tcb.TINY["exact"][1]


def test_tp4_reference_matches_one_device(tp4):
    """The reference over the mesh reads the one-device engine's tokens as
    the one-device reference does, to float32 rounding."""
    a, b = tp4["one"], tp4["one_on_mesh"]
    assert a["tokens_compared"] == b["tokens_compared"]
    for k in ("max_logit_gap", "mean_logit_gap", "request_mean_logit_gap"):
        assert b[k] == pytest.approx(a[k], rel=1e-3, abs=1e-5), k


def test_tp4_control_fails(tp4):
    assert not tp4["control"]["correct"], tp4["control"]["checks"]


def test_tp4_exchange_left_out_fails(tp4):
    assert not tp4["no_exchange"]["correct"], tp4["no_exchange"]["checks"]
    assert tp4["no_exchange"]["checks"]["request_mean_logit_gap"][
        "value"] > 10 * tcb.TINY["exact"][1]
