"""Where the benchmark finds what a cell is made of.

A cell ``<config>.<traffic>`` is an entry of ``BENCHMARK.json``.  Everything
else is found by name, one file each, so that a new configuration, traffic
mix, cell or per-layer metric is a new file and never an edit:

  configs/<config>.json    sizes as run, source, reduced, assumed
  traffic/<traffic>.json   arrival and length parameters, engine slots,
                           cache length, prefill bucket ladder and packing
  cells/<cell>.json        the cell's offered rate and its correctness limit
  families/<family>.py     what depends on the model family, found by the
                           configuration's ``arch.family`` (see below)
  metrics/<metric>.py      the reader of one per-layer metric
  peaks.json               the chip's published peaks, by device kind

A family module provides, for configurations that name it:

  make(arch, seed, mesh)   the weights in the family's own layout, drawn
                           from the seed in one jitted call, each leaf
                           placed on ``mesh`` as the program places it
                           (``weights.placed``)
  to_program(w, arch)      the same arrays in the program's parameter tree
  gaps(w, arch, tokens, targets), padded_len(n)
                           the plain float32 reference: per position, its
                           best logit minus its logit of the served token,
                           under ``highest`` matmul precision, with no
                           kernels, cache or batching
  projections, attended, prefill_flops, decode_flops, axq_call_least_s,
  decode_attn_least_s      the work counts of the whole cell
                           (``workcount.py``)

The cell's ``chips`` in ``BENCHMARK.json`` sets the mesh, ``(1, chips)``
over ``("data", "model")``, and the engine: ``ServeEngine`` on one chip,
``ShardedServeEngine`` with exact collectives over the ``model`` axis on
more.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: where ``family`` looks for ``<family>.py``, in order
FAMILY_DIRS = (HERE / "families",)
_FAMILIES: dict = {}


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    cell: dict            # cells/<cell>.json: rate_rps, limits
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def rate_rps(self) -> float:
        return float(self.cell["rate_rps"])

    @property
    def family(self):
        return family(self.config["arch"]["family"])


def family(name: str):
    """The module of the model family ``name`` (a configuration's
    ``arch.family``): ``<name>.py`` in the first of FAMILY_DIRS that holds
    one, loaded once."""
    path = next((d / f"{name}.py" for d in FAMILY_DIRS
                 if (d / f"{name}.py").is_file()), None)
    if path is None:
        raise KeyError(f"no family module {name}.py in "
                       f"{[str(d) for d in FAMILY_DIRS]}")
    mod = _FAMILIES.get(path)
    if mod is None:
        mod_spec = importlib.util.spec_from_file_location(f"family_{name}",
                                                          path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return mod


def _reported(metrics: list, cell: str, e2e_names=None) -> list:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m["moves"] in e2e_names:
            out.append(m)
    return out


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"unknown workload {name!r}; known: {known}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = _reported(bench["end_to_end"], name)
    per_layer = _reported(bench["per_layer"], name,
                          {m["name"] for m in e2e})
    return Cell(name=name, chips=int(entry["chips"]),
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
                cell=load_json(HERE / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
