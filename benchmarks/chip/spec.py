"""Where the benchmark finds what a cell is made of.

A cell ``<config>.<traffic>`` is an entry of ``BENCHMARK.json``.  Everything
else is found by name, one file each, so that a new configuration, traffic
mix, cell or per-layer metric is a new file and never an edit:

  configs/<config>.json    sizes as run, source, reduced, assumed
  traffic/<traffic>.json   arrival and length parameters, engine slots,
                           cache length, prefill bucket ladder and packing
  cells/<cell>.json        the cell's offered rate and its correctness limit
  metrics/<metric>.py      the reader of one per-layer metric
  peaks.json               the chip's published peaks, by device kind
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


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
