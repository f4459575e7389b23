"""Many runs of one cell in one process: the readings behind a cell's
correctness limit, and the sweep that finds its knee.

    # the program's readings over a dozen seeds, short windows
    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 15
    # the control: the program's next precision down in its place
    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 15 --approx axq4
    # the knee: one seed at several offered rates
    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1 \
        --seconds 30 --rates 0.5,1,1.5,2

Each run prints one line ``{"reading": {...}}`` with the seed, the rate,
the path, the compared numbers and the end-to-end metrics.  Needs a TPU,
like ``run.py``; the benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--approx", default=None,
                    help="serve under this policy instead of the "
                         "configuration's (exact | axqN)")
    ap.add_argument("--rates", default=None,
                    help="comma list of offered rates in req/s (default: "
                         "the cell's)")
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    run.enable_cache()
    dev = run.device_info()
    if dev["platform"] != "tpu":
        print(f"readings: needs a TPU, found {dev['platform']}",
              file=sys.stderr)
        return 3
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [None])
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.time()
            res = run.run_cell(cell, seed, args.seconds, False,
                               approx=args.approx, rate_rps=rate,
                               t_start=t)
            print(json.dumps({"reading": {
                "seed": seed, "rate_rps": rate or cell.rate_rps,
                "approx": args.approx or cell.config["approx"],
                "correct": res["correct"], "attempted": res["attempted"],
                "failed": res["failed"],
                "checks": {k: v["value"] for k, v in res["checks"].items()},
                "compared": res["compared"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "seconds": time.time() - t}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
