#!/usr/bin/env python3
"""Offered-load sweep of a cell, in one process on the chip: where is the
knee, the highest rate whose backlog does not grow through the window?

    python3 bench/sweep.py --workload qwen3-4b.long_prefill \\
        --rates 0.2,0.3,0.4 --seconds 51 --windows 2 --seed 5

Each rate runs the cell's window ``--windows`` times, on seeds ``--seed``,
``--seed + 1``, ... (no reference check), and prints the rate with its TTFT
p50 in each half of the window, the requests still without a first token
when the window closed, and the end-to-end metrics.
A growing backlog shows as a second-half TTFT well above the first half's.
"""
from __future__ import annotations

import time

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import spec as spec_mod  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--drain", type=float, default=10.0,
                    help="seconds to drain after the window (TTFT of what "
                         "is left counts its wait so far)")
    args = ap.parse_args()

    import jax
    cell = spec_mod.load_cell(args.workload)
    cell.traffic = dict(cell.traffic, drain_s=min(
        args.drain, float(cell.traffic.get("drain_s", 0))))
    found, why = run.chip(cell, jax.devices())
    if found is None:
        run.log(f"bench/sweep.py: {why}")
        return 2
    dev, peaks = found
    run.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        for w in range(args.windows):
            res = run.run_cell(cell, args.seed + w, args.seconds, False,
                               peaks=peaks, t_start=time.perf_counter(),
                               rate=rate, device=dev, check=False)
            print(json.dumps({"rate": rate, "window": w, "e2e": res["e2e"],
                              "diag": res["diag"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
