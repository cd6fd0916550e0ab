#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, in one process on the chip.

    python3 bench/control.py --workload qwen3-4b.long_prefill \\
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 11,12,13 \\
        --faults stale_decode,half_batch --fault-seeds 31,32,33 \\
        --seconds 51 --out chiprun_out/limits_qwen3-4b.long_prefill.json

For each seed: one run of the cell (``run.run_cell``, at the cell's own
load and window, the same sample of finished requests a run compares) and
its mean logit gap against the plain reference: the program's reading.
For the control seeds also the control's: at the same positions of the
same prompts and served tokens, the mean gap of the token that the
reference computed in float8 puts first, judged by the run's own
comparison.  For each of ``--faults`` (``bench/faults.py``) one run per
fault seed with that fault planted in the timed path: the fault's reading.
Set-up is paid once; the benchmark's own runs never run the control.

``--write-limit`` then writes ``bench/limits/<cell>.json`` from two
readings of the mean gap: the lower is the largest program reading; the
upper the smallest of the control's readings and of the readings of each
fault whose smallest reads three times the lower or more (a fault that
reads less is left to another number).  The limit lies between them at
``lower**(1/3) * upper**(2/3)``, more room above the lower, since fresh
seeds read higher than a dozen did.  Where the control does not read three
times the lower no limit is written: the comparison cannot tell the
program from the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import faults  # noqa: E402
import run  # noqa: E402
import spec as spec_mod  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def limit_from(rows) -> dict:
    """The two readings and the limit between them (``limit`` None where
    the control does not read three times the program's largest)."""
    prog = max(r["program_gap_mean"] for r in rows if r["fault"] is None)
    control = [r["control_gap_mean"] for r in rows
               if r["control_gap_mean"] is not None]
    out = {"lower_reading": prog,
           "control_reading": min(control) if control else None,
           "fault_readings": {}, "seeds": sum(r["fault"] is None
                                              for r in rows),
           "control_seeds": len(control), "limit": None}
    for f in sorted({r["fault"] for r in rows if r["fault"]}):
        out["fault_readings"][f] = min(r["program_gap_mean"] for r in rows
                                       if r["fault"] == f)
    if not control or min(control) < 3 * prog:
        out["upper_reading"] = out["control_reading"]
        return out
    ups = [("control", min(control))] + [
        (f, v) for f, v in out["fault_readings"].items() if v >= 3 * prog]
    out["upper_from"], out["upper_reading"] = min(ups, key=lambda u: u[1])
    out["limit"] = prog ** (1 / 3) * out["upper_reading"] ** (2 / 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help=f"comma-separated, of {sorted(faults.FAULTS)}")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limit", action="store_true")
    args = ap.parse_args()
    planted = [f for f in args.faults.split(",") if f]
    for f in planted:
        if f not in faults.FAULTS:
            ap.error(f"no fault {f!r}; known: {sorted(faults.FAULTS)}")

    import jax
    cell = spec_mod.load_cell(args.workload)
    found, why = run.chip(cell, jax.devices())
    if found is None:
        run.log(f"bench/control.py: {why}")
        return 2
    dev, peaks = found
    run.enable_cache()
    ctrl = set(_seeds(args.control_seeds))
    plan = [(s, None) for s in _seeds(args.seeds)] + [
        (s, f) for f in planted for s in _seeds(args.fault_seeds)]
    rows = []
    for s, f in plan:
        t0 = time.perf_counter()
        res = run.run_cell(cell, s, args.seconds, False, peaks=peaks,
                           t_start=t0, control=f is None and s in ctrl,
                           device=dev,
                           fault=faults.FAULTS[f] if f else None)
        gaps = res["gaps"]
        row = {"seed": s, "fault": f,
               "program_gap_mean": float(gaps.mean()),
               "tokens": int(gaps.size), "correct": res["line"]["correct"],
               "control_gap_mean": res["diag"].get("control_gap_mean"),
               "control_correct": res["diag"].get("control_correct"),
               **{k: v for k, v in res["diag"].items()
                  if k.startswith(("logit_gap", "control_gap"))},
               "requests": res["diag"]["compared_requests"],
               "setup_s": res["e2e"]["setup_s"],
               "failed": res["line"]["failed"],
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        run.log(json.dumps(row))
        with open(args.out, "w") as fh:
            json.dump({"workload": cell.name, "seconds": args.seconds,
                       "rows": rows}, fh, indent=1)
    summary = limit_from(rows)
    print(json.dumps(summary), flush=True)
    if args.write_limit:
        if summary["limit"] is None:
            run.log("bench/control.py: the control does not read three "
                    "times the program's reading; no limit written")
            return 1
        path = HERE / "limits" / f"{cell.name}.json"
        path.parent.mkdir(exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"logit_gap_mean": summary}, fh, indent=1)
            fh.write("\n")
        run.log(f"wrote {path}: {json.dumps(summary)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
