"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py); every
module also writes structured JSON to ``benchmarks/out/<name>.json``.

  attn_latency     Figure 5(a)/(c)  attention-module latency vs length
  ttft             Figure 5(b)/(d)  end-to-end time-to-first-token
  decode_latency   Figure 6         decode-step latency vs cache length
  accuracy_proxy   Tables 1 & 3     RULER/LongBench attention-level proxies
  niah             Figure 4         scratch-trained needle retrieval
  ablations        Tables 9-12      scoring / aggregation / B_CP / N_Q
  complexity       Table 4          analytic + measured scoring complexity
  roofline_table   EXPERIMENTS §Roofline (from dry-run artifacts)
  serving_throughput  §4.6 under load: continuous batching vs one-at-a-time
                      + the prefix_reuse (cache-hit TTFT) scenario

Suites bundle related benchmarks:

  --suite serving  serving_throughput (throughput + prefix_reuse) + ttft —
                   the set the CI regression gate checks
                   (benchmarks/check_regression.py); combine with --smoke
                   for the fast-tier geometry.
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback

SUITES = {
    "serving": ("serving_throughput", "ttft"),
}

TRAJECTORY = os.path.join(os.path.dirname(__file__), "out",
                          "BENCH_trajectory.json")


def _append_trajectory(ran, failures) -> None:
    """Append one compact record per driver run to BENCH_trajectory.json
    (a list; benchmarks/out/ is gitignored — full out/*.json dumps are NOT
    committed, CI uploads the whole directory as the perf-trajectory
    artifact instead).  The record keeps the machine-readable headline —
    every emitted metric's name -> us_per_call — plus enough provenance
    (time, commit, argv) to line trajectories up across PRs."""
    from benchmarks.common import RECORDS
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except Exception:
        commit = ""
    rec = {
        "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "commit": commit or None,
        "argv": sys.argv[1:],
        "ran": sorted(ran),
        "failed": sorted(failures),
        "metrics": {r["name"]: r["us_per_call"] for r in RECORDS},
    }
    os.makedirs(os.path.dirname(TRAJECTORY), exist_ok=True)
    history = []
    if os.path.exists(TRAJECTORY):
        try:
            with open(TRAJECTORY) as f:
                history = json.load(f)
            assert isinstance(history, list)
        except Exception:
            history = []        # corrupt file: restart, don't crash the run
    history.append(rec)
    with open(TRAJECTORY, "w") as f:
        json.dump(history, f, indent=1)
    print(f"# appended run record -> {TRAJECTORY} "
          f"({len(history)} runs)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="named benchmark bundle (e.g. 'serving' runs "
                         "throughput + ttft + prefix_reuse in one go)")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow trained-model NIAH benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometries for the fast CI tier (benchmarks "
                         "that support it)")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (ablations, accuracy_proxy, attn_latency,
                            complexity, decode_latency, niah, roofline_table,
                            serving_throughput, ttft)
    smoke = {"smoke": True} if args.smoke else {}
    todo = {
        "attn_latency": lambda: attn_latency.run(**smoke),
        "ttft": lambda: ttft.run(**smoke),
        "decode_latency": decode_latency.run,
        "accuracy_proxy": accuracy_proxy.run,
        "ablations": ablations.run,
        "complexity": complexity.run,
        "niah": niah.run,
        "roofline_table": roofline_table.run,
        "serving_throughput": lambda: serving_throughput.run(**smoke),
    }
    if args.fast:
        todo.pop("niah")
    keep = set()
    if args.suite:
        keep |= set(SUITES[args.suite])
    if args.only:
        keep |= set(args.only.split(","))
    if keep:
        todo = {k: v for k, v in todo.items() if k in keep}
    print("name,us_per_call,derived")
    failures = []
    for name, fn in todo.items():
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    _append_trajectory(todo.keys(), failures)
    if failures:
        print(f"FAILED benchmarks: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
