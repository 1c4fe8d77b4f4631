"""Run one cell several times, each in a fresh process as the check runs
it, and report each metric's median and spread (interquartile distance
over the median) per set.

    python chipbench/sets.py --workload <cell> --seconds 20 \
        --sets 2 --seeds 11 12 13 14 15 16 [--trace 0] [--out runs.jsonl]

Every set uses the same seeds, in the same order. Each run's result line
is appended to ``--out``; the summary goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    results = []
    for k in range(args.sets):
        for seed in args.seeds:
            cmd = [sys.executable, str(RUN), "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(args.seconds),
                   "--trace", str(args.trace)]
            p = subprocess.run(cmd, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            line = {"set": k, "seed": seed, "rc": p.returncode}
            if p.returncode == 0 and lines:
                line.update(json.loads(lines[-1]))
            line["stderr"] = p.stderr[-3000:]
            results.append(line)
            print(json.dumps({k: v for k, v in line.items()
                              if k != "stderr"}), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    for k in range(args.sets):
        runs = [r for r in results if r["set"] == k and "metrics" in r]
        names = sorted({m for r in runs for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in runs
                    if m in r["metrics"]]
            if len(vals) >= 2:
                print(f"set {k} {m}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals) if len(vals) >= 3 else None!r} "
                      f"values {vals!r}")
        print(f"set {k}: correct {sum(r['correct'] for r in runs)} of "
              f"{len(runs)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
