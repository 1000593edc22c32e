"""Run a cell several times, one process after another, and report each
metric's median and spread.

    python bench/sets.py --workload <cell> --seconds 50 --seeds 1 2 3 4 5 6 \
        [--trace 1] [--out chiprun_out/<cell>.jsonl]

Each run is ``python3 bench/run.py`` as the driver starts it; this parent
never touches JAX, so every run holds the chip alone.  The spread of a
metric is the distance between its first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of its median.  Each
run's result line goes to ``--out`` with its seed, exit code, wall time and
the end of its standard error.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values):
    """(median, IQR / median) of ``values``."""
    med = statistics.median(values)
    if len(values) < 2 or med == 0:
        return med, 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / abs(med)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = open(args.out, "a") if args.out else None
    values = {}
    try:
        for seed in args.seeds:
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload, "--seed",
                 str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = proc.stdout.strip().splitlines()
            result = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
            rec = {"workload": args.workload, "seed": seed, "trace": args.trace,
                   "rc": proc.returncode, "wall_s": wall, "result": result,
                   "stderr_tail": proc.stderr[-3000:]}
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
            if result is None:
                print(f"seed {seed}: rc={proc.returncode}\n{proc.stderr[-3000:]}", flush=True)
                continue
            for name, m in result["metrics"].items():
                values.setdefault(name, []).append(m["value"])
            print(f"seed {seed}: rc=0 wall={wall:.1f}s correct={result['correct']} "
                  f"attempted={result['attempted']} failed={result['failed']} "
                  + " ".join(f"{k}={v['value']!r}" for k, v in result["metrics"].items())
                  + f" mem={result['device']['memory_peak_bytes']}", flush=True)
    finally:
        if out:
            out.close()
    for name, vals in values.items():
        med, sp = spread(vals)
        print(f"{args.workload} {name}: n={len(vals)} median={med!r} spread={sp!r}")


if __name__ == "__main__":
    main()
