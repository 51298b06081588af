"""Sets of runs of one cell, as the bounds are set from them.

    python3 chipbench/tests/sets.py --workload <name> --seeds 1,2,3,4,5,6 \
        --sets 2 --seconds 30 [--trace-seeds 7,8,9] --out <dir>

Runs `chipbench/run.py` once per seed in each set, then once with
`--trace 1` per trace seed, each in a process of its own and one after
another (a chip belongs to one process at a time; this one never touches
JAX).  Every run's output goes to <dir>/<set>.<seed>.{out,err}.  Then it
prints, for each end-to-end metric and set, the median and the spread (first
to third quartile over the median, `statistics.quantiles(n=4)`), five times
the widest spread, and each run's compared numbers beside their limits.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run(workload, seed, seconds, trace, out, root=ROOT):
    t = time.perf_counter()
    with open(out + ".out", "w") as so, open(out + ".err", "w") as se:
        rc = subprocess.call(
            [sys.executable, os.path.join(root, "chipbench", "run.py"),
             "--workload", workload, "--seed", str(seed),
             "--seconds", str(seconds), "--trace", str(int(trace))],
            cwd=root, stdout=so, stderr=se)
    try:
        with open(out + ".out") as f:
            result = json.loads(f.read().strip().splitlines()[-1])
    except (IndexError, ValueError):
        result = None
    print(f"== {os.path.basename(out)} rc={rc} "
          f"wall={time.perf_counter() - t:.1f}", flush=True)
    if result:
        print(json.dumps({k: result[k] for k in (
            "correct", "metrics", "device", "step_s", "checks")
            if k in result}), flush=True)
    return result


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summary(sets):
    names = sorted({k for rows in sets.values() for r in rows
                    for k in r["metrics"]})
    for n in names:
        widest = 0.0
        for s, rows in sets.items():
            v = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            if len(v) < 2:
                continue
            widest = max(widest, spread(v))
            print(f"{n} set {s}: median {statistics.median(v)!r} "
                  f"spread {spread(v):.4%} values {v}")
        print(f"{n}: five times the widest spread {5 * widest:.4%}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets = {}
    for s in range(1, args.sets + 1) if seeds else ():
        sets[s] = [r for r in (
            run(args.workload, seed, args.seconds, False,
                os.path.join(args.out, f"{s}.{seed}")) for seed in seeds)
            if r]
    for seed in [int(s) for s in args.trace_seeds.split(",") if s]:
        run(args.workload, seed, args.seconds, True,
            os.path.join(args.out, f"trace.{seed}"))
    summary(sets)


if __name__ == "__main__":
    main()
