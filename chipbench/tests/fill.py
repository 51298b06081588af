"""A training cell at larger batches, to find the batch that fills the chip.

    python3 chipbench/tests/fill.py --workload <name> --batches 16,32 \
        --seed <n> --seconds 10 --out <dir>

For each batch, a copy of the benchmark in a temporary directory whose
cell's traffic has that batch (its `src` a link to this checkout's), and
one run of the cell there in a process of its own, so that each reads its
own `memory_peak_bytes`; the run's output goes to <dir>/b<batch>.{out,err}.
Prints each run's tokens/s, memory and checks.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

from sets import ROOT, run


def variant(dest: str, workload: str, batch: int) -> None:
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        w = next(x for x in json.load(f)["workloads"]
                 if x["name"] == workload)
    path = os.path.join(dest, "chipbench", "traffic", w["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["batch"] = batch
    with open(path, "w") as f:
        json.dump(traffic, f)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    for b in [int(x) for x in args.batches.split(",")]:
        dest = tempfile.mkdtemp(prefix=f"fill-b{b}-")
        try:
            variant(dest, args.workload, b)
            run(args.workload, args.seed, args.seconds, False,
                os.path.join(out, f"b{b}"), root=dest)
        finally:
            shutil.rmtree(dest, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
