"""Readings that set the limits of `correct`.

    python3 chipbench/tests/control.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds <s> [--keep-trace <file>]

On the chip, at the cell's own size and in one process: for every seed a
run of the cell, whose numbers are the program's readings against the
float32 reference; for each control seed the control, the reference
computed with float8 matmuls put in the program's place, and for training
also the half-batch fault planted in the reference, each read by the same
comparison, and judged against the cell's committed limits as a run's
numbers are (`control_correct`, `half_batch_correct`).  One JSON line per
seed.  test_control.py runs it on the CPU at a tiny size.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def readings(root, workload, seeds, control_seeds, seconds,
             allow_cpu=False, keep_trace=None):
    from harness import cell, correct, device, spec
    from reference import train as rtrain
    bench = spec.Bench(root)
    w = bench.workload(workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    limits = bench.limits(workload)
    devices = device.require(w["chips"], allow_cpu)
    rows = []
    for i, seed in enumerate(seeds):
        detail, t = {}, time.perf_counter()
        trace = keep_trace is not None and i == 0
        r = cell.run(root, workload, seed, seconds, trace, t, allow_cpu,
                     keep_trace=keep_trace if trace else None, detail=detail)
        row = {"seed": seed, "correct": r["correct"],
               "program": {k: c["value"] for k, c in r["checks"].items()},
               "metrics": {k: m["value"] for k, m in r["metrics"].items()},
               "memory_peak_bytes": r["device"]["memory_peak_bytes"],
               "run_s": time.perf_counter() - t}
        if seed in control_seeds:
            t = time.perf_counter()
            if traffic["kind"] == "train":
                ref = detail["reference"]
                args = (config, traffic, seed, limits["check_steps"],
                        devices)
                rows_ = limits["reference_rows"]
                row["control"] = correct.train(
                    rtrain.readings(*args, precision="fp8", rows=rows_), ref)
                row["half_batch"] = correct.train(
                    rtrain.readings(*args, rows=rows_, half_batch=True), ref)
            else:
                row["control"] = correct.serve(
                    config, traffic, seed, detail["program"]["done"],
                    limits["sample_requests"], control=True)
                row["control"].pop("served_tokens")
            for k in ("control", "half_batch"):
                if k in row:
                    row[k + "_correct"] = cell.judge(
                        cell.compare(limits, row[k]))
            row["control_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace")
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    readings(ROOT, args.workload, seeds, control, args.seconds,
             keep_trace=args.keep_trace)


if __name__ == "__main__":
    main()
