"""Run one cell of BENCHMARK.json once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on, which must hold a TPU with as many
chips as the cell asks for; otherwise it exits 2 and prints no result.
The last line of standard output is one JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error and the result's last key.  Exits 1 when the comparison fails.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from harness import cell, device
    try:
        result = cell.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except device.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
