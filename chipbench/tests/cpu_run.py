"""One run of a cell of a benchmark tree on the CPU, in a process of its own.

    python3 chipbench/tests/cpu_run.py --root <tree> --workload <name> \
        --seed <n> --seconds <s>

The tree's own `chipbench/` comes first on the path, so that a module the
tree adds, such as a family's reference, is found there; the program is
this checkout's `src/`.  Set XLA_FLAGS=--xla_force_host_platform_device_count
before the start to give the CPU backend the devices a cell asks for.
Prints one JSON line: the result line's fields under `result`, the
reference's readings under `reference`, the cell's `flops_per_step`, how
the largest leaf of the step's parameters and of the reference's first
gradient lie over the devices (`largest_param`,
`reference_largest_grad`), and, where the cell has more than one device,
the reference's readings on the first of them alone
(`reference_one_device`).
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def largest(tree) -> dict:
    """The shape of the largest leaf of `tree` and of each of its
    addressable shards."""
    import jax
    leaf = max(jax.tree.leaves(tree), key=lambda x: x.size)
    return {"shape": list(leaf.shape),
            "shards": [list(s.data.shape) for s in leaf.addressable_shards]}


def record_first_trees(into: dict):
    """Describe into `into` the largest leaf of the parameters of the first
    state a compiled program is called with, under `largest_param`, and of
    the first tree the reference takes norms of, its first gradient, under
    `reference_largest_grad`."""
    import jax
    from reference import train as rtrain
    call, norms = jax.stages.Compiled.__call__, rtrain.leaf_norms

    def recording_call(self, *args, **kwargs):
        state = args[0] if args else None
        if isinstance(state, dict) and "params" in state:
            into.setdefault("largest_param", largest(state["params"]))
        return call(self, *args, **kwargs)

    def recording_norms(tree):
        into.setdefault("reference_largest_grad", largest(tree))
        return norms(tree)

    jax.stages.Compiled.__call__ = recording_call
    rtrain.leaf_norms = recording_norms


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(args.root, "chipbench"),
                    os.path.join(ROOT, "src")]
    import jax
    # compiled programs of the CPU tests stay out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    from harness import cell, device, flops, spec
    from reference import train as rtrain

    seen, detail = {}, {}
    record_first_trees(seen)
    r = cell.run(args.root, args.workload, args.seed, args.seconds, False,
                 time.perf_counter(), allow_cpu=True, detail=detail)
    bench = spec.Bench(args.root)
    w = bench.workload(args.workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    out = {"result": r, "reference": detail["reference"],
           "flops_per_step": flops.train_step_flops(
               config, traffic["batch"], traffic["seq_len"]), **seen}
    devices = device.require(w["chips"], allow_cpu=True)
    if len(devices) > 1:
        limits = bench.limits(args.workload)
        out["reference_one_device"] = rtrain.readings(
            config, traffic, args.seed, limits["check_steps"], devices[:1],
            rows=limits["reference_rows"])
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
