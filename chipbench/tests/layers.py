"""Where a training cell's device time goes, by the model's named scopes.

    python3 chipbench/tests/layers.py --workload <name> --seeds 1,2,3 \
        --seconds 30 [--trace-seconds <s>] [--tiny] [--keep <dir>]

On the chip, in one process: for each seed, the cell's training harness
(`harness/train.py`) with its traced window, as a `--trace 1` run drives
it, under `repro.launch.mesh.compile_work`, so that the compile work of
set-up is counted.  The compiled step's text is kept as the harness
compiles it (the largest program compiled in the run), and the window's
trace is read through it (`harness/scopes.py`).  One JSON line per seed:
`step_device_ms.train`, device ms per step of each scope and kernel
scope, the unscoped and unmatched shares of busy time, the compile work
before the window, and the seconds the reduction after the window took.
No reference is computed: `correct` is control.py's business.

`--tiny` runs the tiny cells of `tiny.py`; `--trace-seconds` shortens the
traced window; `--keep <dir>` writes each seed's trace and the step's
`program_ops` there, gzipped, as the recorded fixtures of test_scopes.py
were written.
"""

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def keep_compiled():
    """Every program compiled from here on, appended to the list returned."""
    import jax
    kept, compile_ = [], jax.stages.Lowered.compile

    def compile_and_keep(self, *args, **kwargs):
        kept.append(compile_(self, *args, **kwargs))
        return kept[-1]

    jax.stages.Lowered.compile = compile_and_keep
    return kept


def layers(root, workload, seed, seconds, kept, trace_seconds=None,
           keep=None, allow_cpu=False):
    """One seed's reading; `kept` is keep_compiled()'s list."""
    from harness import cell, device, scopes, spec
    from harness import trace as tracemod
    from harness import train
    from harness.peaks import PEAKS
    from repro.launch.mesh import compile_work, use_compile_cache
    from repro.models.scopes import KERNELS, program_ops, top_scope
    import jax

    bench = spec.Bench(root)
    w = bench.workload(workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    if trace_seconds is not None:
        traffic = dict(traffic, trace_seconds=trace_seconds)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = device.require(w["chips"], allow_cpu)
    kept.clear()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-layers-")
    try:
        ctx = cell.Context(config, traffic, bench.limits(workload), seed,
                           seconds, time.perf_counter(), trace_dir, None,
                           devices)
        with compile_work() as work:
            out = train.run(ctx)
        t = time.perf_counter()
        step = max(kept, key=lambda c: len(c.as_text()))
        program = program_ops(step.as_text())
        path = tracemod.find(trace_dir)
        times = scopes.op_times(path, program)
        reduce_s = time.perf_counter() - t
        summary = tracemod.reduce(path)
        if keep:
            os.makedirs(keep, exist_ok=True)
            stem = os.path.join(keep, f"{workload}.{seed}")
            with open(path, "rb") as f, gzip.open(stem + ".xplane.pb.gz",
                                                  "wb") as g:
                shutil.copyfileobj(f, g)
            with gzip.open(stem + ".program.json.gz", "wt") as g:
                json.dump(program, g)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    n = out["counters"]["steps_traced"]
    reading = cell.Reading("train", config, traffic, summary, out["counters"],
                           PEAKS.get(devices[0].device_kind), len(devices))
    metrics = {m["name"]: bench.reader(m["name"])(reading)
               for m in bench.per_layer(workload)}
    per_step = {k: 1e3 * s / n for k, s in scopes.by_scope(times).items()}
    kernels = {k: 1e3 * s / n for k, s in scopes.by_kernel(times).items()}
    busy = sum(per_step.values())
    top = sorted(((s, k) for k, s in times.items() if top_scope(k) is None),
                 reverse=True)

    def share(part, whole):         # None off a TPU, where nothing is busy
        return part / whole if whole else None

    return {
        "workload": workload, "seed": seed, "steps_traced": n,
        "metrics": metrics,
        "scope_ms": per_step, "kernel_ms": {k: kernels[k] for k in KERNELS},
        "scoped_plus_unscoped_over_busy": share(1e-3 * busy * n,
                                                summary.busy_s),
        "unscoped_share": share(100 * per_step[scopes.UNSCOPED], busy),
        "unmatched_share": share(100 * per_step[scopes.UNMATCHED], busy),
        "top_unscoped_ms": [[k, 1e3 * s / n] for s, k in top[:8]],
        "setup_s": out["e2e"]["setup_s"], "setup_compile": work,
        "reduce_s": reduce_s, "memory_peak_bytes": out["memory_peak"],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--keep")
    args = ap.parse_args()
    sys.path[:0] = [HERE, BENCH, os.path.join(ROOT, "src")]
    root = ROOT
    if args.tiny:
        import tiny
        root = tiny.make(tempfile.mkdtemp(prefix="chipbench-tiny-"))
    kept = keep_compiled()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(layers(root, args.workload, seed, args.seconds, kept,
                                args.trace_seconds, args.keep)), flush=True)


if __name__ == "__main__":
    main()
