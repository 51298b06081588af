"""Run one cell once: set-up, the measured window, then the comparison
with the plain reference, and the result line's fields."""

import dataclasses
import importlib
import shutil
import tempfile
from typing import Optional

import numpy as np

from . import correct, device, spec
from . import trace as tracemod
from .peaks import PEAKS


@dataclasses.dataclass
class Context:
    """What a runner is given."""
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    t_start: float                  # the process's clock at its start
    trace_dir: Optional[str]
    fault: Optional[str]
    devices: list                   # the cell's chips, as device.require


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given."""
    kind: str                       # the traffic's kind: train | serve
    config: dict
    traffic: dict
    trace: tracemod.Trace
    counters: dict
    peaks: object                   # harness.peaks.Peaks, None off a TPU
    chips: int


def step_intervals(ends: list) -> dict:
    """Quantiles of the seconds between the ends of the window's steps:
    shows whether a slow run is slow in every step or stalls in a few."""
    d = np.diff(ends)
    return {"n": len(d), **{q: float(np.quantile(d, p)) for q, p in (
        ("min", 0), ("median", 0.5), ("p95", 0.95), ("max", 1))}}


def compare(limits: dict, got: dict) -> dict:
    """Each number compared beside its limit, in a fixed order."""
    return {k: {"value": v, "limit": limits["limits"][k]}
            for k, v in got.items()}


def judge(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def checks(ctx: Context, kind: str, out: dict, detail: dict,
           control: bool = False) -> dict:
    """The numbers compared, each with its limit.  With `control`, the
    control stands in the program's place: the plain reference computed
    with float8 matrix products (reference/common.py)."""
    if kind == "train":
        from reference import train as ref
        args = (ctx.config, ctx.traffic, ctx.seed, ctx.limits["check_steps"],
                ctx.devices)
        rows = ctx.limits["reference_rows"]
        detail["reference"] = ref.readings(*args, rows=rows)
        prog = (ref.readings(*args, precision="fp8", rows=rows) if control
                else out["program"])
        got = correct.train(prog, detail["reference"])
    else:
        got = correct.serve(ctx.config, ctx.traffic, ctx.seed,
                            out["program"]["done"],
                            ctx.limits["sample_requests"], control=control)
        got.pop("served_tokens")
    return compare(ctx.limits, got)


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, allow_cpu: bool = False, fault: str = None,
        keep_trace: str = None, detail: dict = None,
        control: bool = False) -> dict:
    """One run of the cell; returns the result line's fields.  allow_cpu,
    fault (planted under the timed path, harness/faults.py), control (put
    in the program's place in the comparison), keep_trace and detail
    (which receives the program's and the reference's readings) serve the
    tests and the readings that set the limits."""
    bench = spec.Bench(root)
    w = bench.workload(workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    limits = bench.limits(workload)

    import jax
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = device.require(w["chips"], allow_cpu)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    try:
        ctx = Context(config, traffic, limits, seed, seconds, t_start,
                      trace_dir, fault, devices)
        kind = traffic["kind"]
        out = importlib.import_module("harness." + kind).run(ctx)
        if trace:
            path = tracemod.find(trace_dir)
            if keep_trace:
                shutil.copy(path, keep_trace)
            summary = tracemod.reduce(path)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    detail = {} if detail is None else detail
    detail["program"] = out["program"]
    compared = checks(ctx, kind, out, detail, control)
    dev = device.describe(devices, out["memory_peak"])
    if trace:
        reading = Reading(kind, config, traffic, summary, out["counters"],
                          PEAKS.get(devices[0].device_kind), len(devices))
        metrics = {}
        for m in bench.per_layer(workload):
            v = bench.reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench.end_to_end(workload)}
    result = {"correct": judge(compared),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["step_s"] = step_intervals(out["ends"])
    result["checks"] = compared
    return result
