"""Device time per model scope: a profiler trace read through the compiled
step's op metadata.

A device trace names each op by its HLO instruction (`%fusion.633 = ...`)
and each run of a program by its module (`jit_train_step(<id>)`, on the
line `XLA Modules`).  The compiled step's text gives every instruction's
op_name, which holds the model's named scopes (`repro.models.scopes`):
`program` is `repro.models.scopes.program_ops(compiled.as_text())`.

`op_times` sums device self time per op_name inside the traced window
(`bench.window`), as `trace.reduce` sums it per op; an op outside the
step's module, or an instruction the text does not name, goes under
UNMATCHED.  The sum over all keys is the busy time of `trace.reduce`.
`by_scope` folds op_names into the disjoint top-level scopes, with
UNSCOPED for the rest; `by_kernel` gives the time inside each kernel
scope.
"""

import collections
import re

from repro.models.scopes import KERNELS, SCOPES, in_scope, top_scope

from . import trace

MODULES_LINE = "XLA Modules"
UNMATCHED = "unmatched"
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")


def window(pd):
    """(start, end) in ns of the host span `bench.window`."""
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW:
                        return e.start_ns, e.start_ns + e.duration_ns
    raise ValueError(f"no {trace.WINDOW} span in the trace")


def _inside(events, lo, hi):
    return sorted((max(a, lo), min(b, hi), n) for a, b, n in events
                  if b > lo and a < hi)


def _keys(ops, modules, program):
    """Each op's op_name, or UNMATCHED, by the module run it starts in."""
    ops_of = program["ops"]
    out, j = [], 0
    for a, b, name in ops:
        while j < len(modules) and modules[j][1] <= a:
            j += 1
        ours = (j < len(modules) and modules[j][0] <= a
                and modules[j][2] == program["module"])
        m = _INSTRUCTION.match(name)
        key = ops_of.get(m.group(1)) if ours and m else None
        out.append((a, b, UNMATCHED if key is None else key))
    return out


def op_times(path: str, program: dict) -> dict:
    """Seconds of device self time per op_name inside the traced window,
    mean over device planes."""
    pd = trace.load(path)
    lo, hi = window(pd)
    total, planes = collections.Counter(), 0
    for plane in pd.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events] for line in plane.lines}
        ops = _inside(lines.get(trace.OPS_LINE, []), lo, hi)
        if not ops:
            continue
        modules = [(a, b, n.split("(")[0])
                   for a, b, n in sorted(lines.get(MODULES_LINE, []))]
        total.update(trace.self_times(_keys(ops, modules, program)))
        planes += 1
    return {k: v / max(planes, 1) for k, v in total.items()}


def by_scope(times: dict) -> dict:
    """Seconds per top-level scope, UNSCOPED and UNMATCHED; sums to the
    total of `times`."""
    out = dict.fromkeys(SCOPES + (UNSCOPED, UNMATCHED), 0.0)
    for name, s in times.items():
        key = UNMATCHED if name == UNMATCHED else top_scope(name) or UNSCOPED
        out[key] += s
    return out


def by_kernel(times: dict) -> dict:
    """Seconds inside each kernel scope, whichever implementation ran."""
    return {k: sum(s for name, s in times.items() if in_scope(name, k))
            for k in KERNELS}
