"""From a profiler trace to device busy time, top operations and idle gaps.

Device planes are those named `/device:...`; their operations are the
events of the line named `XLA Ops`, where a loop's event encloses the
events of its body.  Busy time is the union of those intervals inside the
traced window, which is the host span `bench.window`; an operation's time
is its self time, what its children leave of it.  Each stretch of the
window in which no operation runs is put down to the harness's host span
(`bench.*`) that overlaps it most.  Host and device events share one
clock in the trace.
"""

import collections
import dataclasses
import glob
import gzip
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
UNLABELLED = "host.unlabelled"


@dataclasses.dataclass
class Trace:
    busy_s: float          # mean over device planes
    window_s: float        # length of the traced window
    device_ops: list       # [[name, seconds]], most time first
    idle_gaps: list        # [[host span, seconds]], most idle time first


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def gaps(busy, lo, hi):
    """The stretches of [lo, hi] that no interval of `busy` covers."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap_list, spans):
    """Seconds of idle per host span name; spans: [(start, end, name)]."""
    spans = sorted(spans)
    total = collections.Counter()
    j = 0
    for a, b in gap_list:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        best, name = 0, UNLABELLED
        k = j
        while k < len(spans) and spans[k][0] < b:
            s, e, n = spans[k]
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
            k += 1
        total[name] += (b - a) * 1e-9
    return total


def self_times(events):
    """Seconds of self time per name, for properly nested events."""
    out = collections.Counter()
    stack = []                      # [end, name, child time]
    for a, b, n in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, name, child, start = stack.pop()
            out[name] += (end - start - child) * 1e-9
            if stack:
                stack[-1][2] += end - start
        stack.append([b, n, 0, a])
    while stack:
        end, name, child, start = stack.pop()
        out[name] += (end - start - child) * 1e-9
        if stack:
            stack[-1][2] += end - start
    return out


def op_name(hlo: str) -> str:
    """An op's event name is its HLO line; keep its head."""
    return hlo[:96]


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def reduce(path: str, top: int = 10) -> Trace:
    pd = load(path)
    ops, spans, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                ops.append(evs)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == WINDOW:
                        window = iv
                    else:
                        spans.append(iv)
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = window[0], window[1]
    busy, by_name, idle = 0.0, collections.Counter(), collections.Counter()
    for evs in ops:
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in evs
                  if b > lo and a < hi]
        merged = merge((a, b) for a, b, _ in inside)
        busy += sum(b - a for a, b in merged) * 1e-9
        by_name.update(self_times(inside))
        idle.update(attribute(gaps(merged, lo, hi), spans))
    n = max(len(ops), 1)
    return Trace(
        busy_s=busy / n, window_s=(hi - lo) * 1e-9,
        device_ops=[[k, v / n] for k, v in by_name.most_common(top)],
        idle_gaps=[[k, v / n] for k, v in idle.most_common(top)])
