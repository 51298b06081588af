"""The whole decode step's share of its roofline, in %: per traced step
the larger of its FLOPs over peak FLOP/s and the bytes the work needs (the
weights and the live KV entries, harness.flops) over peak bandwidth,
summed, over the device's busy time in those steps."""


def read(r):
    steps = r.counters.get("decode", [])
    if (r.kind != "serve" or not steps or r.peaks is None
            or r.trace.busy_s <= 0):
        return None
    least = sum(max(f / r.peaks.flops, b / r.peaks.hbm_bw) for f, b in steps)
    return 100.0 * least / (r.trace.busy_s * r.chips)
