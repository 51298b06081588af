"""Device time per training step: busy time in the trace over the steps
traced, in ms."""


def read(r):
    n = r.counters.get("steps_traced", 0)
    if r.kind != "train" or n == 0 or r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.busy_s / n
