"""Model FLOP/s utilisation of training, in %: the step's model FLOPs
(harness.flops; recomputation not counted) times the steps traced, over
the traced wall time times chips times the chip's peak."""


def read(r):
    n, wall = r.counters.get("steps_traced", 0), r.counters.get("window_s")
    if r.kind != "train" or n == 0 or not wall or r.peaks is None:
        return None
    return 100.0 * r.counters["flops_per_step"] * n / (
        wall * r.chips * r.peaks.flops)
