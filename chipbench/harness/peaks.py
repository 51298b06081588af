"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

A copy kept with the benchmark, so that the yardstick does not move when
the program's own table changes.  A kind that is not listed is an error.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    hbm_bytes: float    # HBM capacity


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect.
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
