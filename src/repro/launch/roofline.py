"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell, per DESIGN.md S7:

    t_compute = FLOPs_per_device / peak FLOP/s
    t_memory  = bytes_per_device / HBM bandwidth
    t_coll    = collective_bytes_per_device / (ICI links * ICI bandwidth)

with the peaks of the dry run's target chip, TPU v5e (`V5E`).

`cost_analysis()` on this jax/XLA reports per-device cost and counts a
while (scan) body ONCE (verified in tests/test_roofline.py), so callers
pass the full program's cost plus a single-unit program's cost and we
extrapolate: total = full + (n_units - 1) * unit.

Collective bytes are parsed from the compiled HLO text: every line defines
`%name = TYPE op(...)`, so a name->bytes map recovers operand sizes, and
per-op ring-transfer multipliers convert payloads into link bytes.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float                  # bf16 FLOP/s
    hbm_bw: float                 # B/s
    ici_bw: float                 # B/s per link
    ici_links: int                # usable links per chip in a 2D torus slice


# Published per-chip peaks, keyed by `jax.Device.device_kind`.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s of interchip interconnect (4 x 50 GB/s).
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             ici_links=4),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip; an unlisted kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


V5E = peaks("TPU v5 lite")        # the production meshes' chip

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.-]+)\s*=\s*(.*?)\s*"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute|all-reduce-start|all-gather-start|"
                     r"collective-permute-start)\(", re.M)
_ANYDEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.-]+)\s*=\s*([^\s]+(?:\s*,\s*[^\s)]+)*?)\s+[\w-]+\(", re.M)

# link bytes per payload byte for a ring schedule over n shards (n large)
_RING_FACTOR = {
    "all-reduce": 2.0, "all-reduce-start": 2.0,
    "all-gather": 1.0, "all-gather-start": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0, "collective-permute-start": 1.0,
}


def _type_bytes(type_str: str) -> int:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    per_op: Dict[str, float]
    payload_bytes: float          # sum of payloads
    link_bytes: float             # ring-multiplied

    def __add__(self, o: "CollectiveStats") -> "CollectiveStats":
        per = dict(self.per_op)
        for k, v in o.per_op.items():
            per[k] = per.get(k, 0.0) + v
        return CollectiveStats(per, self.payload_bytes + o.payload_bytes,
                               self.link_bytes + o.link_bytes)

    @staticmethod
    def zero() -> "CollectiveStats":
        return CollectiveStats({}, 0.0, 0.0)


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Parse collective payload bytes out of compiled (or lowered) HLO."""
    per_op: Dict[str, float] = {}
    payload = 0.0
    link = 0.0
    for m in _DEF_RE.finditer(hlo_text):
        _, type_str, op = m.groups()
        b = _type_bytes(type_str)
        if op.startswith("all-gather"):
            pass  # result is the gathered buffer: the payload
        per_op[op] = per_op.get(op, 0.0) + b
        payload += b
        link += b * _RING_FACTOR.get(op, 1.0)
    return CollectiveStats(per_op, payload, link)


@dataclasses.dataclass
class Roofline:
    flops: float                  # per device
    hbm_bytes: float              # per device
    coll_link_bytes: float        # per device
    coll_per_op: Dict[str, float]

    @property
    def t_compute(self) -> float:
        return self.flops / V5E.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / V5E.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_link_bytes / (V5E.ici_links * V5E.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """No-overlap upper bound; with perfect overlap it is the max."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_link_bytes": self.coll_link_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "coll_per_op": self.coll_per_op,
        }


def extract(compiled, n_units: int = 1,
            unit_compiled=None) -> Roofline:
    """Roofline terms from compiled artifacts with scan-body extrapolation:
    total = full + (n_units - 1) * unit."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    if unit_compiled is not None and n_units > 1:
        uca = unit_compiled.cost_analysis()
        ucoll = collective_bytes(unit_compiled.as_text())
        k = n_units - 1
        flops += k * float(uca.get("flops", 0.0))
        byts += k * float(uca.get("bytes accessed", 0.0))
        coll = coll + CollectiveStats(
            {o: k * v for o, v in ucoll.per_op.items()},
            k * ucoll.payload_bytes, k * ucoll.link_bytes)
    return Roofline(flops, byts, coll.link_bytes, coll.per_op)


def model_flops(param_count: int, active_param_count: int, tokens: int,
                mode: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    mult = 6.0 if mode == "train" else 2.0
    return mult * active_param_count * tokens
