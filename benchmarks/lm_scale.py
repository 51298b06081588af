"""LM-scale benchmarks: roofline table from the dry-run JSONs + the
paper's hybrid-plane schedule applied to each cell's collectives."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.core.hybrid_schedule import balance_cell, sweep_cell

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def load_cells(dryrun_dir: str = DRYRUN_DIR) -> List[dict]:
    out = []
    for fn in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        if "__h_" in os.path.basename(fn):
            continue  # hillclimb-tagged variants live beside the baselines
        with open(fn) as f:
            out.append(json.load(f))
    return out


def roofline_table(mesh: str = "pod",
                   dryrun_dir: str = DRYRUN_DIR) -> List[dict]:
    """One row per (arch x shape): the three terms + dominant + useful
    ratio (EXPERIMENTS.md SRoofline)."""
    rows = []
    for c in load_cells(dryrun_dir):
        if c.get("mesh") != mesh or c.get("status") != "ok":
            continue
        r = c.get("roofline")
        if not r:
            continue
        rows.append({
            "arch": c["arch"], "shape": c["shape"],
            "t_compute": r["t_compute"], "t_memory": r["t_memory"],
            "t_collective": r["t_collective"], "dominant": r["dominant"],
            "useful_ratio": r.get("useful_ratio", 0.0),
            "step_time": max(r["t_compute"], r["t_memory"],
                             r["t_collective"]),
        })
    return rows


def hybrid_plane_report(mesh: str = "pod",
                        dryrun_dir: str = DRYRUN_DIR,
                        memory: str = "floor") -> List[dict]:
    """The paper's technique on each LM cell's compiled collectives:
    swept decision function + the closed-form balancer.

    memory="floor" uses the analytic HBM floor (resident state bytes from
    memory_analysis / HBM bandwidth) as the memory term — XLA's
    `bytes accessed` is a no-fusion upper bound that would mask every
    collective-bound cell (EXPERIMENTS.md §Roofline); "xla" keeps the raw
    metric for comparison."""
    from repro.launch.roofline import V5E
    rows = []
    for c in load_cells(dryrun_dir):
        if c.get("mesh") != mesh or c.get("status") != "ok":
            continue
        r = c.get("roofline")
        if not r or not r.get("coll_per_op"):
            continue
        if memory == "floor":
            args = c.get("memory", {}).get("argument_size_in_bytes", 0)
            t_mem = args / V5E.hbm_bw
        else:
            t_mem = r["t_memory"]
        swept, (thr, p) = sweep_cell(r["coll_per_op"], r["t_compute"],
                                     t_mem)
        bal = balance_cell(r["coll_per_op"], r["t_compute"], t_mem)
        rows.append({
            "arch": c["arch"], "shape": c["shape"],
            "t_compute": r["t_compute"], "t_mem_floor": t_mem,
            "t_coll_wired": swept.t_coll_wired,
            "swept_step_speedup": swept.step_speedup,
            "swept_cfg": {"threshold": thr, "injection": p},
            "balancer_step_speedup": bal.step_speedup,
            "balancer_coll_speedup": bal.coll_speedup,
            "offloaded_GB": bal.offloaded_bytes / 1e9,
        })
    return rows


def dryrun_summary(dryrun_dir: str = DRYRUN_DIR) -> Dict:
    cells = load_cells(dryrun_dir)
    ok = [c for c in cells if c.get("status") == "ok"]
    return {"total": len(cells), "ok": len(ok),
            "failed": [f'{c["arch"]}/{c["shape"]}/{c["mesh"]}'
                       for c in cells if c.get("status") != "ok"]}
