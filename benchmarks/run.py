"""Benchmark driver: one function per paper table/figure.  Prints
``name,us_per_call,derived`` CSV rows and writes the full
structured results to experiments/bench_results.json (keys sorted, and
``--only <row>`` merges into the existing file — so adding or refreshing
one row churns only that row's diff).  Each run also persists a
``_bench_meta`` block — per-row wall time and the derived-metric string —
so the perf trajectory is machine-readable from the committed file.

``--check`` turns the driver into a regression gate: it re-runs the
requested rows (all rows with committed metrics when no ``--only`` is
given), parses each derived metric numerically, and exits non-zero with
a readable delta table if anything drifts beyond the row's tolerance
from the committed ``bench_results.json``.  Check mode never writes.

Every write run also appends one line per row to
``experiments/bench_history.jsonl`` (wall time, derived metrics,
provenance hash) — the long-horizon perf ledger ``benchmarks/history.py
--plot-text`` renders, and the fallback ``--check`` gates against when
the results file lacks ``_bench_meta``."""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

#: the _bench_meta key holding per-row wall time + derived metrics
META_KEY = "_bench_meta"

#: Per-row relative tolerance overrides for ``--check``.  CI gates
#: EVERY row with committed ``_bench_meta`` (``--check`` with no
#: ``--only``), so this table is the one place that documents how much
#: drift each row may absorb and why.  Every row is seeded and
#: deterministic; the default only needs to cover float jitter across
#: BLAS builds / platforms.  The full row table:
#:
#:   row                       rtol     nature of the row
#:   ------------------------  -------  -----------------------------
#:   fig2_bottleneck           default  closed-form GEMINI shares
#:   fig4_speedup              default  batched sweep, closed form
#:   fig5_heatmap              default  batched sweep, closed form
#:   fig4_mac_channels         default  batched sweep, closed form
#:   sim_fidelity              default  event engine vs analytic
#:   sim_policies              default  seeded policies, deterministic
#:   fig_critpath_whatif       default  DAG replay, exact arithmetic
#:   llm_collectives           default  collective lowering, closed form
#:   scaling_frontier          default  batched sweep, closed form
#:   hetero_codesign           1e-4     seeded annealer: accept/reject
#:                                      branches sit on float compares,
#:                                      so cross-platform reassociation
#:                                      can flip a late SA step
#:   balancer_vs_sweep         default  integer win counts
#:   mapping_sensitivity       default  closed-form ratio
#:   edp_report                default  closed-form energy-delay
#:   fig_resilience            1e-6     deterministic scenario grid
#:                                      (explicit entry: retained
#:                                      ratios divide two engine
#:                                      totals, so float jitter
#:                                      compounds — keep at default
#:                                      unless a platform drifts)
#:
#: Raise a row's entry here (with a rationale line above it) if a
#: legitimate source of run-to-run variance ever lands; never widen
#: "default" to paper over a real regression.
CHECK_RTOL = {
    "default": 1e-6,
    "hetero_codesign": 1e-4,
    "fig_resilience": 1e-6,
}
CHECK_ATOL = 1e-12

_NUM = r"[-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?"


def parse_derived(derived: str) -> dict:
    """``"k=v;k=v"`` -> {key: float} for every numerically-comparable v.

    Handles plain/scientific floats, ``12.3%``, ``2.29x``, ``13/15``
    fractions (compared as a/b), and ``True``/``False`` booleans.
    """
    out = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        v = v.strip()
        if v in ("True", "False"):
            out[k] = float(v == "True")
            continue
        m = re.fullmatch(rf"({_NUM})\s*/\s*({_NUM})", v)
        if m:
            a, b = float(m.group(1)), float(m.group(2))
            out[k] = a / b if b else a
            continue
        m = re.fullmatch(rf"({_NUM})\s*[%x]?", v)
        if m:
            out[k] = float(m.group(1))
    return out


def _run(name, fn, derived_fn):
    t0 = time.perf_counter()
    result = fn()
    us = (time.perf_counter() - t0) * 1e6
    derived = derived_fn(result)
    print(f"{name},{us:.0f},{derived}")
    return result, {"us_per_call": round(us, 1), "derived": derived}


# ---------------------------------------------------------------------------
# run history: one JSONL line per (run, row), appended on every write run
# ---------------------------------------------------------------------------

def history_path(results_file: str) -> str:
    """The history ledger lives next to the results file."""
    return os.path.join(os.path.dirname(results_file) or ".",
                        "bench_history.jsonl")


def append_history(path: str, meta: dict) -> None:
    """Append one line per row: wall time, derived metrics (raw string
    and parsed), and the provenance hash of the row's outcome."""
    from repro.obs.provenance import config_hash
    ts = time.time()
    with open(path, "a") as f:
        for name, m in sorted(meta.items()):
            f.write(json.dumps({
                "ts": round(ts, 3),
                "row": name,
                "us_per_call": m["us_per_call"],
                "derived": m["derived"],
                "metrics": parse_derived(m["derived"]),
                "hash": config_hash({"row": name,
                                     "derived": m["derived"]}),
            }, sort_keys=True) + "\n")


def load_history(path: str) -> list:
    """All parseable entries of the ledger, oldest first."""
    entries = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entries.append(json.loads(line))
                except json.JSONDecodeError:
                    continue       # a torn tail line never blocks --check
    return entries


def latest_by_row(entries: list) -> dict:
    """row -> its most recent ledger entry."""
    out = {}
    for e in entries:
        if "row" in e:
            out[e["row"]] = e
    return out


def check_rows(rows, committed: dict, rtol: float | None = None) -> int:
    """Regression gate: re-run ``rows``, diff against ``committed``.

    Returns the number of drifted metrics (0 = pass) and prints a delta
    table for anything out of tolerance.
    """
    meta = committed.get(META_KEY, {})
    deltas = []
    for name, fn, derived_fn in rows:
        if name not in meta:
            deltas.append((name, "(row)", "missing from committed "
                           f"{META_KEY}", "", ""))
            continue
        want = parse_derived(meta[name]["derived"])
        result, m = _run(name, fn, derived_fn)
        got = parse_derived(m["derived"])
        tol = rtol if rtol is not None else CHECK_RTOL.get(
            name, CHECK_RTOL["default"])
        for k, w in want.items():
            if k not in got:
                deltas.append((name, k, f"{w:g}", "(missing)", ""))
                continue
            g = got[k]
            if abs(g - w) > CHECK_ATOL + tol * abs(w):
                rel = abs(g - w) / (abs(w) or 1.0)
                deltas.append((name, k, f"{w:g}", f"{g:g}",
                               f"{100 * rel:.3g}%"))
        for k in got.keys() - want.keys():
            deltas.append((name, k, "(missing)", f"{got[k]:g}", ""))
    if deltas:
        hdrs = ("row", "metric", "committed", "got", "drift")
        wid = [max(len(str(r[i])) for r in deltas + [hdrs])
               for i in range(len(hdrs))]
        print("\nBENCH CHECK FAILED — metrics out of tolerance:",
              file=sys.stderr)
        for r in [hdrs] + deltas:
            print("  " + "  ".join(str(c).ljust(w)
                                   for c, w in zip(r, wid)),
                  file=sys.stderr)
    else:
        print(f"bench check OK ({len(rows)} row(s) within tolerance)")
    return len(deltas)


def main(argv=None) -> int:
    from benchmarks import paper_figs
    from repro.core import make_trace
    from repro.core.workloads import WORKLOADS

    # traces are built on first use: --only rows that never read them
    # (hetero_codesign, scaling_frontier) and the unknown-row error path
    # skip the 15-workload build entirely
    _traces = {}

    def traces():
        if not _traces:
            _traces.update({wl: make_trace(wl) for wl in WORKLOADS})
        return _traces

    results = {}
    rows = [
        ("fig2_bottleneck",
         lambda: paper_figs.fig2_bottleneck(traces()),
         lambda r: "mean_nop_share=%.2f" % (
             sum(v["nop"] for v in r.values()) / len(r))),
        ("fig4_speedup",
         lambda: paper_figs.fig4_speedup(traces()),
         lambda r: "mean64=%.1f%%;mean96=%.1f%%;max96=%.1f%%" % (
             100 * (r["_summary"][64]["mean"] - 1),
             100 * (r["_summary"][96]["mean"] - 1),
             100 * (r["_summary"][96]["max"] - 1))),
        ("fig5_heatmap",
         lambda: paper_figs.fig5_heatmap(traces=traces()),
         lambda r: "peak=%.1f%%;worst=%.1f%%" % (
             max(max(v) for v in r["grid"].values()),
             min(min(v) for v in r["grid"].values()))),
        ("fig4_mac_channels",
         lambda: paper_figs.fig4_mac_channels(traces()),
         lambda r: "ideal_mean=%.1f%%;tdma_mean=%.1f%%;token_mean=%.1f%%" % (
             100 * (r["_summary"]["ideal/1ch"]["mean"] - 1),
             100 * (r["_summary"]["tdma/1ch"]["mean"] - 1),
             100 * (r["_summary"]["token/1ch"]["mean"] - 1))),
        ("sim_fidelity",
         lambda: paper_figs.fig_sim_fidelity(traces()),
         lambda r: "striped_err=%.1e;adaptive_err=%.1f%%;xy_err=%.1f%%" % (
             r["_summary"]["striped"]["worst_speedup_rel_err"],
             100 * r["_summary"]["adaptive"]["worst_speedup_rel_err"],
             100 * r["_summary"]["xy"]["worst_speedup_rel_err"])),
        ("sim_policies",
         lambda: paper_figs.fig_sim_policies(traces()),
         lambda r: "adaptive_beats_grid=%s;greedy_beats_grid=%s;"
         "mean_adaptive=%.1f%%" % (
             r["_summary"]["adaptive"]["beats_grid"],
             r["_summary"]["greedy"]["beats_grid"],
             100 * (r["_summary"]["adaptive"]["mean_speedup"] - 1))),
        ("fig_critpath_whatif",
         lambda: paper_figs.fig_critpath_whatif(traces()),
         lambda r: "mean_div=%.3f;max_div=%.3f;worst_proj_err=%.2f%%;"
         "sum_ok=%s;guided_match=%s;guided_frac=%.2f" % (
             r["_summary"]["mean_divergence"],
             r["_summary"]["max_divergence"],
             100 * r["_summary"]["worst_proj_err"],
             r["_summary"]["all_sum_ok"],
             r["_summary"]["guided_matches_exhaustive"],
             r["_summary"]["guided_fraction"])),
        ("llm_collectives",
         paper_figs.fig_llm_collectives,
         lambda r: "prefill_mean96=%.1f%%;decode_mean96=%.1f%%;"
         "prefill_coll_share=%.2f" % (
             100 * (r["_summary_prefill"]["mean_best_96"] - 1),
             100 * (r["_summary_decode"]["mean_best_96"] - 1),
             r["_summary_prefill"]["mean_collective_share"])),
        ("scaling_frontier",
         paper_figs.fig_scaling_frontier,
         lambda r: "mean8x8_single=%.1f%%;mean8x8_reuse=%.1f%%;"
         "mean16x16_single=%.1f%%;mean16x16_reuse=%.1f%%" % (
             100 * (r["_summary"]["8x8"]["mean_single"] - 1),
             100 * (r["_summary"]["8x8"]["mean_reuse"] - 1),
             100 * (r["_summary"]["16x16"]["mean_single"] - 1),
             100 * (r["_summary"]["16x16"]["mean_reuse"] - 1))),
        ("hetero_codesign",
         paper_figs.hetero_codesign,
         lambda r: "mean_codesign=%.1f%%;max_codesign=%.1f%%;"
         "spread_shrunk=%d/%d" % (
             100 * (r["_summary"]["_overall"]["mean_speedup_codesigned"]
                    - 1),
             100 * (r["_summary"]["_overall"]["max_speedup_codesigned"]
                    - 1),
             r["_summary"]["_overall"]["spread_shrunk"],
             r["_summary"]["_overall"]["n"])),
        ("balancer_vs_sweep",
         lambda: paper_figs.balancer_vs_sweep(traces()),
         lambda r: "balancer_wins=%d/%d" % (
             sum(v["balancer"] >= v["swept_best"] - 1e-9
                 for v in r.values()), len(r))),
        ("mapping_sensitivity",
         paper_figs.mapping_sensitivity,
         lambda r: "mac_only/comm_aware=%.2fx" % (
             sum(v["ratio"] for v in r.values()) / len(r))),
        ("edp_report",
         lambda: paper_figs.edp_report(traces()),
         lambda r: "mean_edp_gain=%.3f;max=%.3f" % (
             sum(v["edp_gain"] for v in r.values()) / len(r),
             max(v["edp_gain"] for v in r.values()))),
        ("fig_resilience",
         paper_figs.fig_resilience,
         lambda r: "static_ret=%.3f;adaptive_ret=%.3f;reshard_ret=%.3f;"
         "never_slower=%s;resharded=%d/%d" % (
             r["_summary"]["mean_retained"]["static"],
             r["_summary"]["mean_retained"]["adaptive"],
             r["_summary"]["mean_retained"]["online-reshard"],
             r["_summary"]["reshard_never_slower"],
             r["_summary"]["resharded_cells"],
             r["_summary"]["n_cells"])),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", metavar="ROW",
                    help="run only the named row (repeatable); the "
                         "result is merged into bench_results.json")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: re-run the requested rows and "
                         "fail if any derived metric drifts beyond "
                         "tolerance from the committed results file "
                         "(never writes)")
    ap.add_argument("--rtol", type=float, default=None,
                    help="override the per-row relative tolerance for "
                         "--check")
    ap.add_argument("--file", default=None, metavar="PATH",
                    help="results file (default: "
                         "experiments/bench_results.json)")
    args = ap.parse_args(argv)
    if args.only:
        known = {name for name, _, _ in rows}
        unknown = sorted(set(args.only) - known)
        if unknown:
            ap.error(f"unknown row(s) {unknown}; pick from {sorted(known)}")
        rows = [r for r in rows if r[0] in set(args.only)]

    out = args.file or os.path.join(os.path.dirname(__file__), "..",
                                    "experiments", "bench_results.json")

    if args.check:
        committed = {}
        if os.path.exists(out):
            with open(out) as f:
                committed = json.load(f)
        if not committed.get(META_KEY):
            # results file absent or predates _bench_meta: fall back to
            # the latest bench_history.jsonl entry per row
            hist = latest_by_row(load_history(history_path(out)))
            if not hist:
                print(f"bench check: no committed results at {out} and "
                      f"no history at {history_path(out)}",
                      file=sys.stderr)
                return 2
            print(f"bench check: {out} lacks {META_KEY}; falling back "
                  "to the latest bench_history.jsonl entries",
                  file=sys.stderr)
            committed[META_KEY] = {
                row: {"derived": e["derived"],
                      "us_per_call": e.get("us_per_call", 0.0)}
                for row, e in hist.items()}
        if not args.only:   # default: gate every row with committed meta
            rows = [r for r in rows
                    if r[0] in committed.get(META_KEY, {})]
        print("name,us_per_call,derived")
        return 1 if check_rows(rows, committed, args.rtol) else 0

    meta = {}
    print("name,us_per_call,derived")
    for name, fn, d in rows:
        results[name], meta[name] = _run(name, fn, d)

    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    merged = {}
    if args.only and os.path.exists(out):   # --only refreshes rows in place
        with open(out) as f:                # (full runs rewrite the file,
            merged = json.load(f)           # so removed rows don't linger)
    merged.update(results)
    merged.setdefault(META_KEY, {}).update(meta)
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True, default=str)
    append_history(history_path(out), meta)
    return 0


if __name__ == "__main__":
    sys.exit(main())
