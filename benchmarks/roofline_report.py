"""Roofline summary rows from the dry-run sweep JSONL (§Roofline).

Reads ``results/dryrun_single.jsonl`` (written by
``python -m repro.launch.dryrun --all --out ...``) and emits one CSV row
per (arch x shape) cell with the three terms and the bottleneck.  This is
the benchmark counterpart of the EXPERIMENTS.md table.
"""
from __future__ import annotations

import json
import os
from typing import List

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun_single.jsonl")
HILLCLIMB = os.path.join(os.path.dirname(__file__), "..", "results",
                         "hillclimb.jsonl")


def rows(path: str = RESULTS, hillclimb: str = HILLCLIMB) -> List[str]:
    if not os.path.exists(path):
        return ["roofline_report,skipped,no dryrun results "
                "(run python -m repro.launch.dryrun --all --out "
                "results/dryrun_single.jsonl)"]
    out = []
    best = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("status") != "ok":
                continue
            best[(r["arch"], r["shape"])] = r  # keep last run of each cell
    if os.path.exists(hillclimb):  # §Perf optimized variants, labelled
        with open(hillclimb) as f:
            for line in f:
                r = json.loads(line)
                if r.get("status") == "ok":
                    best[(r["arch"] + "+opt", r["shape"])] = r
    from repro.launch.roofline import V5E, wire_bytes

    for (arch, shape), r in sorted(best.items()):
        roof = r["roofline"]
        # recompute the collective term with ring-wire weights (all-reduce
        # moves 2x) so old records render consistently with make_tables
        t_coll = wire_bytes(roof.get("coll_breakdown", {})) / V5E.ici_bw
        terms = {"compute": roof["t_compute_s"], "memory": roof["t_memory_s"],
                 "collective": t_coll}
        bound = max(terms, key=terms.get)
        t_max = max(terms.values())
        mfu = roof["model_flops"] / (t_max * r["chips"] * V5E.flops) \
            if t_max > 0 else float("nan")
        out.append(
            f"roofline_{arch}_{shape},{t_max * 1e6:.0f},"
            f"bottleneck={bound};"
            f"compute_ms={roof['t_compute_s'] * 1e3:.2f};"
            f"memory_ms={roof['t_memory_s'] * 1e3:.2f};"
            f"collective_ms={t_coll * 1e3:.2f};"
            f"useful_flops={roof['useful_flops_ratio']:.3f};"
            f"mfu_bound={mfu:.4f}")
    return out
