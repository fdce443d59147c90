"""Render EXPERIMENTS.md §Dry-run and §Roofline markdown tables from the
dry-run JSONL results.

    PYTHONPATH=src python -m benchmarks.make_tables \
        results/dryrun_single.jsonl results/dryrun_multi.jsonl
"""
from __future__ import annotations

import json
import sys

from repro.configs import ARCH_IDS, SHAPES, cells


def load(path):
    best = {}
    try:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                best[(r["arch"], r["shape"])] = r
    except FileNotFoundError:
        pass
    return best


def fmt_bytes(n):
    if n is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.1f}{unit}"
        n /= 1024
    return f"{n:.1f}PB"


def _fmt_t(s):
    if s is None or s != s or s == float("inf"):
        return "—"
    return f"{s * 1e6:.1f}µs"


def crossover_table(path=None):
    """Render the per-(kernel, layout) backend-calibration records from
    ``BENCH_pallas_fusion.json`` (the measured crossover points behind
    ``use_pallas="auto"``; see docs/kernels.md)."""
    import os
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_pallas_fusion.json")
    try:
        with open(path) as f:
            bench = json.load(f)
    except (FileNotFoundError, ValueError):
        print("\n### §Backend crossover: PENDING "
              "(run `python -m benchmarks.pallas_fusion`)\n")
        return
    print("\n### §Backend crossover (use_pallas=\"auto\" calibration, "
          f"device={bench.get('device', '?')})\n")
    print("| kernel | layout | chosen | t_pallas | t_xla | roofline bound "
          "| interpreted |")
    print("|--------|--------|--------|----------|-------|----------------"
          "|-------------|")
    for r in bench.get("crossover", []):
        import ast
        try:
            args = ast.literal_eval(r["layout"])[0]
            shapes = "·".join("x".join(map(str, a[1]))
                              for a in args if a[0] == "arr")
        except (ValueError, SyntaxError):
            shapes = r["layout"][:40]
        print(f"| {r['kernel']} | {shapes} | **{r['backend']}** "
              f"| {_fmt_t(r.get('t_pallas_s'))} | {_fmt_t(r.get('t_xla_s'))} "
              f"| {r.get('bound', '—')} | {'yes' if r.get('interpreted') else 'no'} |")
    for r in bench.get("layouts", []):
        print(f"| mriFusedRecon (end-to-end) "
              f"| {'x'.join(map(str, r['shape']))} "
              f"| **{r.get('auto_resolved_backend', '?')}** "
              f"| {_fmt_t(r.get('t_fused_s'))} | {_fmt_t(r.get('t_staged_s'))}"
              f" (staged) | — | no |")


def ckpt_io_table(path=None):
    """Render ``BENCH_ckpt_io.json``: legacy host-gather vs gather-free
    sharded checkpoint save/restore (see docs/checkpoint.md)."""
    import os
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_ckpt_io.json")
    try:
        with open(path) as f:
            bench = json.load(f)
    except (FileNotFoundError, ValueError):
        print("\n### §Checkpoint I/O: PENDING "
              "(run `python -m benchmarks.ckpt_io`)\n")
        return
    mesh = "x".join(str(v) for v in bench.get("mesh", {}).values())
    print(f"\n### §Checkpoint I/O ({bench.get('state_mb', 0):.1f}MB state, "
          f"mesh {mesh}, {len(bench.get('shard_files', []))} shard files, "
          f"gather-free={bench.get('sharded_save_gather_free')}"
          f"{', SMOKE sizes' if bench.get('smoke') else ''})\n")
    print("| format | save | restore | elastic restore | gather phase "
          "| shard-write phase |")
    print("|--------|------|---------|-----------------|--------------"
          "|-------------------|")
    for fmt in ("legacy", "sharded"):
        t = bench.get("timings", {}).get(fmt)
        if not t:
            continue
        print(f"| {fmt} | {_fmt_t(t['save_s'])} | {_fmt_t(t['restore_s'])} "
              f"| {_fmt_t(t['elastic_restore_s'])} "
              f"| {_fmt_t(t['gather_s']) if t['gather_s'] else '0 (none)'} "
              f"| {_fmt_t(t['shard_write_s']) if t['shard_write_s'] else '—'} |")


def main():
    single = load(sys.argv[1] if len(sys.argv) > 1 else "results/dryrun_single.jsonl")
    multi = load(sys.argv[2] if len(sys.argv) > 2 else "results/dryrun_multi.jsonl")

    print("### §Dry-run (single-pod 16x16 = 256 chips; multi-pod 2x16x16 = 512)\n")
    print("| arch | shape | kind | note | args/chip | temp/chip | multi-pod |")
    print("|------|-------|------|------|-----------|-----------|-----------|")
    for arch, shape, ok, why in cells(include_skips=True):
        if not ok:
            print(f"| {arch} | {shape} | — | **skipped**: {why} | — | — | — |")
            continue
        r = single.get((arch, shape))
        m = multi.get((arch, shape))
        if r is None or r.get("status") != "ok":
            print(f"| {arch} | {shape} | ? | PENDING | | | |")
            continue
        mem = r.get("memory", {})
        mp = "ok" if (m and m.get("status") == "ok") else "PENDING"
        print(f"| {arch} | {shape} | {r['kind']} | {r.get('note','')} "
              f"| {fmt_bytes(mem.get('argument_size_in_bytes'))} "
              f"| {fmt_bytes(mem.get('temp_size_in_bytes'))} | {mp} |")

    print("\n### §Roofline (single-pod, per chip; v5e: 197TF bf16, 819GB/s HBM, 50GB/s ICI)\n")
    print("| arch | shape | t_compute | t_memory | t_collective | bound | "
          "useful FLOPs | MFU bound |")
    print("|------|-------|-----------|----------|--------------|-------|"
          "--------------|-----------|")
    for arch, shape, ok, why in cells(include_skips=False):
        r = single.get((arch, shape))
        if r is None or r.get("status") != "ok":
            continue
        f = r["roofline"]
        # recompute the collective term with ring-wire weights (all-reduce
        # moves 2x) from the stored breakdown, so old and new records render
        # consistently
        from repro.launch.roofline import V5E, wire_bytes
        t_coll = wire_bytes(f.get("coll_breakdown", {})) / V5E.ici_bw
        terms = {"compute": f["t_compute_s"], "memory": f["t_memory_s"],
                 "collective": t_coll}
        bound = max(terms, key=terms.get)
        mfu = f["model_flops"] / (max(terms.values()) * r["chips"] * V5E.flops) \
            if max(terms.values()) > 0 else float("nan")
        print(f"| {arch} | {shape} "
              f"| {f['t_compute_s']*1e3:.1f}ms | {f['t_memory_s']*1e3:.1f}ms "
              f"| {t_coll*1e3:.1f}ms | **{bound}** "
              f"| {f['useful_flops_ratio']*100:.0f}% "
              f"| {mfu*100:.2f}% |")

    crossover_table()
    ckpt_io_table()


if __name__ == "__main__":
    main()
