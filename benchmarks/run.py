"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [table1 fig2 overhead roofline lm lm_decode stream serve fanin pallas ckpt]
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.core import enable_compile_cache
    enable_compile_cache()
    which = set(sys.argv[1:]) or {"table1", "fig2", "overhead", "roofline",
                                  "lm", "lm_decode", "stream", "serve",
                                  "fanin", "pallas", "ckpt"}
    print("name,us_per_call,derived")
    rows = []
    if "table1" in which:
        from benchmarks.paper_tables import table1
        rows += table1()
    if "fig2" in which:
        from benchmarks.paper_tables import fig2
        rows += fig2()
    if "overhead" in which:
        from benchmarks.paper_tables import process_overhead
        rows += process_overhead()
    if "roofline" in which:
        from benchmarks.roofline_report import rows as roofline_rows
        rows += roofline_rows()
    if "lm" in which:
        from benchmarks.lm_step import rows as lm_rows
        rows += lm_rows()
    if "lm_decode" in which:
        from benchmarks.lm_step import decode_rows
        rows += decode_rows()
    if "stream" in which:
        from benchmarks.stream_throughput import rows as stream_rows
        rows += stream_rows()
    if "serve" in which:
        from benchmarks.serve_latency import rows as serve_rows
        rows += serve_rows()
    if "fanin" in which:
        from benchmarks.fanin_throughput import rows as fanin_rows
        rows += fanin_rows()
    if "pallas" in which:
        from benchmarks.pallas_fusion import rows as pallas_rows
        rows += pallas_rows()
    if "ckpt" in which:
        from benchmarks.ckpt_io import rows as ckpt_rows
        rows += ckpt_rows()
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
