"""The control and two departures for an ``lm_moe`` cell, each in the
program's place through the harness, under the configuration's limits.

    python3 chipbench/tools/control_moe.py --workload <cell> \
        --seeds 1,2,3 --kind float8|renorm|no_yarn [--seconds 51]

Run from the root of a checkout, on the chip the cell asks for.  Each
seed makes one whole run of the cell (``harness.run_cell``) with, in the
program's place:

- ``float8``: the weights rounded to float8 e4m3 (the reference's
  ``control_weights``), the precision just below the bfloat16 the
  configuration states; it has to read ``correct`` false;
- ``renorm``: the program with its top-k gates renormalised, which the
  published ``norm_topk_prob: false`` forbids;
- ``no_yarn``: the program with plain RoPE and the plain softmax scale,
  where the configuration asks for YaRN.

The reference stays as the configuration states it.  Prints one JSON
line per seed: the run's ``correct`` and its checks.  The benchmark's own
runs never run this.  It prints the run's metrics too: the first run in
an empty compilation cache reads the cold ``setup_s``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
KINDS = ("float8", "renorm", "no_yarn")


@contextlib.contextmanager
def in_place(kind: str):
    """Runs inside the block have ``kind`` in the program's place."""
    from chipbench.drivers import lm_moe
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r}: one of {KINDS}")
    weights, arch = lm_moe.program_weights, lm_moe.arch_config
    if kind == "float8":
        lm_moe.program_weights = lambda ref, c, cell: ref.control_weights(
            weights(ref, c, cell))
    else:
        change = ({"norm_topk_prob": True} if kind == "renorm"
                  else {"rope_scaling": None})
        lm_moe.arch_config = lambda c: dataclasses.replace(arch(c), **change)
    try:
        yield
    finally:
        lm_moe.program_weights, lm_moe.arch_config = weights, arch


def main() -> None:
    sys.path[0] = str(_ROOT)
    sys.path.insert(1, str(_ROOT / "src"))      # the program under test
    from chipbench import harness
    ap = argparse.ArgumentParser(prog="chipbench/tools/control_moe.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kind", choices=KINDS, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = harness.parse(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)])
        with in_place(args.kind):
            res = harness.run_cell(run, root=_ROOT,
                                   started=time.perf_counter())
        print(json.dumps({"seed": seed, "kind": args.kind,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
