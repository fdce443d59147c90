"""The controls: each configuration's plain reference in the precision
just below the one it states (bfloat16 arrays for complex64 MRI data,
float8 e4m3 weights for a bfloat16 LM), read beside the program and put
in the program's place.

    python3 -m chipbench.controls --workload <cell> --seeds 1,2,3 \
        [--seconds 51] [--in-place]

Run from the root of a checkout, on the chips the cell asks for.

Without ``--in-place``: for an LM cell each seed runs the cell, and the
sample a run compares is read twice: the program's served tokens
against the float32 reference (the number the run checks) and, at the
same positions, the token the float8 control puts first.  For an MRI
cell each seed's pool of scans is reconstructed by the control and
compared with the float64 reference as a run compares the program.

With ``--in-place``: each seed makes one whole run of the cell through
the harness with the control in the program's place (the LM served by
``LMServer`` with float8-rounded weights; every image of the MRI stream
replaced by the control's reconstruction of its scan) and prints the
run's ``correct`` and its checks, under the configuration's own limits.

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from chipbench import generator, harness


@contextlib.contextmanager
def in_place(system: str):
    """Runs inside the block have the control in the program's place."""
    if system == "lm":
        from chipbench.drivers import lm
        orig = lm.program_weights
        lm.program_weights = lambda ref, c, cell: ref.control_weights(
            orig(ref, c, cell))
        try:
            yield
        finally:
            lm.program_weights = orig
        return
    from repro.core import graph
    from chipbench.reference import mri_recon as ref
    orig_run = graph.Pipeline.run
    made = {}                            # id(scan) -> (scan, control image)

    def control(self, inputs, *a, **kw):
        outs = orig_run(self, inputs, *a, **kw)
        for item, o in zip(inputs, outs):
            if id(item) not in made:
                made[id(item)] = (item, ref.control_recon(
                    item.get_ndarray(0).host, item.get_ndarray(1).host))
            o.get_ndarray(0).host[...] = made[id(item)][1]
        return outs
    graph.Pipeline.run = control
    try:
        yield
    finally:
        graph.Pipeline.run = orig_run


def lm_readings(cell):
    from chipbench.drivers import lm
    readings = {}
    orig = lm._check

    def both(cell, ref, reqs, results, finished):
        readings["program"] = orig(cell, ref, reqs, results, finished)
        c = cell.config
        w = ref.make_weights(c, cell.seed, device=cell.devices[0])
        ctrl = ref.control_weights(w)
        readings["control"] = max(
            float(ref.control_gaps(c, w, ctrl, lm.prompt(reqs[rid],
                                                         c["vocab"]),
                                   results[rid]).max())
            for rid in lm.sample(cell, reqs, finished))
        readings["finished"] = len(finished)
        return readings["program"]
    lm._check = both
    try:
        lm.run(cell)
    finally:
        lm._check = orig
    return readings


def mri_readings(cell):
    from chipbench.drivers import mri
    from chipbench.reference import mri_recon as ref
    errs = [ref.max_rel_err(ref.control_recon(k, s), ref.oracle_recon(k, s))
            for k, s in mri._pool(cell)]
    return {"control": max(errs), "control_min": min(errs)}


def main() -> None:
    root = harness.CHECKOUT
    sys.path.insert(1, str(root / "src"))        # the program under test
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.controls")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--in-place", action="store_true")
    args = ap.parse_args()
    bench = harness.load_benchmark(root)
    entry = harness.find(bench["workloads"], args.workload, "workload")
    cfg = harness.find(bench["configs"], entry["config"], "config")
    config = json.loads((root / cfg["file"]).read_text())
    mix = generator.load_mix(root / "chipbench", entry["traffic"])
    devices = harness.require_chips(int(entry["chips"]))
    harness.enable_compile_cache(root)
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.in_place:
            run = harness.parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)])
            with in_place(config["system"]):
                res = harness.run_cell(run, root=root,
                                       started=time.perf_counter())
            out = {"correct": res["correct"], "checks": res["checks"]}
        else:
            cell = harness.Cell(
                config=config, mix=mix, chips=len(devices), seed=seed,
                seconds=args.seconds, devices=devices,
                started=time.perf_counter(),
                tracer=harness.Tracer(False, 0.0, 0.0))
            read = lm_readings if config["system"] == "lm" else mri_readings
            out = read(cell)
        print(json.dumps({"seed": seed, **out}), flush=True)


if __name__ == "__main__":
    main()
