"""Driver for the MRI reconstruction system (``"system": "mri"``).

The configuration gives the scan shape, the size of the pool of phantom
scans made from the seed, and the reconstruction path.  A closed-loop mix
sends the pool's scans as studies through ``Pipeline.run(mode="stream")``,
one study after another.  The window is timed on the host clock from the
first timed study, every image is read back to the host, and a seeded
sample of the images is compared with the float64 numpy reference once
the window has closed.
"""
from __future__ import annotations

import sys
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import generator
from chipbench.harness import (Cell, Outcome, free_program_state,
                               memory_peak_bytes, span)
from chipbench.reference import mri_recon as ref

#: share of the requests whose image is compared with the reference
SAMPLE_SHARE = 0.25


def _pool(cell: Cell):
    c = cell.config
    seeds = generator.rng(cell.seed, 10).integers(0, 2 ** 63, c["pool"])
    return [ref.synthetic_kdata(c["frames"], c["coils"], c["height"],
                                c["width"], seed=int(s))[:2] for s in seeds]


def _sampled(seed: int, index: int) -> bool:
    return generator.rng(seed, 5, index).random() < SAMPLE_SHARE


def _app_and_pipe(cell: Cell):
    from repro.core import CLapp, DeviceTraits, Pipeline
    from repro.processes import SimpleMRIRecon
    app = CLapp().init(device_traits=DeviceTraits(count=cell.chips),
                       model_axis=1)
    recon = cell.config["recon"]
    pipe = Pipeline(app) | SimpleMRIRecon(
        app, mode=recon["mode"], use_pallas=recon["use_pallas"])
    return app, pipe


def run(cell: Cell) -> Outcome:
    from repro.core import KData
    scans = _pool(cell)
    items = [KData({"kdata": k, "sensitivity_maps": s}) for k, s in scans]
    app, pipe = _app_and_pipe(cell)
    counters, kept, metrics = _stream(cell, pipe, items)
    peak = memory_peak_bytes(cell.devices)
    from repro.launch.roofline import default_chooser
    print("kernel backends chosen: " + ", ".join(
        f"{r.kernel}={r.backend}" for r in default_chooser().records()),
        file=sys.stderr)
    del pipe, app, items
    free_program_state()

    want: Dict[int, np.ndarray] = {}
    err = 0.0
    for item, img in kept:
        if item not in want:
            want[item] = ref.oracle_recon(*scans[item])
        err = max(err, ref.max_rel_err(img, want[item]))
    limits = cell.config["limits"]
    checks = {"mri_max_rel_err": (err, limits["mri_max_rel_err"]),
              "no_images_compared": (float(not kept), 0.0),
              "missing_responses": (float(counters["missing"]), 0.0),
              "chips_without_output": (
                  float(counters.get("chips_without_output", 0)), 0.0)}
    return Outcome(metrics=metrics, counters=counters, checks=checks,
                   attempted=counters["attempted"],
                   failed=counters["missing"], memory_peak_bytes=peak)


def _stream(cell: Cell, pipe, items):
    """Closed loop: studies back to back, each one ``pipe.run`` call."""
    mix = cell.mix
    batch, sharded = int(mix["batch"]), bool(mix.get("sharded", False))
    groups = generator.closed_groups(mix, cell.seed, len(items))

    def one(group):
        return pipe.run([items[r.item] for r in group], mode="stream",
                        batch=batch, sharded=sharded)

    with span("warmup"):
        one(next(groups))              # compiles, calibrates, fills caches
    t0 = time.perf_counter()
    setup_s = t0 - cell.started
    records: List[Dict[str, Any]] = []
    kept = []
    used = set()
    scans = missing = 0
    while True:
        cell.tracer.tick(time.perf_counter() - t0)
        group = next(groups)
        a, c = time.perf_counter(), time.process_time()
        with span("pipe.run"):
            outs = one(group)
        b, d = time.perf_counter(), time.process_time()
        records.append({"t0": a - t0, "t1": b - t0, "n": len(group),
                        "cpu_s": d - c, "a": a, "b": b})
        for r, o in zip(group, outs):
            used |= {d.id for d in o.device_blob.devices()}
            if _sampled(cell.seed, r.index):
                kept.append((r.item, o.get_ndarray(0).host))
        scans += len(outs)
        missing += len(group) - len(outs)
        if b - t0 >= cell.seconds:
            break
    cell.tracer.stop()
    for g in records:
        g["traced"] = cell.tracer.covers(g.pop("a"), g.pop("b"))
    window_s = records[-1]["t1"]
    took = sorted(g["t1"] - g["t0"] for g in records)
    print(f"studies: {len(took)} in {window_s:.3f} s, each {took[0]:.3f} / "
          f"{took[len(took) // 2]:.3f} / {took[-1]:.3f} s (min / median / "
          f"max)", file=sys.stderr)
    counters = {"groups": records, "scans": scans, "window_s": window_s,
                "attempted": scans + missing, "missing": missing,
                "chips_without_output": cell.chips - len(used)}
    metrics = {"setup_s": setup_s, "mri_scans_per_s": scans / window_s}
    return counters, kept, metrics
