"""A benchmark root at CPU-test size: the real harness, drivers, readers
and references, with configurations and mixes cut to what a test run can
hold.  Every file is written the way a later change adds a cell: data
files and entries, no code.  Each tiny configuration keeps the limits of
the real one it stands for, and its precision."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
#: a trace recorded on the chip, standing in for the CPU's
RECORDED_TRACE = BENCH / "testdata" / "tiny.xplane.pb"


def _limits(name: str):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text()
                      )["limits"]


MRI = {
    "system": "mri", "name": "mri-tiny", "source": "test size",
    "frames": 2, "coils": 3, "height": 24, "width": 20, "dtype": "complex64",
    "pool": 4, "recon": {"mode": "fused_pallas", "use_pallas": "auto"},
    "reference": "mri_recon", "limits": _limits("mri-cine"),
    "reduced": [], "assumed": {},
}
LM = {
    "system": "lm", "name": "danube-tiny", "source": "test size",
    "family": "dense", "n_layers": 4, "d_model": 128, "n_heads": 4,
    "n_kv_heads": 2, "d_head": 32, "d_ff": 256, "vocab": 512,
    "window": 4096, "rope_theta": 10000.0, "norm_eps": 1e-6,
    "param_dtype": "bfloat16", "dtype": "bfloat16", "param_bytes": 2,
    "cache_bytes": 2, "batch": 2, "max_len": 64,
    "reference": "dense_decoder", "limits": _limits("danube-1.8b"),
    "reduced": [], "assumed": {},
}
MIXES = {
    "t-stream": {"loop": "closed", "group": 6, "batch": 4, "sharded": False},
    "t-stream-4chip": {"loop": "closed", "group": 8, "batch": 4,
                       "sharded": True},
    "t-offline": {"loop": "closed", "group": 2, "prompt_lens": [24, 8, 16],
                  "new_tokens": 24},
}
CELLS = [
    {"name": "tiny.stream", "config": "mri-tiny", "traffic": "t-stream",
     "chips": 1, "why": "test"},
    {"name": "tiny.stream-4chip", "config": "mri-tiny",
     "traffic": "t-stream-4chip", "chips": 4, "why": "test"},
    {"name": "tiny.offline", "config": "danube-tiny",
     "traffic": "t-offline", "chips": 1, "why": "test"},
]
#: the real cell whose metrics each tiny cell reports
TWIN = {"tiny.stream": "mri-cine.stream",
        "tiny.stream-4chip": "mri-cine.stream-4chip",
        "tiny.offline": "danube-1.8b.offline"}


def make_root(tmp: Path) -> Path:
    """A checkout holding the real benchmark plus the tiny cells."""
    shutil.copytree(BENCH, tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for cfg in (MRI, LM):
        path = tmp / "chipbench" / "configs" / f"{cfg['name']}.json"
        path.write_text(json.dumps(cfg))
        bench["configs"].append({
            "name": cfg["name"], "source": "test", "reduced": [],
            "file": f"chipbench/configs/{cfg['name']}.json", "why": "test"})
    for name, mix in MIXES.items():
        (tmp / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench["workloads"] += CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:      # each tiny cell reports what its twin does
            m["workloads"] += [t for t, real in TWIN.items()
                               if real in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cpu_patches():
    """(owner, attribute, value) for a run on the CPU: the harness without
    its look for a chip, the peak table's device kind, the persistent
    cache or a chip trace (a recorded one stands in)."""
    from chipbench import harness, trace_reduce
    from chipbench.peaks import PEAKS

    def summary(self):
        shutil.rmtree(self.dir or "", ignore_errors=True)
        if self.state != "done":
            return None
        return trace_reduce.summarize(
            trace_reduce.read_xplane(str(RECORDED_TRACE)))
    return [(harness, "peaks_for", lambda kind: PEAKS["TPU v5 lite"]),
            (harness, "enable_compile_cache", lambda root: None),
            (harness.Tracer, "summary", summary)]


def run(root: Path, cell: str, trace: int = 0, seed: int = 2 ** 31 + 9,
        seconds: float = 1.5):
    """One run of ``cell`` through the harness on the CPU's devices."""
    import time

    import jax
    from chipbench import harness
    args = harness.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    return harness.run_cell(args, root=root, started=time.perf_counter(),
                            chips_fn=lambda n: jax.devices()[:n])
