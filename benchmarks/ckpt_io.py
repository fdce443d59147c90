"""Checkpoint I/O: legacy host-gather vs gather-free sharded save/restore.

The legacy format gathers every leaf to the host (``jax.tree.map(
np.asarray, state)`` — O(model size) host traffic serialised through one
buffer) before one monolithic arena write.  The ``sharded-v1`` format
(docs/checkpoint.md) writes one arena blob per device holding only the
unique pieces that device owns, concurrently, and restores by
``device_put``-ing pieces straight to their targets — the full array
never exists on the host in either direction.

Device count is locked at the first jax initialisation, so the measured
run happens in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and a
``(data=2, model=4)`` mesh — the same 2D fold the 8-device tests use.
The child round-trips one state tree through both formats, times each
phase (save / restore / elastic restore onto a ``(4, 2)`` mesh), verifies
every restore bit-identical to the host oracle, and reports the profile's
phase records (``gather`` vs ``shard_write``) as the structural proof of
gather-freedom.  Forced host devices share one CPU, so the wall-clock
deltas are I/O-and-copy accounting, not a parallel-speedup claim.

    PYTHONPATH=src python -m benchmarks.ckpt_io            # full
    PYTHONPATH=src python -m benchmarks.ckpt_io --smoke    # CI smoke
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import List

DEVICES = 8
MODEL_AXIS = 4
FULL_MB = 64          # approx state size for the full run
SMOKE_MB = 2
REPS = 3
SMOKE_REPS = 1


def _child(mb: int, reps: int) -> dict:
    import shutil

    import jax
    import numpy as np

    from repro.ckpt import restore_checkpoint, save_checkpoint
    from repro.core import ProfileParameters
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(jax.devices(), model=MODEL_AXIS)
    NS, P = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
    # three sharding families, sized to roughly mb MB total
    # divisible by 8 so every (data, model) fold of 8 devices divides it
    rows = max(8, int(mb * (1 << 20) // 3 // (4 * 4096)) // 8 * 8)
    rng = np.random.default_rng(0)
    shardings = {
        "rows": NS(mesh, P("data")),
        "cols": NS(mesh, P(None, "model")),
        "rep": NS(mesh, P()),
    }
    host_state = {
        "rows": rng.standard_normal((rows, 4096)).astype(np.float32),
        "cols": rng.standard_normal((rows, 4096)).astype(np.float32),
        "rep": rng.standard_normal((rows, 4096)).astype(np.float32),
    }
    state = {k: jax.device_put(v, shardings[k]) for k, v in host_state.items()}
    jax.block_until_ready(state)
    oracle = jax.tree.map(np.asarray, state)
    nbytes = sum(v.nbytes for v in host_state.values())
    like = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), oracle)
    mesh42 = make_data_mesh(jax.devices(), model=2)
    sh42 = {"rows": NS(mesh42, P("data")), "cols": NS(mesh42, P(None, "model")),
            "rep": NS(mesh42, P())}

    def _check(got):
        for k, v in oracle.items():
            np.testing.assert_array_equal(np.asarray(got[k]), v, err_msg=k)

    out = {"devices": jax.device_count(),
           "mesh": dict(mesh.shape), "state_mb": nbytes / (1 << 20)}
    timings: dict = {}
    for fmt, sharded in (("legacy", False), ("sharded", True)):
        t_save, t_restore, t_elastic = [], [], []
        prof = ProfileParameters(enable=True)
        for rep in range(reps):
            d = tempfile.mkdtemp(prefix=f"ckpt_io_{fmt}_")
            try:
                t0 = time.perf_counter()
                save_checkpoint(d, rep, state, sharded=sharded, profile=prof)
                t_save.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                got = restore_checkpoint(d, like, shardings=shardings)
                jax.block_until_ready(got)
                t_restore.append(time.perf_counter() - t0)
                _check(got)
                t0 = time.perf_counter()
                got42 = restore_checkpoint(d, like, shardings=sh42)
                jax.block_until_ready(got42)
                t_elastic.append(time.perf_counter() - t0)
                _check(got42)
            finally:
                shutil.rmtree(d, ignore_errors=True)
        timings[fmt] = {
            "save_s": min(t_save), "restore_s": min(t_restore),
            "elastic_restore_s": min(t_elastic),
            "gather_s": prof.phase_total("gather"),
            "shard_write_s": prof.phase_total("shard_write"),
        }
    out["timings"] = timings
    # the structural claim: the sharded save never recorded a gather
    out["sharded_save_gather_free"] = timings["sharded"]["gather_s"] == 0.0
    # count shard files once for the record
    d = tempfile.mkdtemp(prefix="ckpt_io_files_")
    try:
        p = save_checkpoint(d, 0, state, sharded=True)
        out["shard_files"] = sorted(
            n for n in os.listdir(p) if n.startswith("shard_"))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def _run_child(mb: int, reps: int) -> dict:
    """The forced-host-device child: a CPU emulation by design, held to the
    CPU so it never contends for an accelerator the parent holds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={DEVICES}"
                        ).strip()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.ckpt_io", "--child",
         str(mb), str(reps)],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if r.returncode != 0:
        raise RuntimeError(f"ckpt_io child failed:\n{r.stdout}\n{r.stderr}")
    return {**json.loads(r.stdout.strip().splitlines()[-1]), "device": "cpu"}


def rows(*, smoke: bool = False) -> List[str]:
    mb = SMOKE_MB if smoke else FULL_MB
    reps = SMOKE_REPS if smoke else REPS
    point = _run_child(mb, reps)
    t = point["timings"]
    out_rows = []
    for fmt in ("legacy", "sharded"):
        for op in ("save", "restore", "elastic_restore"):
            sec = t[fmt][f"{op}_s"]
            out_rows.append(
                f"ckpt_{fmt}_{op},{sec * 1e6:.1f},"
                f"mb={point['state_mb']:.1f};"
                f"mb_per_s={point['state_mb'] / sec:.1f}")
    out_rows.append(
        f"ckpt_sharded_gather_free,0.0,"
        f"gather_s={t['sharded']['gather_s']};"
        f"shard_write_s={t['sharded']['shard_write_s']:.4f};"
        f"shard_files={len(point['shard_files'])}")
    bench = {"name": "ckpt_io", "smoke": smoke, **point}
    print("BENCH " + json.dumps(bench))
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_ckpt_io.json")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)
    return out_rows


def main() -> None:
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        print(json.dumps(_child(int(sys.argv[i + 1]), int(sys.argv[i + 2]))))
        return
    print("name,us_per_call,derived")
    for r in rows(smoke="--smoke" in sys.argv):
        print(r)


if __name__ == "__main__":
    main()
