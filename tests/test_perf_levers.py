"""§Perf hillclimb levers must be numerically equivalent to the baseline
(they only change sharding/layout, never math).  Runs on a 16-fake-device
4x4 mesh in-process via conftest-free XLA flag isolation: these tests run
in a subprocess to control device count."""
import json
import os
import subprocess
import sys

import pytest

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.models import build_model
from repro.models.common import mesh_axes

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
checks = []

def check(arch, **flags):
    cfg = get_smoke(arch)
    m0, m1 = build_model(cfg), build_model(cfg.scaled(**flags))
    params = m0.init_params(jax.random.key(0))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32),
             "labels": jnp.ones((4, 32), jnp.int32)}
    with mesh, mesh_axes(mesh):
        l0, _ = jax.jit(m0.loss_fn)(params, batch)
        l1, _ = jax.jit(m1.loss_fn)(params, batch)
    ok = abs(float(l0) - float(l1)) < 2e-3 * max(1.0, abs(float(l0)))
    checks.append((arch, str(flags), ok, float(l0), float(l1)))

check("qwen3-14b", opt_seq_parallel=True)
check("h2o-danube-1.8b", opt_seq_parallel=True)     # sliding-window masks
check("qwen2-7b", opt_seq_parallel=True)            # qkv-bias
check("zamba2-2.7b", opt_ssd_local=True)
check("zamba2-2.7b", opt_ssd_local=True, opt_seq_parallel=True)
check("granite-moe-1b-a400m", opt_seq_parallel=True)

# decode lever: one-hot cache write == dynamic_update_slice
cfg = get_smoke("qwen3-14b")
m0, m1 = build_model(cfg), build_model(cfg.scaled(opt_local_cache_update=True))
params = m0.init_params(jax.random.key(0))
toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
c0, c1 = m0.init_cache(2, 16), m1.init_cache(2, 16)
with mesh, mesh_axes(mesh):
    l0, c0 = jax.jit(m0.prefill)(params, toks, c0)
    l1, c1 = jax.jit(m1.prefill)(params, toks, c1)
    for t in range(3):
        tok = jnp.argmax(l0, -1).astype(jnp.int32)
        l0, c0 = jax.jit(m0.decode_step)(params, tok, jnp.int32(8 + t), c0)
        l1, c1 = jax.jit(m1.decode_step)(params, tok, jnp.int32(8 + t), c1)
diff = float(jnp.max(jnp.abs(l0 - l1)))
checks.append(("qwen3-decode-local-write", "", diff < 2e-3, diff, 0.0))
import json as _json
print("CHECKS " + _json.dumps(checks))
"""


def test_perf_levers_equivalent_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SNIPPET], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("CHECKS ")][-1]
    checks = json.loads(line[len("CHECKS "):])
    bad = [c for c in checks if not c[2]]
    assert not bad, f"lever numerics diverged: {bad}"
