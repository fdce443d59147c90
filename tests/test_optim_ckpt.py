"""Optimizer math, schedules, gradient compression, checkpoint roundtrips."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.ckpt import (CheckpointManager, latest_step, restore_checkpoint,
                        save_checkpoint)
from repro.optim import (AdamWConfig, Schedule, adamw_init, adamw_update,
                         ef_int8_compress, global_norm, make_schedule)


def test_adamw_matches_reference(rng):
    p = {"w": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)}
    g = {"w": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)}
    cfg = AdamWConfig(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
                      clip_norm=None, schedule=Schedule(kind="constant",
                                                        base_lr=1e-2,
                                                        warmup_steps=0))
    st_ = adamw_init(p)
    new_p, new_st, m = adamw_update(p, g, st_, cfg)
    # closed-form first step: m=(1-b1)g, v=(1-b2)g^2, mhat=g, vhat=g^2
    gw = np.asarray(g["w"])
    expect = np.asarray(p["w"]) - 1e-2 * (gw / (np.abs(gw) + 1e-8)
                                          + 0.01 * np.asarray(p["w"]))
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-5, atol=1e-6)
    assert int(new_st["step"]) == 1


def test_grad_clipping():
    p = {"w": jnp.ones((10,), jnp.float32)}
    g = {"w": jnp.full((10,), 100.0, jnp.float32)}
    cfg = AdamWConfig(clip_norm=1.0, weight_decay=0.0,
                      schedule=Schedule(kind="constant", base_lr=1.0, warmup_steps=0))
    st_ = adamw_init(p)
    _, _, m = adamw_update(p, g, st_, cfg)
    assert float(m["grad_norm"]) > 100.0  # reported pre-clip norm
    # post-clip effective norm must be 1: m = g*scale, |delta| bounded
    assert np.isfinite(float(m["lr"]))


def test_schedule_shapes():
    s = make_schedule("cosine", base_lr=1e-3, warmup_steps=10,
                      total_steps=100, min_lr=1e-4)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1e-3) < 1e-9
    assert float(s(100)) == pytest.approx(1e-4, rel=1e-3)
    lin = make_schedule("linear", base_lr=1e-3, warmup_steps=0,
                        total_steps=100, min_lr=0.0)
    assert float(lin(50)) == pytest.approx(5e-4, rel=1e-3)


@given(st.integers(0, 2 ** 31 - 1))
def test_ef_compress_error_feedback_telescopes(seed):
    """sum of dequantized grads + final error == sum of true grads."""
    rng = np.random.default_rng(seed)
    err = jnp.zeros((32,), jnp.float32)
    total_true = np.zeros(32, np.float32)
    total_deq = np.zeros(32, np.float32)
    for _ in range(5):
        g = jnp.asarray(rng.standard_normal(32), jnp.float32)
        q, scale, err = ef_int8_compress(g, err)
        total_true += np.asarray(g)
        total_deq += np.asarray(q, np.float32) * float(scale)
    np.testing.assert_allclose(total_deq + np.asarray(err), total_true,
                               rtol=1e-4, atol=1e-4)


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": jnp.full((4,), 2.0)}
    assert float(global_norm(t)) == pytest.approx(np.sqrt(3 + 16))


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def _state(rng):
    return {"params": {"w": rng.standard_normal((8, 4)).astype(np.float32),
                       "b": rng.standard_normal((4,)).astype(np.float32)},
            "opt": {"m": rng.standard_normal((8, 4)).astype(np.float32),
                    "step": np.asarray(7, np.int32)}}


def test_checkpoint_roundtrip(tmp_path, rng):
    state = _state(rng)
    save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(np.zeros_like, state)
    back = restore_checkpoint(str(tmp_path), like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_cleanup_and_latest(tmp_path, rng):
    state = _state(rng)
    for step in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), step, state, keep_last=2)
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path))
    assert steps == [3, 4]
    assert latest_step(str(tmp_path)) == 4


def test_checkpoint_manager_async(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), interval=2, keep_last=5)
    state = _state(rng)
    saved = [mgr.maybe_save(s, state) for s in range(1, 7)]
    mgr.wait()
    assert saved == [False, True, False, True, False, True]
    assert mgr.latest() == 6


def test_checkpoint_shape_mismatch_rejected(tmp_path, rng):
    state = _state(rng)
    save_checkpoint(str(tmp_path), 1, state)
    bad = jax.tree.map(np.zeros_like, state)
    bad["params"]["w"] = np.zeros((9, 4), np.float32)
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), bad)


def test_elastic_restore_resharding(tmp_path, rng):
    """Blob saved without shardings restores with target shardings applied."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = _state(rng)
    save_checkpoint(str(tmp_path), 3, state)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(lambda a: NamedSharding(mesh, P()), state)
    back = restore_checkpoint(str(tmp_path), jax.tree.map(np.zeros_like, state),
                              shardings=sh)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, np.asarray(b))
