"""Fault-injection tests for sharded, gather-free checkpointing (PR 10).

The sharded format's commit protocol — per-shard tmp+rename with
``manifest.json`` written LAST — makes a crash at ANY point leave either a
complete checkpoint or a detectably-torn one.  These tests inject the torn
states a crash can produce (truncated blob, missing manifest, stale
``step_*.tmp`` litter) and pin down the recovery contract:

* ``latest_step`` never returns a torn step — discovery falls back to the
  newest COMPLETE checkpoint;
* restoring a torn step explicitly raises :class:`CheckpointCorruptError`
  naming the step and the missing piece (the old behaviour was an opaque
  ``FileNotFoundError`` from ``np.fromfile``);
* ``cleanup`` reaps stale ``.tmp`` directories along with old steps;
* the sharded save never gathers to the host (no ``"gather"`` profile
  phase — each piece is a LOCAL device-to-host copy);
* ``CheckpointManager(sharded=True)`` keeps the async double-buffered
  contract, and ``PipelineReplica.warm_start`` restores a checkpoint into
  a live app Data for replica spin-up.

Single-device versions run here in tier-1; the multi-device round-trips
(8 shards, elastic restore across mesh shapes) live in
``test_mesh_stream.py``'s forced-8-device section.
"""
import json
import os
import shutil

import jax
import numpy as np
import pytest

from repro.ckpt import (CheckpointCorruptError, CheckpointFormatError,
                        CheckpointManager, cleanup, latest_step,
                        restore_checkpoint, save_checkpoint)
from repro.core import CLapp, Data, Pipeline, Port, Process, ProfileParameters


def _state(rng):
    return {
        "w": rng.standard_normal((4, 8)).astype(np.float32),
        "scale": np.float32(2.5),
        "mask": (rng.integers(0, 2, (6,)) > 0),
        "empty": np.zeros((0, 3), np.float16),
        "z": (rng.standard_normal((3, 3))
              + 1j * rng.standard_normal((3, 3))).astype(np.complex64),
    }


def _like(state):
    return jax.tree.map(
        lambda a: np.zeros(np.shape(a), np.asarray(a).dtype), state)


def _assert_equal_tree(got, want):
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype, f"{k}: dtype {g.dtype} != {w.dtype}"
        np.testing.assert_array_equal(g, w, err_msg=k)


# ---------------------------------------------------------------------------
# sharded format: round-trip, no gather, no tmp litter
# ---------------------------------------------------------------------------

def test_sharded_roundtrip_no_gather(tmp_path, rng):
    want = _state(rng)
    state = jax.tree.map(jax.device_put, want)
    prof = ProfileParameters(enable=True)
    path = save_checkpoint(str(tmp_path), 5, state, sharded=True,
                           profile=prof)
    # gather-free by construction: the ONLY d2h copies are per-shard local
    # reads — the "gather" phase (legacy full-tree host gather) never fires
    assert prof.phase_total("gather") == 0.0
    assert prof.phase_total("shard_write") > 0
    assert os.path.exists(os.path.join(path, "manifest.json"))
    assert not [n for n in os.listdir(path) if n.endswith(".tmp")], \
        "commit must leave no per-file tmp litter"
    got = restore_checkpoint(str(tmp_path), _like(state))
    _assert_equal_tree(got, want)
    # dtype-preserving empty leaf (zero payload bytes, dtype from manifest)
    assert got["empty"].shape == (0, 3) and got["empty"].dtype == np.float16


def test_legacy_save_records_gather_phase(tmp_path, rng):
    want = _state(rng)
    state = jax.tree.map(jax.device_put, want)
    prof = ProfileParameters(enable=True)
    save_checkpoint(str(tmp_path), 1, state, profile=prof)
    assert prof.phase_total("gather") > 0
    got = restore_checkpoint(str(tmp_path), _like(state))
    _assert_equal_tree(got, want)


# ---------------------------------------------------------------------------
# fault injection: torn checkpoints are skipped, explicit restore is typed
# ---------------------------------------------------------------------------

def _blob_of(step_dir):
    """The one payload blob of a single-device sharded checkpoint (every
    leaf is replicated -> host.arena)."""
    return os.path.join(step_dir, "host.arena")


def test_truncated_blob_skipped_and_typed(tmp_path, rng):
    state = _state(rng)
    save_checkpoint(str(tmp_path), 1, state, sharded=True)
    p2 = save_checkpoint(str(tmp_path), 2, state, sharded=True)
    with open(_blob_of(p2), "r+b") as f:
        f.truncate(3)                       # crash mid-write, post-rename
    assert latest_step(str(tmp_path)) == 1, \
        "a size-mismatched blob must disqualify the step"
    got = restore_checkpoint(str(tmp_path), _like(state))   # falls back to 1
    _assert_equal_tree(got, state)
    with pytest.raises(CheckpointCorruptError) as ei:
        restore_checkpoint(str(tmp_path), _like(state), step=2)
    assert "step 2" in str(ei.value) and "host.arena" in str(ei.value)
    assert ei.value.step == 2


def test_missing_manifest_skipped(tmp_path, rng):
    state = _state(rng)
    save_checkpoint(str(tmp_path), 1, state, sharded=True)
    p2 = save_checkpoint(str(tmp_path), 2, state, sharded=True)
    os.remove(os.path.join(p2, "manifest.json"))   # crash before commit
    assert latest_step(str(tmp_path)) == 1
    with pytest.raises(CheckpointCorruptError) as ei:
        restore_checkpoint(str(tmp_path), _like(state), step=2)
    assert ei.value.step == 2


def test_legacy_missing_blob_typed_error(tmp_path, rng):
    """The PR-10 bugfix: a legacy checkpoint whose ``state.arena`` vanished
    used to surface as an opaque ``FileNotFoundError`` from ``np.fromfile``
    — now it is a :class:`CheckpointCorruptError` naming step and piece."""
    state = _state(rng)
    p1 = save_checkpoint(str(tmp_path), 1, state)
    os.remove(os.path.join(p1, "state.arena"))
    assert latest_step(str(tmp_path)) is None
    with pytest.raises(CheckpointCorruptError) as ei:
        restore_checkpoint(str(tmp_path), _like(state), step=1)
    assert "step 1" in str(ei.value) and "state.arena" in str(ei.value)
    # and with no complete checkpoint at all, discovery still says so
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path), _like(state))


def _plant_v1(path: str, sharded: bool, named) -> None:
    """Rewrite a checkpoint just saved as the writer before the planar
    sub-word codec left it: the old format marker (``sharded-v1``; no
    marker in ``layout.json``) and every leaf in numpy's bytes, 16-bit
    items interleaved two to a word."""
    from repro.core.arena import ArenaLayout
    if sharded:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        manifest["format"] = "sharded-v1"
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        layout, blob_file = manifest["host"]["layout"], "host.arena"
    else:
        with open(os.path.join(path, "layout.json")) as f:
            layout = json.load(f)
        del layout["format"]
        with open(os.path.join(path, "layout.json"), "w") as f:
            json.dump(layout, f)
        blob_file = "state.arena"
    layout = ArenaLayout.from_json(json.dumps(layout))
    blob = np.zeros(layout.total_bytes, np.uint8)
    for e in layout.entries:
        raw = np.ascontiguousarray(named[e.name]).view(np.uint8).reshape(-1)
        blob[e.offset:e.offset + e.nbytes] = raw
    blob.tofile(os.path.join(path, blob_file))


@pytest.mark.parametrize("sharded", [False, True], ids=["legacy", "sharded"])
def test_old_subword_codec_checkpoint_refused(tmp_path, rng, sharded):
    """A checkpoint whose bf16 leaf was written with the interleaved
    sub-word codec is refused with an error naming the codec change,
    never read back as wrong numbers."""
    import ml_dtypes
    state = {"w": rng.standard_normal((4, 8)).astype(ml_dtypes.bfloat16),
             "b": rng.standard_normal((3,)).astype(np.float32)}
    path = save_checkpoint(str(tmp_path), 1, state, sharded=sharded)
    _plant_v1(path, sharded, {"['w']": state["w"], "['b']": state["b"]})
    assert latest_step(str(tmp_path)) == 1
    with pytest.raises(CheckpointFormatError) as ei:
        restore_checkpoint(str(tmp_path), _like(state))
    msg = str(ei.value)
    assert "interleaved" in msg and "planar" in msg and "['w']" in msg
    assert ei.value.step == 1


@pytest.mark.parametrize("sharded", [False, True], ids=["legacy", "sharded"])
def test_old_format_with_word_sized_leaves_restores(tmp_path, rng, sharded):
    """Leaves of 4 bytes or more are stored alike before and after the
    codec change, so such an old checkpoint still restores exactly."""
    state = {"w": rng.standard_normal((4, 8)).astype(np.float32),
             "i": rng.integers(0, 9, (5,)).astype(np.int32)}
    path = save_checkpoint(str(tmp_path), 1, state, sharded=sharded)
    _plant_v1(path, sharded, {"['w']": state["w"], "['i']": state["i"]})
    _assert_equal_tree(restore_checkpoint(str(tmp_path), _like(state)), state)


def test_stale_tmp_ignored_and_reaped(tmp_path, rng):
    state = _state(rng)
    save_checkpoint(str(tmp_path), 3, state, sharded=True)
    save_checkpoint(str(tmp_path), 4, state, sharded=True)
    stale = os.path.join(str(tmp_path), "step_0000000099.tmp")
    os.makedirs(stale)
    with open(os.path.join(stale, "shard_00000.arena"), "wb") as f:
        f.write(b"\x00" * 16)
    assert latest_step(str(tmp_path)) == 4, ".tmp dirs are not checkpoints"
    cleanup(str(tmp_path), keep_last=1)
    assert not os.path.exists(stale), "cleanup must reap stale .tmp dirs"
    assert sorted(os.listdir(str(tmp_path))) == ["step_0000000004"]


# ---------------------------------------------------------------------------
# CheckpointManager(sharded=True)
# ---------------------------------------------------------------------------

def test_manager_sharded_async_roundtrip(tmp_path, rng):
    want = _state(rng)
    state = jax.tree.map(jax.device_put, want)
    mgr = CheckpointManager(str(tmp_path), interval=1, keep_last=2,
                            sharded=True)
    for step in (1, 2, 3):
        assert mgr.maybe_save(step, state)
    mgr.wait()
    assert mgr.latest() == 3
    _assert_equal_tree(mgr.restore(_like(state)), want)
    kept = sorted(n for n in os.listdir(str(tmp_path)))
    assert kept == ["step_0000000002", "step_0000000003"]


def test_manager_falls_back_past_torn_step(tmp_path, rng):
    state = _state(rng)
    mgr = CheckpointManager(str(tmp_path), interval=1, sharded=True,
                            async_save=False)
    mgr.maybe_save(1, state)
    # fabricate the torn step a crash mid-commit leaves behind: the dir
    # was renamed into place but the manifest never landed
    torn = os.path.join(str(tmp_path), "step_0000000002")
    os.makedirs(torn)
    with open(os.path.join(torn, "host.arena"), "wb") as f:
        f.write(b"\x01" * 8)
    assert mgr.latest() == 1
    _assert_equal_tree(mgr.restore(_like(state)), state)


# ---------------------------------------------------------------------------
# replica spin-up: PipelineReplica.warm_start
# ---------------------------------------------------------------------------

class _Bias(Process):
    ports = {"in": Port(names=("img",)), "out": Port(names=("img",)),
             "bias": Port(names=("img",), optional=True)}

    def apply(self, views, aux, params):
        return {"img": views["img"] + aux["bias"]["img"]}


def test_warm_start_restores_aux_from_checkpoint(tmp_path, rng):
    from repro.serve import PipelineReplica

    bias = rng.standard_normal((8, 8)).astype(np.float32)
    ckpt_dir = str(tmp_path / "ckpt")
    save_checkpoint(ckpt_dir, 7, {"img": bias}, sharded=True)
    # plus a newer torn step: spin-up must skip it for the complete one
    torn = os.path.join(ckpt_dir, "step_0000000009")
    os.makedirs(torn)

    app = CLapp().init()
    node = _Bias(app).bind(bias=Data({"img": np.zeros((8, 8), np.float32)}))
    pipe = Pipeline(app) | node
    x = rng.standard_normal((8, 8)).astype(np.float32)
    out0 = pipe.run(Data({"img": x}))
    np.testing.assert_array_equal(out0.get_ndarray(0).host, x)  # zero bias

    server = pipe.serve(batch=2)
    try:
        rep = PipelineReplica("r0", server)
        step = rep.warm_start(ckpt_dir, node.process.aux_handles["bias"])
        assert step == 7
        rid = server.submit(Data({"img": x}))
        (res,) = server.drain()
        assert res.rid == rid
        np.testing.assert_array_equal(
            np.asarray(res.data.device_view("img")), x + bias)
    finally:
        server.close()
    # launch mode reads the restored aux live too
    out1 = pipe.run(Data({"img": x}))
    np.testing.assert_array_equal(out1.get_ndarray(0).host, x + bias)


def test_warm_start_before_first_traffic(tmp_path, rng):
    """True spin-up: a fresh replica restores BEFORE its server ever built
    (no aux handle exists yet) by passing the bound Data itself — the
    restored hosts ride the build's own upload on first traffic."""
    from repro.serve import PipelineReplica

    bias = rng.standard_normal((8, 8)).astype(np.float32)
    ckpt_dir = str(tmp_path / "ckpt")
    save_checkpoint(ckpt_dir, 3, {"img": bias}, sharded=True)

    app = CLapp().init()
    bias_data = Data({"img": np.zeros((8, 8), np.float32)})
    node = _Bias(app).bind(bias=bias_data)
    pipe = Pipeline(app) | node
    server = pipe.serve(batch=2)
    try:
        rep = PipelineReplica("r0", server)
        assert rep.warm_start(ckpt_dir, bias_data) == 3   # pre-build
        x = rng.standard_normal((8, 8)).astype(np.float32)
        server.submit(Data({"img": x}))                   # first build here
        (res,) = server.drain()
        np.testing.assert_array_equal(
            np.asarray(res.data.device_view("img")), x + bias)
    finally:
        server.close()
