"""Property-based round-trips for the arena packers and the sharded
checkpoint format, plus plain pins of the dtype-preserving empty-leaf
contract.

Properties under test:

* ``plan_layout``: offsets are 128-byte aligned, entries never overlap,
  placement order is the spec order, ``total_bytes`` covers the last
  entry;
* ``pack_host``/``unpack_host`` and ``pack_tree_host``/
  ``unpack_tree_host`` round-trip arbitrary dtype/shape mixes (bool,
  complex, float16, size-0 arrays, 0-d scalars) bit-exactly with dtypes
  preserved;
* a sharded checkpoint save → restore round-trips an arbitrary nested
  state tree and its manifest accounts for every leaf exactly once.
"""
import json
import os
import shutil
import tempfile

import jax
import numpy as np
from hypothesis import given, strategies as st

from repro.ckpt import restore_checkpoint, save_checkpoint
from repro.core.arena import (ALIGN, pack_host, pack_tree_host, plan_layout,
                              unpack_host, unpack_tree_host)

_DTYPES = ["float32", "float16", "int32", "int8", "uint8", "bool",
           "complex64"]


def _rand_array(rng, shape, dtype):
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return rng.integers(0, 2, shape) > 0
    if dt.kind == "c":
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(dt)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return rng.integers(info.min, info.max, shape, endpoint=True).astype(dt)
    return rng.standard_normal(shape).astype(dt)


def _draw_arrays(data, min_arrays=1):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**32 - 1)))
    n = data.draw(st.integers(min_arrays, 6))
    arrays = {}
    for i in range(n):
        ndim = data.draw(st.integers(0, 3))
        shape = tuple(data.draw(st.integers(0, 5)) for _ in range(ndim))
        dtype = data.draw(st.sampled_from(_DTYPES))
        arrays[f"a{i}"] = _rand_array(rng, shape, dtype)
    return arrays


@given(st.data())
def test_plan_layout_alignment_and_disjointness(data):
    arrays = _draw_arrays(data)
    layout = plan_layout((k, v.shape, v.dtype) for k, v in arrays.items())
    end = 0
    for e, (k, v) in zip(layout.entries, arrays.items()):
        assert e.name == k, "placement follows spec order"
        assert e.offset % ALIGN == 0
        assert e.offset >= end, "entries must not overlap"
        assert e.nbytes == v.nbytes
        end = e.offset + e.nbytes
    assert layout.total_bytes >= end
    assert layout.total_bytes % ALIGN == 0


@given(st.data())
def test_pack_unpack_host_roundtrip(data):
    arrays = _draw_arrays(data)
    blob, layout = pack_host(arrays)
    assert blob.dtype == np.uint32 and blob.nbytes == layout.total_bytes
    back = unpack_host(blob, layout)
    assert set(back) == set(arrays)
    for k, v in arrays.items():
        assert back[k].dtype == v.dtype, f"{k}: dtype must survive"
        assert back[k].shape == v.shape
        np.testing.assert_array_equal(back[k], v, err_msg=k)


def _draw_tree(data, arrays):
    """Wrap the arrays into a random nested dict/list structure."""
    names = list(arrays)
    k = data.draw(st.integers(0, len(names)))
    inner, outer = names[:k], names[k:]
    tree = {n: arrays[n] for n in outer}
    if inner:
        tree["nested"] = {"leaves": [arrays[n] for n in inner]}
    return tree


@given(st.data())
def test_pack_unpack_tree_roundtrip(data):
    arrays = _draw_arrays(data)
    tree = _draw_tree(data, arrays)
    blob, layout = pack_tree_host(tree)
    back = unpack_tree_host(blob, layout, tree)
    flat_w, td_w = jax.tree_util.tree_flatten(tree)
    flat_g, td_g = jax.tree_util.tree_flatten(back)
    assert td_w == td_g, "tree structure must survive"
    for w, g in zip(flat_w, flat_g):
        assert np.asarray(g).dtype == np.asarray(w).dtype
        np.testing.assert_array_equal(g, w)


@given(st.data())
def test_sharded_checkpoint_roundtrip_and_manifest(data):
    arrays = _draw_arrays(data)
    tree = _draw_tree(data, arrays)
    step = data.draw(st.integers(0, 10**6))
    directory = tempfile.mkdtemp(prefix="ckpt_props_")
    try:
        path = save_checkpoint(directory, step, tree, sharded=True)
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["format"] == "sharded-v2"
        assert manifest["step"] == step
        # every leaf accounted for exactly once
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        names = {jax.tree_util.keystr(p) for p, _ in flat}
        assert {l["name"] for l in manifest["leaves"]} == names
        assert len(manifest["leaves"]) == len(flat)

        like = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree),
            [np.zeros(np.shape(l), np.asarray(l).dtype)
             for l in jax.tree_util.tree_leaves(tree)])
        back = restore_checkpoint(directory, like, step=step)
        for (pw, w), g in zip(flat, jax.tree_util.tree_leaves(back)):
            assert np.asarray(g).dtype == np.asarray(w).dtype
            np.testing.assert_array_equal(
                g, w, err_msg=jax.tree_util.keystr(pw))
    finally:
        shutil.rmtree(directory, ignore_errors=True)


# ---------------------------------------------------------------------------
# always-on (no hypothesis) pins for the headline invariants
# ---------------------------------------------------------------------------

def test_empty_leaf_preserves_dtype_both_formats(tmp_path):
    state = {"e16": np.zeros((0, 4), np.float16),
             "e_c": np.zeros((3, 0), np.complex64),
             "s": np.float32(1.5)}
    for sharded, sub in ((False, "legacy"), (True, "sharded")):
        d = str(tmp_path / sub)
        save_checkpoint(d, 1, state, sharded=sharded)
        like = jax.tree.map(
            lambda a: np.zeros(np.shape(a), np.asarray(a).dtype), state)
        back = restore_checkpoint(d, like)
        assert back["e16"].dtype == np.float16 and back["e16"].shape == (0, 4)
        assert back["e_c"].dtype == np.complex64 and back["e_c"].shape == (3, 0)
        np.testing.assert_array_equal(back["s"], state["s"])


def test_zero_copy_unpack_views(tmp_path):
    """unpack_host returns views into the blob, not copies — the paper's
    zero-copy contract for host-side arena reads."""
    arrays = {"a": np.arange(8, dtype=np.float32)}
    blob, layout = pack_host(arrays)
    views = unpack_host(blob, layout)
    assert views["a"].base is not None
    off = layout.entry("a").offset      # bytes into the word blob
    blob.view(np.uint8)[off:off + 4] = \
        np.frombuffer(np.float32(99.0).tobytes(), np.uint8)
    assert views["a"][0] == 99.0
