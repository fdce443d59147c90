"""Arena (contiguous heterogeneous packing) — unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (ALIGN, ArenaLayout, pack_device, pack_host,
                        pack_tree_host, plan_layout, unpack_device,
                        unpack_host, unpack_tree_host)

DTYPES = ["float32", "int8", "int32", "bfloat16", "complex64", "bool", "uint8"]


def _mk(rng, shape, dtype):
    if dtype == "complex64":
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
                ).astype(np.complex64)
    if dtype == "bool":
        return rng.integers(0, 2, shape).astype(bool)
    if dtype == "bfloat16":
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return rng.standard_normal(shape).astype(np.dtype(dtype)) if "float" in dtype \
        else rng.integers(-10, 100, shape).astype(np.dtype(dtype))


def test_alignment_and_order(rng):
    layout = plan_layout([("a", (3, 5), "float32"), ("b", (7,), "int8"),
                          ("c", (2, 2), "complex64")])
    offs = [e.offset for e in layout.entries]
    assert offs == sorted(offs), "placement must be in declaration order"
    for e in layout.entries:
        assert e.offset % ALIGN == 0
    assert layout.total_bytes % ALIGN == 0


def test_roundtrip_host_and_device(rng):
    arrs = {f"x{i}": _mk(rng, (3, 4 + i), dt) for i, dt in enumerate(DTYPES)}
    blob, layout = pack_host(arrs)
    back = unpack_host(blob, layout)
    for k, v in arrs.items():
        np.testing.assert_array_equal(np.asarray(v), back[k])
    dv = unpack_device(jax.device_put(blob), layout)
    for k, v in arrs.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(dv[k]))
    # device re-pack reproduces the identical blob
    reblob = jax.jit(lambda d: pack_device(d, layout))(
        {k: jnp.asarray(np.asarray(v)) for k, v in arrs.items()})
    np.testing.assert_array_equal(np.asarray(reblob), blob)


def test_layout_json_roundtrip():
    layout = plan_layout([("a", (2, 3), "bfloat16"), ("b", (), "int32")])
    back = ArenaLayout.from_json(layout.to_json())
    assert back == layout


def test_pack_tree_roundtrip(rng):
    tree = {"w": {"a": rng.standard_normal((4, 4)).astype(np.float32)},
            "b": [rng.integers(0, 5, (3,)).astype(np.int32),
                  rng.standard_normal((2,)).astype(np.float32)]}
    blob, layout = pack_tree_host(tree)
    back = unpack_tree_host(blob, layout, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)


def test_duplicate_names_rejected():
    with pytest.raises(ValueError):
        plan_layout([("a", (2,), "float32"), ("a", (3,), "int8")])


@given(st.lists(
    st.tuples(
        st.lists(st.integers(1, 7), min_size=0, max_size=3),
        st.sampled_from(["float32", "int8", "int32", "complex64", "bool"])),
    min_size=1, max_size=6))
def test_property_roundtrip(specs):
    rng = np.random.default_rng(1)
    arrs = {f"v{i}": _mk(rng, tuple(shape), dt)
            for i, (shape, dt) in enumerate(specs)}
    blob, layout = pack_host(arrs)
    back = unpack_host(blob, layout)
    for k, v in arrs.items():
        np.testing.assert_array_equal(np.asarray(v), back[k])
    # invariant: entries are disjoint and inside the blob
    spans = sorted((e.offset, e.offset + e.nbytes) for e in layout.entries)
    for (s0, e0), (s1, _) in zip(spans, spans[1:]):
        assert e0 <= s1
    assert spans[-1][1] <= layout.total_bytes


def test_device_view_offsets_past_int32():
    """Arenas over 8 GiB: an entry past 2**31 words is sliced at its exact
    offset, never through a wrapped int32 start index."""
    from repro.core.arena import ArenaEntry, device_view
    e = ArenaEntry("w", (1000, 1000), "bfloat16", 12_000_000_000, 2_000_000)
    text = jax.jit(lambda b: device_view(b, e)).lower(
        jax.ShapeDtypeStruct((3_100_000_000,), np.uint32)).as_text()
    assert "3000000000:3000500000" in text
    assert "dynamic_slice" not in text
