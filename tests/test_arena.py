"""Arena (contiguous heterogeneous packing) — unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (ALIGN, ArenaLayout, pack_device, pack_host,
                        pack_tree_host, plan_layout, trace, unpack_device,
                        unpack_host, unpack_tree_host, write_host)

DTYPES = ["float32", "int8", "int32", "bfloat16", "complex64", "bool", "uint8",
          "float16", "int16", "uint16"]
#: entries narrower than a word, stored planar by lanes
SUBWORD = ["bfloat16", "float16", "int16", "uint16", "int8", "uint8", "bool"]


def _mk(rng, shape, dtype):
    if dtype == "complex64":
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
                ).astype(np.complex64)
    if dtype == "bool":
        return rng.integers(0, 2, shape).astype(bool)
    if dtype == "bfloat16":
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    if "float" in dtype:
        return rng.standard_normal(shape).astype(np.dtype(dtype))
    info = np.iinfo(np.dtype(dtype))
    return rng.integers(info.min, info.max, shape, endpoint=True).astype(dtype)


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
        st.sampled_from(DTYPES)),
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


def _np(x) -> np.ndarray:
    return np.asarray(x)


@pytest.mark.parametrize("shape", [(0,), (1,), (2,), (3,), (127,), (128,),
                                   (129,), (1025,), (), (3, 5, 7)],
                         ids=lambda s: "x".join(map(str, s)) or "0d")
@pytest.mark.parametrize("dtype", SUBWORD)
def test_subword_entries_pack_alike_on_host_and_device(rng, dtype, shape):
    """A sub-word entry between word-sized neighbours: ``pack_host`` and
    ``pack_device`` write the same words, and the items come back from
    host -> device -> host unchanged."""
    arrs = {"before": _mk(rng, (3,), "float32"), "x": _mk(rng, shape, dtype),
            "after": _mk(rng, (2,), "complex64")}
    blob, layout = pack_host(arrs)
    on_device = jax.jit(lambda d: pack_device(d, layout))(
        {k: jnp.asarray(_np(v)) for k, v in arrs.items()})
    np.testing.assert_array_equal(_np(on_device), blob)
    repacked = jax.jit(lambda b: pack_device(unpack_device(b, layout),
                                             layout))(jax.device_put(blob))
    back = unpack_host(_np(repacked), layout)
    for k, v in arrs.items():
        assert back[k].dtype == _np(v).dtype and back[k].shape == _np(v).shape
        np.testing.assert_array_equal(back[k], _np(v))


@pytest.mark.parametrize("dtype", SUBWORD)
def test_host_codec_chunks_write_the_same_words(rng, dtype, monkeypatch):
    """``pack_host`` writes a sub-word entry a chunk of words at a time;
    chunk edges that fall inside a lane and past its last item change no
    word."""
    from repro.core import arena
    x = _mk(rng, (1025,), dtype)
    whole, layout = pack_host({"x": x})
    monkeypatch.setattr(arena, "_CHUNK", 7)
    chunked, _ = pack_host({"x": x})
    np.testing.assert_array_equal(chunked, whole)
    np.testing.assert_array_equal(unpack_host(chunked, layout)["x"], _np(x))


def test_subword_placement_is_planar_by_lanes():
    """Word j holds item j + k*q in its k-th lane, from the least
    significant bits: pinned with known items in known words, on the host
    and on the device, so the two cannot drift together."""
    u8 = np.array([0x11, 0x22, 0x33, 0x44, 0x55, 0x66], np.uint8)
    u16 = np.arange(1, 6, dtype=np.uint16)
    bf16 = np.array([1.0, 2.0, -1.0], jnp.bfloat16)   # 0x3F80 0x4000 0xBF80
    arrs = {"u8": u8, "u16": u16, "bf16": bf16, "b": np.array([1, 0, 1], bool)}
    blob, layout = pack_host(arrs)
    want = {"u8": [0x00553311, 0x00664422],       # q = 2: items 0,2,4 | 1,3,5
            "u16": [0x00040001, 0x00050002, 0x00000003],        # q = 3
            "bf16": [0xBF803F80, 0x00004000],                    # q = 2
            "b": [0x00010001]}                                   # q = 1
    on_device = _np(jax.jit(lambda d: pack_device(d, layout))(
        {k: jnp.asarray(v) for k, v in arrs.items()}))
    for name, words in want.items():
        start = layout.entry(name).offset // 4
        got = blob[start:start + len(words)].tolist()
        assert got == words, (name, [hex(w) for w in got])
        assert on_device[start:start + len(words)].tolist() == words, name


def test_word_sized_entries_keep_numpy_bytes(rng):
    """Entries of 4 bytes or more are stored as they were: float32 in
    numpy's bytes, complex64 as its real then its imaginary plane."""
    f = _mk(rng, (3, 5), "float32")
    c = _mk(rng, (4, 3), "complex64")
    i = _mk(rng, (7,), "int32")
    blob, layout = pack_host({"f": f, "c": c, "i": i})
    want = np.zeros(layout.total_bytes, np.uint8)
    for name, raw in (("f", f.tobytes()), ("i", i.tobytes()),
                      ("c", np.ascontiguousarray(c.real).tobytes()
                       + np.ascontiguousarray(c.imag).tobytes())):
        off = layout.entry(name).offset
        want[off:off + len(raw)] = np.frombuffer(raw, np.uint8)
    np.testing.assert_array_equal(blob.view(np.uint8), want)
    on_device = jax.jit(lambda d: pack_device(d, layout))(
        {"f": jnp.asarray(f), "c": jnp.asarray(c), "i": jnp.asarray(i)})
    np.testing.assert_array_equal(_np(on_device).view(np.uint8), want)


def _reference_words(arrs, layout):
    """The arena's words by the format's definition, item by item: numpy's
    bytes, a complex entry's real then imaginary plane, a sub-word entry's
    item ``i`` in lane ``i // q`` of word ``i % q``, zeros elsewhere."""
    out = np.zeros(layout.total_words, np.uint32)
    raw = out.view(np.uint8)
    for e in layout.entries:
        a = _np(arrs[e.name]).reshape(-1)
        if a.dtype.kind == "c":
            a = np.concatenate([a.real, a.imag])
        if a.dtype.itemsize >= 4:
            raw[e.offset:e.offset + a.nbytes] = a.view(np.uint8)
            continue
        bits = 8 * a.dtype.itemsize
        q = -(-a.size // (32 // bits))
        for i, v in enumerate(a.view(f"uint{bits}").tolist()):
            out[e.offset // 4 + i % q] |= np.uint32(v << (bits * (i // q)))
    return out


@pytest.mark.parametrize("shape", [(1,), (3, 5), (37,), (1029,)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["complex64", "float32", "float64",
                                   "bfloat16", "int8", "bool"])
def test_row_writer_writes_pack_host_words_over_a_used_row(rng, dtype,
                                                           shape):
    """``write_host`` into a row whose every bit is set gives exactly
    ``pack_host``'s words, and the format's: no bit of what the row held
    survives, in an entry, in a sub-word entry's last word, or in the
    padding after it.  Sizes are neither multiples of 128 bytes nor of
    the lanes a word holds."""
    arrs = {"x": _mk(rng, shape, dtype), "after": _mk(rng, (5,), "int8"),
            "last": _mk(rng, (3,), "float32")}
    blob, layout = pack_host(arrs)
    row = np.full(layout.total_words, 0xFFFFFFFF, np.uint32)
    write_host(row, arrs, layout)
    want = _reference_words(arrs, layout)
    np.testing.assert_array_equal(blob, want)
    np.testing.assert_array_equal(row, want)
    with pytest.raises(ValueError, match="does not match layout"):
        write_host(row[:-1], arrs, layout)


def test_host_codec_counts_subword_bytes(rng):
    """``repro_arena_subword_bytes_total`` grows by the bytes of the
    sub-word entries the host packs or unpacks, and by nothing else."""
    arrs = {"w": _mk(rng, (3, 5), "bfloat16"), "m": _mk(rng, (7,), "bool"),
            "f": _mk(rng, (4,), "float32")}
    before = trace.SUBWORD_BYTES.value()
    blob, layout = pack_host(arrs)
    assert trace.SUBWORD_BYTES.value() - before == 30 + 7
    unpack_host(blob, layout)
    assert trace.SUBWORD_BYTES.value() - before == 2 * (30 + 7)
