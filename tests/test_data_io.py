"""File format readers/writers + Data/XData/KData container behaviour."""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import CLapp, Data, KData, NDArray, SyncSource, XData
from repro.data import io as rio


def test_npz_roundtrip(tmp_path, rng):
    arrs = {"a": rng.standard_normal((3, 4)).astype(np.float32),
            "b": rng.integers(0, 9, (5,)).astype(np.int32)}
    p = str(tmp_path / "x.npz")
    rio.save_any(p, arrs)
    back = rio.load_any(p)
    for k in arrs:
        np.testing.assert_array_equal(arrs[k], back[k])
    sel = rio.load_any(p, ["b"])
    assert list(sel) == ["b"]


@pytest.mark.parametrize("shape", [(16, 16), (7, 9), (8, 8, 3)])
def test_png_roundtrip(tmp_path, rng, shape):
    img = rng.integers(0, 255, shape).astype(np.uint8)
    p = str(tmp_path / "x.png")
    rio.save_any(p, {"img": img})
    back = rio.load_any(p)["data"]
    np.testing.assert_array_equal(img, back)


def test_png_float_and_16bit(tmp_path, rng):
    f = rng.random((6, 5)).astype(np.float32)
    p = str(tmp_path / "f.png")
    rio.save_any(p, {"i": f})
    back = rio.load_any(p)["data"]
    np.testing.assert_allclose(back / 255.0, f, atol=1 / 255.0)
    u16 = rng.integers(0, 65535, (4, 4)).astype(np.uint16)
    p2 = str(tmp_path / "u.png")
    rio.save_any(p2, {"i": u16})
    np.testing.assert_array_equal(rio.load_any(p2)["data"], u16)


@pytest.mark.parametrize("ext,shape", [(".pgm", (9, 7)), (".ppm", (5, 6, 3))])
def test_pnm_roundtrip(tmp_path, rng, ext, shape):
    img = rng.integers(0, 255, shape).astype(np.uint8)
    p = str(tmp_path / ("x" + ext))
    rio.save_any(p, {"img": img})
    np.testing.assert_array_equal(rio.load_any(p)["data"], img)


def test_raw_roundtrip(tmp_path, rng):
    vol = rng.standard_normal((4, 5, 6)).astype(np.float32)
    p = str(tmp_path / "v.raw")
    rio.save_any(p, {"vol": vol})
    np.testing.assert_array_equal(rio.load_any(p)["data"], vol)


def test_register_format(tmp_path):
    def rd(path, variables=None):
        return {"data": np.loadtxt(path).astype(np.float32)}

    def wr(path, arrays):
        np.savetxt(path, np.asarray(next(iter(arrays.values()))))

    rio.register_format(".txt", rd, wr)
    p = str(tmp_path / "t.txt")
    rio.save_any(p, {"x": np.eye(3, dtype=np.float32)})
    np.testing.assert_allclose(rio.load_any(p)["data"], np.eye(3), atol=1e-6)


def test_unknown_format_raises(tmp_path):
    with pytest.raises(ValueError):
        rio.load_any(str(tmp_path / "x.xyz"))


# -- Data containers ---------------------------------------------------------

def test_xdata_from_file_and_save(tmp_path, rng):
    img = rng.integers(0, 255, (8, 8)).astype(np.uint8)
    p = str(tmp_path / "in.png")
    rio.save_any(p, {"img": img})
    d = XData(p, dtype=np.float32)
    assert d.get_ndarray(0).dtype == np.float32
    app = CLapp().init()
    h = app.addData(d)
    d.save(str(tmp_path / "out.npz"), SyncSource.BUFFER_ONLY)
    back = rio.load_any(str(tmp_path / "out.npz"))
    np.testing.assert_allclose(next(iter(back.values())), img.astype(np.float32))


def test_kdata_structure(rng):
    k = (rng.standard_normal((2, 3, 8, 8)) + 0j).astype(np.complex64)
    s = (rng.standard_normal((3, 8, 8)) + 0j).astype(np.complex64)
    d = KData({"kdata": k, "sensitivity_maps": s})
    assert d.n_coils == 3 and d.n_frames == 2
    assert d.x_shape() == (2, 8, 8)


def test_ndarray_width_height():
    a = NDArray(shape=(3, 160, 161), dtype=np.float32, name="v")
    assert a.width == 161 and a.height == 160 and a.ndim == 3


def test_spec_only_data_gets_zero_blob():
    app = CLapp().init()
    d = Data(None)
    d.add(NDArray(shape=(4, 4), dtype=np.float32, name="x"))
    h = app.addData(d)
    assert float(np.abs(np.asarray(d.device_view("x"))).sum()) == 0.0


def test_device_view_unpacks_one_entry_alone(monkeypatch):
    """``device_view`` reads one entry by name or index without unpacking
    the others (an eager whole-blob unpack to read one entry allocates
    and decodes the whole arena); an unknown name is a KeyError."""
    from repro.core import data as data_mod
    rng = np.random.default_rng(0)
    arrays = {"state": rng.standard_normal((3, 40)).astype(np.float16),
              "token": rng.integers(0, 99, (4, 1)).astype(np.int32),
              "mask": rng.integers(0, 2, (5,)).astype(np.uint8),
              "k": (rng.standard_normal(6)
                    + 1j * rng.standard_normal(6)).astype(np.complex64)}
    app = CLapp().init()
    d = Data(arrays)
    app.addData(d)

    def whole(*a, **k):
        raise AssertionError("unpacked every entry")
    monkeypatch.setattr(data_mod, "unpack_device", whole)
    for i, (name, want) in enumerate(arrays.items()):
        np.testing.assert_array_equal(np.asarray(d.device_view(name)), want)
        np.testing.assert_array_equal(np.asarray(d.device_view(i)), want)
    with pytest.raises(KeyError):
        d.device_view("absent")
