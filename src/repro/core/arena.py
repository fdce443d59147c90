"""Contiguous, aligned packing of heterogeneous array sets (paper §III-A.2).

OpenCLIPER guarantees that *"a single data set is always aligned and
contiguous, even though it is highly heterogeneous"* and that data objects
are *"transferred in a single call"* using pinned memory.  The TPU/JAX
adaptation is the **arena**: a set of N-D arrays of arbitrary shapes and
dtypes is packed into one contiguous byte blob with a predictable,
128-byte-aligned offset table.  One blob means

* one ``jax.device_put`` (the single-call transfer; fewer, larger DMAs is
  the TPU analogue of pinned-memory streaming),
* one contiguous write per checkpoint shard (see ``repro.ckpt``),
* one fused all-reduce over a whole gradient set instead of per-tensor
  collectives (used by the DP optimizer path).

The offset table is the analogue of OpenCLIPER's on-device position/size
table that its OpenCL kernels read; here host code slices views out of the
blob (zero-copy on host; lazily sliced+bitcast on device).

Offsets and sizes are in bytes, but a blob is held as 32-bit words
(``uint32``) on host and device alike.  On a TPU a byte array is a poor
carrier: turning ``u8[4n]`` into ``f32[n]`` goes through a ``u8[n, 4]``
array whose minor dimension of 4 is padded to the 128-lane tile, 32x the
data.  From words, every 4-byte view is a free bitcast.  For the same
reason no entry is stored interleaved:

* a complex entry is planar, all real parts and then all imaginary parts
  (numpy's interleaved pairs would need an ``(n, 2)`` shuffle);
* an entry of 8- or 16-bit items (bf16, f16, int16, uint16, int8, uint8,
  bool as uint8) is planar by lanes: with ``per = 4 // itemsize`` items
  a word and ``q = ceil(n / per)`` words, word ``j`` holds item
  ``j + k*q`` in its ``k``-th lane of ``8 * itemsize`` bits, counted from
  the least significant.  Packing is shifts and ors of ``per`` contiguous
  slices, a view the shifts narrowed and concatenated: elementwise work
  on 1-D arrays, with no array whose minor dimension is the packing
  factor.

Every other entry is stored in numpy's memory order.  Host and device
produce the same bytes.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Dict, Iterable, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import trace

ALIGN = 128  # bytes; TPU lane width (128 x f32) and a safe DMA alignment
WORD = np.dtype(np.uint32)  # the blob's element type
#: words of a sub-word entry the host packs at a time (bounds the
#: temporaries to a few MiB, however large the entry)
_CHUNK = 1 << 20


def _round_up(n: int, align: int = ALIGN) -> int:
    return (n + align - 1) // align * align


@dataclasses.dataclass(frozen=True)
class ArenaEntry:
    """Placement of one logical array inside the arena blob."""

    name: str
    shape: Tuple[int, ...]
    dtype: str           # numpy dtype name, e.g. "float32", "bfloat16"
    offset: int          # byte offset into the blob (ALIGN-aligned)
    nbytes: int          # payload bytes (not including alignment padding)

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(jnp.dtype(self.dtype))


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Immutable offset table for a packed arena."""

    entries: Tuple[ArenaEntry, ...]
    total_bytes: int

    @property
    def total_words(self) -> int:
        """Length of the blob (``total_bytes`` is a multiple of ``ALIGN``)."""
        return self.total_bytes // WORD.itemsize

    def __post_init__(self):
        names = [e.name for e in self.entries]
        if len(set(names)) != len(names):
            raise ValueError("duplicate names in arena layout")

    @property
    def names(self) -> List[str]:
        return [e.name for e in self.entries]

    @functools.cached_property
    def payload_bytes(self) -> int:
        """Bytes of the entries themselves, without alignment padding."""
        return sum(e.nbytes for e in self.entries)

    def entry(self, name: str) -> ArenaEntry:
        for e in self.entries:
            if e.name == name:
                return e
        raise KeyError(name)

    # -- (de)serialisation: the checkpoint metadata format ------------------
    def to_json(self) -> str:
        return json.dumps(
            {
                "total_bytes": self.total_bytes,
                "entries": [dataclasses.asdict(e) for e in self.entries],
            }
        )

    @staticmethod
    def from_json(text: str) -> "ArenaLayout":
        obj = json.loads(text)
        entries = tuple(
            ArenaEntry(
                name=e["name"],
                shape=tuple(e["shape"]),
                dtype=e["dtype"],
                offset=e["offset"],
                nbytes=e["nbytes"],
            )
            for e in obj["entries"]
        )
        return ArenaLayout(entries=entries, total_bytes=obj["total_bytes"])


def plan_layout(specs: Iterable[Tuple[str, Sequence[int], Any]]) -> ArenaLayout:
    """Compute an aligned layout for ``(name, shape, dtype)`` specs.

    Placement is in the given order (predictable — the paper's requirement),
    each entry rounded up to ``ALIGN`` bytes.
    """
    entries: List[ArenaEntry] = []
    offset = 0
    for name, shape, dtype in specs:
        nd = np.dtype(jnp.dtype(dtype))
        # np.prod of an empty shape is 1, so 0-d scalars get one item
        nbytes = int(np.prod(tuple(shape), dtype=np.int64)) * nd.itemsize
        entries.append(
            ArenaEntry(name=str(name), shape=tuple(int(s) for s in shape),
                       dtype=jnp.dtype(dtype).name, offset=offset, nbytes=int(nbytes))
        )
        offset += _round_up(max(int(nbytes), 1))
    return ArenaLayout(entries=tuple(entries), total_bytes=offset)


# ---------------------------------------------------------------------------
# Host-side pack / unpack (numpy, zero-copy views on unpack)
# ---------------------------------------------------------------------------

def _as_numpy(x) -> np.ndarray:
    if isinstance(x, np.ndarray):
        return x
    return np.asarray(x)


def pack_host(arrays: Mapping[str, Any], layout: ArenaLayout | None = None) -> Tuple[np.ndarray, ArenaLayout]:
    """Pack named host arrays into one contiguous word blob."""
    if layout is None:
        layout = plan_layout(
            (name, _as_numpy(a).shape, _as_numpy(a).dtype) for name, a in arrays.items()
        )
    # zeros, not empty: a GB blob of lanes fills faster on zeroed pages
    words = np.zeros(layout.total_words, dtype=WORD)
    write_host(words, arrays, layout)
    return words, layout


def write_host(row: np.ndarray, arrays: Mapping[str, Any], layout: ArenaLayout) -> None:
    """Write named host arrays into the ``(total_words,)`` word row ``row``
    in place: the words :func:`pack_host` makes, each array copied once
    (a complex one as a copy of its real plane and one of its imaginary
    plane).  Every word of the row is assigned, padding included, so a
    reused row keeps no bit of what it held before."""
    if row.shape != (layout.total_words,) or row.dtype != WORD:
        raise ValueError(
            f"row shape {row.shape}/{row.dtype} does not match layout "
            f"({layout.total_words},)/{WORD}")
    blob = row.view(np.uint8)
    end = 0                                   # first word not yet written
    for e in layout.entries:
        a = _as_numpy(arrays[e.name])
        if tuple(a.shape) != e.shape:
            raise ValueError(f"{e.name}: shape {a.shape} != layout {e.shape}")
        want = e.np_dtype
        if a.dtype != want:
            a = a.astype(want)
        start = e.offset // WORD.itemsize
        row[end:start] = 0
        end = start + _n_words(e)
        raw = blob[e.offset : e.offset + e.nbytes]
        if want.kind == "c":
            planes = raw.view(np.finfo(want).dtype)
            np.copyto(planes[: a.size].reshape(e.shape), a.real)
            np.copyto(planes[a.size :].reshape(e.shape), a.imag)
        elif want.itemsize < WORD.itemsize:
            _pack_lanes(row[start:end], np.ascontiguousarray(a).reshape(-1))
            trace.SUBWORD_BYTES.inc(e.nbytes)
        else:
            np.copyto(raw.view(want).reshape(e.shape), a)
    row[end:] = 0


def unpack_host(blob: np.ndarray, layout: ArenaLayout) -> Dict[str, np.ndarray]:
    """Zero-copy views of each entry out of a host blob (words, or the
    same bytes as read back from a file).  Complex and sub-word entries,
    stored planar, come back as assembled copies."""
    blob = blob.view(np.uint8)
    out: Dict[str, np.ndarray] = {}
    for e in layout.entries:
        raw = blob[e.offset : e.offset + e.nbytes]
        dt = e.np_dtype
        if dt.kind == "c":
            parts = raw.view(np.finfo(dt).dtype)
            arr = np.empty(e.shape, dt)
            arr.real = parts[:arr.size].reshape(e.shape)
            arr.imag = parts[arr.size:].reshape(e.shape)
            out[e.name] = arr
        elif dt.itemsize < WORD.itemsize:
            words = blob[e.offset : e.offset + _n_words(e) * WORD.itemsize]
            items = _unpack_lanes(words.view(WORD), dt, e.nbytes // dt.itemsize)
            trace.SUBWORD_BYTES.inc(e.nbytes)
            out[e.name] = items.reshape(e.shape)
        else:
            out[e.name] = raw.view(dt).reshape(e.shape)
    return out


def _n_words(e: ArenaEntry) -> int:
    return -(-e.nbytes // WORD.itemsize)


def _lanes(dt) -> Tuple[int, np.dtype]:
    """Items of a sub-word dtype a word holds, and the unsigned dtype of
    their width."""
    item = np.dtype(dt).itemsize
    return WORD.itemsize // item, np.dtype(f"uint{8 * item}")


def _pack_lanes(words: np.ndarray, a: np.ndarray) -> None:
    """Write the 1-D sub-word array ``a`` into ``words`` in place, planar
    by lanes, a chunk of words at a time.  Lane 0 covers every word and is
    assigned, so nothing ``words`` held before survives."""
    per, unsigned = _lanes(a.dtype)
    bits = 8 * a.dtype.itemsize
    items = a.view(unsigned)
    q, n = words.shape[0], items.shape[0]
    lane = np.empty(min(q, _CHUNK), WORD)
    for c0 in range(0, q, _CHUNK):
        out = words[c0 : c0 + _CHUNK]
        for k in range(per):
            part = items[k * q + c0 : min(k * q + c0 + out.shape[0], n)]
            if k == 0:
                out[: part.shape[0]] = part
            elif part.shape[0]:
                tmp = lane[: part.shape[0]]
                np.left_shift(part, bits * k, out=tmp, dtype=WORD)
                np.bitwise_or(out[: part.shape[0]], tmp,
                              out=out[: part.shape[0]])


def _unpack_lanes(words: np.ndarray, dt: np.dtype, n: int) -> np.ndarray:
    """The ``n`` items of dtype ``dt`` that :func:`_pack_lanes` wrote into
    ``words``, as a new 1-D array."""
    per, unsigned = _lanes(dt)
    q = words.shape[0]
    items = np.empty(per * q, unsigned)
    for k in range(per):
        # the assignment narrows to the lane's width, keeping the low bits
        items[k * q : (k + 1) * q] = words >> (8 * dt.itemsize * k)
    return items[:n].view(dt)


# ---------------------------------------------------------------------------
# Device-side unpack (lazy slice + bitcast inside jit; no host round trip)
# ---------------------------------------------------------------------------

def _from_words(words: jax.Array, dt, shape: Tuple[int, ...]) -> jax.Array:
    """The items of dtype ``dt`` and shape ``shape`` held in ``words``
    (1-D)."""
    dt = jnp.dtype(dt)
    item = dt.itemsize
    if item == WORD.itemsize:
        return jax.lax.bitcast_convert_type(words, dt).reshape(shape)
    if item > WORD.itemsize:
        return jax.lax.bitcast_convert_type(
            words.reshape(-1, item // WORD.itemsize), dt).reshape(shape)
    per, unsigned = _lanes(dt)
    # narrowing keeps the low bits: lane k is the word shifted right by k
    items = jnp.concatenate(
        [(words >> (8 * item * k)).astype(unsigned) for k in range(per)])
    n = int(np.prod(shape, dtype=np.int64))
    if n < items.shape[0]:
        items = items[:n]
    return jax.lax.bitcast_convert_type(items, dt).reshape(shape)


def _to_words(a: jax.Array) -> jax.Array:
    """Inverse of :func:`_from_words`: ``a`` as 1-D words, a sub-word
    array planar by lanes (zero-padded to a whole word)."""
    item = a.dtype.itemsize
    if item >= WORD.itemsize:
        return jax.lax.bitcast_convert_type(a.reshape(-1), WORD).reshape(-1)
    per, unsigned = _lanes(a.dtype)
    a = _flatten(jax.lax.bitcast_convert_type(a, unsigned))
    q = -(-a.shape[0] // per)
    a = jnp.pad(a, (0, per * q - a.shape[0]))
    words = a[:q].astype(WORD)
    for k in range(1, per):
        words = words | (a[k * q : (k + 1) * q].astype(WORD) << (8 * item * k))
    return words


def _flatten(x: jax.Array) -> jax.Array:
    """``x.reshape(-1)``, by way of rows of 128 where the size allows.

    The TPU compiler spends minutes on one large reshape straight to 1-D
    from a minor dimension that is not a multiple of 128 (a 63 MB cache
    leaf of minor dimension 80: about two), and about a second on the
    two steps; the barrier keeps it from merging them back into one."""
    if x.ndim < 2 or x.size % 128:
        return x.reshape(-1)
    return jax.lax.optimization_barrier(x.reshape(-1, 128)).reshape(-1)


def device_view(blob: jax.Array, entry: ArenaEntry) -> jax.Array:
    """Slice one logical array out of a device-resident word blob.

    Works under ``jit``; the compiler folds the slice and the decode into
    the consumer so chained Processes read the arena in place (zero copy).
    ``bitcast_convert_type`` rejects bool/complex, so those are read as
    uint8 / the real and the imaginary plane.
    """
    dt = jnp.dtype(entry.dtype)
    n = int(np.prod(entry.shape, dtype=np.int64))
    start = entry.offset // WORD.itemsize
    # a static slice: offsets past 2**31 (arenas over 8 GiB) stay exact,
    # where a dynamic slice's int32 index would wrap
    words = jax.lax.slice_in_dim(blob, start, start + _n_words(entry))
    if dt == jnp.bool_:
        return _from_words(words, jnp.uint8, entry.shape) != 0
    if jnp.issubdtype(dt, jnp.complexfloating):
        real_dt = jnp.float32 if dt == jnp.complex64 else jnp.float64
        parts = _from_words(words, real_dt, (2 * n,))
        return jax.lax.complex(parts[:n], parts[n:]).astype(dt).reshape(
            entry.shape)
    return _from_words(words, dt, entry.shape)


def unpack_device(blob: jax.Array, layout: ArenaLayout) -> Dict[str, jax.Array]:
    return {e.name: device_view(blob, e) for e in layout.entries}


def pack_device(arrays: Mapping[str, jax.Array], layout: ArenaLayout) -> jax.Array:
    """Pack device arrays into a word blob (jit-compatible).  Built by
    concatenation, so no offset becomes an int32 index."""
    parts = []
    end = 0
    for e in layout.entries:
        dt = jnp.dtype(e.dtype)
        a = jnp.asarray(arrays[e.name]).astype(dt)
        if dt == jnp.bool_:
            a = a.astype(jnp.uint8)
        elif jnp.issubdtype(dt, jnp.complexfloating):
            a = a.reshape(-1)
            a = jnp.concatenate([jnp.real(a), jnp.imag(a)])
        start = e.offset // WORD.itemsize
        raw = _to_words(a)
        parts += [jnp.zeros((start - end,), WORD), raw]
        end = start + raw.shape[0]
    parts.append(jnp.zeros((layout.total_words - end,), WORD))
    # the blob is materialised as a stage boundary: a chain traced into one
    # program then computes each stage as its own program does (same
    # fusions, same rounding), not folded into the next stage's consumer
    return jax.lax.optimization_barrier(jnp.concatenate(parts))


# ---------------------------------------------------------------------------
# Batched layouts: k identical arenas stacked on a leading axis (streaming)
# ---------------------------------------------------------------------------

def blob_spec(layout: ArenaLayout) -> jax.ShapeDtypeStruct:
    """AOT spec of one arena blob: ``(total_words,)`` words."""
    return jax.ShapeDtypeStruct((layout.total_words,), WORD)


def entry_specs(layout: ArenaLayout) -> Tuple[jax.ShapeDtypeStruct, ...]:
    """AOT specs of an arena's entries held as their own arrays (a static
    Data): one per entry, in layout order."""
    return tuple(jax.ShapeDtypeStruct(e.shape, e.np_dtype)
                 for e in layout.entries)


def batched_spec(layout: ArenaLayout, batch: int) -> jax.ShapeDtypeStruct:
    """AOT spec for ``batch`` stacked arena blobs: ``(batch, total_words)``
    words.  The per-item layout is unchanged — a vmapped program sees each
    row as one ordinary 1-D arena blob."""
    return jax.ShapeDtypeStruct((int(batch), layout.total_words), WORD)


def split_batched_blob(stacked: jax.Array) -> List[jax.Array]:
    """Per-item 1-D arena blobs out of a ``(k, total_words)`` stacked blob.

    For a batch-sharded stacked blob (``NamedSharding`` with the leading
    axis on the mesh's ``data`` axis) rows are sliced out of the LOCAL
    ``addressable_shards``, so each item's output blob stays resident on
    the device that computed it — no cross-device gather, no implicit
    transfer back to device 0.  A row held by several devices (replicated
    over the ``model`` axis of a 2D mesh: the model group that computed
    it) stays on every one of them, as one array replicated over that
    group.  A single-device stacked blob is one shard covering every row,
    which reduces to plain row indexing.
    """
    k = int(stacked.shape[0])
    copies: List[Dict[Any, jax.Array]] = [{} for _ in range(k)]
    for shard in stacked.addressable_shards:
        row0 = shard.index[0].start or 0
        for r in range(shard.data.shape[0]):
            copies[row0 + r].setdefault(shard.device, shard.data[r])
    missing = [i for i, c in enumerate(copies) if not c]
    if missing:
        raise ValueError(
            f"stacked blob rows {missing} have no addressable shard "
            "(multi-process sharding is not supported by split_batched_blob)")
    items: List[jax.Array] = []
    for row in copies:
        if len(row) == 1:
            items.extend(row.values())
            continue
        from repro.launch.mesh import group_sharding  # lazy: no import cycle
        devices = list(row)
        items.append(jax.make_array_from_single_device_arrays(
            (stacked.shape[1],), group_sharding(devices),
            [row[d] for d in devices]))
    return items


# ---------------------------------------------------------------------------
# Pytree arenas: pack any pytree of arrays (used by repro.ckpt)
# ---------------------------------------------------------------------------

def _flatten_with_names(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        out.append((name, leaf))
    return out


def pack_tree_host(tree) -> Tuple[np.ndarray, ArenaLayout]:
    named = dict(_flatten_with_names(tree))
    return pack_host(named)


def unpack_tree_host(blob: np.ndarray, layout: ArenaLayout, treedef_like):
    """Restore a pytree with the structure of ``treedef_like`` from a blob."""
    named = unpack_host(blob, layout)
    flat = _flatten_with_names(treedef_like)
    leaves = [named[name] for name, _ in flat]
    _, treedef = jax.tree_util.tree_flatten(treedef_like)
    return jax.tree_util.tree_unflatten(treedef, leaves)
