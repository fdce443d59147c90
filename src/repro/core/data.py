"""Heterogeneous data containers (paper §III-B: Data / NDArray / Concrete*).

The paper's three-level split (``Data`` -> ``NDArray`` -> ``ConcreteNDArray``)
exists to isolate machine dtype details from user classes in C++.  Python is
duck-typed, so ``ConcreteNDArray`` collapses into :class:`NDArray` (which owns
a concrete numpy buffer and/or a shape/dtype spec); the *structure* — a Data
set holding many differently-shaped, differently-typed arrays that moves to
and from the device as ONE contiguous buffer — is preserved via
:mod:`repro.core.arena`.

Out-of-the-box specialisations, as in the paper:

* :class:`XData` — data with direct physical interpretation (images, volumes)
* :class:`KData` — complex K-space data + per-coil sensitivity maps
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .arena import (ArenaLayout, pack_host, plan_layout, unpack_device,
                    unpack_host, write_host)
from .arena import device_view as entry_view
from .sync import Coherence, SyncSource, resolve_source


class NDArray:
    """A signal/image/volume of one dtype.  May be host-backed, spec-only,
    or a view into a device arena owned by the parent :class:`Data`."""

    def __init__(self, value: Any = None, *, shape: Sequence[int] | None = None,
                 dtype: Any = None, name: str | None = None):
        if value is not None:
            self._host: Optional[np.ndarray] = np.asarray(value)
            self.shape: Tuple[int, ...] = tuple(self._host.shape)
            self.dtype = jnp.dtype(self._host.dtype)
        else:
            if shape is None or dtype is None:
                raise ValueError("spec-only NDArray needs shape and dtype")
            self._host = None
            self.shape = tuple(int(s) for s in shape)
            self.dtype = jnp.dtype(dtype)
        self.name = name

    # -- paper's NDARRAYWIDTH/NDARRAYHEIGHT macros ---------------------------
    @property
    def width(self) -> int:
        return self.shape[-1]

    @property
    def height(self) -> int:
        return self.shape[-2] if len(self.shape) >= 2 else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * np.dtype(self.dtype).itemsize

    @property
    def host(self) -> Optional[np.ndarray]:
        return self._host

    def set_host(self, value: np.ndarray) -> None:
        value = np.asarray(value)
        if tuple(value.shape) != self.shape:
            raise ValueError(f"shape mismatch {value.shape} != {self.shape}")
        self._host = value.astype(np.dtype(self.dtype), copy=False)

    def spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def __repr__(self):
        kind = "host" if self._host is not None else "spec"
        return f"NDArray<{kind}>({self.name or ''}, shape={self.shape}, dtype={self.dtype})"


class Data:
    """A set of :class:`NDArray` objects moved to/from the device as a unit.

    Mirrors the paper's abstract ``Data``: arbitrary heterogeneity, single
    registered device buffer, predictable layout (``self.layout``), explicit
    coherence between host and device copies.
    """

    def __init__(self, arrays: Sequence[NDArray] | Mapping[str, Any] | None = None):
        self._arrays: List[NDArray] = []
        if arrays is not None:
            if isinstance(arrays, Mapping):
                for k, v in arrays.items():
                    a = v if isinstance(v, NDArray) else NDArray(v, name=k)
                    a.name = k
                    self._arrays.append(a)
            else:
                for i, a in enumerate(arrays):
                    if not isinstance(a, NDArray):
                        a = NDArray(a)
                    if a.name is None:
                        a.name = f"nd{i}"
                    self._arrays.append(a)
        # device side (owned by CLIPERApp.addData)
        self.layout: Optional[ArenaLayout] = None
        self.device_blob: Optional[jax.Array] = None
        # residency plan annotations (set by Pipeline.build on edge Data):
        # 'host' = pinned host path (graph inputs/outputs), 'device' =
        # internal edge whose blob never lands on the host mid-chain.
        self.residency: str = "host"
        self.residency_edge: Optional[str] = None   # edge name in the graph
        self.producer_name: Optional[str] = None    # stage that writes it
        # persistent-state contract (decode caches, recurrent state): this
        # Data lives on the device ACROSS launches even when it sits on a
        # graph input/output edge (bound as both the input and the output
        # of a step process).  Pipeline.build keeps residency='device' for
        # it, so every step's result is stamped Coherence.DEVICE_RESIDENT
        # and run(sync=False) never round-trips it through the host.
        self.persistent: bool = False
        # set by Process.launch when a downstream stage donated this blob
        # to XLA; reads must fail loudly (with graph context when known)
        self.donated_by: Optional[str] = None
        # spec-only sets (no arrays, or any array without host values) start
        # EMPTY: there is nothing authoritative to read yet.  Stamping them
        # HOST_FRESH would make authoritative()/save() trust absent host
        # arrays.  HOST_FRESH requires every array to be host-backed.
        self.coherence: Coherence = (
            Coherence.HOST_FRESH
            if self._arrays and all(a.host is not None for a in self._arrays)
            else Coherence.EMPTY
        )

    # -- container protocol ---------------------------------------------------
    def add(self, array: NDArray) -> None:
        if array.name is None:
            array.name = f"nd{len(self._arrays)}"
        self._arrays.append(array)
        # an EMPTY set becomes HOST_FRESH once every array is host-backed;
        # adding a spec-only array to a HOST_FRESH set demotes it to EMPTY
        if self.device_blob is None:
            self.coherence = (
                Coherence.HOST_FRESH
                if all(a.host is not None for a in self._arrays)
                else Coherence.EMPTY
            )

    def get_ndarray(self, i: int) -> NDArray:
        return self._arrays[i]

    def __len__(self) -> int:
        return len(self._arrays)

    def __iter__(self):
        return iter(self._arrays)

    @property
    def names(self) -> List[str]:
        return [a.name for a in self._arrays]

    # -- construction helpers --------------------------------------------------
    @classmethod
    def from_layout(cls, layout: ArenaLayout) -> "Data":
        """Spec-only Data matching an existing arena layout (names, shapes,
        dtypes; no host values).  Used by the streaming executor to build
        per-item output containers that alias rows of a batched result."""
        d = cls(None)
        for e in layout.entries:
            d.add(NDArray(shape=e.shape, dtype=e.dtype, name=e.name))
        d.layout = layout
        return d

    @classmethod
    def from_specs(cls, specs: Mapping[str, jax.ShapeDtypeStruct]) -> "Data":
        """Spec-only Data from ``{name -> ShapeDtypeStruct}`` (the inverse
        of :meth:`specs`).  Used by the Pipeline builder to allocate
        intermediate/output edge Data from inferred operator specs."""
        d = cls(None)
        for name, s in specs.items():
            d.add(NDArray(shape=s.shape, dtype=s.dtype, name=name))
        return d

    def spec_clone(self) -> "Data":
        """Same-shaped, spec-only copy of this Data (the paper's
        ``XData(src, copy_values=False)`` generalised to any Data)."""
        d = Data(None)
        for a in self._arrays:
            d.add(NDArray(shape=a.shape, dtype=a.dtype, name=a.name))
        d.layout = self.layout
        return d

    # -- layout / packing -----------------------------------------------------
    def plan(self) -> ArenaLayout:
        self.layout = plan_layout((a.name, a.shape, a.dtype) for a in self._arrays)
        return self.layout

    def pack_host(self, out: np.ndarray | None = None) -> np.ndarray:
        """The host arrays as arena words: a new blob, or written in place
        into the word row ``out``."""
        if self.layout is None:
            self.plan()
        missing = [a.name for a in self._arrays if a.host is None]
        if missing:
            raise ValueError(f"cannot pack spec-only arrays: {missing}")
        arrays = {a.name: a.host for a in self._arrays}
        if out is None:
            out, _ = pack_host(arrays, self.layout)
        else:
            write_host(out, arrays, self.layout)
        return out

    # -- donation bookkeeping ---------------------------------------------------
    def mark_donated(self, consumer: str) -> None:
        """Record that ``consumer`` donated this Data's device blob to XLA
        (the buffer is dead); drop the reference so later reads raise."""
        self.device_blob = None
        self.donated_by = consumer

    def _raise_donated(self) -> None:
        from .process import DonatedBufferError  # local: process imports data

        if self.producer_name or self.residency_edge:
            edge = self.residency_edge or "?"
            producer = self.producer_name or "?"
            raise DonatedBufferError(
                f"device blob of edge '{edge}' (produced by stage "
                f"'{producer}') was donated to downstream stage "
                f"'{self.donated_by}' and no longer exists; read the "
                f"pipeline's OUTPUT edge instead of a donated internal one, "
                f"or rebuild with residency disabled for this edge")
        raise DonatedBufferError(
            f"device blob was donated to '{self.donated_by}' and no longer "
            f"exists; re-upload with host2device before reusing this Data")

    # -- device views ----------------------------------------------------------
    def _device_blob(self) -> jax.Array:
        if self.device_blob is None or self.layout is None:
            if self.donated_by is not None:
                self._raise_donated()
            raise ValueError("Data not registered on a device (use CLapp.addData)")
        return self.device_blob

    def device_views(self) -> Dict[str, jax.Array]:
        return unpack_device(self._device_blob(), self.layout)

    def device_view(self, name_or_idx) -> jax.Array:
        """One entry, unpacked alone: called eagerly, unpacking every entry
        would allocate and decode the whole blob to read one of them."""
        blob = self._device_blob()
        name = (self._arrays[name_or_idx].name
                if isinstance(name_or_idx, int) else name_or_idx)
        return entry_view(blob, self.layout.entry(name))

    # -- host sync --------------------------------------------------------------
    def sync_to_host(self) -> None:
        """Copy the device blob back into the host NDArrays (paper's
        ``device2Host``)."""
        if self.device_blob is None or self.layout is None:
            if self.donated_by is not None:
                self._raise_donated()
            raise ValueError("no device buffer to sync from")
        with trace.span("data.to_host", bytes=self.device_blob.nbytes):
            blob = np.asarray(self.device_blob)
            trace.D2H_BYTES.inc(blob.nbytes)
            views = unpack_host(blob, self.layout)
            for a in self._arrays:
                a.set_host(views[a.name])
        self.coherence = Coherence.IN_SYNC

    def authoritative(self, sync: SyncSource = SyncSource.AUTO) -> str:
        return resolve_source(sync, self.coherence)

    # -- specs for AOT lowering --------------------------------------------------
    def specs(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return {a.name: a.spec() for a in self._arrays}

    # -- IO (paper: file formats out of the box) ---------------------------------
    def save(self, path: str, sync: SyncSource = SyncSource.AUTO) -> None:
        from repro.data import io as repro_io  # local import; io is substrate

        if self.authoritative(sync) == "device":
            self.sync_to_host()
        repro_io.save_any(path, {a.name: a.host for a in self._arrays})

    def matlab_save(self, path: str, var: str | None = None,
                    sync: SyncSource = SyncSource.AUTO) -> None:
        """Save in the .mat-analogue container (npz)."""
        self.save(path if path.endswith(".npz") else path + ".npz", sync)

    @classmethod
    def load(cls, path: str, variables: Sequence[str] | None = None) -> "Data":
        from repro.data import io as repro_io

        arrays = repro_io.load_any(path, variables)
        return cls(arrays)

    def __repr__(self):
        return f"{type(self).__name__}({', '.join(map(repr, self._arrays))})"


class XData(Data):
    """Data with a direct physical interpretation (images, volumes)."""

    def __init__(self, src: Any = None, copy_values: bool = True, dtype: Any = None,
                 arrays: Sequence[NDArray] | Mapping[str, Any] | None = None):
        if isinstance(src, str):
            # construct from file, as in listing 1
            from repro.data import io as repro_io
            loaded = repro_io.load_any(src)
            if dtype is not None:
                loaded = {k: np.asarray(v).astype(jnp.dtype(dtype)) for k, v in loaded.items()}
            super().__init__(loaded)
        elif isinstance(src, Data):
            # "create output with same size as input" (listing 1, copy=False)
            if copy_values:
                super().__init__({a.name: np.array(a.host) for a in src})
            else:
                super().__init__(None)
                for a in src:
                    self.add(NDArray(shape=a.shape, dtype=a.dtype, name=a.name))
        else:
            super().__init__(arrays if arrays is not None else src)


class KData(Data):
    """Complex K-space data + sensitivity maps (paper §IV-A).

    Layout: arrays named ``kdata`` with shape (frames, coils, H, W) complex
    and ``sensitivity_maps`` with shape (coils, H, W) complex.
    """

    KDATA = "kdata"
    SMAPS = "sensitivity_maps"

    def __init__(self, src: Any = None, variables: Sequence[str] | None = None):
        if isinstance(src, str):
            from repro.data import io as repro_io
            names = list(variables or [self.KDATA, self.SMAPS])
            if len(names) != 2:
                raise ValueError(
                    f"KData needs exactly (kdata, smaps) variables, got {names}")
            loaded = repro_io.load_any(src, names)
            # normalise external variable names to canonical ones — indexed
            # by the REQUESTED names, never by the loader's dict order (a
            # reader is free to return variables in file order, which would
            # silently swap kdata and the sensitivity maps)
            missing = [n for n in names if n not in loaded]
            if missing:
                raise KeyError(f"variables {missing} not found in {src!r} "
                               f"(loaded: {sorted(loaded)})")
            super().__init__({self.KDATA: loaded[names[0]],
                              self.SMAPS: loaded[names[1]]})
        elif isinstance(src, Mapping):
            super().__init__({self.KDATA: src[self.KDATA], self.SMAPS: src[self.SMAPS]})
        else:
            super().__init__(src)

    @property
    def kdata(self) -> NDArray:
        return self._arrays[self.names.index(self.KDATA)]

    @property
    def smaps(self) -> NDArray:
        return self._arrays[self.names.index(self.SMAPS)]

    @property
    def n_coils(self) -> int:
        return self.kdata.shape[-3]

    @property
    def n_frames(self) -> int:
        return self.kdata.shape[0]

    def x_shape(self) -> Tuple[int, ...]:
        """Shape of the reconstructed X-space image set (frames, H, W)."""
        f, _, h, w = self.kdata.shape
        return (f, h, w)
