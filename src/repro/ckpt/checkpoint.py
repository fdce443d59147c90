"""Arena-blob checkpoints: the paper's contiguous-layout idea applied to
fault tolerance.

Two on-disk formats share one directory scheme (``step_NNNNNNNNNN/``):

**Logical (legacy)** — ONE contiguous byte blob (the packed arena of every
leaf in the train state) plus a JSON offset table: a single sequential
write/read per host, the transfer-bandwidth-maximizing analogue of
OpenCLIPER's pinned single-call transfers.  Saving gathers every leaf to
the host first (recorded as the ``"gather"`` profile phase), so the blob
stores *logical* shapes and restores onto any mesh.

**Sharded** (``save_checkpoint(..., sharded=True)``) — gather-free: each
device's local shard pieces (read via ``Array.addressable_shards`` — a
device-to-host copy of the LOCAL piece, never a cross-device gather) are
packed into one arena blob per device (``shard_00000.arena`` ...), with
fully-replicated / host-only leaves deduplicated into a single
``host.arena``.  Every blob is written atomically (per-file tmp+rename)
and the ``manifest.json`` naming every piece is committed LAST, so a
partially-written step is detectable: ``latest_step`` skips it and
``restore_checkpoint`` raises :class:`CheckpointCorruptError` naming the
step and the missing piece.  Restore is gather-free too when the target
shardings' per-device indices match the saved pieces — each piece is
``device_put`` straight to its target device and stitched with
``jax.make_array_from_single_device_arrays``; on a different mesh shape
the *elastic fallback* assembles the logical arrays host-side from the
pieces (recorded as the ``"gather"`` phase) and re-shards.

Writes are optionally asynchronous (the per-shard device-to-host snapshot
is taken synchronously, the file writes happen on a worker thread — the
device never waits for the filesystem).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arena import (ArenaLayout, _flatten_with_names, pack_host,
                              pack_tree_host, unpack_host)

_BLOB = "state.arena"
_META = "layout.json"
_MANIFEST = "manifest.json"
_HOST = "host.arena"
#: on-disk formats: v2 stores 8- and 16-bit leaves planar by lanes
#: (``repro.core.arena``); v1 (``layout.json`` without a ``format`` key,
#: or ``sharded-v1``) stored them as interleaved pairs
_FORMAT = "sharded-v2"
_LOGICAL_FORMAT = "logical-v2"


class CheckpointFormatError(ValueError):
    """A checkpoint holds 8- or 16-bit leaves in the arena codec before
    the planar one: its bytes would be misread, so it is refused."""

    def __init__(self, step: int, fmt: str, leaf: str, dtype: str):
        self.step = step
        self.format = fmt
        super().__init__(
            f"checkpoint step {step} is in format {fmt!r}, which stored "
            f"8- and 16-bit leaves as interleaved pairs; this version "
            f"stores them planar by lanes ({_LOGICAL_FORMAT!r}, "
            f"{_FORMAT!r}) and cannot read leaf {leaf!r} ({dtype})")


def _check_codec(step: int, fmt: str, current: str,
                 layouts: List[Dict[str, Any]]) -> None:
    """Refuse a checkpoint of another format whose layouts (as stored in
    JSON) hold a sub-word leaf; word-sized leaves are stored alike in
    every format."""
    if fmt == current:
        return
    for layout in layouts:
        for e in layout["entries"]:
            if np.dtype(jnp.dtype(e["dtype"])).itemsize < 4:
                raise CheckpointFormatError(step, fmt, e["name"], e["dtype"])


class CheckpointCorruptError(RuntimeError):
    """A checkpoint step directory exists but is torn or incomplete.

    Carries the ``step`` and the name of the missing/invalid ``piece``
    (e.g. ``"manifest.json"``, ``"shard_00003.arena"``) so an operator can
    tell a crashed writer from a wrong path.  ``latest_step`` never
    *returns* a torn step — this error means a step was requested
    explicitly or the directory was corrupted after listing."""

    def __init__(self, step: int, piece: str, detail: str = ""):
        self.step = step
        self.piece = piece
        msg = (f"checkpoint step {step} is corrupt: "
               f"missing or invalid {piece}")
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


def _step_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:010d}")


def _shard_file(k: int) -> str:
    return f"shard_{k:05d}.arena"


def _atomic_write(path: str, blob: np.ndarray) -> None:
    """Per-file atomicity: a reader never sees a half-written blob under
    its final name (crash leaves only ``*.tmp`` litter, reaped by
    ``cleanup``)."""
    blob.tofile(path + ".tmp")
    os.rename(path + ".tmp", path)


# ---------------------------------------------------------------------------
# shard-piece index bookkeeping
# ---------------------------------------------------------------------------

def _norm_index(index, shape) -> List[List[int]]:
    """``Shard.index`` (a tuple of slices) as ``[[start, stop], ...]``."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _index_slices(idx) -> Tuple[slice, ...]:
    return tuple(slice(a, b) for a, b in idx)


def _index_key(idx) -> Tuple[Tuple[int, int], ...]:
    return tuple((int(a), int(b)) for a, b in idx)


def _is_full(idx, shape) -> bool:
    return all(a == 0 and b == d for (a, b), d in zip(idx, tuple(shape)))


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------

def _sharded_save_plan(state: Any) -> Dict[str, Any]:
    """Snapshot ``state`` for a gather-free sharded save.

    Device-to-host copies happen HERE (synchronously, one local
    ``np.asarray`` per addressable shard) so the asynchronous writer never
    races the train loop donating the buffers.  Replicated pieces are
    deduplicated first-device-wins, mirroring ``split_batched_blob``."""
    flat = _flatten_with_names(state)
    host_arrays: Dict[str, np.ndarray] = {}
    leaves_meta: List[Dict[str, Any]] = []
    shard_data: Dict[int, Dict[str, np.ndarray]] = {}
    shard_pieces: Dict[int, List[Dict[str, Any]]] = {}
    mesh_info = None
    for name, leaf in flat:
        if isinstance(leaf, jax.Array):
            sh = leaf.sharding
            if mesh_info is None and isinstance(sh, jax.sharding.NamedSharding):
                mesh_info = {"axes": list(sh.mesh.axis_names),
                             "shape": [int(s) for s in sh.mesh.devices.shape]}
            shards = list(leaf.addressable_shards)
            idxs = [_norm_index(s.index, leaf.shape) for s in shards]
            dtype = jnp.dtype(leaf.dtype).name
            if not shards or all(_is_full(i, leaf.shape) for i in idxs):
                # fully replicated (or single-device): ONE host copy —
                # still a local d2h, not a gather
                src = shards[0].data if shards else leaf
                host_arrays[name] = np.asarray(src)
                leaves_meta.append({"name": name, "shape": list(leaf.shape),
                                    "dtype": dtype, "placement": "host"})
                continue
            seen = set()
            for s, idx in zip(shards, idxs):
                key = _index_key(idx)
                if key in seen:
                    continue                     # replicated copy: first wins
                seen.add(key)
                did = int(s.device.id)
                shard_data.setdefault(did, {})[name] = np.asarray(s.data)
                shard_pieces.setdefault(did, []).append(
                    {"name": name, "index": idx})
            leaves_meta.append({"name": name, "shape": list(leaf.shape),
                                "dtype": dtype, "placement": "sharded"})
        else:
            arr = np.asarray(leaf)
            host_arrays[name] = arr
            leaves_meta.append({"name": name, "shape": list(arr.shape),
                                "dtype": jnp.dtype(arr.dtype).name,
                                "placement": "host"})
    return {"mesh": mesh_info, "leaves": leaves_meta, "host": host_arrays,
            "shards": shard_data, "pieces": shard_pieces}


def _write_sharded(directory: str, step: int, plan: Dict[str, Any],
                   keep_last: Optional[int],
                   profile: Any = None) -> str:
    os.makedirs(directory, exist_ok=True)
    final = _step_dir(directory, step)
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    t0 = time.perf_counter()
    device_ids = sorted(plan["shards"])

    def _write_one(arg: Tuple[int, int]) -> Dict[str, Any]:
        k, did = arg
        blob, layout = pack_host(plan["shards"][did])
        fname = _shard_file(k)
        _atomic_write(os.path.join(tmp, fname), blob)
        return {"file": fname, "bytes": int(blob.nbytes),
                "device_id": did,
                "layout": json.loads(layout.to_json()),
                "pieces": plan["pieces"][did]}

    if device_ids:
        with ThreadPoolExecutor(max_workers=min(8, len(device_ids))) as ex:
            shard_entries = list(ex.map(_write_one, enumerate(device_ids)))
    else:
        shard_entries = []
    host_entry = None
    if plan["host"]:
        hblob, hlayout = pack_host(plan["host"])
        _atomic_write(os.path.join(tmp, _HOST), hblob)
        host_entry = {"file": _HOST, "bytes": int(hblob.nbytes),
                      "layout": json.loads(hlayout.to_json())}
    manifest = {"format": _FORMAT, "step": step, "mesh": plan["mesh"],
                "leaves": plan["leaves"], "host": host_entry,
                "shards": shard_entries}
    mpath = os.path.join(tmp, _MANIFEST)
    with open(mpath + ".tmp", "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.rename(mpath + ".tmp", mpath)            # manifest committed LAST
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    if profile is not None and getattr(profile, "enable", False):
        profile.record_phase("shard_write", time.perf_counter() - t0)
    if keep_last:
        cleanup(directory, keep_last)
    return final


def save_checkpoint(directory: str, step: int, state: Any,
                    keep_last: Optional[int] = None, *,
                    sharded: bool = False,
                    profile: Any = None) -> str:
    """Atomic save; returns the checkpoint path.

    ``sharded=False`` (legacy) gathers every leaf to the host (the
    ``"gather"`` profile phase) and writes one logical arena blob.
    ``sharded=True`` writes one arena blob per device from the leaves'
    ``addressable_shards`` — zero host gather (no ``"gather"`` phase is
    ever recorded), per-shard tmp+rename, manifest committed last."""
    if sharded:
        plan = _sharded_save_plan(state)
        return _write_sharded(directory, step, plan, keep_last, profile)
    os.makedirs(directory, exist_ok=True)
    t0 = time.perf_counter()
    host_state = jax.tree.map(np.asarray, state)          # gather to host
    if profile is not None and getattr(profile, "enable", False):
        profile.record_phase("gather", time.perf_counter() - t0)
    blob, layout = pack_tree_host(host_state)
    final = _step_dir(directory, step)
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, _META), "w") as f:
        json.dump({"format": _LOGICAL_FORMAT, **json.loads(layout.to_json())},
                  f)
    blob.tofile(os.path.join(tmp, _BLOB))
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    if keep_last:
        cleanup(directory, keep_last)
    return final


# ---------------------------------------------------------------------------
# completeness / discovery
# ---------------------------------------------------------------------------

def _manifest_missing(path: str, manifest: Dict[str, Any]) -> Optional[str]:
    """Name of the first missing/size-mismatched piece, or None."""
    for se in manifest.get("shards", ()):
        fp = os.path.join(path, se["file"])
        if not os.path.exists(fp):
            return se["file"]
        if os.path.getsize(fp) != se["bytes"]:
            return f"{se['file']} (truncated: {os.path.getsize(fp)} of " \
                   f"{se['bytes']} bytes)"
    h = manifest.get("host")
    if h:
        fp = os.path.join(path, h["file"])
        if not os.path.exists(fp):
            return h["file"]
        if os.path.getsize(fp) != h["bytes"]:
            return f"{h['file']} (truncated: {os.path.getsize(fp)} of " \
                   f"{h['bytes']} bytes)"
    return None


def _step_complete(path: str) -> bool:
    """True iff the step directory holds a fully-committed checkpoint in
    either format — the torn-write detector behind ``latest_step``."""
    mpath = os.path.join(path, _MANIFEST)
    if os.path.exists(mpath):
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return False
        return _manifest_missing(path, manifest) is None
    meta = os.path.join(path, _META)
    blob = os.path.join(path, _BLOB)
    if os.path.exists(meta) and os.path.exists(blob):
        try:
            with open(meta) as f:
                layout = ArenaLayout.from_json(f.read())
        except (OSError, ValueError, KeyError):
            return False
        return os.path.getsize(blob) == layout.total_bytes
    return False


def latest_step(directory: str) -> Optional[int]:
    """Newest COMPLETE step (torn/partial checkpoints are skipped, so a
    crash mid-save falls back to the last good one)."""
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and _step_complete(os.path.join(directory, name)):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------

def _restore_legacy(path: str, step: int, state_like: Any,
                    shardings: Any) -> Any:
    meta = os.path.join(path, _META)
    if not os.path.exists(meta):
        raise CheckpointCorruptError(step, _META)
    with open(meta) as f:
        text = f.read()
    stored = json.loads(text)
    _check_codec(step, stored.get("format", "logical-v1"), _LOGICAL_FORMAT,
                 [stored])
    layout = ArenaLayout.from_json(text)
    bp = os.path.join(path, _BLOB)
    if not os.path.exists(bp):
        raise CheckpointCorruptError(step, _BLOB)
    blob = np.fromfile(bp, dtype=np.uint8)
    if blob.nbytes != layout.total_bytes:
        raise CheckpointCorruptError(
            step, _BLOB,
            f"truncated: {blob.nbytes} of {layout.total_bytes} bytes")
    named = unpack_host(blob, layout)

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_like)
    leaves = []
    for pathkey, like in flat:
        name = jax.tree_util.keystr(pathkey)
        if name not in layout.names:
            raise CheckpointCorruptError(step, f"leaf {name!r}",
                                         "not in checkpoint layout")
        arr = named[name]
        if tuple(arr.shape) != tuple(np.shape(like)):
            raise ValueError(
                f"{name}: ckpt shape {arr.shape} != state {np.shape(like)}")
        leaves.append(arr)
    restored = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state_like), leaves)
    if shardings is not None:
        restored = jax.tree.map(
            lambda a, s: jax.device_put(a, s), restored, shardings)
    return restored


def _restore_sharded(path: str, step: int, state_like: Any,
                     shardings: Any, profile: Any) -> Any:
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)
    missing = _manifest_missing(path, manifest)
    if missing is not None:
        raise CheckpointCorruptError(step, missing)
    blobs = manifest["shards"] + ([manifest["host"]]
                                  if manifest.get("host") else [])
    _check_codec(step, manifest.get("format"), _FORMAT,
                 [b["layout"] for b in blobs])

    blob_cache: Dict[str, Dict[str, np.ndarray]] = {}

    def shard_named(se: Dict[str, Any]) -> Dict[str, np.ndarray]:
        if se["file"] not in blob_cache:
            blob = np.fromfile(os.path.join(path, se["file"]), dtype=np.uint8)
            layout = ArenaLayout.from_json(json.dumps(se["layout"]))
            blob_cache[se["file"]] = unpack_host(blob, layout)
        return blob_cache[se["file"]]

    host_named: Dict[str, np.ndarray] = {}
    if manifest.get("host"):
        h = manifest["host"]
        hblob = np.fromfile(os.path.join(path, h["file"]), dtype=np.uint8)
        host_named = unpack_host(
            hblob, ArenaLayout.from_json(json.dumps(h["layout"])))

    pieces: Dict[str, List[Tuple[Any, Dict[str, Any]]]] = {}
    for se in manifest["shards"]:
        for p in se["pieces"]:
            pieces.setdefault(p["name"], []).append((p["index"], se))
    leaf_meta = {l["name"]: l for l in manifest["leaves"]}

    flat, _ = jax.tree_util.tree_flatten_with_path(state_like)
    # None leaves mean "leave this leaf where restore puts it naturally";
    # is_leaf keeps them (plain pytree flattening would drop them)
    shard_list = (jax.tree_util.tree_leaves(
        shardings,
        is_leaf=lambda x: x is None or isinstance(x, jax.sharding.Sharding))
        if shardings is not None else None)
    if shard_list is not None and len(shard_list) != len(flat):
        raise ValueError(
            f"shardings pytree has {len(shard_list)} leaves, state has "
            f"{len(flat)}")

    out_leaves = []
    t_gather = 0.0
    for i, (pathkey, like) in enumerate(flat):
        name = jax.tree_util.keystr(pathkey)
        meta = leaf_meta.get(name)
        if meta is None:
            raise CheckpointCorruptError(step, f"leaf {name!r}",
                                         "not in manifest")
        shape = tuple(meta["shape"])
        if shape != tuple(np.shape(like)):
            raise ValueError(
                f"{name}: ckpt shape {shape} != state {np.shape(like)}")
        dtype = np.dtype(jnp.dtype(meta["dtype"]))
        target = shard_list[i] if shard_list is not None else None

        if meta["placement"] == "host":
            arr = host_named.get(name)
            if arr is None:
                raise CheckpointCorruptError(step, f"leaf {name!r}",
                                             "not in host arena")
            out_leaves.append(jax.device_put(arr, target)
                              if target is not None else arr)
            continue

        plist = pieces.get(name, [])
        if not plist:
            raise CheckpointCorruptError(step, f"leaf {name!r}",
                                         "no shard pieces in manifest")
        # direct, gather-free path: every per-device index of the TARGET
        # sharding was saved verbatim -> device_put each piece straight to
        # its device, never materialising the logical array on the host
        if isinstance(target, jax.sharding.NamedSharding):
            imap = target.addressable_devices_indices_map(shape)
            by_idx = {_index_key(idx): se for idx, se in plist}
            wanted = {d: _index_key(_norm_index(ix, shape))
                      for d, ix in imap.items()}
            if all(k in by_idx for k in wanted.values()):
                per_dev = [
                    jax.device_put(shard_named(by_idx[key])[name], d)
                    for d, key in wanted.items()]
                out_leaves.append(jax.make_array_from_single_device_arrays(
                    shape, target, per_dev))
                continue
        # elastic fallback (mesh shape changed): assemble the logical
        # array host-side from the saved pieces, then re-shard
        t0 = time.perf_counter()
        full = np.zeros(shape, dtype)
        for idx, se in plist:
            full[_index_slices(idx)] = shard_named(se)[name]
        t_gather += time.perf_counter() - t0
        out_leaves.append(jax.device_put(full, target)
                          if target is not None else full)
    if t_gather and profile is not None and getattr(profile, "enable", False):
        profile.record_phase("gather", t_gather)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state_like), out_leaves)


def restore_checkpoint(directory: str, state_like: Any,
                       step: Optional[int] = None,
                       shardings: Any = None, *,
                       profile: Any = None) -> Any:
    """Restore onto the CURRENT mesh.

    Legacy checkpoints host-unpack then ``device_put`` with the target
    shardings.  Sharded checkpoints ``device_put`` each saved piece
    straight to its target device when the shardings' indices match the
    manifest (gather-free); otherwise they fall back to host-side
    assembly (elastic restart across mesh shapes — the saved mesh is
    irrelevant).  Torn checkpoints raise :class:`CheckpointCorruptError`
    naming the step and the missing piece."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoints in {directory}")
    path = _step_dir(directory, step)
    if not os.path.isdir(path):
        raise FileNotFoundError(
            f"{directory} has no checkpoint for step {step}")
    if os.path.exists(os.path.join(path, _MANIFEST)):
        return _restore_sharded(path, step, state_like, shardings, profile)
    return _restore_legacy(path, step, state_like, shardings)


def cleanup(directory: str, keep_last: int) -> None:
    """Drop all but the newest ``keep_last`` steps AND reap stale
    ``step_*.tmp`` litter left by a crashed writer."""
    steps = []
    for name in os.listdir(directory):
        if re.fullmatch(r"step_(\d+)\.tmp", name):
            shutil.rmtree(os.path.join(directory, name), ignore_errors=True)
            continue
        if (m := re.fullmatch(r"step_(\d+)", name)):
            steps.append(int(m.group(1)))
    for s in sorted(steps)[:-keep_last]:
        shutil.rmtree(_step_dir(directory, s), ignore_errors=True)


class CheckpointManager:
    """Async double-buffered checkpointing for the train loop.

    ``sharded=True`` switches to the gather-free per-device format: the
    snapshot taken synchronously before the worker thread starts is one
    LOCAL device-to-host copy per addressable shard (the train loop may
    donate the buffers immediately after ``maybe_save`` returns)."""

    def __init__(self, directory: str, interval: int = 100, keep_last: int = 3,
                 async_save: bool = True, sharded: bool = False):
        self.directory = directory
        self.interval = interval
        self.keep_last = keep_last
        self.async_save = async_save
        self.sharded = sharded
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def maybe_save(self, step: int, state: Any, force: bool = False) -> bool:
        if not force and (self.interval <= 0 or step % self.interval != 0):
            return False
        self.wait()
        if self.sharded:
            plan = _sharded_save_plan(state)      # local d2h, no gather

            def _write():
                try:
                    _write_sharded(self.directory, step, plan, self.keep_last)
                except BaseException as e:  # surfaced on next wait()
                    self._error = e
        else:
            # snapshot synchronously (device -> host gather), write async
            host_state = jax.tree.map(np.asarray, state)

            def _write():
                try:
                    save_checkpoint(self.directory, step, host_state,
                                    self.keep_last)
                except BaseException as e:  # surfaced on next wait()
                    self._error = e

        if self.async_save:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()
        else:
            _write()
            self._raise_if_failed()
        return True

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_if_failed()

    def _raise_if_failed(self):
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(f"async checkpoint failed: {err!r}") from err

    def latest(self) -> Optional[int]:
        return latest_step(self.directory)

    def restore(self, state_like: Any, shardings: Any = None,
                step: Optional[int] = None) -> Any:
        return restore_checkpoint(self.directory, state_like, step, shardings)
