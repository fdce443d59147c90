"""Autoregressive decode as first-class Pipeline processes.

The model zoo (:mod:`repro.models`) speaks pytrees: ``init_cache`` returns a
nested dict of KV/recurrent-state leaves, ``prefill``/``decode_step`` take
and return that tree.  The Pipeline world speaks arena-backed :class:`Data`:
named NDArrays packed into one device blob.  This module is the bridge — it
flattens the cache tree into arena entries (:class:`TreeCodec`) and wraps
the model's serve entry points as typed-port :class:`Process` es, so decode
runs through the SAME graph/residency/donation machinery as every other
workload:

* **weights as one static Data** — every params leaf held on the device
  as its own array (:func:`weights_data`), handed to each prefill and
  decode program as it is: no program decodes the weights.
* **decode state as one persistent arena Data** — ``token`` (B,1) i32,
  ``positions`` (B,) i32, ``active`` (B,) i32, plus every flattened cache
  leaf.  The Data is marked :attr:`~repro.core.data.Data.persistent`:
  ``Pipeline.build`` keeps it device-resident even though it sits on the
  step graph's input AND output edge, so each step's result is stamped
  ``Coherence.DEVICE_RESIDENT`` and the cache never round-trips the host.
* **:class:`DecodeStep`** — one greedy decode step over the whole batch,
  bound in-place (``infile == outfile`` == the state handle) so the
  compiled program *donates* the previous step's blob to XLA: step-to-step
  the cache moves zero bytes and allocates nothing new.
* **:class:`PrefillProcess`** — prompt -> fresh decode state (cache built
  inside the traced program; for encoder-decoder models the audio frames
  ride in on an optional second input port).
* **:class:`WhisperEncode` / :class:`WhisperPrefill`** — the encoder and
  the decoder-side prefill as separate graph nodes joined on an ``enc``
  edge: a real fan-in Pipeline (frames -> encoder ~ tokens -> decoder
  prefill) whose internal edge is device-resident and donated.
* **:class:`CacheSplice` / :class:`SlotRelease`** — continuous-batching
  primitives: splice a single-row prefill into one slot of the batched
  state / retire a finished slot, both wired in-place on the state handle
  (donation, not copies).  :class:`repro.serve.pipeline.LMServer` drives
  them.

:class:`DecodeSession` packages the full-batch loop (used by
``benchmarks/lm_step.py`` and the decode tests); per-slot continuous
batching lives in :class:`repro.serve.pipeline.LMServer`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.app import CLapp
from repro.core.data import Data
from repro.core.graph import Pipeline
from repro.core.process import (Port, Process, ProfileParameters,
                                current_compile_mesh)
from repro.launch.mesh import mesh_axis, model_axis_size


class TreeCodec:
    """Stable pytree <-> named-array bridge for one tree *structure*.

    Names are derived from the tree paths (``jax.tree_util.keystr``) with a
    fixed prefix, so the same codec maps any tree of the same structure —
    batch-1 row caches and batch-B full caches share one codec."""

    def __init__(self, tree: Any, prefix: str = ""):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.treedef = treedef
        self.names: Tuple[str, ...] = tuple(
            prefix + jax.tree_util.keystr(path) for path, _ in flat)

    def flatten(self, tree: Any) -> Dict[str, jax.Array]:
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.names):
            raise ValueError(
                f"tree has {len(leaves)} leaves, codec expects "
                f"{len(self.names)}")
        return dict(zip(self.names, leaves))

    def unflatten(self, named: Dict[str, jax.Array]) -> Any:
        return jax.tree_util.tree_unflatten(
            self.treedef, [named[n] for n in self.names])


def _abstract_cache(model, batch: int, max_len: int,
                    enc_len: Optional[int] = None):
    """Shape/dtype skeleton of ``model.init_cache`` without allocating."""
    if model.cfg.family == "encdec":
        if enc_len is None:
            raise ValueError("encoder-decoder models need enc_len")
        return jax.eval_shape(
            lambda: model.init_cache(batch, max_len, enc_len))
    return jax.eval_shape(lambda: model.init_cache(batch, max_len))


def weights_data(params: Any, prefix: str = "w") -> Tuple[Data, TreeCodec]:
    """Flatten a params tree into one Data (the ``weights`` aux of every
    decode process) plus its codec.  The Data is marked
    :attr:`~repro.core.data.Data.static`: it is uploaded as its own arrays,
    one per leaf, and every program takes those arrays as they are."""
    codec = TreeCodec(params, prefix=prefix)
    named = codec.flatten(params)
    data = Data({n: np.asarray(v) for n, v in named.items()})
    data.static = True
    return data, codec


def decode_state_data(model, batch: int, max_len: int,
                      enc_len: Optional[int] = None,
                      ) -> Tuple[Data, TreeCodec]:
    """Spec-only persistent decode-state Data: sampling bookkeeping
    (``token``/``positions``/``active``) + every flattened cache leaf.
    Marked persistent/device-resident — the KV-cache-as-arena contract."""
    cache = _abstract_cache(model, batch, max_len, enc_len)
    codec = TreeCodec(cache, prefix="cache")
    specs: Dict[str, jax.ShapeDtypeStruct] = {
        "token": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        "positions": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "active": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
    for name, leaf in zip(codec.names, jax.tree_util.tree_leaves(cache)):
        specs[name] = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    state = Data.from_specs(specs)
    state.persistent = True
    state.residency = "device"
    return state, codec


class _LMProcess(Process):
    """Shared plumbing: model + weights/cache codecs + a static key that
    separates compiled programs per architecture (two models with equal
    arena layouts must not share an executable)."""

    def __init__(self, app, model, wcodec: TreeCodec, ccodec: TreeCodec, *,
                 max_len: int, tag: str):
        super().__init__(app)
        self.model = model
        self.wcodec = wcodec
        self.ccodec = ccodec
        self.max_len = max_len
        self.set_launch_parameters((tag, repr(model.cfg), max_len))

    def _weights(self, aux):
        return self.wcodec.unflatten(aux["weights"])

    def _state_from(self, logits, cache, prompt_len: int):
        """Greedy-sample the prefill logits and assemble a fresh state."""
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, 1)
        b = token.shape[0]
        out = {"token": token,
               "positions": jnp.full((b,), prompt_len, jnp.int32),
               "active": jnp.ones((b,), jnp.int32)}
        out.update(self.ccodec.flatten(cache))
        return out


class PrefillProcess(_LMProcess):
    """Prompt tokens -> fresh decode state (cache initialised and prefilled
    inside the one traced program; greedy first token sampled on device).
    Encoder-decoder models bind the optional ``frames`` input port."""

    ports = {"in": Port(names=("tokens",), dtype=jnp.integer,
                        doc="prompt token ids (B, S)"),
             "frames": Port(optional=True,
                            doc="audio frame embeddings (B, T_enc, D), "
                                "encoder-decoder families only"),
             "out": Port(names=("token", "positions", "active")),
             "weights": Port(aux=True, doc="flattened model params")}

    def __init__(self, app, model, wcodec, ccodec, *, max_len: int):
        super().__init__(app, model, wcodec, ccodec, max_len=max_len,
                         tag="prefill")

    def apply(self, views, aux, params):
        w = self._weights(aux)
        tokens = views["tokens"]
        b, s = tokens.shape
        if self.model.cfg.family == "encdec":
            if "frames" not in aux:
                raise ValueError(
                    "encoder-decoder prefill needs the 'frames' port bound")
            frames = aux["frames"]["frames"]
            cache = self.model.init_cache(b, self.max_len, frames.shape[1])
            logits, cache = self.model.prefill(w, frames, tokens, cache)
        else:
            cache = self.model.init_cache(b, self.max_len)
            logits, cache = self.model.prefill(w, tokens, cache)
        return self._state_from(logits, cache, s)


class WhisperEncode(Process):
    """Audio frames -> encoder states, as its own graph node (the fan-in
    showcase: its ``enc`` output edge is internal — device-resident and
    donated to the decoder prefill that joins on it)."""

    ports = {"in": Port(names=("frames",), doc="frame embeddings (B,T,D)"),
             "out": Port(names=("enc",)),
             "weights": Port(aux=True)}

    def __init__(self, app, model, wcodec: TreeCodec):
        super().__init__(app)
        self.model = model
        self.wcodec = wcodec
        self.set_launch_parameters(("whisper_encode", repr(model.cfg)))

    def apply(self, views, aux, params):
        w = self.wcodec.unflatten(aux["weights"])
        return {"enc": self.model.encode(w, views["frames"])}


class WhisperPrefill(_LMProcess):
    """Decoder-side prefill from precomputed encoder states: joins the
    ``enc`` edge produced by :class:`WhisperEncode` (cross-attention K/V
    are computed here and land in the cache)."""

    ports = {"in": Port(names=("tokens",), dtype=jnp.integer),
             "enc": Port(names=("enc",), doc="encoder states (B, T_enc, D)"),
             "out": Port(names=("token", "positions", "active")),
             "weights": Port(aux=True)}

    def __init__(self, app, model, wcodec, ccodec, *, max_len: int):
        super().__init__(app, model, wcodec, ccodec, max_len=max_len,
                         tag="whisper_prefill")

    def apply(self, views, aux, params):
        w = self._weights(aux)
        tokens = views["tokens"]
        enc = aux["enc"]["enc"]
        b, s = tokens.shape
        cache = self.model.init_cache(b, self.max_len, enc.shape[1])
        logits, cache = self.model.prefill_from_enc(w, enc, tokens, cache)
        return self._state_from(logits, cache, s)


class DecodeStep(_LMProcess):
    """One greedy decode step over the whole batch, in place on the state.

    Matches the legacy ``ServeEngine.step`` math exactly: decode every row
    at ``pos = positions.max()`` (inactive rows keep re-feeding their last
    token; the per-position cache masks stale entries), then advance only
    the active rows.

    Compiled under a mesh whose ``model`` axis is non-trivial, the step is
    ``shard_map``-partitioned over decode **slots** (the ``slot`` logical
    axis, :data:`repro.launch.mesh.LOGICAL_AXES`): each model-group member
    decodes its strip of rows + cache, with the one cross-slot quantity —
    the shared position scalar — reduced by an exact integer ``pmax``, so
    the partitioned step is bit-identical to the 1D one.  No-op when the
    mesh is 1D, the slot count does not divide, or any cache leaf's batch
    axis cannot be identified."""

    ports = {"in": Port(names=("token", "positions", "active")),
             "out": Port(names=("token", "positions", "active")),
             "weights": Port(aux=True)}

    def __init__(self, app, model, wcodec, ccodec, *, max_len: int):
        super().__init__(app, model, wcodec, ccodec, max_len=max_len,
                         tag="decode_step")

    @staticmethod
    def _slot_axis(leaf, b: int) -> Optional[int]:
        """Batch (slot) axis of one cache leaf: 0 for per-row leaves, 1 for
        stacked-layer ``(L, B, ...)`` leaves — the same heuristic as
        ``_splice_row`` (ambiguous when L == B; axis 0 wins there)."""
        if leaf.ndim >= 1 and leaf.shape[0] == b:
            return 0
        if leaf.ndim >= 2 and leaf.shape[1] == b:
            return 1
        return None

    def apply(self, views, aux, params):
        w = self._weights(aux)
        token = views["token"]
        positions = views["positions"]
        active = views["active"]
        cache = self.ccodec.unflatten(views)

        def step(w, token, positions, active, cache, pos):
            logits, cache = self.model.decode_step(w, token, pos, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, 1)
            live = active[:, None] > 0
            return (jnp.where(live, nxt, token), positions + active,
                    active, cache)

        mesh = current_compile_mesh()
        ax = mesh_axis("slot")          # mesh axis the slot dim is bound to
        b = int(token.shape[0])
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        slot_axes = [self._slot_axis(leaf, b) for leaf in leaves]
        nm = model_axis_size(mesh) if ax == "model" else 1
        if nm > 1 and b % nm == 0 and all(a is not None for a in slot_axes):
            P = jax.sharding.PartitionSpec
            cache_specs = tuple(
                P(*([None] * a + [ax])) for a in slot_axes)

            def body(w, token, positions, active, *leaves):
                cache = jax.tree_util.tree_unflatten(treedef, leaves)
                pos = jax.lax.pmax(jnp.max(positions), ax).astype(jnp.int32)
                t, p, act, cache = step(w, token, positions, active,
                                        cache, pos)
                return (t, p, act) + tuple(jax.tree_util.tree_leaves(cache))

            outs = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(ax, None), P(ax), P(ax)) + cache_specs,
                out_specs=(P(ax, None), P(ax), P(ax)) + cache_specs,
                check_vma=False)(w, token, positions, active, *leaves)
            token, positions, active = outs[0], outs[1], outs[2]
            cache = jax.tree_util.tree_unflatten(treedef, outs[3:])
        else:
            pos = jnp.max(positions).astype(jnp.int32)
            token, positions, active, cache = step(
                w, token, positions, active, cache, pos)
        out = {"token": token, "positions": positions, "active": active}
        out.update(self.ccodec.flatten(cache))
        return out


def _splice_row(full: jax.Array, row: jax.Array, slot) -> jax.Array:
    """Insert a 1-row leaf into slot ``slot`` of the batched leaf — the
    legacy ``ServeEngine._splice`` heuristic (batch axis is 0 for leaves
    whose leading axis differs, 1 for stacked-layer leaves), extended to
    the rank-1 bookkeeping arrays."""
    if full.ndim == 1 or (row.ndim >= 2 and full.shape[1:] == row.shape[1:]
                          and full.shape[0] != row.shape[0]):
        return jax.lax.dynamic_update_slice_in_dim(full, row, slot, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(full, row, slot, axis=1)


def slot_data(slot: int) -> Data:
    """The ``slot`` aux of :class:`CacheSplice` and :class:`SlotRelease`:
    the slot index as a traced int32, so one executable serves every
    slot."""
    return Data({"slot": np.asarray([slot], np.int32)})


class CacheSplice(Process):
    """Continuous-batching admission: splice a single-row prefilled state
    (the ``row`` aux, batch 1) into slot ``slot`` of the batched persistent
    state.  Wired in place (``in`` == ``out`` == the state handle) so the
    old state blob is donated, not copied.  The slot is the traced ``slot``
    aux (:func:`slot_data`): one executable for every slot."""

    ports = {"in": Port(names=("token", "positions", "active")),
             "out": Port(names=("token", "positions", "active")),
             "row": Port(aux=True, doc="batch-1 state from a row prefill"),
             "slot": Port(aux=True, names=("slot",),
                          doc="(1,) int32 slot index")}

    def __init__(self, app):
        super().__init__(app)
        self.set_launch_parameters(("cache_splice",))

    def apply(self, views, aux, params):
        slot = aux["slot"]["slot"][0]
        row = aux["row"]
        return {name: _splice_row(full, row[name], slot)
                for name, full in views.items()}


class SlotRelease(Process):
    """Retire the slot the traced ``slot`` aux names: zero its ``active``
    flag on device (freezing its position and token exactly like the
    legacy host-side bookkeeping) while passing the rest of the state
    through in place.  One executable for every slot."""

    ports = {"in": Port(names=("token", "positions", "active")),
             "out": Port(names=("token", "positions", "active")),
             "slot": Port(aux=True, names=("slot",),
                          doc="(1,) int32 slot index")}

    def __init__(self, app):
        super().__init__(app)
        self.set_launch_parameters(("slot_release",))

    def apply(self, views, aux, params):
        slot = aux["slot"]["slot"][0]
        out = dict(views)
        out["active"] = jax.lax.dynamic_update_slice_in_dim(
            views["active"], jnp.zeros((1,), jnp.int32), slot, axis=0)
        return out


class DecodeSession:
    """Full-batch decode through the Pipeline stack: one prefill graph
    (the whisper encoder→decoder fan-in for encoder-decoder models), then
    a single in-place :class:`DecodeStep` node launched per token.

    The state Data is persistent: after the one zero-state upload folded
    into the first launch, every step donates the previous blob and stamps
    the result ``DEVICE_RESIDENT`` — ``step()`` reads back only the (B, 1)
    token view.  ``benchmarks/lm_step.py`` measures this path; per-slot
    continuous batching is :class:`repro.serve.pipeline.LMServer`."""

    def __init__(self, app: CLapp, model, params, *, batch: int,
                 max_len: int, enc_len: Optional[int] = None):
        self.app = app
        self.model = model
        self.batch = batch
        self.max_len = max_len
        self.encdec = model.cfg.family == "encdec"
        if self.encdec and enc_len is None:
            raise ValueError("encoder-decoder models need enc_len")
        wdata, self.wcodec = weights_data(params)
        self.weights_h = app.addData(wdata)     # uploaded once
        self.state, self.ccodec = decode_state_data(
            model, batch, max_len, enc_len)
        self.state_h = app.addData(self.state, to_device=False)
        if self.encdec:
            enc_node = WhisperEncode(app, model, self.wcodec).bind(
                infile="frames", outfile="enc", weights=self.weights_h)
            pre_node = WhisperPrefill(
                app, model, self.wcodec, self.ccodec,
                max_len=max_len).bind(
                    infile="tokens", outfile=self.state_h,
                    enc="enc", weights=self.weights_h)
            self.prefill_pipe = Pipeline.from_graph(
                app, [enc_node, pre_node])
        else:
            self.prefill_pipe = Pipeline(app) | PrefillProcess(
                app, model, self.wcodec, self.ccodec,
                max_len=max_len).bind(
                    infile="tokens", outfile=self.state_h,
                    weights=self.weights_h)
        self.decode_pipe = Pipeline(app) | DecodeStep(
            app, model, self.wcodec, self.ccodec, max_len=max_len).bind(
                infile=self.state_h, outfile=self.state_h,
                weights=self.weights_h)

    def tokens(self) -> np.ndarray:
        """Device -> host copy of the (B, 1) current-token view (the only
        per-step readback; the cache itself never leaves the device)."""
        return np.asarray(self.state.device_view("token")).copy()

    def prefill(self, tokens: np.ndarray, frames: Optional[np.ndarray] = None,
                profile: Optional[ProfileParameters] = None) -> np.ndarray:
        """Run the prefill graph for the whole batch; returns the greedy
        first tokens (B, 1)."""
        td = Data({"tokens": np.asarray(tokens, np.int32)})
        if self.encdec:
            inputs: Any = {"tokens": td,
                           "frames": Data({"frames": np.asarray(
                               frames, np.float32)})}
        else:
            inputs = td
        self.prefill_pipe.run(inputs, sync=False, profile=profile)
        return self.tokens()

    def step(self, profile: Optional[ProfileParameters] = None) -> np.ndarray:
        """One batched decode step (in-place, device-resident); returns
        the new (B, 1) tokens."""
        self.decode_pipe.run(None, sync=False, profile=profile)
        return self.tokens()
