"""Persistent-state decode through the Pipeline stack (ISSUE 7 tentpole).

The KV cache / recurrent state is ONE arena-backed Data that lives on the
device across launches: marked ``persistent``, planned device-resident even
though it sits on a graph input/output edge, donated from step to step, and
never mirrored back to the host.  These tests pin down:

* the persistent-state contract — DEVICE_RESIDENT coherence across N
  steps, zero host arrays, zero ``"transfer"``/``"compile"`` phase time
  after step 0, and donation resurrection (the in-place donated blob is
  re-registered on the output handle every launch);
* bit-identity of :class:`~repro.processes.lm.DecodeSession` against an
  inline ``jax.jit`` prefill+decode loop (the model serve contract driven
  directly), for a dense, a recurrent and an MLA/MoE model;
* the weights as a static Data: each leaf its own program argument, and
  one copy of them on the device;
* bit-identity of :class:`~repro.serve.LMServer` (continuous batching via
  per-slot cache splices) against a verbatim inline copy of the legacy
  ``ServeEngine`` slot loop — transformer, rwkv6 and whisper;
* the whisper encoder→decoder fan-in prefill graph: the ``enc`` edge is
  planned device-resident and donated to its single consumer;
* the ``SamplingConfig`` default: a fresh instance per engine (the old
  mutable dataclass default was shared process-wide).
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.core.app import CLapp
from repro.core.data import Coherence
from repro.core.process import ProfileParameters
from repro.models import build_model
from repro.models.common import ArchConfig
from repro.processes.lm import DecodeSession
from repro.serve import LMServer, SamplingConfig, ServeEngine

TINY = dict(n_layers=2, d_model=16, n_heads=2, n_kv_heads=2, d_ff=32,
            vocab=48, remat=False, dtype="float32", param_dtype="float32")


def _steady_decode_steps(before):
    """The ``lm.step`` calls recorded since ``before`` (a set of span ids)
    that admitted nothing: steady decode steps."""
    return [c for c in trace.calls("lm.step")
            if c.span.id not in before and "lm.admit" not in c.counts]


def _tiny_model(family: str):
    if family == "dense":
        cfg = ArchConfig(name="tiny", family="dense", **TINY)
    elif family == "ssm":
        cfg = ArchConfig(name="tiny-rwkv", family="ssm", rwkv_head_dim=8,
                         **TINY)
    elif family == "mla_moe":
        from repro.configs import get_smoke
        cfg = get_smoke("deepseek-v2-lite-16b").scaled(n_layers=2)
    elif family == "encdec":
        cfg = ArchConfig(name="tiny-whisper", family="encdec",
                         enc_layers=2, dec_layers=2, use_rope=False,
                         **{**TINY, "n_layers": 4})
    else:
        raise ValueError(family)
    model = build_model(cfg)
    if family == "encdec":
        params = model.init_params(jax.random.key(0), max_dec_positions=64)
    else:
        params = model.init_params(jax.random.key(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# persistent-state contract
# ---------------------------------------------------------------------------

def test_state_device_resident_across_steps():
    """N decode steps: state stays DEVICE_RESIDENT, no host mirrors, the
    donated blob is resurrected each launch, and after step 0 the profile
    records ONLY compute — zero host2device on the cache edge."""
    cfg, model, params = _tiny_model("dense")
    app = CLapp().init()
    sess = DecodeSession(app, model, params, batch=2, max_len=32)
    rng = np.random.default_rng(0)
    prompts = np.asarray(rng.integers(0, cfg.vocab, (2, 4)), np.int32)

    warm = ProfileParameters(enable=True)
    sess.prefill(prompts, profile=warm)
    # prefill uploaded the prompt tokens; the zero state never moved — the
    # output blob was produced on device.
    assert sess.state.coherence is Coherence.DEVICE_RESIDENT
    assert sess.state.residency == "device"
    assert sess.state.persistent

    prof = ProfileParameters(enable=True)
    sess.step(prof)                       # step 0: AOT compile lands here
    blobs = []
    steady = ProfileParameters(enable=True)
    for _ in range(5):
        sess.step(steady)
        # donation resurrection: launch donates the previous blob into the
        # XLA program, then re-registers the fresh result on the SAME
        # handle — readable again immediately, coherence restored.
        assert sess.state.device_blob is not None
        assert sess.state.donated_by is None
        assert sess.state.coherence is Coherence.DEVICE_RESIDENT
        blobs.append(sess.state.device_blob)
    assert set(steady.phases) == {"compute"}
    assert steady.phase_total("transfer") == 0.0
    assert steady.phase_total("compile") == 0.0
    assert len(steady.phases["compute"]) == 5
    # the state never grew a host mirror: device-only end to end
    assert all(a.host is None for a in sess.state._arrays)
    # tokens() reads back only the (B, 1) token view
    assert sess.tokens().shape == (2, 1)


# ---------------------------------------------------------------------------
# DecodeSession == direct jit loop (the model serve contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "ssm", "mla_moe"])
def test_decode_session_matches_jit_loop(family):
    cfg, model, params = _tiny_model(family)
    B, P, steps = 2, 4, 5
    rng = np.random.default_rng(1)
    prompts = np.asarray(rng.integers(0, cfg.vocab, (B, P)), np.int32)

    # reference: drive the serve contract directly
    cache = model.init_cache(B, 32)
    logits, cache = jax.jit(model.prefill)(params, jnp.asarray(prompts),
                                           cache)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    want = [np.asarray(tok).copy()]
    pos = P
    dec = jax.jit(model.decode_step)
    for _ in range(steps):
        logits, cache = dec(params, tok, jnp.int32(pos), cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(np.asarray(tok).copy())
        pos += 1

    app = CLapp().init()
    sess = DecodeSession(app, model, params, batch=B, max_len=32)
    sess.prefill(prompts)
    got = [sess.tokens()]
    for _ in range(steps):
        sess.step()
        got.append(sess.tokens())
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(g, w, err_msg=f"step {i}")


@pytest.mark.parametrize("family", ["dense", "mla_moe"])
def test_lm_programs_take_each_weight_as_its_own_argument(family):
    """The compiled prefill and decode step take every params leaf as an
    argument of its own, in its shape and dtype and in codec order, and no
    ``uint32`` argument the size of the weights' arena."""
    cfg, model, params = _tiny_model(family)
    app = CLapp().init()
    sess = DecodeSession(app, model, params, batch=2, max_len=16)
    sess.prefill(np.zeros((2, 3), np.int32))
    sess.step()
    weights = app.getData(sess.weights_h)
    want = [(leaf.shape, leaf.dtype)
            for leaf in jax.tree_util.tree_leaves(params)]
    for pipe in (sess.prefill_pipe, sess.decode_pipe):
        args = jax.tree_util.tree_leaves(
            pipe._built.executor._compiled.args_info)
        assert [(a.shape, a.dtype) for a in args[1:]] == want
        assert not any(a.dtype == np.uint32
                       and a.shape[0] >= weights.layout.total_words
                       for a in args)


def test_weights_are_one_copy_on_the_device():
    """Uploading the weights leaves one copy of their bytes on the device:
    the entry arrays, and no arena blob beside them."""
    cfg, model, params = _tiny_model("dense")
    host = jax.tree.map(np.asarray, params)
    del params
    gc.collect()                   # earlier tests' arrays go first
    live = lambda: sum(a.nbytes for a in jax.live_arrays())
    before = live()
    server = LMServer(model, host, batch=2, max_len=16,
                      sampling=SamplingConfig(max_new_tokens=2))
    weights = server.app.getData(server._weights_h)
    wbytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(host))
    assert weights.device_blob is None
    assert sum(e.nbytes for e in weights.device_entries) == wbytes
    slots = sum(server.app.getData(h).layout.total_words * 4
                for h in server._slot_h)
    assert live() - before == wbytes + slots
    server.submit([1, 2, 3])
    assert len(server.run()[0]) == 2
    assert not any(a.dtype == np.uint32
                   and a.size >= weights.layout.total_words
                   for a in jax.live_arrays())


def test_whisper_fanin_prefill_matches_and_enc_is_device_resident():
    """frames→encode ~ tokens→prefill joined on ``enc``: the fan-in edge is
    planned device-resident and donated to its single consumer, and the
    decode stream is bitwise equal to driving the model directly."""
    cfg, model, params = _tiny_model("encdec")
    B, P, enc_len, steps = 2, 3, 8, 4
    rng = np.random.default_rng(2)
    prompts = np.asarray(rng.integers(0, cfg.vocab, (B, P)), np.int32)
    frames = rng.standard_normal((B, enc_len, cfg.d_model)).astype(np.float32)

    cache = model.init_cache(B, 32, enc_len)
    logits, cache = jax.jit(model.prefill)(
        params, jnp.asarray(frames), jnp.asarray(prompts), cache)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    want = [np.asarray(tok).copy()]
    pos = P
    dec = jax.jit(model.decode_step)
    for _ in range(steps):
        logits, cache = dec(params, tok, jnp.int32(pos), cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(np.asarray(tok).copy())
        pos += 1

    app = CLapp().init()
    sess = DecodeSession(app, model, params, batch=B, max_len=32,
                         enc_len=enc_len)
    sess.prefill(prompts, frames=frames)
    assert sess.prefill_pipe.residency_plan["enc"] == "device"
    assert sess.prefill_pipe._built.donated_edges.get("enc") == \
        "WhisperPrefill"
    got = [sess.tokens()]
    for _ in range(steps):
        sess.step()
        got.append(sess.tokens())
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(g, w, err_msg=f"step {i}")


# ---------------------------------------------------------------------------
# LMServer == the legacy ServeEngine slot loop (verbatim oracle)
# ---------------------------------------------------------------------------

class _LegacyOracle:
    """Verbatim copy of the pre-refactor ``ServeEngine`` continuous-batching
    loop (host-side cache pytree, per-step jit calls), kept here as the
    behavioural oracle.  Greedy only; extended with the whisper
    frames/enc_len plumbing the Pipeline path adds."""

    def __init__(self, model, params, batch, max_len, sampling,
                 enc_len=None):
        self.model, self.params = model, params
        self.batch, self.max_len = batch, max_len
        self.sampling = sampling
        self.encdec = model.cfg.family == "encdec"
        if self.encdec:
            self.cache = model.init_cache(batch, max_len, enc_len)
        else:
            self.cache = model.init_cache(batch, max_len)
        self.active = np.zeros(batch, dtype=bool)
        self.positions = np.zeros(batch, dtype=np.int32)
        self.req_of_slot = np.full(batch, -1, dtype=np.int64)
        self.results = []
        self.queue = []
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self._last_tok = np.zeros((batch, 1), dtype=np.int32)

    def submit(self, prompt, frames=None):
        rid = len(self.results)
        self.results.append([])
        self.queue.append((rid, list(prompt), frames))
        return rid

    def _admit(self):
        for slot in np.where(~self.active)[0]:
            if not self.queue:
                break
            rid, prompt, frames = self.queue.pop(0)
            toks = jnp.asarray(prompt, jnp.int32)[None, :]
            if self.encdec:
                row_cache = self.model.init_cache(
                    1, self.max_len, frames.shape[0])
                logits, row_cache = self._prefill(
                    self.params, jnp.asarray(frames)[None], toks, row_cache)
            else:
                row_cache = self.model.init_cache(1, self.max_len)
                logits, row_cache = self._prefill(self.params, toks,
                                                  row_cache)
            tok = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
            self.cache = jax.tree.map(
                lambda full, row: self._splice(full, row, int(slot)),
                self.cache, row_cache)
            self.active[slot] = True
            self.positions[slot] = len(prompt)
            self.req_of_slot[slot] = rid
            self.results[rid] = [int(tok[0, 0])]
            self._last_tok[slot] = tok[0]

    @staticmethod
    def _splice(full, row, slot):
        if (row.ndim >= 2 and full.shape[1:] == row.shape[1:]
                and full.shape[0] != row.shape[0]):
            return jax.lax.dynamic_update_slice_in_dim(full, row, slot,
                                                       axis=0)
        return jax.lax.dynamic_update_slice_in_dim(full, row, slot, axis=1)

    def step(self):
        self._admit()
        if not self.active.any():
            return
        pos = jnp.asarray(int(self.positions.max()), jnp.int32)
        tok = jnp.asarray(self._last_tok)
        logits, self.cache = self._decode(self.params, tok, pos, self.cache)
        new = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
        for slot in np.where(self.active)[0]:
            t = int(new[slot, 0])
            rid = int(self.req_of_slot[slot])
            self.results[rid].append(t)
            self.positions[slot] += 1
            self._last_tok[slot] = new[slot]
            done = (self.sampling.eos_id is not None
                    and t == self.sampling.eos_id)
            if done or len(self.results[rid]) >= self.sampling.max_new_tokens:
                self.active[slot] = False

    def run(self, max_steps=10_000):
        steps = 0
        while (self.queue or self.active.any()) and steps < max_steps:
            self.step()
            steps += 1
        return self.results


@pytest.mark.parametrize("family", ["dense", "ssm", "encdec"])
def test_lmserver_matches_legacy_engine(family):
    cfg, model, params = _tiny_model(family)
    batch, max_len, enc_len = 2, 32, (8 if family == "encdec" else None)
    sampling = SamplingConfig(max_new_tokens=4)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab, size=int(n)))
               for n in rng.integers(2, 6, size=5)]
    frames = [rng.standard_normal((enc_len, cfg.d_model)).astype(np.float32)
              if enc_len else None for _ in prompts]

    oracle = _LegacyOracle(model, params, batch, max_len, sampling,
                           enc_len=enc_len)
    for p, f in zip(prompts, frames):
        oracle.submit(p, frames=f)
    want = oracle.run()

    server = LMServer(model, params, batch=batch, max_len=max_len,
                      sampling=sampling, enc_len=enc_len)
    for p, f in zip(prompts, frames):
        server.submit(p, frames=f)
    before = {c.span.id for c in trace.calls("lm.step")}
    got = server.run()

    assert got == want
    # continuous batching through the graph: no steady decode step copies
    # a byte to the device — the cache edge stays on device.
    steady = _steady_decode_steps(before)
    assert steady and all(c.deltas["repro_h2d_bytes_total"] == 0
                          for c in steady)
    assert server.steps > 0
    assert server.state.coherence is Coherence.DEVICE_RESIDENT


def test_splice_and_release_build_one_executable_for_64_slots():
    """The slot is a traced argument of CacheSplice and SlotRelease: 64
    slots admitted and released compile one program of each, where a
    slot baked into the program compiled 128."""
    cfg, model, params = _tiny_model("dense")
    server = LMServer(model, params, batch=64, max_len=16,
                      sampling=SamplingConfig(max_new_tokens=2))
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab, size=3)) for _ in range(64)]
    for p in prompts:
        server.submit(p)
    since = trace.records()[-1].id
    got = server.run()
    assert all(len(r) == 2 for r in got) and not server.active.any()
    compiled = [r.attrs.get("fun") or "" for r in trace.records()
                if r.id > since and r.name == "compile"]
    assert sum("CacheSplice" in f for f in compiled) == 1, compiled
    assert sum("SlotRelease" in f for f in compiled) == 1, compiled
    # the last slot's row lands in the last slot: same tokens as a server
    # that admits that prompt alone
    alone = LMServer(model, params, batch=1, max_len=16,
                     sampling=SamplingConfig(max_new_tokens=2))
    alone.submit(prompts[-1])
    assert alone.run()[0] == got[-1]


def test_serve_engine_shim_delegates_and_matches():
    """The compatibility wrapper serves the same results and exposes the
    legacy introspection attributes."""
    cfg, model, params = _tiny_model("dense")
    sampling = SamplingConfig(max_new_tokens=3)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab, size=3)) for _ in range(3)]

    oracle = _LegacyOracle(model, params, 2, 32, sampling)
    for p in prompts:
        oracle.submit(p)
    want = oracle.run()

    eng = ServeEngine(model, params, batch=2, max_len=32, sampling=sampling)
    for p in prompts:
        eng.submit(p)
    before = {c.span.id for c in trace.calls("lm.step")}
    assert eng.run() == want
    assert not eng.active.any()
    assert eng.positions.shape == (2,)
    steady = _steady_decode_steps(before)
    assert steady and all(c.deltas["repro_h2d_bytes_total"] == 0
                          for c in steady)


# ---------------------------------------------------------------------------
# satellites: sampling default, stochastic guard
# ---------------------------------------------------------------------------

def test_sampling_default_is_fresh_per_engine():
    """sampling=None must build a FRESH SamplingConfig per engine — the old
    ``sampling: SamplingConfig = SamplingConfig()`` dataclass-style default
    was one shared mutable instance."""
    cfg, model, params = _tiny_model("dense")
    a = ServeEngine(model, params, batch=1, max_len=16)
    b = ServeEngine(model, params, batch=1, max_len=16)
    assert a.sampling is not b.sampling
    a.sampling.max_new_tokens = 1
    assert b.sampling.max_new_tokens != 1


def test_lmserver_rejects_stochastic_sampling():
    cfg, model, params = _tiny_model("dense")
    with pytest.raises(NotImplementedError):
        LMServer(model, params, batch=1, max_len=16,
                 sampling=SamplingConfig(temperature=0.7))
