"""Request/response serving loop over a built operator Pipeline.

This is the ROADMAP's serve-engine integration for Data-set workloads
(MRI reconstructions, image operators): wrap the sharded streaming
executor in a request/response loop —

    admission queue  ->  dynamic batcher  ->  batched (sharded) launches

* **Admission** — ``submit()`` packs the request's Data into host arena
  blobs immediately (validating each against the pipeline's input edges)
  and appends it to a pending deque.  A fan-in pipeline (several input
  edges) takes a **multi-tensor request**: one Data per input edge, as a
  ``{edge name -> Data}`` mapping — each edge is packed and batched
  independently, then joined in one launch.
* **Dynamic batching** — ``drain()`` groups whatever is pending into
  stacked blobs of up to ``batch`` rows **per input edge**, row-aligned
  across edges (request i is row i of every edge's batch).
  Partially-full flushes follow the streaming executor's ragged-tail
  policy (:class:`repro.core.stream._BatchPlan`): pad by repetition when
  the waste is small, or run a second executable compiled for the flush
  size — both results are bit-identical to full batches.  Requests
  submitted while a drain is in progress are picked up by the same drain.
* **Transfer/compute overlap** — the stacked blobs feed per-edge
  :class:`repro.core.stream.StreamQueue` s (the admission buffer per the
  ROADMAP), zipped before each launch: batch *i+1* is in flight to the
  device — sharded across the mesh's ``data`` axis when ``sharded=True``
  — while batch *i* computes.
* **Flush timeout** — with ``flush_timeout`` (seconds) set, a background
  drain thread serves continuously: full batches launch immediately, and
  a PARTIAL batch is flushed once its oldest request has waited
  ``flush_timeout`` instead of waiting for a full batch (the
  latency-sensitive serving policy from the ROADMAP).  Responses are
  picked up with :meth:`PipelineServer.collect` (or a final ``drain()``);
  ``close()`` stops the thread after flushing what is left.
  ``benchmarks/serve_latency.py`` reports the p50/p99 impact.

Each response carries its request id and wall-clock latency from
``submit()`` to result-ready, which is what ``benchmarks/serve_latency.py``
aggregates into p50/p99.  Responses are produced in launch order; callers
that need submit order sort by ``rid`` (``Pipeline.run(mode="serve")``
does).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import trace
from repro.core.app import CLapp
from repro.core.arena import split_batched_blob
from repro.core.data import Data
from repro.core.graph import Pipeline
from repro.core.process import PortError
from repro.core.stream import (DEPTH, StreamQueue, _BatchPlan, _JoinFeed,
                               _edge_blobs)
from repro.core.sync import Coherence


class PromptTooLongError(ValueError):
    """A prompt does not fit the server's compiled cache capacity.

    :class:`LMServer`'s decode state is ONE arena-backed Data whose cache
    leaves are compiled for ``max_len`` positions; a prompt of ``T``
    tokens prefills positions ``0..T-1`` and every generated token needs
    one more, so ``T`` must satisfy ``1 <= T <= max_len - 1``.  Raised by
    :meth:`LMServer.submit` *before* the request is queued — previously
    an over-long prompt surfaced as an opaque shape error deep inside the
    prefill compile."""

    def __init__(self, prompt_len: int, max_len: int):
        super().__init__(
            f"prompt of {prompt_len} token(s) does not fit the compiled "
            f"cache capacity max_len={max_len}: need 1 <= len(prompt) <= "
            f"{max_len - 1} (prefill fills len(prompt) positions and each "
            "generated token needs one more)")
        self.prompt_len = prompt_len
        self.max_len = max_len


@dataclasses.dataclass
class ServeResponse:
    """One served result: the output Data plus latency accounting."""

    rid: int
    data: Data
    submitted_s: float          # perf_counter at submit()
    completed_s: float          # perf_counter when the result was ready

    @property
    def latency_s(self) -> float:
        return self.completed_s - self.submitted_s


@dataclasses.dataclass
class _Request:
    rid: int
    blobs: Tuple[Any, ...]      # packed host arena blobs, one per input edge
    submitted_s: float


class PipelineServer:
    """Serving front-end for one :class:`repro.core.graph.Pipeline`.

    Usage::

        server = pipe.serve(batch=8, sharded=True)
        rids = [server.submit(kdata) for kdata in requests]
        responses = server.drain()          # ServeResponse per request

        # fan-in pipeline: multi-tensor requests, one Data per input edge
        rid = server.submit({"kspace": kd, "smaps": sm})

        # latency-sensitive: background drain with a partial-batch flush
        server = pipe.serve(batch=8, flush_timeout=0.010)
        rids = [server.submit(r) for r in requests]   # flushes on its own
        responses = server.collect(len(rids), timeout=5.0)
        server.close()

    The pipeline is built lazily from the first submitted request (or
    reused if already built); every launch reuses the one AOT-compiled
    batched program, so serving keeps the paper's per-iteration overhead
    at zero.
    """

    def __init__(self, pipeline, *, batch: int = 8, sharded: bool = False,
                 flush_timeout: Optional[float] = None):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if flush_timeout is not None and flush_timeout <= 0:
            raise ValueError(
                f"flush_timeout must be > 0 seconds, got {flush_timeout}")
        self.pipeline = pipeline
        self.batch = batch
        self.sharded = sharded
        self.flush_timeout = flush_timeout
        self._pending: Deque[_Request] = deque()
        self._next_rid = 0
        self._plan: Optional[_BatchPlan] = None
        self._built = None
        self._aux_blobs: Optional[List[Any]] = None
        self.served = 0             # completed requests (introspection)
        self.launches = 0           # batched launches issued
        # background drain state (flush_timeout mode)
        self._cv = threading.Condition()
        self._completed: List[ServeResponse] = []
        self._worker: Optional[threading.Thread] = None
        self._busy = False          # worker is launching a group
        self._force_flush = False
        self._stop_flag = False
        self._closed = False        # close() ran (flush_timeout mode only)
        self._worker_error: Optional[BaseException] = None

    # ------------------------------------------------------------ lifecycle
    def _ensure_built(self, request: Any) -> None:
        if self._plan is not None:
            return
        built = self.pipeline.build(request)
        self._built = built
        self._plan = _BatchPlan(built.executor, self.batch,
                                sharded=self.sharded).init()
        # aux wiring is fixed for the server's lifetime: prepare (and, when
        # sharded, mesh-replicate) the aux blobs ONCE, not per drain
        app = built.executor.getApp()
        self._aux_blobs = self._plan.prepare_aux()
        app.wait_transfers(self._plan.launchable.aux_handles)

    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._pending)

    @property
    def input_edges(self) -> Tuple[str, ...]:
        """The pipeline's input edges in batch position order (the order
        multi-tensor requests are stacked in)."""
        if self._built is None:
            raise RuntimeError("server not built yet (submit a request)")
        return self._built.input_order

    def warmup(self) -> None:
        """Pre-compile every executable a drain might need: the full
        batch plus every partial-flush row count the ragged-tail policy
        can pick.  Keeps first-seen group sizes (e.g. timing-dependent
        partial flushes under ``flush_timeout``) from paying XLA compile
        time inside a served window."""
        if self._plan is None:
            raise RuntimeError("server not built yet (submit a request)")
        for r in range(1, self.batch + 1):
            self._plan.precompile(r)

    # ------------------------------------------------------------ admission
    def _pack_request(self, request: Any) -> Tuple[Any, ...]:
        """Normalize + validate one request into per-edge host blobs
        (same pack/validate loop as the streaming executor, displaying
        graph edge names and raising PortError, the serve-layer type)."""
        la = self._plan.launchable
        item = self.pipeline._item_tuple(self._built, request,
                                         what="request")
        if isinstance(item, Data):
            item = (item,)
        return _edge_blobs(item, la, what="request",
                           names=self._built.input_order, err=PortError)

    def submit(self, request: Any) -> int:
        """Admit one request: validate, pack to host arena blobs (one per
        input edge), queue.  Returns the request id used to match the
        response.  With ``flush_timeout`` set this also (lazily) starts
        the background drain thread and wakes it."""
        self._ensure_built(request)
        with trace.span("stream.pack") as s:
            blobs = self._pack_request(request)
            s.attrs["bytes"] = sum(b.nbytes for b in blobs)
        with self._cv:
            self._check_closed()
            self._check_worker_error()
            rid = self._next_rid
            self._next_rid += 1
            self._pending.append(_Request(rid, blobs, time.perf_counter()))
            if self.flush_timeout is not None:
                if self._worker is None:
                    self._worker = threading.Thread(
                        target=self._worker_loop,
                        name="pipeline-server-drain", daemon=True)
                    self._worker.start()
                self._cv.notify_all()
        return rid

    def _check_closed(self) -> None:
        """(Caller holds the lock.)  A closed server can neither admit
        nor serve: raising beats silently restarting the background
        thread (submit) or sleeping forever on responses that can no
        longer arrive (drain/collect)."""
        if self._closed:
            raise RuntimeError(
                "server is closed (close() was called); create a new "
                "server via pipe.serve()")

    def _check_worker_error(self) -> None:
        """(Caller holds the lock.)  A launch/compile failure in the
        background thread is terminal for the server: surface it to every
        later caller instead of hanging or silently dropping requests."""
        if self._worker_error is not None:
            raise RuntimeError(
                "the background drain thread died; the server cannot "
                "serve any more requests (requests of the failing batch "
                "were dropped)") from self._worker_error

    # ------------------------------------------------------------- serving
    def _responses_for(self, group: Sequence[_Request],
                       out: jax.Array, t_done: float) -> List[ServeResponse]:
        la = self._plan.launchable
        with trace.span("stream.split"):
            per_item = split_batched_blob(out)[:len(group)]
            self.launches += 1
            responses = []
            for req, blob in zip(group, per_item):
                d = Data.from_layout(la.out_layout)
                d.device_blob = blob
                d.coherence = Coherence.DEVICE_FRESH
                responses.append(ServeResponse(
                    rid=req.rid, data=d, submitted_s=req.submitted_s,
                    completed_s=t_done))
        return responses

    def drain(self) -> List[ServeResponse]:
        """Serve every pending request (including ones admitted while the
        drain runs); returns the responses in completion (launch) order.

        With the background drain thread active this instead forces an
        immediate flush of any partial batch, waits for the thread to go
        idle, and returns everything completed but not yet collected.
        On a closed server this raises ``RuntimeError`` — after
        ``close()`` there is no thread left to flush, and waiting on the
        queue would hang forever."""
        with self._cv:
            self._check_closed()
        if self._worker is not None:
            with self._cv:
                self._force_flush = True
                self._cv.notify_all()
                while (self._pending or self._busy) \
                        and self._worker_error is None:
                    self._cv.wait()
                self._check_worker_error()
                self._force_flush = False
                out, self._completed = self._completed, []
            return out
        if self._plan is None or not self._pending:
            return []
        plan = self._plan
        la = plan.launchable
        aux_blobs = self._aux_blobs

        # compile the expected tail executable(s) BEFORE the launch loop so
        # a partial flush never stalls serving (nor charges XLA compile
        # time to the requests' recorded latencies)
        tail = len(self._pending) % self.batch
        if tail:
            plan.precompile(tail)

        groups: Deque[List[_Request]] = deque()

        def group_iter():
            # dynamic batcher: whatever is pending right now, up to `batch`
            # rows per launch; the parallel `groups` deque carries the
            # request bookkeeping in the same order the feeds yield blobs
            while True:
                with self._cv:
                    if not self._pending:
                        return
                    group: List[_Request] = []
                    while self._pending and len(group) < self.batch:
                        group.append(self._pending.popleft())
                groups.append(group)
                yield [r.blobs for r in group]

        # one row-aligned feed per input edge, zipped per launch (the
        # fan-in join path; single-input pipelines are the 1-edge case)
        feed = _JoinFeed(plan, group_iter())
        queues = [StreamQueue(feed.feed(e), device=plan.place, depth=DEPTH)
                  for e in range(la.n_inputs)]
        responses: List[ServeResponse] = []
        for dev_blobs in zip(*queues):  # next flush transfers while this runs
            group = groups.popleft()
            with _flush_span(group):
                out = plan.launch(dev_blobs, aux_blobs)
                jax.block_until_ready(out)  # latency = result actually ready
                t_done = time.perf_counter()
                responses.extend(self._responses_for(group, out, t_done))
        self.served += len(responses)
        return responses

    # ------------------------------------------- background drain (timeout)
    def _worker_loop(self) -> None:
        plan = self._plan
        while True:
            with self._cv:
                while True:
                    if self._pending:
                        n = len(self._pending)
                        if (n >= self.batch or self._force_flush
                                or self._stop_flag):
                            break
                        waited = time.perf_counter() - \
                            self._pending[0].submitted_s
                        remaining = self.flush_timeout - waited
                        if remaining <= 0:
                            break           # oldest request timed out: flush
                        self._cv.wait(timeout=remaining)
                    else:
                        if self._stop_flag:
                            return
                        self._cv.wait()
                k = min(len(self._pending), self.batch)
                group = [self._pending.popleft() for _ in range(k)]
                self._busy = True
            responses: List[ServeResponse] = []
            error: Optional[BaseException] = None
            try:
                with _flush_span(group):
                    stacked = tuple(
                        plan.place(blob)
                        for blob in plan.stack_group([r.blobs
                                                      for r in group]))
                    out = plan.launch(stacked, self._aux_blobs)
                    jax.block_until_ready(out)
                    responses = self._responses_for(group, out,
                                                    time.perf_counter())
            except BaseException as e:    # noqa: BLE001 — must not die silent
                error = e
            finally:
                # responses (or the terminal error) land under the SAME lock
                # transition that clears busy: a concurrent drain() cannot
                # observe idle-but-empty, nor hang on a dead worker
                with self._cv:
                    self._completed.extend(responses)
                    self.served += len(responses)
                    self._busy = False
                    if error is not None:
                        self._worker_error = error
                    self._cv.notify_all()
            if error is not None:
                return                    # terminal: callers re-raise it

    def collect(self, n: Optional[int] = None,
                timeout: Optional[float] = None) -> List[ServeResponse]:
        """Take completed responses from the background drain.  Blocks
        until at least ``n`` responses are available (or ``timeout``
        seconds passed); ``n=None`` returns whatever is ready now.
        Requires ``flush_timeout`` — without the background thread only
        ``drain()`` produces responses and waiting here could never
        succeed."""
        if self.flush_timeout is None:
            raise RuntimeError(
                "collect() needs the background drain thread "
                "(flush_timeout=...); without it use drain()")
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._cv:
            self._check_closed()
            while n is not None and len(self._completed) < n:
                # a dead worker can never produce the missing responses —
                # raise instead of sleeping out the timeout.  Responses
                # that already completed stay retrievable: collect(None)
                # after the error returns them without raising.
                self._check_worker_error()
                rem = None if deadline is None \
                    else deadline - time.perf_counter()
                if rem is not None and rem <= 0:
                    break
                self._cv.wait(timeout=rem)
            out, self._completed = self._completed, []
        return out

    def close(self) -> None:
        """Stop the background drain thread (flushing anything pending
        first) and mark the server closed: later ``submit``/``drain``/
        ``collect`` calls raise ``RuntimeError`` instead of hanging on a
        queue nothing serves any more.  Idempotent and thread-safe — the
        worker is claimed under the lock, so two concurrent (or
        sequential) ``close()`` calls can never both ``join()`` it, and
        closing after a background launch failure (the thread already
        dead) just reaps it without re-raising.  Unclosed servers die
        with the process (daemon thread); servers without the background
        thread (no ``flush_timeout``) have nothing to close and stay
        usable."""
        if self.flush_timeout is None:
            return
        with self._cv:
            self._closed = True
            worker, self._worker = self._worker, None
            if worker is None:
                return              # second close(), or never started
            self._stop_flag = True
            self._cv.notify_all()
        worker.join()

    def __enter__(self) -> "PipelineServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _flush_span(group: Sequence[_Request]) -> trace.span:
    """The span of one batch flush: how many requests it carries and how
    long the oldest of them waited in the queue."""
    return trace.span(
        "serve.flush", fill=len(group),
        oldest_wait_s=time.perf_counter() - group[0].submitted_s)


class LMServer:
    """Slot-based continuous batching for autoregressive decode, built
    entirely from Pipeline-stack primitives (the ONE batching
    implementation; ``repro.serve.engine.ServeEngine`` is now a thin
    compatibility wrapper over this class).

    Each of the ``batch`` rows of one persistent, arena-backed decode
    state (:func:`repro.processes.lm.decode_state_data` — sampling
    bookkeeping + every KV/recurrent cache leaf) is a **slot**:

    * **admission** — a queued prompt claims a free slot: a per-prompt-
      shape prefill :class:`~repro.core.graph.Pipeline` produces a batch-1
      row state on device, and an in-place :class:`~repro.processes.lm.
      CacheSplice` donates the old batched state and writes the row into
      the slot.  New requests join IN-FLIGHT decode batches the moment a
      slot frees — no full-batch-or-timeout wait.
    * **decode** — one in-place :class:`~repro.processes.lm.DecodeStep`
      launch per token advances every active slot; the state blob is
      donated step-to-step and stays ``DEVICE_RESIDENT``, so the only
      per-step traffic is the (B, 1) token readback: the
      ``repro_h2d_bytes_total`` counter does not grow across a decode
      step.
    * **release** — a finished request retires its slot with an in-place
      :class:`~repro.processes.lm.SlotRelease` (device ``active`` flag
      zeroed; position/token freeze exactly like the legacy host-side
      bookkeeping, keeping ``pos = positions.max()`` bit-compatible).
      For an MoE model the step that releases reads the rows' routing
      counts (the state's ``moe_counts`` leaf, accumulated on the device
      by prefill and decode) once, and adds each released row's to the
      ``repro_moe_*`` counters of :data:`repro.core.trace.METRICS`.

    The splice and the release take the slot as a traced argument (one
    ``slot`` Data a slot, uploaded at construction), so each is one
    executable whatever the batch.

    Decoding is greedy (``temperature=0``) — the sampling math runs on
    device inside the compiled step, so the host loop never sees logits.
    Stochastic sampling is rejected at construction rather than silently
    approximated.  Encoder-decoder models (whisper) pass per-request
    ``frames`` to :meth:`submit`; their prefill graph is the encoder→
    decoder fan-in join.
    """

    def __init__(self, model, params, *, batch: int, max_len: int,
                 sampling=None, enc_len: Optional[int] = None,
                 app: Optional[CLapp] = None):
        from repro.serve.engine import SamplingConfig  # lazy: engine wraps us
        from repro.processes import lm as lmp

        self.sampling = sampling if sampling is not None else SamplingConfig()
        if self.sampling.temperature > 0 or self.sampling.top_k:
            raise NotImplementedError(
                "LMServer decodes greedily on device (the sampling runs "
                "inside the compiled step); temperature/top_k sampling is "
                "not wired into the device-resident path")
        self.model, self.params = model, params
        self.batch, self.max_len = batch, max_len
        self.enc_len = enc_len
        self.encdec = model.cfg.family == "encdec"
        if self.encdec and enc_len is None:
            raise ValueError("encoder-decoder models need enc_len")
        self.app = app if app is not None else CLapp().init()
        self._lmp = lmp
        wdata, self._wcodec = lmp.weights_data(params)
        self._weights_h = self.app.addData(wdata)       # uploaded once
        self.state, self._ccodec = lmp.decode_state_data(
            model, batch, max_len, enc_len)
        self.state_h = self.app.addData(self.state, to_device=False)
        self._decode_pipe = Pipeline(self.app) | lmp.DecodeStep(
            self.app, model, self._wcodec, self._ccodec,
            max_len=max_len).bind(
                infile=self.state_h, outfile=self.state_h,
                weights=self._weights_h)
        self._decode_pipe.build()        # AOT at construction
        self._prefill_pipes: Dict[Any, Pipeline] = {}   # prompt-shape keyed
        self._splice = lmp.CacheSplice(self.app)
        self._release = lmp.SlotRelease(self.app)
        for proc in (self._splice, self._release):
            proc.in_handles["in"] = self.state_h
            proc.out_handle = self.state_h
            proc.graph_name = type(proc).__name__
        self._slot_h = [self.app.addData(lmp.slot_data(slot))
                        for slot in range(batch)]       # uploaded once
        counts = "cache['moe_counts']"
        self._counts_name = counts if counts in self._ccodec.names else None
        self._counts: Optional[np.ndarray] = None       # read this step
        # host mirrors — identical bookkeeping (and attribute names) to the
        # legacy ServeEngine so callers and tests carry over unchanged
        self.active = np.zeros(batch, dtype=bool)
        self.positions = np.zeros(batch, dtype=np.int32)
        self.req_of_slot = np.full(batch, -1, dtype=np.int64)
        self.results: List[List[int]] = []
        self.queue: List[tuple] = []
        self.steps = 0
        self.admitted = 0

    # -- request lifecycle ----------------------------------------------------
    def submit(self, prompt: Sequence[int],
               frames: Optional[np.ndarray] = None) -> int:
        """Queue one request.  ``frames`` (T_enc, D) or (1, T_enc, D) is
        required for encoder-decoder models, rejected otherwise.

        Validation is up-front and typed: a prompt that cannot fit the
        compiled cache (``len(prompt) > max_len - 1``, or empty) raises
        :class:`PromptTooLongError` here instead of failing later inside
        the prefill shape checks, and encoder frames must match the
        compiled ``enc_len``."""
        prompt = list(prompt)
        if not 1 <= len(prompt) <= self.max_len - 1:
            raise PromptTooLongError(len(prompt), self.max_len)
        if self.encdec and frames is None:
            raise ValueError(
                "encoder-decoder models take per-request frames")
        if not self.encdec and frames is not None:
            raise ValueError(f"{self.model.cfg.family!r} models take no "
                             "frames")
        if frames is not None:
            frames = np.asarray(frames, np.float32)
            if frames.ndim == 2:
                frames = frames[None]
            if frames.shape[1] != self.enc_len:
                raise ValueError(
                    f"frames cover {frames.shape[1]} encoder positions "
                    f"but the decode state was compiled for "
                    f"enc_len={self.enc_len}")
        rid = len(self.results)
        self.results.append([])
        self.queue.append((rid, list(prompt), frames))
        return rid

    def _prefill_pipe(self, key: Any) -> Pipeline:
        pipe = self._prefill_pipes.get(key)
        if pipe is None:
            proc = self._lmp.PrefillProcess(
                self.app, self.model, self._wcodec, self._ccodec,
                max_len=self.max_len)
            if self.encdec:
                node = proc.bind(infile="tokens", frames="frames",
                                 weights=self._weights_h)
            else:
                node = proc.bind(infile="tokens", weights=self._weights_h)
            pipe = Pipeline(self.app) | node
            self._prefill_pipes[key] = pipe
        return pipe

    def _admit(self) -> None:
        """Claim free slots for queued prompts: single-row prefill through
        the Pipeline, then an in-place splice into the slot."""
        for slot in np.where(~self.active)[0]:
            if not self.queue:
                break
            slot = int(slot)
            rid, prompt, frames = self.queue.pop(0)
            with trace.span("lm.admit", rid=rid):
                toks = Data({"tokens": np.asarray(prompt, np.int32)[None, :]})
                if self.encdec:
                    key = (len(prompt), frames.shape)
                    inputs: Any = {"tokens": toks,
                                   "frames": Data({"frames": frames})}
                else:
                    key = len(prompt)
                    inputs = toks
                pipe = self._prefill_pipe(key)
                with trace.span("lm.prefill", rid=rid):
                    row = pipe.run(inputs, sync=False)
                with trace.span("lm.prefill_token", rid=rid):
                    tok = int(_read_token(row)[0, 0])
                # the aux handles are read live at launch: re-point them at
                # THIS prompt-shape pipe's output and this slot (all row
                # states share one layout, all slots another, so the one
                # compiled splice executable is reused as-is)
                sp = self._splice
                sp.aux_handles["row"] = pipe._built.output_handle
                sp.aux_handles["slot"] = self._slot_h[slot]
                with trace.span("lm.splice", rid=rid):
                    sp.launch()
            self.active[slot] = True
            self.positions[slot] = len(prompt)
            self.req_of_slot[slot] = rid
            self.results[rid] = [tok]
            self.admitted += 1

    def _release_slot(self, slot: int) -> None:
        rl = self._release
        rl.aux_handles["slot"] = self._slot_h[slot]
        with trace.span("lm.release", rid=int(self.req_of_slot[slot])):
            if self._counts_name is not None:
                with trace.span("lm.moe_counts"):
                    if self._counts is None:        # once a step
                        self._counts = np.asarray(
                            self.state.device_view(self._counts_name))
                        trace.D2H_BYTES.inc(self._counts.nbytes)
                    routed, held, rows = (int(v) for v in self._counts[slot])
                    trace.MOE_ASSIGNMENTS.inc(routed)
                    trace.MOE_HELD_ASSIGNMENTS.inc(held)
                    trace.MOE_ROWS.inc(rows)
            rl.launch()

    # -- decode ----------------------------------------------------------------
    def step(self) -> None:
        """Admit whatever fits, then one batched decode step for every
        active slot (a single in-place donated launch)."""
        with trace.span("lm.step"):
            self._admit()
            if not self.active.any():
                return
            with trace.span("lm.decode"):
                self._decode_pipe.run(None, sync=False)
            self.steps += 1
            with trace.span("lm.token_readback"):
                new = _read_token(self.state)           # (B, 1) readback
            self._counts = None
            for slot in np.where(self.active)[0]:
                slot = int(slot)
                t = int(new[slot, 0])
                rid = int(self.req_of_slot[slot])
                self.results[rid].append(t)
                self.positions[slot] += 1
                done = (self.sampling.eos_id is not None
                        and t == self.sampling.eos_id)
                if done or len(self.results[rid]) >= \
                        self.sampling.max_new_tokens:
                    self.active[slot] = False
                    self._release_slot(slot)

    def run(self, max_steps: int = 10_000) -> List[List[int]]:
        steps = 0
        while (self.queue or self.active.any()) and steps < max_steps:
            self.step()
            steps += 1
        return self.results


def _read_token(state: Data) -> np.ndarray:
    """The ``token`` entry of a decode state, copied to the host."""
    token = np.asarray(state.device_view("token"))
    trace.D2H_BYTES.inc(token.nbytes)
    return token
