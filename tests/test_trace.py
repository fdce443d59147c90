"""The program's span recorder and counters (``repro.core.trace``)."""
import collections
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CLapp, Pipeline, Process, XData, trace
from repro.core.trace import SpanRecord


class AddConst(Process):
    def apply(self, views, aux, params):
        return {k: v + 1.0 for k, v in views.items()}


def _new(records, since):
    return [r for r in records if r.id > since]


def _last_id():
    recs = trace.records()
    return recs[-1].id if recs else 0


def test_spans_nest_with_parent_and_request_ids():
    since = _last_id()
    with trace.span("t.root", rid=7) as root:
        with trace.span("t.a", item=3):
            with trace.span("t.b"):
                pass
        with trace.span("t.c") as c:
            c.attrs["bytes"] = 12
    recs = {r.name: r for r in _new(trace.records(), since)
            if r.name.startswith("t.")}
    assert recs["t.root"].parent is None
    assert recs["t.a"].parent == recs["t.root"].id
    assert recs["t.b"].parent == recs["t.a"].id
    assert recs["t.c"].parent == recs["t.root"].id
    assert recs["t.root"].attrs == {"rid": 7}
    assert recs["t.a"].attrs == {"item": 3}
    assert recs["t.c"].attrs == {"bytes": 12}
    assert root.attrs == {"rid": 7}
    r = recs["t.root"]
    assert r.start_ns <= recs["t.a"].start_ns <= recs["t.b"].start_ns
    assert recs["t.c"].end_ns <= r.end_ns
    # only roots carry counter deltas
    assert set(r.deltas) >= {"repro_h2d_bytes_total", "repro_compiles_total"}
    assert recs["t.a"].deltas is None
    (call,) = [x for x in trace.calls("t.root") if x.span.id == r.id]
    assert call.counts == {"t.root": 1, "t.a": 1, "t.b": 1, "t.c": 1}
    assert sum(call.self_s.values()) == pytest.approx(call.duration_s,
                                                      abs=1e-9)


def test_spans_of_other_threads_are_roots():
    import threading
    since = _last_id()
    with trace.span("t.main"):
        th = threading.Thread(target=lambda: trace.span("t.other")
                              .__enter__().__exit__(None, None, None))
        th.start()
        th.join(10)
    assert not th.is_alive()
    recs = {r.name: r for r in _new(trace.records(), since)}
    assert recs["t.other"].parent is None


def _rec(id, parent, name, start, end):
    return SpanRecord(id, parent, name, start, end, {},
                      {} if parent is None else None)


@pytest.mark.parametrize("order", [1, -1])
def test_self_time_of_nested_and_overlapping_children(monkeypatch, order):
    """root [0, 100]: children a [10, 40] and b [30, 60] overlap, c [90,
    120] runs past the root's end; a holds a1 [15, 20]; a second child a
    [70, 80] sums with the first under one name."""
    recs = [_rec(2, 1, "a", 10, 40), _rec(3, 2, "a1", 15, 20),
            _rec(4, 1, "b", 30, 60), _rec(5, 1, "c", 90, 120),
            _rec(6, 1, "a", 70, 80), _rec(1, None, "root", 0, 100),
            _rec(7, None, "other", 0, 5)]
    monkeypatch.setattr(trace, "_RING", collections.deque(recs[::order]))
    (call,) = trace.calls("root")
    ns = {k: round(v * 1e9) for k, v in call.self_s.items()}
    # root: 100 - (union [10, 60] + [70, 80] + [90, 100]) = 100 - 70
    assert ns == {"root": 30, "a": 25 + 10, "a1": 5, "b": 30, "c": 30}
    assert call.counts == {"root": 1, "a": 2, "a1": 1, "b": 1, "c": 1}
    assert call.duration_s == pytest.approx(100e-9)
    assert [c.span.name for c in trace.calls("other")] == ["other"]
    assert trace.calls("a") == []           # not a root


def test_calls_are_oldest_first(monkeypatch):
    recs = [_rec(3, None, "r", 50, 60), _rec(1, None, "r", 0, 10),
            _rec(2, None, "r", 20, 30)]
    monkeypatch.setattr(trace, "_RING", collections.deque(recs))
    assert [c.span.id for c in trace.calls("r")] == [1, 2, 3]


def test_ring_keeps_the_newest_spans():
    since = _last_id()
    for i in range(trace.RING_SPANS + 10):
        with trace.span("t.ring", i=i):
            pass
    recs = trace.records()
    assert len(recs) == trace.RING_SPANS
    ours = [r.attrs["i"] for r in _new(recs, since) if r.name == "t.ring"]
    # the oldest went first (gc spans of the loop take slots as well)
    assert ours[0] >= 10
    assert ours == list(range(ours[0], trace.RING_SPANS + 10))


def test_spans_land_in_a_profiler_trace_on_one_offset(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    since = _last_id()
    try:
        for i in range(6):
            with trace.span("t.profiled", i=i):
                time.sleep(0.002)
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    ours = [r for r in _new(trace.records(), since)
            if r.name == "t.profiled"]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted(
        (e for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name == "repro.t.profiled"), key=lambda e: e.start_ns)
    assert len(events) == len(ours) == 6
    offsets = [e.start_ns - r.start_ns for e, r in zip(events, ours)]
    assert max(offsets) - min(offsets) < 100_000       # 100 us
    for e, r in zip(events, ours):
        assert abs(e.duration_ns - (r.end_ns - r.start_ns)) < 100_000


def test_compiles_are_counted_once_per_compile():
    x = jnp.arange(7.0)

    def tripled_plus_one(v):
        return v * 3 + 1

    f = jax.jit(tripled_plus_one)
    since = _last_id()
    n0 = trace.COMPILES.total()
    f(x).block_until_ready()
    n1 = trace.COMPILES.total()
    f(x).block_until_ready()                # in-memory cache hit
    n2 = trace.COMPILES.total()
    assert n1 == n0 + 1 and n2 == n1
    (rec,) = [r for r in _new(trace.records(), since) if r.name == "compile"]
    assert rec.attrs["fun"] == "jit(tripled_plus_one)"
    assert trace.COMPILE_SECONDS.total() > 0


def test_gc_pauses_are_spans_and_seconds():
    import gc
    since = _last_id()
    s0 = trace.GC_PAUSE_SECONDS.total()
    with trace.span("t.collect"):
        gc.collect()
    assert trace.GC_PAUSE_SECONDS.total() > s0
    (call,) = [c for c in trace.calls("t.collect") if c.span.id > since]
    assert call.counts.get("gc", 0) >= 1


def test_stream_run_counts_its_bytes_and_its_children_cover_it():
    app = CLapp().init()
    pipe = Pipeline(app) | AddConst(app)
    rng = np.random.default_rng(0)
    items = [XData({"img": rng.standard_normal((512, 512))
                    .astype(np.float32)}) for _ in range(8)]
    pipe.run(items, mode="stream", batch=4)          # builds and compiles
    la = pipe._built.executor.launchable()
    in_bytes = la.in_layouts[0].total_words * 4
    out_bytes = la.out_layout.total_words * 4
    since = _last_id()
    for _ in range(3):
        outs = pipe.run(items, mode="stream", batch=4)
    for o, it in zip(outs, items):
        np.testing.assert_array_equal(o.get_ndarray(0).host,
                                      it.get_ndarray(0).host + 1.0)
    calls = [c for c in trace.calls("pipeline.run") if c.span.id > since]
    assert len(calls) == 3
    for c in calls:
        assert c.deltas["repro_h2d_bytes_total"] == 8 * in_bytes
        assert c.deltas["repro_d2h_bytes_total"] == 8 * out_bytes
        assert c.deltas["repro_compiles_total"] == 0
        assert c.counts["stream.pack"] == 8
        assert c.counts["data.to_host"] == 8
        assert c.counts["stream.stack"] == 2
        assert c.counts["stream.place"] == 2
        assert c.counts["stream.launch"] == 2
        # one staging buffer a batch, the first of each call left over
        # from the call before
        assert c.deltas["repro_staging_reuses_total"] \
            + c.deltas["repro_staging_allocs_total"] == 2
        assert c.deltas["repro_staging_reuses_total"] >= 1
    # the phases account for the call: the root's own time is what no
    # phase covers (best of three calls, so a descheduled moment of a busy
    # test machine does not decide it)
    assert min(c.self_s["pipeline.run"] / c.duration_s for c in calls) \
        <= 0.10


def test_programs_carry_stable_names():
    app = CLapp().init()
    pipe = Pipeline(app) | AddConst(app)
    item = XData({"img": np.ones((4, 4), np.float32)})
    pipe.run(item)
    pipe.run([item, item], mode="stream", batch=2)
    executor = pipe._built.executor
    launch_module = executor._compiled.as_text().split("\n", 1)[0]
    assert launch_module.startswith("HloModule jit_AddConst"), launch_module
    from repro.core.stream import BatchedProcess
    bp = BatchedProcess(executor, 2).init()
    batched_module = bp._compiled.as_text().split("\n", 1)[0]
    assert batched_module.startswith("HloModule jit_AddConst_vmap"), \
        batched_module
    assert "jit_fn" not in launch_module + batched_module


def test_metrics_registry_renders_the_program_counters():
    text = trace.METRICS.render()
    for name in ("repro_h2d_bytes_total", "repro_d2h_bytes_total",
                 "repro_compiles_total", "repro_compile_seconds_total",
                 "repro_gc_pause_seconds_total",
                 "repro_compile_cache_hits_total",
                 "repro_compile_cache_misses_total",
                 "repro_arena_subword_bytes_total",
                 "repro_staging_reuses_total", "repro_staging_allocs_total"):
        assert f"\n{name} " in "\n" + text, name
    from repro.serve import control
    assert control.Metrics is trace.Metrics
    assert control.Counter is trace.Counter


def test_serve_flushes_are_spans_with_fill_and_queue_wait():
    app = CLapp().init()
    pipe = Pipeline(app) | AddConst(app)
    items = [XData({"img": np.full((4, 4), i, np.float32)})
             for i in range(6)]
    since = _last_id()
    outs = pipe.run(items, mode="serve", batch=4)
    assert [float(o.get_ndarray(0).host[0, 0]) for o in outs] == \
        [i + 1.0 for i in range(6)]
    flushes = [r for r in _new(trace.records(), since)
               if r.name == "serve.flush"]
    assert [r.attrs["fill"] for r in flushes] == [4, 2]
    assert all(r.attrs["oldest_wait_s"] >= 0 for r in flushes)
    (call,) = [c for c in trace.calls("pipeline.run") if c.span.id > since]
    assert call.counts["serve.flush"] == 2
    assert call.counts["stream.pack"] == 6
