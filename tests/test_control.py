"""The FrontDoor serving control plane: priority admission with
backpressure, replica routing, health/metrics.

Covers the PR-8 subsystem end to end: admission overflow policies
(block / reject / shed) under contention, priority dispatch ordering,
deadline expiry, router policy selection (incl. the profile-weighted
split on a skewed pool), unhealthy-replica exclusion + probe recovery,
metrics counter/gauge/histogram correctness and Prometheus rendering,
the PipelineServer close/LMServer validation satellites, and bit-identity
of results routed through the control plane vs. a direct PipelineServer.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import CLapp, Pipeline, Process, XData
from repro.serve import (AdmissionRejected, CallableReplica, FrontDoor,
                         Metrics, PipelineReplica, PriorityClass, Router)
from repro.serve.control import Counter, DeviceProfile, Gauge, Histogram


@pytest.fixture
def app():
    return CLapp().init()


def _img(rng, shape=(6, 5)):
    return XData({"img": rng.standard_normal(shape).astype(np.float32)})


class Scale(Process):
    def apply(self, views, aux, params):
        return {k: v * params for k, v in views.items()}


def _echo(name, **kw):
    return CallableReplica(name, lambda p: p, **kw)


def _drain_statuses(fd, timeout=10.0):
    outs = fd.drain(timeout=timeout)
    return {o.rid: o.status for o in outs}, outs


# ---------------------------------------------------------------------------
# admission: overflow policies under contention
# ---------------------------------------------------------------------------

def _gated_frontdoor(capacity, overflow, **kw):
    """A FrontDoor whose single replica blocks on an event.  Two plug
    requests occupy the service slot and the one-batch-ahead inbox, so
    every later submit lands in the admission queue deterministically."""
    gate = threading.Event()

    def fn(p):
        gate.wait(10.0)
        return p

    fd = FrontDoor([CallableReplica("r", fn, max_batch=1)],
                   capacity=capacity, overflow=overflow, **kw)
    plugs = [fd.submit("plug-0", priority="interactive")]
    time.sleep(0.08)                  # worker takes it off the inbox
    plugs.append(fd.submit("plug-1", priority="interactive"))
    time.sleep(0.08)                  # dispatcher refills the inbox
    assert fd.queue_depth == 0
    return fd, gate, plugs


def test_reject_policy_full_queue():
    fd, gate, plugs = _gated_frontdoor(2, "reject")
    try:
        a = fd.submit("a")
        b = fd.submit("b")                # queue now at capacity (2)
        with pytest.raises(AdmissionRejected) as exc:
            fd.submit("c")
        assert exc.value.reason == "full"
        assert exc.value.priority == "normal"
        gate.set()
        statuses, _ = _drain_statuses(fd)
        assert statuses == {r: "ok" for r in plugs + [a, b]}
        assert fd.metrics.counter(
            "frontdoor_requests_rejected_total").value(**{"class": "normal"}) == 1
    finally:
        gate.set()
        fd.close()


def test_block_policy_waits_for_room_then_times_out():
    fd, gate, plugs = _gated_frontdoor(1, "block", block_timeout_s=0.15)
    try:
        fd.submit("a")                    # queue full (capacity 1)
        t0 = time.perf_counter()
        with pytest.raises(AdmissionRejected) as exc:
            fd.submit("b")                # blocks, then times out
        waited = time.perf_counter() - t0
        assert exc.value.reason == "blocked_timeout"
        assert waited >= 0.1, "block policy must actually wait"
        # with the gate open the queue drains and a blocked submit ADMITS
        gate.set()
        rid = fd.submit("c")
        statuses, _ = _drain_statuses(fd)
        assert statuses[rid] == "ok"
    finally:
        gate.set()
        fd.close()


def test_shed_policy_evicts_oldest_lowest_priority():
    fd, gate, plugs = _gated_frontdoor(2, "shed")
    try:
        r_old = fd.submit("old-batch", priority="batch")
        r_new = fd.submit("new-batch", priority="batch")
        # full queue + an interactive request: the OLDEST batch-class
        # entry is shed, the new request is admitted
        r_hi = fd.submit("urgent", priority="interactive")
        gate.set()
        statuses, outs = _drain_statuses(fd)
        assert statuses[r_old] == "shed"
        assert statuses[r_new] == "ok"
        assert statuses[r_hi] == "ok"
        shed = [o for o in outs if o.status == "shed"]
        assert shed[0].priority == "batch" and not shed[0].ok
        assert fd.metrics.counter(
            "frontdoor_requests_shed_total").value(**{"class": "batch"}) == 1
    finally:
        gate.set()
        fd.close()


def test_shed_never_evicts_more_urgent_work():
    fd, gate, plugs = _gated_frontdoor(2, "shed")
    try:
        fd.submit("hi-1", priority="interactive")
        fd.submit("hi-2", priority="interactive")
        # queue full of strictly-higher-priority work: the batch request
        # itself is refused instead of shedding urgent work
        with pytest.raises(AdmissionRejected) as exc:
            fd.submit("lowly", priority="batch")
        assert exc.value.reason == "higher_priority_only"
        gate.set()
        statuses, _ = _drain_statuses(fd)
        assert set(statuses.values()) == {"ok"}
    finally:
        gate.set()
        fd.close()


def test_closed_frontdoor_rejects_submissions():
    fd = FrontDoor([_echo("r")])
    fd.submit(1)
    fd.drain(timeout=5.0)
    fd.close()
    with pytest.raises(RuntimeError, match="closed"):
        fd.submit(2)
    fd.close()                            # idempotent


# ---------------------------------------------------------------------------
# priority ordering + deadline expiry
# ---------------------------------------------------------------------------

def test_priority_classes_dispatch_in_order():
    """Admit a full mix before starting the threads: dispatch (and hence
    a single serial replica's service order) follows class level, FIFO
    within a class."""
    order = []

    def record(p):
        order.append(p)
        return p

    fd = FrontDoor([CallableReplica("r", record, max_batch=1)],
                   capacity=16, auto_start=False)
    fd.submit("b1", priority="batch")
    fd.submit("n1", priority="normal")
    fd.submit("i1", priority="interactive")
    fd.submit("b2", priority="batch")
    fd.submit("i2", priority="interactive")
    assert fd.queue_depth == 5            # nothing moves before start()
    fd.start()
    statuses, _ = _drain_statuses(fd)
    fd.close()
    assert order == ["i1", "i2", "n1", "b1", "b2"]
    assert set(statuses.values()) == {"ok"}


def test_unknown_priority_class_rejected():
    fd = FrontDoor([_echo("r")], auto_start=False)
    with pytest.raises(ValueError, match="unknown priority class"):
        fd.submit(1, priority="vip")
    fd.close()


def test_deadline_expiry_drops_stale_requests():
    """A request older than its deadline completes as timed_out and is
    never launched."""
    launched = []
    gate = threading.Event()

    def fn(p):
        gate.wait(10.0)
        launched.append(p)
        return p

    fd = FrontDoor([CallableReplica("r", fn, max_batch=1)], capacity=16,
                   classes=[PriorityClass("rt", 0, deadline_s=0.05),
                            PriorityClass("bg", 1)],
                   default_class="bg")
    try:
        fd.submit("first", priority="bg")     # occupies the replica
        time.sleep(0.02)
        stale = fd.submit("stale", priority="rt")
        time.sleep(0.12)                      # rt deadline passes queued
        gate.set()
        statuses, _ = _drain_statuses(fd)
        assert statuses[stale] == "timed_out"
        assert "stale" not in launched
        assert fd.metrics.counter(
            "frontdoor_requests_timed_out_total").value(**{"class": "rt"}) == 1
    finally:
        gate.set()
        fd.close()


def test_per_request_deadline_overrides_class():
    gate = threading.Event()
    fd = FrontDoor([CallableReplica(
        "r", lambda p: (gate.wait(10.0), p)[1], max_batch=1)], capacity=16)
    try:
        fd.submit("first")
        time.sleep(0.02)
        stale = fd.submit("stale", deadline_s=0.03)
        fresh = fd.submit("fresh")            # no deadline
        time.sleep(0.1)
        gate.set()
        statuses, _ = _drain_statuses(fd)
        assert statuses[stale] == "timed_out"
        assert statuses[fresh] == "ok"
    finally:
        gate.set()
        fd.close()


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------

def test_router_round_robin_cycles():
    a, b, c = _echo("a"), _echo("b"), _echo("c")
    r = Router("round-robin")
    picks = [r.pick([a, b, c]).name for _ in range(6)]
    assert picks == ["a", "b", "c", "a", "b", "c"]


def test_router_least_outstanding():
    a, b = _echo("a"), _echo("b")
    a.in_flight, b.in_flight = 3, 1
    assert Router("least-outstanding").pick([a, b]) is b


def test_router_profile_weighted_skew():
    """Smooth weighted RR over measured rates: a 3:1 skew yields an
    exactly 3:1 pick ratio over any aligned window."""
    fast, slow = _echo("fast"), _echo("slow")
    fast.set_rate(300.0)
    slow.set_rate(100.0)
    r = Router("profile")
    picks = [r.pick([fast, slow]).name for _ in range(40)]
    assert picks.count("fast") == 30 and picks.count("slow") == 10
    # cold replicas weigh in at the mean warm rate
    cold = _echo("cold")
    assert Router("profile").weights([fast, cold]) == [300.0, 300.0]
    assert Router("profile").weights([cold, _echo("cold2")]) == [1.0, 1.0]


def test_router_unknown_policy():
    with pytest.raises(ValueError, match="unknown routing policy"):
        Router("fastest-first")


def test_eager_profile_routing_splits_by_rate():
    """End to end: under eager dispatch the profile policy carves a
    burst across a skewed pool by measured items/sec.  The gate holds
    every routing decision at the seeded 3:1 rates — completions would
    otherwise refresh the EMA mid-dispatch."""
    gate = threading.Event()

    def up(p):
        gate.wait(10.0)
        return p

    fast = CallableReplica("fast", up)
    slow = CallableReplica("slow", up)
    fast.set_rate(300.0)
    slow.set_rate(100.0)
    fd = FrontDoor([fast, slow], capacity=40, policy="profile",
                   dispatch_ahead=None, auto_start=False)
    for i in range(40):
        fd.submit(i)
    fd.start()
    deadline = time.perf_counter() + 5.0
    while fd.queue_depth > 0 and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert fd.queue_depth == 0            # all 40 routed, none served yet
    gate.set()
    statuses, _ = _drain_statuses(fd)
    fd.close()
    assert set(statuses.values()) == {"ok"}
    assert fast.served == 30 and slow.served == 10


def test_demand_bounded_dispatch_holds_work_in_queue():
    """Default dispatch hands a replica at most max_batch requests ahead;
    the rest stay in the priority queue."""
    gate = threading.Event()
    fd = FrontDoor([CallableReplica(
        "r", lambda p: (gate.wait(10.0), p)[1], max_batch=2)], capacity=16)
    try:
        for i in range(6):
            fd.submit(i)
        time.sleep(0.1)
        # 1 batch processing (up to 2) + at most 2 dispatched ahead
        assert fd.queue_depth >= 2
        gate.set()
        statuses, _ = _drain_statuses(fd)
        assert set(statuses.values()) == {"ok"}
    finally:
        gate.set()
        fd.close()


# ---------------------------------------------------------------------------
# health: unhealthy exclusion + probe recovery
# ---------------------------------------------------------------------------

def test_unhealthy_replica_excluded_then_recovers():
    state = {"broken": True}

    def flaky(p):
        if state["broken"]:
            raise RuntimeError("injected replica failure")
        return p + 100

    flk = CallableReplica("flaky", flaky, probe_payload=0)
    ok = CallableReplica("ok", lambda p: p + 100)
    fd = FrontDoor([flk, ok], capacity=16, policy="round-robin",
                   probe_interval_s=0.02, max_retries=3)
    try:
        rids = [fd.submit(i) for i in range(6)]
        statuses, outs = _drain_statuses(fd)
        # every request completed OK: the failing replica's work was
        # re-routed (requeued counter > 0), nothing crashed
        assert [statuses[r] for r in rids] == ["ok"] * 6
        assert all(o.result == o.rid + 100 for o in outs)
        assert fd.metrics.counter(
            "frontdoor_requests_requeued_total").value() > 0
        h = fd.health()
        assert h["ok"]                       # pool degraded, not down
        assert not h["replicas"]["flaky"]["healthy"]
        assert "injected" in h["replicas"]["flaky"]["last_error"]
        assert fd.metrics.gauge(
            "frontdoor_replica_healthy").value(replica="flaky") == 0.0
        # probe succeeds once the fault clears -> replica rejoins routing
        state["broken"] = False
        deadline = time.perf_counter() + 5.0
        while not flk.healthy and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert flk.healthy
        assert fd.health()["replicas"]["flaky"]["healthy"]
        rid = fd.submit(50)
        fd.submit(51)
        statuses, _ = _drain_statuses(fd)
        assert statuses[rid] == "ok"
        assert flk.served > 0                # it genuinely serves again
    finally:
        fd.close()


def test_whole_pool_down_completes_as_error_after_retries():
    def broken(p):
        raise RuntimeError("always down")

    fd = FrontDoor([CallableReplica("b", broken)], capacity=4,
                   probe_interval_s=0.01, max_retries=2)
    try:
        rid = fd.submit(1)
        statuses, outs = _drain_statuses(fd, timeout=10.0)
        assert statuses[rid] == "error"
        err = [o for o in outs if o.rid == rid][0]
        assert "always down" in repr(err.error)
        assert not fd.health()["ok"]
    finally:
        fd.close()


def test_close_with_down_pool_does_not_hang():
    def broken(p):
        raise RuntimeError("down")

    fd = FrontDoor([CallableReplica("b", broken, probe_payload=1)],
                   capacity=4, probe_interval_s=10.0, max_retries=100)
    fd.submit(1)
    t0 = time.perf_counter()
    fd.close(timeout=5.0)
    assert time.perf_counter() - t0 < 5.0
    outs = fd.collect()
    assert len(outs) == 1 and outs[0].status == "error"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counter_gauge_histogram():
    m = Metrics()
    c = m.counter("requests_total", "all requests")
    c.inc()
    c.inc(2, method="post")
    assert c.value() == 1 and c.value(method="post") == 2
    assert c.total() == 3
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = m.gauge("depth")
    g.set(7)
    assert g.value() == 7
    h = m.histogram("latency_seconds")
    for v in [0.01, 0.02, 0.03, 0.04]:
        h.observe(v, replica="r0")
    assert h.count(replica="r0") == 4
    assert h.percentile(50.0, replica="r0") == pytest.approx(0.025)
    # get-or-create returns the same object; kind clashes raise
    assert m.counter("requests_total") is c
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("requests_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        m.counter("bad-name")


def test_metrics_render_prometheus_format():
    m = Metrics()
    m.counter("admitted_total", "requests admitted").inc(
        3, **{"class": "normal"})
    m.gauge("queue_depth").set(2)
    h = m.histogram("latency_seconds")
    h.observe(0.5, replica="r0")
    text = m.render()
    assert "# TYPE admitted_total counter" in text
    assert 'admitted_total{class="normal"} 3' in text
    assert "# HELP admitted_total requests admitted" in text
    assert "# TYPE queue_depth gauge" in text
    assert "queue_depth 2" in text
    assert "# TYPE latency_seconds summary" in text
    assert 'latency_seconds{replica="r0",quantile="0.5"} 0.5' in text
    assert 'latency_seconds{replica="r0",quantile="0.999"} 0.5' in text
    assert 'latency_seconds_count{replica="r0"} 1' in text
    assert 'latency_seconds_sum{replica="r0"} 0.5' in text
    assert text.endswith("\n")


def test_frontdoor_metrics_accounting():
    """Counters reconcile: admitted == completed + shed + timed_out over
    a mixed run, queue depth returns to 0, latency histogram has one
    sample per served request."""
    fd, gate, plugs = _gated_frontdoor(2, "shed")
    try:
        fd.submit(0, priority="batch")
        fd.submit(1, priority="batch")
        fd.submit(2, priority="interactive")     # sheds the oldest batch
        gate.set()
        _, outs = _drain_statuses(fd)
        m = fd.metrics
        admitted = m.counter("frontdoor_requests_admitted_total").total()
        completed = m.counter("frontdoor_requests_completed_total").total()
        shed = m.counter("frontdoor_requests_shed_total").total()
        assert admitted == 5 and completed == 4 and shed == 1
        assert m.gauge("frontdoor_queue_depth").value() == 0
        assert m.histogram("frontdoor_request_latency_seconds").count(
            replica="r") == 4
        assert m.counter("frontdoor_replica_dispatched_total").value(
            replica="r") == 4
        health = fd.health()
        assert health["queue_depth"] == 0 and health["outstanding"] == 0
        assert health["replicas"]["r"]["served"] == 4
        assert health["replicas"]["r"]["p50_ms"] > 0
    finally:
        gate.set()
        fd.close()


def test_device_profile_records_ema():
    p = DeviceProfile(ema=0.5)
    assert p.cold and p.rate != p.rate          # nan
    p.record(10, 1.0)                           # first sample sets directly
    assert p.rate == pytest.approx(10.0)
    p.record(20, 1.0)                           # 0.5*20 + 0.5*10
    assert p.rate == pytest.approx(15.0)
    assert p.items == 30
    assert len(p.seconds.samples) == 2          # raw wall times kept
    assert p.seconds.mean() == pytest.approx(1.0)


def test_device_profile_ignores_degenerate_samples():
    p = DeviceProfile()
    p.record(0, 1.0)
    p.record(4, 0.0)
    p.record(4, -1.0)
    assert p.cold


def test_device_profile_set_rate():
    p = DeviceProfile()
    p.set_rate(3.0)
    assert p.rate == 3.0 and not p.cold
    with pytest.raises(ValueError):
        p.set_rate(-1.0)


def test_replica_rate_self_calibrates():
    """Without seeding, completed batches feed the replica EMA — the
    profile signal warms itself."""
    r = CallableReplica("r", lambda p: p)
    assert r.rate != r.rate                   # cold: nan
    fd = FrontDoor([r], capacity=8)
    try:
        for i in range(4):
            fd.submit(i)
        fd.drain(timeout=5.0)
        assert r.rate > 0
    finally:
        fd.close()


# ---------------------------------------------------------------------------
# control plane over real pipelines: bit-identity + CLapp.split
# ---------------------------------------------------------------------------

def test_routed_results_bit_identical_to_direct_server(app, rng):
    """The FrontDoor adds routing, not math: results routed through
    PipelineReplicas match a direct PipelineServer bitwise."""
    ds = [_img(rng) for _ in range(10)]

    pipe_direct = Pipeline(app) | Scale(app).bind(params=2.5)
    server = pipe_direct.serve(batch=4)
    rids = [server.submit(d) for d in ds]
    by_rid = {r.rid: r.data for r in server.drain()}
    want = [np.asarray(by_rid[r].device_view("img")) for r in rids]

    replicas = []
    for i in range(2):
        p = Pipeline(app) | Scale(app).bind(params=2.5)
        replicas.append(PipelineReplica(f"r{i}", p.serve(batch=4)))
    fd = FrontDoor(replicas, capacity=16, policy="round-robin")
    try:
        fids = [fd.submit(d) for d in ds]
        outs = {o.rid: o for o in fd.drain(timeout=30.0)}
        served_by = set()
        for fid, w in zip(fids, want):
            o = outs[fid]
            assert o.ok, o.error
            got = np.asarray(o.result.device_view("img"))
            np.testing.assert_array_equal(got, w)
            served_by.add(o.replica)
        assert served_by == {"r0", "r1"}, "round-robin must use the pool"
    finally:
        fd.close()


def test_pipeline_replica_probe_recovers_real_server(app, rng):
    """A PipelineReplica with a probe request recovers after its server
    heals (fault injected at the launch plan, as in the PR-4 tests)."""
    pipe = Pipeline(app) | Scale(app).bind(params=3.0)
    server = pipe.serve(batch=2)
    rep = PipelineReplica("r0", server, probe_request=_img(rng))
    fd = FrontDoor([rep], capacity=8, probe_interval_s=0.02, max_retries=2)
    try:
        rid = fd.submit(_img(rng))
        outs = fd.drain(timeout=30.0)
        assert outs[0].rid == rid and outs[0].ok

        def boom(items):
            raise RuntimeError("injected launch failure")
        server._plan.stack_group = boom                        # break it
        bad = fd.submit(_img(rng))
        deadline = time.perf_counter() + 5.0
        while rep.healthy and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert not rep.healthy
        del server._plan.stack_group                           # heal it
        statuses, _ = _drain_statuses(fd, timeout=30.0)
        assert statuses[bad] == "ok"
        assert rep.healthy
    finally:
        fd.close()


def test_clapp_split_partitions_devices(app):
    n = len(app.devices)
    parts = app.split(n)
    assert [len(p.devices) for p in parts] == [1] * n
    assert [p.device for p in parts] == list(app.devices)
    for p in parts:
        assert p.mesh is not None
    with pytest.raises(ValueError, match="at least one device"):
        app.split(n + 1)
    with pytest.raises(ValueError, match="n >= 1"):
        app.split(0)


def test_frontdoor_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        FrontDoor([])
    with pytest.raises(ValueError, match="unique"):
        FrontDoor([_echo("a"), _echo("a")])
    with pytest.raises(ValueError, match="capacity"):
        FrontDoor([_echo("a")], capacity=0)
    with pytest.raises(ValueError, match="overflow"):
        FrontDoor([_echo("a")], overflow="drop-newest")
    with pytest.raises(ValueError, match="dispatch_ahead"):
        FrontDoor([_echo("a")], dispatch_ahead=0)
    with pytest.raises(ValueError, match="default class"):
        FrontDoor([_echo("a")], default_class="vip")


# ---------------------------------------------------------------------------
# satellites: PipelineServer close semantics, LMServer prompt validation
# ---------------------------------------------------------------------------

def test_pipeline_server_closed_raises_instead_of_hanging(app, rng):
    pipe = Pipeline(app) | Scale(app).bind(params=2.0)
    server = pipe.serve(batch=4, flush_timeout=0.02)
    server.submit(_img(rng))
    assert len(server.collect(1, timeout=30.0)) == 1
    server.close()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(_img(rng))
    with pytest.raises(RuntimeError, match="closed"):
        server.drain()
    with pytest.raises(RuntimeError, match="closed"):
        server.collect(1, timeout=1.0)


def test_pipeline_server_close_idempotent_and_concurrent(app, rng):
    """close() twice (and from two threads at once) joins the worker
    exactly once; a close after a worker death reaps without raising."""
    pipe = Pipeline(app) | Scale(app).bind(params=2.0)
    server = pipe.serve(batch=4, flush_timeout=0.02)
    server.submit(_img(rng))
    server.collect(1, timeout=30.0)
    errors = []

    def closer():
        try:
            server.close()
        except BaseException as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=closer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.close()
    assert errors == []

    # close() after the background thread died from a launch failure
    server2 = pipe.serve(batch=4, flush_timeout=0.02)
    server2.submit(_img(rng))
    server2.collect(1, timeout=30.0)

    def boom(items):
        raise RuntimeError("injected launch failure")
    server2._plan.stack_group = boom
    server2.submit(_img(rng))
    with pytest.raises(RuntimeError, match="drain thread died"):
        server2.collect(1, timeout=30.0)
    server2.close()                         # reaps the dead thread quietly
    server2.close()


def test_pipeline_server_without_flush_timeout_unaffected_by_close(app, rng):
    """No background thread -> close() is a no-op and drain() keeps
    working (the Pipeline.run(mode='serve') path)."""
    pipe = Pipeline(app) | Scale(app).bind(params=2.0)
    server = pipe.serve(batch=4)
    server.close()
    rid = server.submit(_img(rng))
    resp = server.drain()
    assert [r.rid for r in resp] == [rid]


def test_lmserver_prompt_length_validated_up_front():
    from repro.models import build_model
    from repro.models.common import ArchConfig
    from repro.serve import LMServer, PromptTooLongError, SamplingConfig

    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=32, vocab=48, remat=False,
                     dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    server = LMServer(model, params, batch=1, max_len=8,
                      sampling=SamplingConfig(max_new_tokens=2))
    with pytest.raises(PromptTooLongError) as exc:
        server.submit(list(range(8)))       # max_len tokens: no decode room
    assert exc.value.prompt_len == 8 and exc.value.max_len == 8
    assert "max_len=8" in str(exc.value)
    assert isinstance(exc.value, ValueError)
    with pytest.raises(PromptTooLongError):
        server.submit([])                   # empty prompt
    assert server.queue == [] and server.results == []  # nothing queued
    rid = server.submit(list(range(1, 8)))  # max_len - 1 fits
    outs = server.run()
    assert len(outs[rid]) == 2
