"""Serving latency: single-instance request/response mode + the FrontDoor
control plane under sustained load.

Scenario 1 — **dynamic batching** (PR 3): N independent multicoil K-space
requests into a :class:`repro.serve.pipeline.PipelineServer` over the
SimpleMRIRecon graph, drained at max-batch 1 / 4 / 8; p50/p99 submit-to-
ready latency and throughput per batch size.

Scenario 2 — **flush_timeout** (PR 4): requests trickle in (fixed
inter-arrival gap) at max-batch 8, with and without the background
partial-batch flush; the timeout caps the queueing term of p50/p99.

Scenario 3 — **sustained load** (PR 8): Poisson arrivals at several
offered loads through a :class:`repro.serve.control.FrontDoor` over a
pool of emulated replicas (synthetic service times — queueing/admission
behaviour without device contention, the same emulation idea as the
mesh-scaling bench).  Reports p50/p99/p999 of served requests plus the
shed/timed-out rates per offered load.  Past the saturation point the
bounded admission queue + ``"shed"`` overflow policy keep the tail
latency of *served* requests bounded (worst case ≈ queue capacity /
pool rate) and degrade by shedding instead of growing the queue without
bound — ``p99_bound_ms`` in the JSON is that analytic bound, and the
results show nonzero ``shed_rate`` only past saturation.

Scenario 4 — **profile-informed routing** (PR 8): a burst of requests
through a 2-replica pool with a 4:1 speed skew under **eager dispatch**
(``dispatch_ahead=None`` — the router commits each request immediately,
as a front-end before remote replicas must), routed ``"round-robin"``
vs ``"profile"`` (smooth weighted round-robin over each replica's
measured items/sec, a :class:`~repro.serve.control.DeviceProfile` EMA).
Round-robin sends half the burst to the slow replica; the profile policy
sends work where the capacity is and wins on makespan and p99;
``speedup`` in the JSON is round-robin makespan / profile makespan.

Prints the harness CSV rows plus one ``BENCH {json}`` line, and writes
``BENCH_serve_latency.json`` next to this file for the perf trajectory
(``--smoke`` shrinks every scenario and skips the JSON write — the CI
mode).
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

from repro.core import CLapp, KData, Pipeline
from repro.processes import FFT, ComplexElementProd, XImageSum
from repro.processes.coil_combine import CombineParams
from repro.processes.complex_elementprod import ComplexElementProdParams
from repro.processes.fft import FFTParams
from repro.serve import CallableReplica, FrontDoor

FRAMES, COILS, H, W = 4, 4, 64, 64
N_REQUESTS = 24
BATCHES = (1, 4, 8)
REPS = 3   # drains per batch size; stats over the best drain (min p50)

# flush-timeout scenario: a trickle of requests into a batch-8 server
TRICKLE_N = 12
TRICKLE_GAP_S = 0.004        # inter-arrival gap
FLUSH_TIMEOUT_S = 0.010

# sustained-load scenario: Poisson arrivals into a FrontDoor over an
# emulated pool (per-request service time; sleeps release the GIL, so the
# replica workers genuinely overlap)
POOL_REPLICAS = 2
SERVICE_S = 0.004            # per-request service time of one replica
QUEUE_CAPACITY = 32
OFFERED = (0.5, 0.9, 1.6)    # offered load as a multiple of pool capacity
SUSTAINED_N = 300            # requests per offered load

# routing scenario: 4:1 speed skew, closed-loop burst
SKEW_FAST_S, SKEW_SLOW_S = 0.002, 0.008
SKEW_N = 80


def _requests(n: int) -> List[KData]:
    rng = np.random.default_rng(0)
    smaps = (rng.standard_normal((COILS, H, W))
             + 1j * rng.standard_normal((COILS, H, W))).astype(np.complex64)
    out = []
    for i in range(n):
        r = np.random.default_rng(200 + i)
        k = (r.standard_normal((FRAMES, COILS, H, W))
             + 1j * r.standard_normal((FRAMES, COILS, H, W))).astype(np.complex64)
        out.append(KData({"kdata": k, "sensitivity_maps": smaps}))
    return out


def _pipeline(app: CLapp) -> Pipeline:
    return (Pipeline(app)
            | FFT(app).bind(params=FFTParams("backward", var="kdata"))
            | ComplexElementProd(app).bind(
                params=ComplexElementProdParams(conjugate=True))
            | XImageSum(app).bind(params=CombineParams()))


def _emulated(name: str, service_s: float) -> CallableReplica:
    def fn(payload):
        time.sleep(service_s)
        return payload
    r = CallableReplica(name, fn)
    r.set_rate(1.0 / service_s)      # seeded like an already-calibrated pool
    return r


def sustained_rows(*, smoke: bool = False) -> (List[str], Dict):
    """Poisson arrivals at several offered loads; outcomes per load."""
    # enough arrivals past saturation to overflow the queue even in smoke
    # (backlog grows at (offered - pool) rps and must exceed `capacity`)
    n = 150 if smoke else SUSTAINED_N
    pool_rate = POOL_REPLICAS / SERVICE_S            # requests/sec capacity
    # served requests wait at most a full queue in front of the pool
    p99_bound_ms = (QUEUE_CAPACITY / pool_rate + SERVICE_S) * 1e3
    results, out_rows = [], []
    for mult in OFFERED:
        offered_rps = pool_rate * mult
        rng = np.random.default_rng(7)
        gaps = rng.exponential(1.0 / offered_rps, size=n)
        fd = FrontDoor([_emulated(f"r{i}", SERVICE_S)
                        for i in range(POOL_REPLICAS)],
                       capacity=QUEUE_CAPACITY, overflow="shed",
                       policy="least-outstanding")
        t0 = time.perf_counter()
        for gap in gaps:
            fd.submit(None)
            time.sleep(gap)
        outcomes = fd.drain(timeout=60.0)
        wall = time.perf_counter() - t0
        fd.close()
        assert len(outcomes) == n
        ok = sorted(o.latency_s for o in outcomes if o.ok)
        stats = {
            "offered_x": mult,
            "offered_rps": round(offered_rps, 1),
            "served_rps": round(len(ok) / wall, 1),
            "p50_ms": round(float(np.percentile(ok, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(ok, 99)) * 1e3, 3),
            "p999_ms": round(float(np.percentile(ok, 99.9)) * 1e3, 3),
            "shed_rate": round(sum(o.status == "shed"
                                   for o in outcomes) / n, 3),
            "timed_out_rate": round(sum(o.status == "timed_out"
                                        for o in outcomes) / n, 3),
        }
        results.append(stats)
        out_rows.append(
            f"serve_sustained_{mult}x,{stats['p50_ms'] * 1e3:.1f},"
            f"p99_ms={stats['p99_ms']:.2f};p999_ms={stats['p999_ms']:.2f};"
            f"shed_rate={stats['shed_rate']:.3f}")
    # degradation contract: below saturation nothing is shed; past it the
    # bounded queue sheds instead of growing, and the served tail stays
    # under the analytic bound (generous 3x margin for thread scheduling)
    past = [r for r in results if r["offered_x"] > 1.0]
    under = [r for r in results if r["offered_x"] <= 0.9]
    checks = {
        "shed_only_past_saturation": bool(
            all(r["shed_rate"] == 0.0 for r in under)
            and all(r["shed_rate"] > 0.0 for r in past)),
        "p99_bounded": bool(all(r["p99_ms"] < 3 * p99_bound_ms
                                for r in results)),
    }
    bench = {
        "replicas": POOL_REPLICAS,
        "service_ms": SERVICE_S * 1e3,
        "capacity": QUEUE_CAPACITY,
        "overflow": "shed",
        "n_per_load": n,
        "pool_rps": pool_rate,
        "p99_bound_ms": round(p99_bound_ms, 3),
        "results": results,
        "checks": checks,
    }
    return out_rows, bench


def routing_rows(*, smoke: bool = False) -> (List[str], Dict):
    """Round-robin vs profile-weighted routing on a 4:1 skewed pool."""
    n = 24 if smoke else SKEW_N
    results, out_rows = [], []
    for policy in ("round-robin", "profile"):
        fd = FrontDoor([_emulated("fast", SKEW_FAST_S),
                        _emulated("slow", SKEW_SLOW_S)],
                       capacity=n, overflow="block", policy=policy,
                       dispatch_ahead=None)
        t0 = time.perf_counter()
        for i in range(n):
            fd.submit(i)
        outcomes = fd.drain(timeout=60.0)
        makespan = time.perf_counter() - t0
        health = fd.health()
        fd.close()
        assert len(outcomes) == n and all(o.ok for o in outcomes)
        lat = sorted(o.latency_s for o in outcomes)
        results.append({
            "policy": policy,
            "makespan_ms": round(makespan * 1e3, 3),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "served": {name: rep["served"]
                       for name, rep in health["replicas"].items()},
        })
        out_rows.append(
            f"serve_routing_{policy},{results[-1]['p50_ms'] * 1e3:.1f},"
            f"p99_ms={results[-1]['p99_ms']:.2f};"
            f"makespan_ms={results[-1]['makespan_ms']:.1f}")
    speedup = results[0]["makespan_ms"] / results[1]["makespan_ms"]
    bench = {
        "n": n,
        "service_ms": {"fast": SKEW_FAST_S * 1e3, "slow": SKEW_SLOW_S * 1e3},
        "results": results,
        "speedup_profile_vs_rr": round(speedup, 3),
        "profile_beats_rr": bool(speedup > 1.0),
    }
    return out_rows, bench


def rows(*, smoke: bool = False) -> List[str]:
    app = CLapp().init()
    n_requests = 8 if smoke else N_REQUESTS
    batches = (1, 4) if smoke else BATCHES
    reps = 1 if smoke else REPS
    trickle_n = 4 if smoke else TRICKLE_N
    requests = _requests(n_requests)
    pipe = _pipeline(app)
    pipe.build(requests[0])                  # AOT compile outside the timing

    out_rows: List[str] = []
    results = []
    for batch in batches:
        server = pipe.serve(batch=batch)
        server.submit(requests[0])
        server.drain()                       # warm up the batched compiles
        best = None
        for _ in range(reps):
            rids = [server.submit(r) for r in requests]
            t0 = time.perf_counter()
            responses = server.drain()
            total_s = time.perf_counter() - t0
            assert len(responses) == len(rids)
            lat = np.asarray(sorted(r.latency_s for r in responses))
            stats = {
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "throughput_rps": len(responses) / max(total_s, 1e-12),
            }
            if best is None or stats["p50_ms"] < best["p50_ms"]:
                best = stats
        results.append({"batch": batch, **{k: round(v, 3)
                                           for k, v in best.items()}})
        out_rows.append(
            f"serve_latency_b{batch},{best['p50_ms'] * 1e3:.1f},"
            f"p99_ms={best['p99_ms']:.2f};"
            f"throughput_rps={best['throughput_rps']:.1f}")
    # ---- flush_timeout impact: trickle arrivals, partial-batch flushes ----
    def trickle(flush_timeout):
        server = pipe.serve(batch=8, flush_timeout=flush_timeout)
        server.submit(requests[0])
        if flush_timeout is None:
            server.drain()                       # warm the batched compiles
        else:
            server.collect(1, timeout=60.0)
        # equal compile-warmth for both policies: pre-compile EVERY
        # partial-flush size so timing-dependent group sizes under
        # flush_timeout never compile inside a timed rep
        server.warmup()
        lats = []
        for _ in range(reps):
            rids = []
            for r in requests[:trickle_n]:
                rids.append(server.submit(r))
                time.sleep(TRICKLE_GAP_S)
            if flush_timeout is None:
                responses = server.drain()       # manual flush at the end
            else:
                responses = server.collect(len(rids), timeout=60.0)
            assert len(responses) == len(rids)
            lats.append(np.asarray(sorted(r.latency_s for r in responses)))
        server.close()
        best = min(lats, key=lambda a: float(np.percentile(a, 50)))
        return {"p50_ms": float(np.percentile(best, 50) * 1e3),
                "p99_ms": float(np.percentile(best, 99) * 1e3)}

    flush_results = []
    for label, timeout in (("no_flush_timeout", None),
                           (f"flush_{FLUSH_TIMEOUT_S * 1e3:.0f}ms",
                            FLUSH_TIMEOUT_S)):
        stats = trickle(timeout)
        flush_results.append({"policy": label,
                              **{k: round(v, 3) for k, v in stats.items()}})
        out_rows.append(
            f"serve_trickle_{label},{stats['p50_ms'] * 1e3:.1f},"
            f"p99_ms={stats['p99_ms']:.2f}")

    # ---- control plane: sustained Poisson load + profile routing ----------
    sustained_out, sustained_bench = sustained_rows(smoke=smoke)
    out_rows.extend(sustained_out)
    routing_out, routing_bench = routing_rows(smoke=smoke)
    out_rows.extend(routing_out)

    bench = {
        "name": "serve_latency",
        "n_requests": n_requests,
        "shape": [FRAMES, COILS, H, W],
        "results": results,
        "flush_timeout": {
            "trickle_n": trickle_n,
            "gap_ms": TRICKLE_GAP_S * 1e3,
            "flush_timeout_ms": FLUSH_TIMEOUT_S * 1e3,
            "batch": 8,
            "results": flush_results,
        },
        "sustained": sustained_bench,
        "routing": routing_bench,
    }
    print("BENCH " + json.dumps(bench))
    if not smoke:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_serve_latency.json")
        with open(out_path, "w") as f:
            json.dump(bench, f, indent=2)
    return out_rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for r in rows(smoke="--smoke" in sys.argv):
        print(r)
