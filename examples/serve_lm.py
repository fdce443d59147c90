"""Serving example: continuous-batching decode through the Pipeline stack.

Trains nothing — initializes a small qwen3-family model and a small whisper
encoder-decoder, then drives :class:`repro.serve.LMServer` (the engine
behind the legacy ``ServeEngine`` wrapper):

* the KV cache is ONE persistent arena-backed Data — device-resident and
  donated from step to step, so after the one-time zero-state upload the
  cache edge moves zero bytes host<->device (the ``repro_h2d_bytes_total``
  counter over each steady decode step proves it below);
* each queued prompt claims a free slot via a single-row prefill Pipeline
  plus an in-place cache splice, joining the in-flight decode batch;
* whisper requests carry per-request audio frames, and their prefill graph
  is a real fan-in Pipeline: frames -> encoder ~ tokens -> decoder prefill
  joined on a device-resident, donated ``enc`` edge;
* the :class:`repro.serve.FrontDoor` control plane fronts TWO decode
  replicas with priority admission, least-outstanding routing, and a
  Prometheus-style metrics surface (docs/serving.md).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core import enable_compile_cache, trace
from repro.models import build_model
from repro.serve import CallableReplica, FrontDoor, LMServer, SamplingConfig


def serve_transformer() -> None:
    cfg = get_smoke("qwen3-14b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))

    server = LMServer(model, params, batch=4, max_len=64,
                      sampling=SamplingConfig(max_new_tokens=16))

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, size=rng.integers(3, 10)))
               for _ in range(10)]
    for p in prompts:
        server.submit(p)

    before = {c.span.id for c in trace.calls("lm.step")}
    t0 = time.perf_counter()
    outputs = server.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(o) for o in outputs)
    print(f"[qwen3] served {len(prompts)} requests through 4 slots: "
          f"{total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s)")
    for i, o in enumerate(outputs[:4]):
        print(f"  request {i}: {len(o)} tokens -> {o[:8]}...")
    assert all(len(o) > 0 for o in outputs)
    steady = [c for c in trace.calls("lm.step")
              if c.span.id not in before and "lm.admit" not in c.counts]
    h2d = sum(c.deltas["repro_h2d_bytes_total"] for c in steady)
    print(f"  host2device bytes over {len(steady)} steady decode steps: "
          f"{h2d:.0f}")
    assert steady and h2d == 0


def serve_whisper() -> None:
    cfg = get_smoke("whisper-large-v3")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(1))

    enc_len = 16
    server = LMServer(model, params, batch=2, max_len=32, enc_len=enc_len,
                      sampling=SamplingConfig(max_new_tokens=8))
    rng = np.random.default_rng(1)
    for _ in range(4):
        prompt = list(rng.integers(0, cfg.vocab, size=3))
        frames = rng.standard_normal((enc_len, cfg.d_model)).astype(np.float32)
        server.submit(prompt, frames=frames)
    outputs = server.run()
    print(f"[whisper] served {len(outputs)} audio requests "
          f"(encoder→decoder fan-in prefill): "
          f"{[len(o) for o in outputs]} tokens each")
    assert all(len(o) == 8 for o in outputs)


def serve_front_door() -> None:
    """Two LMServer replicas behind the FrontDoor control plane."""
    cfg = get_smoke("qwen3-14b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))

    def make_replica(name: str) -> CallableReplica:
        lm = LMServer(model, params, batch=2, max_len=32,
                      sampling=SamplingConfig(max_new_tokens=8))

        def decode(prompt):
            rid = lm.submit(list(prompt))
            return lm.run()[rid]

        return CallableReplica(name, decode, max_batch=2)

    fd = FrontDoor([make_replica("lm-0"), make_replica("lm-1")],
                   capacity=16, overflow="shed",
                   policy="least-outstanding")
    rng = np.random.default_rng(2)
    rids = [fd.submit(list(rng.integers(0, cfg.vocab, size=5)),
                      priority="interactive" if i % 3 == 0 else "batch")
            for i in range(6)]
    outcomes = {o.rid: o for o in fd.drain(timeout=600.0)}
    for rid in rids:
        o = outcomes[rid]
        assert o.status == "ok", o
        print(f"[frontdoor] rid {rid} ({o.priority}) -> {o.replica}: "
              f"{len(o.result)} tokens in {o.latency_s * 1e3:.0f}ms")
    health = fd.health()
    print(f"[frontdoor] health ok={health['ok']}, served "
          + str({n: r['served'] for n, r in health['replicas'].items()}))
    for line in fd.metrics.render().splitlines():
        if line.startswith("frontdoor_requests_completed_total"):
            print(f"[frontdoor] {line}")
    fd.close()


def main() -> None:
    enable_compile_cache()
    serve_transformer()
    serve_whisper()
    serve_front_door()
    print("all requests completed")


if __name__ == "__main__":
    main()
