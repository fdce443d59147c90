"""Driver for a decoder LM served by ``LMServer`` (``"system": "lm"``).

The configuration gives the architecture at its published widths, the
server's batch and cache length, and the module of its plain reference
under ``chipbench/reference/``.  Weights are made from the seed on the
chip in one jitted call by that reference module, in the dtype they are
served in, and handed to the program; ``LMServer`` takes host arrays, so
they travel to the host and back once during set-up.

A closed-loop mix sends waves of ``group`` requests, one prompt length
per wave from a fixed cycle, each wave after the previous one has been
answered: offline batch generation.  Set-up warms every program a wave
uses (each prompt length's prefill, every slot's splice and release, the
decode step) with one request per prompt length and per slot that stops
after two tokens.  Once the window has closed, a seeded sample of the
requests finished in it, the longest prompt among them, is
teacher-forced through the float32 reference, and the widest gap by
which a served token's logit lies below the reference's best is compared
with the configuration's limit.  A mix whose cycle starts with its
longest prompt has that prompt's wave finished in every window.
"""
from __future__ import annotations

import importlib
import sys
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import generator
from chipbench.harness import (Cell, Outcome, free_program_state,
                               memory_peak_bytes, span)

#: requests whose served tokens are compared with the reference
SAMPLE = 4


def arch_config(c: Dict[str, Any]):
    """The program's config object for the configuration file."""
    from repro.models.common import ArchConfig
    return ArchConfig(
        name=c["name"], family=c["family"], n_layers=c["n_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], d_head=c["d_head"], d_ff=c["d_ff"],
        vocab=c["vocab"], window=c["window"], rope_theta=c["rope_theta"],
        param_dtype=c["param_dtype"], dtype=c["dtype"])


def check_layout(model, weights) -> None:
    """The program must take the weight tree the reference makes."""
    import jax
    want = jax.eval_shape(model.init_params, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree differs from the "
                           "one the reference makes")


def program_weights(ref, c: Dict[str, Any], cell: Cell):
    """The weights handed to the program: the seed's, made on the chip in
    the dtype they are served in."""
    return ref.make_weights(c, cell.seed, device=cell.devices[0])


def prompt(req, vocab: int) -> List[int]:
    return generator.rng(req.seed).integers(0, vocab, req.prompt_len
                                            ).tolist()


def run(cell: Cell) -> Outcome:
    import jax
    from repro.core import CLapp, DeviceTraits
    from repro.models import build_model
    from repro.serve import LMServer, SamplingConfig

    c, mix = cell.config, cell.mix
    ref = importlib.import_module(f"chipbench.reference.{c['reference']}")
    model = build_model(arch_config(c))
    batch, new = int(c["batch"]), int(mix["new_tokens"])
    weights = program_weights(ref, c, cell)
    check_layout(model, weights)
    host = jax.tree.map(np.asarray, weights)
    del weights
    app = CLapp().init(device_traits=DeviceTraits(count=1))
    server = LMServer(model, host, batch=batch, max_len=int(c["max_len"]),
                      sampling=SamplingConfig(max_new_tokens=2), app=app)
    del host

    lens = mix["prompt_lens"]
    with span("warmup"):                  # every prompt length, every slot
        for i in range(max(batch, len(lens))):
            server.submit(generator.rng(cell.seed, 8, i).integers(
                0, c["vocab"], lens[i % len(lens)]).tolist())
        while server.queue or server.active.any():
            server.step()                 # admits, decodes, releases
    server.sampling = SamplingConfig(max_new_tokens=new)

    groups = generator.closed_groups(mix, cell.seed, 1)
    reqs: Dict[int, Any] = {}
    steps: List[Dict[str, Any]] = []
    t0 = time.perf_counter()
    setup_s = t0 - cell.started
    cpu0 = time.process_time()
    window_s = None
    while window_s is None:
        group = next(groups)
        for r in group:
            reqs[server.submit(prompt(r, c["vocab"]))] = r
        while server.queue or server.active.any():
            cell.tracer.tick(time.perf_counter() - t0)
            admit = bool(server.queue) and bool((~server.active).any())
            release = any(len(server.results[int(server.req_of_slot[s])]) + 1
                          >= new for s in np.where(server.active)[0])
            kind = "admit" if admit else "release" if release else "decode"
            active0, admitted0 = int(server.active.sum()), server.admitted
            a = time.perf_counter()
            with span(f"lm.{kind}_step"):
                server.step()
            b = time.perf_counter()
            admitted = server.admitted - admitted0
            steps.append({
                "t0": a - t0, "t1": b - t0, "kind": kind,
                "admitted": admitted,
                "prompt_tokens": admitted * group[0].prompt_len,
                "rows": active0 + admitted,
                "pos": int(server.positions.max()) - 1, "a": a, "b": b})
            if b - t0 >= cell.seconds:
                window_s = b - t0
                break
    cpu_s = time.process_time() - cpu0
    cell.tracer.stop()
    for st in steps:
        st["traced"] = cell.tracer.covers(st.pop("a"), st.pop("b"))
    tokens = sum(len(server.results[rid]) for rid in reqs)
    peak = memory_peak_bytes(cell.devices)
    results = {rid: list(server.results[rid]) for rid in reqs}
    del server, app
    free_program_state()

    prompt_tokens = sum(st["prompt_tokens"] for st in steps)
    took = sorted(st["t1"] - st["t0"] for st in steps
                  if st["kind"] == "decode")
    print(f"steps: {len(steps)} in {window_s:.3f} s; decode-only steps "
          f"{len(took)}, median {took[len(took) // 2] if took else 0:.4f} s; "
          f"admission steps {sum(1 for st in steps if st['kind'] == 'admit')}"
          f"; {tokens} tokens, {prompt_tokens} prompt tokens",
          file=sys.stderr)
    inside = sum(st["t1"] - st["t0"] for st in steps)
    slow = sorted(steps, key=lambda st: st["t0"] - st["t1"])[:5]
    print(f"outside steps {window_s - inside:.3f} s; longest steps: "
          + ", ".join(f"{st['kind']} at {st['t0']:.3f} s took "
                      f"{st['t1'] - st['t0']:.4f} s" for st in slow),
          file=sys.stderr)
    finished = [rid for rid in reqs if len(results[rid]) == new]
    bad = sum(1 for rid in reqs for t in results[rid]
              if not 0 <= t < c["vocab"])
    gap = _check(cell, ref, reqs, results, finished)
    checks = {"lm_max_logit_gap": (gap, c["limits"]["lm_max_logit_gap"]),
              "lm_token_ids_out_of_range": (float(bad), 0.0),
              "no_requests_compared": (float(not finished), 0.0)}
    counters = {"steps": steps, "window_s": window_s, "tokens": tokens,
                "prompt_tokens": prompt_tokens, "cpu_s": cpu_s,
                "finished": len(finished)}
    metrics = {"setup_s": setup_s, "lm_tokens_per_s": tokens / window_s}
    return Outcome(metrics=metrics, counters=counters, checks=checks,
                   attempted=len(reqs), failed=0, memory_peak_bytes=peak)


def sample(cell: Cell, reqs, finished) -> List[int]:
    """The requests whose served tokens are compared: drawn from the
    seed, the longest prompt among them."""
    pick = generator.rng(cell.seed, 6)
    longest = max(reqs[rid].prompt_len for rid in finished)
    first = [rid for rid in finished if reqs[rid].prompt_len == longest]
    chosen = [first[int(pick.integers(len(first)))]]
    rest = [rid for rid in finished if rid not in chosen]
    return chosen + [rest[i] for i in pick.permutation(len(rest))[:SAMPLE - 1]]


def _check(cell: Cell, ref, reqs, results, finished) -> float:
    """Widest reference-logit gap of a served token over the sample."""
    if not finished:
        return 0.0
    c = cell.config
    weights = ref.make_weights(c, cell.seed, device=cell.devices[0])
    gap = 0.0
    for rid in sample(cell, reqs, finished):
        g = ref.served_gaps(c, weights, prompt(reqs[rid], c["vocab"]),
                            results[rid])
        gap = max(gap, float(g.max()))
    return gap
