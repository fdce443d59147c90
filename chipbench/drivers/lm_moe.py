"""Driver for a DeepSeek-V2 decoder served by ``LMServer``
(``"system": "lm_moe"``): latent attention and a share of the routed
experts on one chip.

The configuration file holds the published ``config.json``'s keys, the
layers and experts held here (``num_hidden_layers``, ``n_experts`` from
``expert_offset``; the router keeps ``n_routed_experts`` outputs), the
server's batch and cache length and the module of its plain reference.
The weights, the closed-loop waves, the window and the comparison with
the float32 reference are those of :mod:`chipbench.drivers.lm`, whose
model-agnostic parts this driver takes.  Set-up warms one request per
prompt length: the splice and the release are one program each, whatever
the slot.

Besides the steps, the counters give the readers the window's routing
counts (the program's ``repro_moe_*`` counters over the window: pairs
routed, pairs computed on the chip, token rows through an expert layer)
and, from a traced run, the device time of the decode program and of its
grouped matmuls (ops named ``ragged-dot*``, read from the trace's ops by
the program they ran in, which the harness's summary does not keep).
"""
from __future__ import annotations

import dataclasses
import glob
import importlib
import os
import sys
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import generator
from chipbench.drivers.lm import _check, check_layout, prompt
from chipbench.harness import (Cell, Outcome, free_program_state,
                               memory_peak_bytes, span)

#: what the program's layer does; a configuration asking for more is refused
SUPPORTED = {"first_k_dense_replace": 1, "moe_layer_freq": 1,
             "q_lora_rank": None, "n_group": 1, "topk_group": 1,
             "topk_method": "greedy", "scoring_func": "softmax",
             "hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
             "routed_scaling_factor": 1}
#: a decode program's module name holds this; its grouped matmuls' ops (the
#: Mosaic kernels ``ragged-dot-metadata`` and ``ragged-dot-none.<n>``) are
#: named with this
DECODE_PROGRAM, GROUPED_MATMUL = "DecodeStep", "ragged-dot"
#: the routing counters, by the name the readers read them under
ROUTING = {"moe_assignments": "repro_moe_assignments_total",
           "moe_held_assignments": "repro_moe_held_assignments_total",
           "moe_rows": "repro_moe_rows_total"}


def arch_config(c: Dict[str, Any]):
    """The program's config object for the configuration file."""
    from repro.models.common import ArchConfig, YaRN
    off = {k: c.get(k) for k, v in SUPPORTED.items() if c.get(k) != v}
    if off:
        raise ValueError(f"the program's DeepSeek-V2 layer needs {SUPPORTED}"
                         f"; the configuration has {off}")
    ys = c.get("rope_scaling")
    if ys and ys.get("type") != "yarn":
        raise ValueError(f"rope_scaling {ys.get('type')!r}: only yarn")
    return ArchConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab=c["vocab_size"],
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        first_dense_ff=c["intermediate_size"],
        norm_topk_prob=c["norm_topk_prob"],
        experts_held=c["n_experts"], expert_offset=c["expert_offset"],
        mla=True, kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(ys["factor"]),
            original_max_position_embeddings=int(
                ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])) if ys else None,
        param_dtype=c["param_dtype"], dtype=c["dtype"])


def program_weights(ref, c: Dict[str, Any], cell: Cell):
    """The weights handed to the program: the seed's, made on the chip in
    the dtype they are served in."""
    return ref.make_weights(c, cell.seed, device=cell.devices[0])


def routing_counts() -> Dict[str, float]:
    from repro.core import trace
    return {k: trace.METRICS.counter(name).value()
            for k, name in ROUTING.items()}


def decode_program_ops(tracer) -> Optional[Dict[str, float]]:
    """From a stopped trace: the decode program's device seconds and
    calls in the traced window, and the seconds of its grouped-matmul
    ops, summed over chips; None without a trace or a decode program."""
    from chipbench import trace_reduce as tr
    if tracer.state != "done" or not tracer.dir:
        return None
    paths = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    t = tr.read_xplane(paths[0])
    win = tr.window_of(t)
    out: Dict[str, float] = defaultdict(float)
    grouped = set()
    for dev in t.devices.values():
        ops = tr.clip(dev.ops, win.start_ns, win.end_ns)
        for op, prog in zip(ops, tr.program_of(ops, dev.modules)):
            if prog and DECODE_PROGRAM in prog:
                out["decode_s"] += op.dur_ns * 1e-9
                # an op's trace name is its HLO line; the op's own name is
                # what precedes " = " (its operands name other ops)
                own = op.name.split(" = ", 1)[0].lstrip("%")
                if own.startswith(GROUPED_MATMUL):
                    out["grouped_s"] += op.dur_ns * 1e-9
                    grouped.add(own)
        out["decode_calls"] += sum(
            1 for m in dev.modules if DECODE_PROGRAM in m.name
            and win.start_ns <= m.start_ns < win.end_ns)
    print(f"grouped-matmul ops of the decode program: {sorted(grouped)}",
          file=sys.stderr)
    return dict(out) if out.get("decode_calls") else None


def run(cell: Cell) -> Outcome:
    import jax
    from repro.core import CLapp, DeviceTraits
    from repro.models import build_model
    from repro.serve import LMServer, SamplingConfig

    # the shared comparison reads the vocabulary as "vocab"
    cell = dataclasses.replace(
        cell, config=dict(cell.config, vocab=cell.config["vocab_size"]))
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
    with span("warmup"):                  # every prompt length
        for i, n in enumerate(lens):
            server.submit(generator.rng(cell.seed, 8, i).integers(
                0, c["vocab"], n).tolist())
        while server.queue or server.active.any():
            server.step()                 # admits, decodes, releases
    server.sampling = SamplingConfig(max_new_tokens=new)

    groups = generator.closed_groups(mix, cell.seed, 1)
    reqs: Dict[int, Any] = {}
    steps: List[Dict[str, Any]] = []
    t0 = time.perf_counter()
    setup_s = t0 - cell.started
    cpu0 = time.process_time()
    routed0 = routing_counts()
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
    routed = {k: v - routed0[k] for k, v in routing_counts().items()}
    cell.tracer.stop()
    ops = decode_program_ops(cell.tracer)
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
    admits = [st["t1"] - st["t0"] for st in steps if st["kind"] == "admit"]
    print(f"steps: {len(steps)} in {window_s:.3f} s; decode-only steps "
          f"{len(took)}, median {took[len(took) // 2] if took else 0:.4f} s; "
          f"admission steps {len(admits)}, {sum(admits):.3f} s"
          f"; {tokens} tokens, {prompt_tokens} prompt tokens; routing "
          f"{routed}; decode program {ops}", file=sys.stderr)
    finished = [rid for rid in reqs if len(results[rid]) == new]
    bad = sum(1 for rid in reqs for t in results[rid]
              if not 0 <= t < c["vocab"])
    gap = _check(cell, ref, reqs, results, finished)
    checks = {"lm_max_logit_gap": (gap, c["limits"]["lm_max_logit_gap"]),
              "lm_token_ids_out_of_range": (float(bad), 0.0),
              "no_requests_compared": (float(not finished), 0.0)}
    counters = {"steps": steps, "window_s": window_s, "tokens": tokens,
                "prompt_tokens": prompt_tokens, "cpu_s": cpu_s,
                "finished": len(finished), **routed,
                "decode_program": ops}
    metrics = {"setup_s": setup_s, "lm_tokens_per_s": tokens / window_s}
    return Outcome(metrics=metrics, counters=counters, checks=checks,
                   attempted=len(reqs), failed=0, memory_peak_bytes=peak)
