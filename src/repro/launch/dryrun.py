import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any jax import (device count is locked
# at first init).  Everything below is ordinary code.

r"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, AOT-lower and compile the
train/prefill/decode step on the production mesh (16x16 single-pod and
2x16x16 multi-pod), print ``memory_analysis()`` (it fits) and
``cost_analysis()`` (FLOPs/bytes for §Roofline), and parse collective bytes
from the compiled HLO.  Results append to a JSONL for EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (Roofline, collective_bytes, cost_dict,
                                   model_flops)
from repro.launch.specs import build_lowerable, named_shardings
from repro.models.common import mesh_axes, resolve_tree


def _compile_cell(low, mesh):
    from repro.launch.specs import fit_pspecs
    with mesh, mesh_axes(mesh):
        in_ps = fit_pspecs(resolve_tree(low.in_pspecs), low.specs, mesh)
        # outputs reuse the fitted input spec for the aliased state/cache arg
        if low.kind == "train":
            out_ps = (in_ps[0], None)
        else:
            out_ps = (None, in_ps[-1])
        jitted = jax.jit(
            low.fn,
            in_shardings=named_shardings(in_ps, mesh),
            out_shardings=named_shardings(out_ps, mesh),
            donate_argnums=low.donate,
        )
        lowered = jitted.lower(*low.specs)
        return lowered.compile()


def _costs_of(compiled) -> Dict[str, Any]:
    cost = cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def _cost_add(a, b, sa=1.0, sb=1.0):
    kinds = set(a["coll"]) | set(b["coll"])
    return {
        "flops": max(0.0, sa * a["flops"] + sb * b["flops"]),
        "bytes": max(0.0, sa * a["bytes"] + sb * b["bytes"]),
        "coll": {k: max(0.0, sa * a["coll"].get(k, 0) + sb * b["coll"].get(k, 0))
                 for k in kinds},
    }


def _analysis_variants(cfg):
    """Reduced-layer UNROLLED configs for loop-aware cost reconstruction.

    XLA's cost_analysis counts a while-loop body ONCE (trip count ignored;
    verified in tests/test_roofline.py), so scan-over-layers costs must be
    reconstructed:  cost(L) = base + L * layer, with `layer` measured as the
    delta between python-unrolled 2-layer and 1-layer compiles (unrolling
    puts every layer's ops in the top-level HLO where they are counted)."""
    cfg = cfg.scaled(unroll_layers=True)
    fam = cfg.family
    if fam == "hybrid":
        mk = lambda s, p: cfg.scaled(n_layers=s * p, attn_every=p)
        return {"c11": mk(1, 1), "c12": mk(1, 2), "c21": mk(2, 1)}
    if fam == "encdec":
        mk = lambda e, d: cfg.scaled(enc_layers=e, dec_layers=d, n_layers=e + d)
        return {"c11": mk(1, 1), "c21": mk(2, 1), "c12": mk(1, 2)}
    extra = 1 if cfg.first_dense_ff else 0
    return {"c1": cfg.scaled(n_layers=1 + extra),
            "c2": cfg.scaled(n_layers=2 + extra)}


def _reconstruct(cfg, costs) -> Dict[str, Any]:
    if cfg.family == "hybrid":
        s, p = cfg.n_layers // cfg.attn_every, cfg.attn_every
        layer = _cost_add(costs["c12"], costs["c11"], 1, -1)
        shared = _cost_add(_cost_add(costs["c21"], costs["c11"], 1, -1), layer, 1, -1)
        base = _cost_add(_cost_add(costs["c11"], shared, 1, -1), layer, 1, -1)
        return _cost_add(base, _cost_add(shared, layer, s, s * p))
    if cfg.family == "encdec":
        enc = _cost_add(costs["c21"], costs["c11"], 1, -1)
        dec = _cost_add(costs["c12"], costs["c11"], 1, -1)
        base = _cost_add(_cost_add(costs["c11"], enc, 1, -1), dec, 1, -1)
        return _cost_add(base, _cost_add(enc, dec, cfg.enc_layers, cfg.dec_layers))
    extra = 1 if cfg.first_dense_ff else 0
    l_scan = cfg.n_layers - extra
    layer = _cost_add(costs["c2"], costs["c1"], 1, -1)
    return _cost_add(costs["c1"], layer, 1, l_scan - 1)


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             verbose: bool = True, mesh=None, skip_analysis: bool = False,
             **build_kw) -> Dict[str, Any]:
    t0 = time.time()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    low = build_lowerable(arch, shape, **build_kw)
    compiled = _compile_cell(low, mesh)   # the runnable artifact: must fit

    mem = compiled.memory_analysis()
    cfg = build_kw.get("cfg_override") or get_config(arch)

    # loop-aware cost reconstruction from reduced-layer analysis compiles
    from repro.kernels.ref import unchunked_attention
    raw = _costs_of(compiled)
    if skip_analysis:
        total = raw
    else:
        akw = dict(build_kw)
        akw["microbatches"] = 1
        var_costs = {}
        with unchunked_attention():
            for name, vcfg in _analysis_variants(cfg).items():
                akw["cfg_override"] = vcfg
                vlow = build_lowerable(arch, shape, **akw)
                var_costs[name] = _costs_of(_compile_cell(vlow, mesh))
        total = _reconstruct(cfg, var_costs)

    params_specs = low.specs[0]["params"] if low.kind == "train" else low.specs[0]
    mf = model_flops(cfg, params_specs, low.kind,
                     SHAPES[shape].batch, SHAPES[shape].seq)
    from repro.launch.roofline import wire_bytes
    roof = Roofline(
        flops=total["flops"],
        hbm_bytes=total["bytes"],
        coll_bytes=wire_bytes(total["coll"]),
        coll_breakdown={k: int(v) for k, v in total["coll"].items()},
        model_flops=mf,
    )

    mem_dict: Dict[str, Any] = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        try:
            mem_dict[attr] = int(getattr(mem, attr))
        except Exception:
            pass

    rec = {
        "arch": arch, "shape": shape, "kind": low.kind,
        "mesh": dict(mesh.shape), "chips": n_chips,
        "multi_pod": multi_pod, "note": low.note,
        "memory": mem_dict,
        "roofline": roof.to_dict(n_chips),
        "raw_cost_body_once": raw,
        "compile_s": round(time.time() - t0, 1),
        "status": "ok",
    }
    if verbose:
        print(f"== {arch} x {shape} [{low.kind}] mesh={dict(mesh.shape)} "
              f"({rec['compile_s']}s) ==")
        print(f"   memory_analysis: {mem_dict or mem}")
        print(f"   cost_analysis: flops/chip={roof.flops:.3e} "
              f"bytes/chip={roof.hbm_bytes:.3e}")
        print(f"   collectives/chip: {roof.coll_breakdown} -> {roof.coll_bytes:.3e} B")
        print(f"   roofline: compute={roof.t_compute*1e3:.2f}ms "
              f"memory={roof.t_memory*1e3:.2f}ms "
              f"collective={roof.t_collective*1e3:.2f}ms "
              f"-> {roof.bottleneck}-bound; "
              f"useful_flops={roof.useful_flops_ratio(n_chips):.2%} "
              f"mfu_bound={roof.mfu_bound(n_chips):.2%}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="run every runnable cell")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run each cell on single-pod AND multi-pod meshes")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="compile-only (no roofline reconstruction compiles)")
    ap.add_argument("--opt", nargs="*", default=[],
                    help="ArchConfig overrides, e.g. opt_seq_parallel=1")
    args = ap.parse_args(argv)

    if args.all:
        todo = [(a, s) for a, s, ok, _ in cells(include_skips=False)]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    meshes = [True, False] if args.both_meshes else [args.multi_pod]
    build_kw = dict(microbatches=args.microbatches,
                    zero1=not args.no_zero1,
                    compress_grads=args.compress_grads)
    if args.opt:
        overrides = {}
        for kv in args.opt:
            k, _, v = kv.partition("=")
            overrides[k] = bool(int(v)) if v in ("0", "1") else v
        def _with_overrides(arch):
            return get_config(arch).scaled(**overrides)
        build_kw["_overrides"] = overrides
    failures = 0
    overrides = build_kw.pop("_overrides", None)
    for arch, shape in todo:
        for mp in meshes:
            try:
                kw = dict(build_kw)
                if overrides:
                    kw["cfg_override"] = get_config(arch).scaled(**overrides)
                rec = run_cell(arch, shape, multi_pod=mp,
                               skip_analysis=args.skip_analysis, **kw)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "error", "error": repr(e)}
                print(f"== {arch} x {shape} multi_pod={mp} FAILED: {e!r}",
                      file=sys.stderr)
                traceback.print_exc()
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
