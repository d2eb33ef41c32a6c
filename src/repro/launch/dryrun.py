import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh; print memory_analysis (fits?) and cost_analysis
(FLOPs/bytes for §Roofline); parse the post-SPMD HLO for collective bytes.

The XLA_FLAGS line above MUST run before any jax import (device count locks
on first init) — hence this module sets it at line 1-2 and nothing else in
the repo sets it globally (smoke tests/benches see 1 device).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh both] [--out benchmarks/artifacts]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, SHAPES, all_cells, get_config,
                           shapes_for)
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch import blocks as B
from repro.launch.hlo_analysis import collective_bytes, collective_counts
from repro.launch.inputs import batch_specs, decode_specs
from repro.launch.mesh import chips, make_production_mesh
from repro.launch.sharding import make_axes
from repro.models import transformer as T
from repro.models.params import shape_tree
from repro.train.step import make_train_step, train_state_specs

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")


def _default_rc(kind: str, overrides: dict | None = None) -> RunConfig:
    rc = RunConfig() if kind == "train" else \
        RunConfig(param_dtype="bfloat16", zero1=False)
    if overrides:
        rc = dataclasses.replace(rc, **overrides)
    return rc


def _analyze(compiled) -> dict:
    out = {}
    try:
        ca = compiled.cost_analysis() or {}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        out["cost_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        out["memory_error"] = repr(e)
    txt = compiled.as_text()
    out["collectives"] = collective_bytes(txt)
    out["collective_counts"] = collective_counts(txt)
    out["hlo_chars"] = len(txt)
    return out


def lower_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
               rc_overrides: dict | None = None, verbose: bool = True,
               mesh=None, cfg=None) -> dict:
    cfg = cfg or get_config(arch)
    rc = _default_rc(shape.kind, rc_overrides)
    mesh = mesh if mesh is not None else \
        make_production_mesh(multi_pod=multi_pod)
    ax = make_axes(mesh, rc)
    # scan-body multiplier for the cost adjustment: the layer scan runs once
    # per microbatch (grad-accum scan), so the block module (lowered at the
    # micro batch size) executes M × n_superblocks times per step.
    block_mult = cfg.n_superblocks * (rc.microbatches
                                      if shape.kind == "train" else 1)
    res = {"arch": arch, "shape": shape.name,
           "mesh": "multi" if multi_pod else "single",
           "chips": chips(mesh), "kind": shape.kind,
           "n_superblocks": cfg.n_superblocks,
           "block_multiplier": block_mult,
           "pattern_len": len(cfg.block_pattern()),
           "rc": {k: getattr(rc, k) for k in
                  ("remat", "attn_impl", "moe_impl", "seq_parallel",
                   "microbatches", "param_dtype", "zero1", "fsdp_axis")}}

    with mesh:
        t0 = time.time()
        if shape.kind == "train":
            state = shape_tree(train_state_specs(cfg, rc),
                               dtype=jnp.dtype(rc.param_dtype),
                               resolver=ax.resolve, mesh=mesh)
            # optimizer moments are always fp32
            opt = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                               sharding=s.sharding),
                state.opt)
            state = state._replace(
                opt=opt, step=jax.ShapeDtypeStruct((), jnp.int32))
            batch = batch_specs(cfg, shape, ax, train=True)
            step = make_train_step(cfg, rc, ax)
            lowered = jax.jit(step).lower(state, batch)
        elif shape.kind == "prefill":
            params = shape_tree(T.model_specs(cfg),
                                dtype=jnp.dtype(rc.param_dtype),
                                resolver=ax.resolve, mesh=mesh)
            batch = batch_specs(cfg, shape, ax, train=False)
            fn = lambda p, t, f=None: T.prefill(cfg, rc, p, t, ax, f)
            args = (params, batch["tokens"]) + (
                (batch["frontend"],) if "frontend" in batch else ())
            lowered = jax.jit(fn).lower(*args)
        else:  # decode
            params = shape_tree(T.model_specs(cfg),
                                dtype=jnp.dtype(rc.param_dtype),
                                resolver=ax.resolve, mesh=mesh)
            d = decode_specs(cfg, shape, ax)
            fn = lambda p, tok, cache, pos: T.decode_step(
                cfg, rc, p, tok, cache, pos, ax)
            lowered = jax.jit(fn).lower(params, d["token"], d["cache"],
                                        d["pos"])
        res["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t0, 2)
        res["full"] = _analyze(compiled)

        # ---- single-superblock module (scan-body cost adjustment) ----
        t0 = time.time()
        if shape.kind == "train":
            bfn = B.train_block_fn(cfg, rc, ax, shape.seq_len)
            bargs = B.block_input_specs(cfg, rc, shape, ax)
        elif shape.kind == "prefill":
            bfn = B.prefill_block_fn(cfg, rc, ax, shape.seq_len)
            bargs = B.block_input_specs(cfg, rc, shape, ax)
        else:
            bfn = B.decode_block_fn(cfg, rc, ax)
            bargs = B.block_input_specs(cfg, rc, shape, ax)
        bcompiled = jax.jit(bfn).lower(*bargs).compile()
        res["block_s"] = round(time.time() - t0, 2)
        res["block"] = _analyze(bcompiled)

    if verbose:
        mem = res["full"].get("memory", {})
        print(f"[{arch} × {shape.name} × {res['mesh']}] "
              f"compile {res['compile_s']}s  "
              f"flops/dev {res['full'].get('flops', 0):.3e}  "
              f"args {mem.get('argument_bytes', 0)/2**30:.2f} GiB  "
              f"temp {mem.get('temp_bytes', 0)/2**30:.2f} GiB  "
              f"coll {res['full']['collectives'].get('total', 0)/2**20:.1f} MiB")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops=%.4e bytes=%.4e" %
              (res["full"].get("flops", 0),
               res["full"].get("bytes_accessed", 0)))
    return res


def artifact_path(out_dir: str, arch: str, shape: str, mesh: str) -> str:
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(ARTIFACT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--rc", default="", help="json RunConfig overrides")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    overrides = json.loads(args.rc) if args.rc else None
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = all_cells() if args.all else \
        [(args.arch, SHAPES[args.shape])]

    failures = 0
    for arch, shape in cells:
        if shape not in shapes_for(arch):
            continue
        for mp in meshes:
            mname = "multi" if mp else "single"
            path = artifact_path(args.out, arch, shape.name, mname)
            if args.skip_existing and os.path.exists(path):
                print(f"skip {path}")
                continue
            try:
                res = lower_cell(arch, shape, mp, overrides)
            except Exception:
                failures += 1
                res = {"arch": arch, "shape": shape.name, "mesh": mname,
                       "error": traceback.format_exc()}
                print(f"FAILED {arch} × {shape.name} × {mname}")
                print(res["error"])
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("dry-run complete")


if __name__ == "__main__":
    main()
