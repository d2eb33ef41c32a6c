#!/usr/bin/env python3
"""Bring-up smoke of the banked-memory system on one TPU chip.

Drives the system's two device paths once, through the entry points a user
calls, and checks what comes out:

  device   JAX must see a TPU.  Anything else exits non-zero and names the
           platform it found; nothing falls back to the CPU.
  costing  ``cost_many`` prices the nine paper memories against the whole
           decode-step trace of mixtral-8x22b (~109k ops; traffic only, no
           weights) and against the Table II transposes.  Every integer
           must equal the per-architecture reference loop
           (``MemoryArchitecture._cost_loop``) and the symbolic prover, and
           the Table II cells the tests pin must come out cycle-exact.
  serving  minicpm-2b at its full width (40 layers, d_model 2304, 36 KV
           heads, an 18432-wide page line) with random bf16 weights from
           --seed.  A paged ``ServeEngine`` on the 16B-offset memory answers
           a batch with greedy ``generate`` (tokens must equal the dense-KV
           engine's), then serves a seeded ``run_scheduler`` day whose live
           trace must be bit-equal to ``simulate_scheduler_stream``.  The
           compiled decode step must hold the Pallas kernels
           (``tpu_custom_call``).

Each phase prints its set-up (compile) seconds, wall seconds, token counts
and peak device bytes; these are a bring-up record, not a benchmark.  The
last line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every check passed.

There is no multi-chip phase: serving runs unsharded and ``cost_many``
prices on one device, so every path users run today needs one chip.

    python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

COST_MODEL = "mixtral-8x22b"
SERVE_MODEL = "minicpm-2b"
MEM_ARCH = "16B-offset"
TRANSPOSE_SIZES = (32, 64, 128)
#: serving budget: 8 lanes × 512 tokens of 8-token pages (80 K/V pools of
#: 1024 pages × 18432 bf16 words = 3.0 GB beside 5.4 GB of weights)
BATCH, MAX_SEQ, PAGE_LEN = 8, 512, 8
PROMPT_LEN, NEW_TOKENS, N_REQUESTS = 100, 16, 8


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else "not reported"


def device_phase() -> dict:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log("device", **info)
    check(info["platform"] == "tpu",
          f"no TPU: JAX found platform {info['platform']!r}")
    return info


def costing_phase(model=COST_MODEL, mem_arch=MEM_ARCH,
                  sizes=TRANSPOSE_SIZES) -> None:
    from benchmarks.paper_data import TABLE2_EXACT
    from repro.analysis.symbolic import prove_many
    from repro.bench import transpose_workload
    from repro.core.arch import PAPER_ARCHITECTURES as ARCHS
    from repro.core.cost_engine import cost_many
    from repro.isa.programs import transpose as tr_prog
    from repro.models.trace import model_step_symbolic, model_step_trace

    def price(name, trace, symbolic) -> dict:
        t0 = time.perf_counter()
        costs = cost_many(ARCHS, trace)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = cost_many(ARCHS, trace)
        wall = time.perf_counter() - t0
        check(again == costs, f"{name}: a second cost_many pass differs")
        dense = trace.materialize() if hasattr(trace, "materialize") else trace
        for a, c in zip(ARCHS, costs):
            check(c == a._cost_loop(dense),
                  f"{name}: cost_many != _cost_loop under {a.name}")
        for proof, c in zip(prove_many(ARCHS, symbolic), costs):
            check(proof.cost == c,
                  f"{name}: cost_many != symbolic prover under {proof.arch}")
        log("costing", trace=name, ops=dense.n_ops, archs=len(ARCHS),
            first_call_s=first, wall_s=wall,
            cycles={a.name: c.total_cycles for a, c in zip(ARCHS, costs)})
        return {a.name: c for a, c in zip(ARCHS, costs)}

    price(f"{getattr(model, 'name', model)}-step@{mem_arch}",
          model_step_trace(model, mem_arch),
          model_step_symbolic(model, mem_arch))
    for n in sizes:
        costs = price(f"transpose{n}", transpose_workload(n).trace(),
                      tr_prog.symbolic_trace(n))
        for mem, want in TABLE2_EXACT[n].items():
            got = (costs[mem].load_cycles, costs[mem].store_cycles)
            check(got == want,
                  f"Table II n={n} {mem}: (load, store) {got} != {want}")
    log("costing", ok=True, peak_bytes=peak_bytes())


def build_engines(cfg, seed: int, batch=BATCH, max_seq=MAX_SEQ):
    """(dense, paged) engines sharing one set of random bf16 weights."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.models import init_tree, model_specs
    from repro.serving.engine import ServeEngine

    rc = RunConfig(param_dtype="bfloat16", remat="none", attn_impl="dense")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_tree(
        model_specs(cfg), jax.random.PRNGKey(seed), dtype=jnp.bfloat16))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    log("serving", model=cfg.name, n_layers=cfg.n_layers,
        d_model=cfg.d_model, params=n_params,
        init_s=time.perf_counter() - t0)
    return tuple(ServeEngine(cfg, rc, params, NO_AXES, max_batch=batch,
                             max_seq=max_seq, mem_arch=MEM_ARCH,
                             kv_mode=mode, page_len=PAGE_LEN)
                 for mode in ("dense", "paged"))


def decode_step_compiled(eng):
    """Compile the lane-ragged paged decode step at the engine's shapes
    (what ``run_scheduler`` calls every tick)."""
    import jax
    import jax.numpy as jnp

    cfg, kv, b = eng.cfg, eng.kv_cfg, eng.max_batch

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    pool = spec((kv.n_pages,) + kv.page_shape,
                jnp.dtype(eng.rc.compute_dtype))
    pools = {f"b{j}s{sb}": {"k": pool, "v": pool}
             for j, _ in enumerate(cfg.block_pattern())
             for sb in range(cfg.n_superblocks)}
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), eng.params)
    n_pt = -(-eng.max_seq // kv.page_len)
    return eng._decode_sched.lower(
        params, spec((b, 1), jnp.int32), pools, spec((b, n_pt), jnp.int32),
        spec((b,), jnp.int32), spec((b,), jnp.bool_),
        spec((), jnp.int32)).compile()


def serving_phase(dense, paged, seed: int, prompt_len=PROMPT_LEN,
                  new_tokens=NEW_TOKENS, n_requests=N_REQUESTS) -> None:
    import numpy as np

    from repro.serving.scheduler import (simulate_scheduler_stream,
                                         synthesize_requests,
                                         total_new_tokens)

    cfg, b = paged.cfg, paged.max_batch
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(b, prompt_len)).astype(np.int32)
    # generate() returns host arrays, so each timing ends with the device
    # done (a device->host read is a block_until_ready)
    t0 = time.perf_counter()
    want = dense.generate(prompts, max_new_tokens=new_tokens).tokens
    dense_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    paged.generate(prompts, max_new_tokens=new_tokens)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = paged.generate(prompts, max_new_tokens=new_tokens).tokens
    wall = time.perf_counter() - t0
    check(np.array_equal(got, want),
          "paged generate tokens differ from the dense engine's")
    log("serving", path="generate", batch=b, prompt_len=prompt_len,
        tokens=got.size, dense_first_s=dense_s, paged_first_s=first,
        paged_wall_s=wall, tokens_per_s=got.size / wall,
        peak_bytes=peak_bytes())

    reqs = synthesize_requests(n_requests, context_dist="mixed",
                               max_seq=paged.max_seq, seed=seed,
                               vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    first_run = paged.run_scheduler(reqs)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = paged.run_scheduler(reqs)
    wall = time.perf_counter() - t0
    check(res.outputs.keys() == first_run.outputs.keys() and all(
        np.array_equal(res.outputs[k], first_run.outputs[k])
        for k in res.outputs), "two runs of the same day decode differently")
    for r in reqs:
        check(len(res.outputs[r.rid]) == r.max_new_tokens,
              f"request {r.rid} got {len(res.outputs[r.rid])} tokens, "
              f"asked for {r.max_new_tokens}")
    live = paged.scheduler_stream().materialize()
    sim = simulate_scheduler_stream(
        paged.mem_arch, reqs, n_lanes=b, max_seq=paged.max_seq,
        page_len=paged.page_len, n_kv_layers=paged.n_kv_layers,
        policy="seq-skew").materialize()
    for field in ("addrs", "kinds", "instr", "mask"):
        check(np.array_equal(np.asarray(getattr(live, field)),
                             np.asarray(getattr(sim, field))),
              f"live scheduler trace differs from the simulation in {field}")
    tokens = total_new_tokens(reqs)
    log("serving", path="run_scheduler", requests=len(reqs),
        ticks=res.ticks, tokens=tokens, trace_ops=live.n_ops,
        first_run_s=first, wall_s=wall, tokens_per_s=tokens / wall,
        peak_bytes=peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        epilog="Needs one TPU chip; there is no multi-chip phase (serving "
               "runs unsharded and cost_many prices on one device).")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and day")
    args = ap.parse_args(argv)

    info = device_phase()
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log("device", compile_cache=enable_compile_cache())

    costing_phase()

    dense, paged = build_engines(get_config(SERVE_MODEL), args.seed)
    t0 = time.perf_counter()
    compiled = decode_step_compiled(paged)
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled paged decode step holds no Pallas kernel")
    mem = compiled.memory_analysis()
    log("serving", decode_step_compile_s=time.perf_counter() - t0,
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes)
    serving_phase(dense, paged, args.seed)

    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
