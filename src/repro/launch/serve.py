"""Serving launcher: batched generate with --arch <id> (smoke configs on
CPU; full configs lower via repro.launch.dryrun decode cells).

The KV cache runs on the banked paged pool by default (--kv-mode paged);
--mem-arch picks the memory architecture the pool derives its banking from,
and --cost prints the recorded serving AddressTrace priced under a set of
paper memories (docs/SERVING.md walks through the numbers).

  python -m repro.launch.serve --arch llama3.2-1b --batch 4 \
      --mem-arch 16B --cost

--schedule switches to continuous batching: a seeded multi-tenant day
(--n-requests jobs, --arrival-rate per tick, --context-dist lengths) is
driven lane-ragged through ``ServeEngine.run_scheduler`` with the
--policy preferred-bank allocation; --cost prices the recorded scheduler
trace the same way.

  python -m repro.launch.serve --arch llama3.2-1b --schedule \
      --n-requests 8 --arrival-rate 1.5 --context-dist mixed --cost

--fault-bank injects a seeded bank-loss fault into the scheduled day
(docs/ROBUSTNESS.md): the bank goes offline at --fault-tick, live pages
migrate through the banked kernels, and the run finishes degraded — the
summary reports the fault counters and, with --cost, prices the recorded
trace on the degraded ``!d`` architecture variant next to the healthy one.

  python -m repro.launch.serve --arch llama3.2-1b --schedule \
      --n-requests 8 --fault-bank 1 --fault-tick 4 --cost
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config
from repro.configs.base import RunConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.sharding import NO_AXES
from repro.models import init_tree, model_specs
from repro.serving.engine import ServeEngine

COST_MEMORIES = ("16B", "16B-offset", "8B", "4B", "4R-1W", "4R-2W")


def _cost_table(trace, extra_line: str):
    from repro.core import arch as _arch
    print(extra_line)
    print(f"{'memory':<12}{'total_cyc':>10}{'total_us':>9}")
    for name in COST_MEMORIES:
        a = _arch.get(name)
        c = a.cost(trace)
        print(f"{name:<12}{c.total_cycles:>10}{c.time_us(a.fmax_mhz):>9.2f}")


def run_batch(args, engine, cfg):
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len)).astype(np.int32)
    res = engine.generate(prompts, max_new_tokens=args.new_tokens)
    for b in range(args.batch):
        print(f"req{b}: {res.tokens[b].tolist()}")

    if args.cost:
        from repro.core import arch as _arch
        step = engine.step_trace()
        full = engine.serving_trace()
        print(f"\nserving KV traffic ({engine.n_kv_layers} KV layers, "
              f"page_len={args.page_len}): step {step.n_ops} ops, "
              f"generation {full.n_ops} ops")
        print(f"{'memory':<12}{'step_cyc':>9}{'total_cyc':>10}"
              f"{'total_us':>9}")
        for name in COST_MEMORIES:
            a = _arch.get(name)
            cs, cf = a.cost(step), a.cost(full)
            print(f"{name:<12}{cs.total_cycles:>9}{cf.total_cycles:>10}"
                  f"{cf.time_us(a.fmax_mhz):>9.2f}")


def run_schedule(args, engine, cfg):
    from repro.serving.scheduler import synthesize_requests
    reqs = synthesize_requests(
        args.n_requests, arrival_rate=args.arrival_rate,
        context_dist=args.context_dist, max_seq=engine.max_seq,
        seed=args.seed, vocab_size=cfg.vocab_size)
    plan = None
    if args.fault_bank is not None:
        from repro.runtime import FaultEvent, FaultPlan
        plan = FaultPlan((FaultEvent(tick=args.fault_tick,
                                     kind="bank_offline",
                                     bank=args.fault_bank),))
    res = engine.run_scheduler(reqs, policy=args.policy, fault_plan=plan)
    for r in reqs:
        out = res.outputs[r.rid]
        print(f"req{r.rid} (t={r.arrival} prompt={r.prompt_len} "
              f"new={r.max_new_tokens}): {out.tolist()}")
    s = res.stats
    print(f"\n{res.ticks} ticks, {int(s['decode_ticks'])} decode steps, "
          f"lane occupancy {s['lane_occupancy']:.2f}, bank occupancy skew "
          f"mad={s['bank_mad']:.2f} max/min={s['bank_max_min_ratio']:.2f} "
          f"(policy={args.policy})")
    f = s["faults"]
    if f["degraded"]:
        print(f"faults: bank(s) {f['dead_banks']} lost at tick "
              f"{args.fault_tick}, {f['migrated_pages']} live pages "
              f"migrated; day finished degraded (every request completed)")
    if args.cost:
        trace = (engine.scheduler_stream()
                 .materialize())  # lint: allow-materialize — tiny CLI day
        _cost_table(trace, f"\nscheduler KV traffic ({engine.n_kv_layers} "
                           f"KV layers): {trace.n_ops} ops")
        if f["degraded"]:
            deg = engine.mem_arch.degrade(tuple(f["dead_banks"]))
            c = deg.cost(trace)
            print(f"{deg.name:<12}{c.total_cycles:>10}"
                  f"{c.time_us(deg.fmax_mhz):>9.2f}  (degraded survivors)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mem-arch", default="16B",
                    help="memory architecture the paged-KV pool banks on "
                         "(any repro.core.arch name, e.g. 16B-offset)")
    ap.add_argument("--kv-mode", choices=("paged", "dense"), default="paged")
    ap.add_argument("--page-len", type=int, default=8,
                    help="tokens per KV page")
    ap.add_argument("--cost", action="store_true",
                    help="price the recorded serving trace on the paper "
                         "memories (paged mode only)")
    ap.add_argument("--schedule", action="store_true",
                    help="continuous batching: schedule a synthesized "
                         "multi-tenant day instead of one fixed batch")
    ap.add_argument("--arrival-rate", type=float, default=1.0,
                    help="mean request arrivals per scheduler tick")
    ap.add_argument("--context-dist", default="mixed",
                    help="context-length distribution "
                         "(repro.serving.scheduler.CONTEXT_DISTS)")
    ap.add_argument("--n-requests", type=int, default=8,
                    help="requests in the synthesized day (--schedule)")
    ap.add_argument("--policy", default="seq-skew",
                    help="preferred-bank allocation policy "
                         "(kvcache.ALLOC_POLICIES: paper | seq-skew)")
    ap.add_argument("--fault-bank", type=int, default=None,
                    help="inject a bank-offline fault into the scheduled "
                         "day: this pool bank dies at --fault-tick "
                         "(--schedule only; docs/ROBUSTNESS.md)")
    ap.add_argument("--fault-tick", type=int, default=4,
                    help="scheduler tick the --fault-bank loss fires at")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.fault_bank is not None and not args.schedule:
        ap.error("--fault-bank needs --schedule (fault plans run on the "
                 "continuous-batching scheduler)")
    if args.cost and args.kv_mode != "paged":
        ap.error("--cost needs --kv-mode paged (dense mode records no "
                 "serving traces)")
    if args.schedule and args.kv_mode != "paged":
        ap.error("--schedule needs --kv-mode paged (continuous batching "
                 "lives on the banked page pool)")

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    rc = RunConfig(remat="none", attn_impl="dense")
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, rc, params, NO_AXES, max_batch=args.batch,
                         max_seq=args.prompt_len + args.new_tokens + 4,
                         mem_arch=args.mem_arch, kv_mode=args.kv_mode,
                         page_len=args.page_len)
    if args.schedule:
        run_schedule(args, engine, cfg)
    else:
        run_batch(args, engine, cfg)


if __name__ == "__main__":
    main()
