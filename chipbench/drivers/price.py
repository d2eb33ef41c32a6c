"""Driver of the pricing cells: ``core/cost_engine.cost_many`` pricing a
whole-model decode step (``models/trace.model_step_trace``) over a list of
memories, the path an explorer or the autotuner runs.

A mix either builds a fresh step every pass (routing from the seed and the
pass index: host construction is part of the work) or builds and
materializes one step in set-up and prices it again every pass.  Set-up
prices one step first, which compiles every block shape the passes use.
The window runs whole passes back to back until ``--seconds`` have passed
and ends with the last of them; the rate counts every op priced under
every memory over the whole window.  Afterwards a seeded sample of the passes (every pass, when
they all priced one step) is compared, field by field and memory by
memory, with the plain reference, which must agree exactly.
"""
from __future__ import annotations

import time

import reference_price
import traffic as traffic_mod
from harness import Check, RunResult
from stats import rate


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    c = conf["config"]
    experts = int(c.get("num_local_experts", 0))
    return ModelConfig(
        name=conf["name"], family="moe" if experts else "dense",
        n_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        n_experts=experts, experts_per_token=int(
            c.get("num_experts_per_tok", 0)),
        capacity_factor=float(conf["deployment"]["capacity_factor"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)))


class Timed:
    """A trace whose block construction is timed as ``construct`` spans
    (the time spent inside the trace's own block generator)."""

    def __init__(self, trace, spans):
        self.trace, self.spans, self.meta = trace, spans, trace.meta

    def blocks(self, block_ops=None):
        it = iter(self.trace.blocks(block_ops))
        while True:
            with self.spans("construct"):
                blk = next(it, None)
            if blk is None:
                return
            yield blk


def instrument(spans):
    """Spans around the engine's per-block device dispatch and host fold
    (module attributes the engine looks up at call time); returns the
    function that puts the engine's own back."""
    from repro.core import cost_engine
    dispatch, fold = cost_engine._block_kind_cycles, cost_engine._fold

    def traced_dispatch(*a, **k):
        with spans("dispatch"):
            return dispatch(*a, **k)

    def traced_fold(*a, **k):
        with spans("fold"):
            return fold(*a, **k)

    cost_engine._block_kind_cycles = traced_dispatch
    cost_engine._fold = traced_fold

    def restore():
        cost_engine._block_kind_cycles, cost_engine._fold = dispatch, fold
    return restore


def run(cell, seed: int, seconds: float, trace: bool, env) -> RunResult:
    from repro.core import arch as arch_mod
    from repro.core.cost_engine import cost_many
    from repro.models.trace import model_step_trace
    conf, traffic = cell.config, cell.traffic
    cfg = model_config(conf)
    archs = [arch_mod.get(m) for m in traffic["memories"]]
    block_ops = int(traffic["block_ops"])
    fresh = bool(traffic["fresh_trace_per_pass"])
    spans = env.spans

    def build(routing_seed: int):
        with spans("construct"):
            return model_step_trace(
                cfg, traffic["page_map"], batch=int(traffic["batch"]),
                prompt_len=int(traffic["position"]),
                page_len=int(traffic["page_len"]), block_ops=block_ops,
                seed=routing_seed)

    seeds = traffic_mod.pricing_passes(traffic, seed)
    recorded = None if fresh else build(next(seeds)).materialize()
    cost_many(archs, recorded if recorded is not None
              else build(traffic_mod.derived_seed(seed, 4)),
              block_ops=block_ops)
    if trace:
        restore = instrument(spans)
        env.start_profile()
    env.mark_setup_done()
    passes = []                               # (routing seed, costs)
    t0 = time.perf_counter()
    with spans("window"):
        for rs in seeds:
            with spans("pass"):
                step = recorded if recorded is not None else Timed(
                    build(rs), spans)
                passes.append((rs, cost_many(archs, step,
                                             block_ops=block_ops)))
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
    if trace:
        env.stop_profile()
        restore()
    env.read_memory_peak()
    window = t - t0
    work = sum((c[0].n_load_ops + c[0].n_store_ops + c[0].n_tw_ops)
               * len(c) for _, c in passes)
    e2e = {"price_op_archs_per_s": rate(work, window)}
    if fresh:
        rng = traffic_mod.seed_stream(seed, 5)
        k = min(int(traffic["check"]["sample_passes"]), len(passes))
        picked = [passes[i] for i in sorted(rng.choice(len(passes), k,
                                                       replace=False))]
    else:
        picked = passes
    refs: dict = {}
    controls: dict = {}
    bad, failed, program_bad = 0, 0, 0
    for rs, costs in picked:
        if rs not in refs:
            refs[rs] = reference_price.price(conf, traffic, rs,
                                             traffic["memories"])
            if env.control:
                controls[rs] = reference_price.price(
                    conf, traffic, rs, traffic["memories"], drop_masks=True)
        n = reference_price.mismatches(costs, refs[rs])
        program_bad += n
        if env.control:
            # the control's costs stand in for the pass's, and the same
            # comparison decides ``correct``
            n = reference_price.mismatches(controls[rs], refs[rs])
        bad += n
        failed += n > 0
    limit = float(conf["correct"]["mismatched_fields"])
    values = {"passes": len(passes)}
    notes = {"checked_passes": len(picked)}
    if env.control:
        notes["program_mismatched_fields"] = program_bad
    return RunResult(end_to_end=e2e,
                     checks=[Check("mismatched_fields", bad, limit)],
                     attempted=len(passes), failed=failed, readings=values,
                     notes=notes)
