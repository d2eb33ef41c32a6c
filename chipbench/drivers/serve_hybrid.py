"""Driver of the hybrid serving cells: a Jamba-style model (Mamba-1 mixers
with attention every ``attn_layer_period`` layers) decoding a closed loop
of requests through ``ServeEngine.run_scheduler``, the path users run.

It is ``drivers/serve.py`` with another model: the window scheduler, the
requests, the warm-up, the spans and the sample come from there.  What
differs: the ``ModelConfig`` (no positional encoding, RMSNorms on the
mixer's dt, B and C), the weights (``weights_jamba.py``), the scheduler,
which carries one SSM state slot per lane in every Mamba layer, the plain
reference (``reference_jamba.py``), and what the per-layer readers read:
the bytes one decode step must move (``hybrid_roofline.py``).
"""
from __future__ import annotations

from pathlib import Path

import harness
import hybrid_roofline
import reference_jamba
import weights_jamba
from harness import Check, RunResult, SetupError
from stats import percentile, rate, token_gaps

serve = harness.driver("serve", Path(__file__).resolve().parents[1])


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Jamba configuration file."""
    from repro.configs.base import ModelConfig
    if "ssm_dt_bc_norms" not in ModelConfig.__dataclass_fields__:
        raise SetupError("this program has no Jamba mixer (ModelConfig "
                         "lacks ssm_dt_bc_norms / attn_rope)")
    if int(conf["num_experts"]) != 1 or conf.get("sliding_window") \
            or conf["mamba_proj_bias"] or not conf["mamba_conv_bias"] \
            or not conf["tie_word_embeddings"]:
        raise SetupError(f"{conf['name']}: only a dense, tied, full-"
                         f"attention Jamba with conv bias and no projection "
                         f"bias is served here")
    s = weights_jamba.sizes(conf)
    return ModelConfig(
        name=conf["name"], family="hybrid", n_layers=s["layers"],
        d_model=s["d"], n_heads=s["h"], n_kv_heads=s["kv"], d_ff=s["f"],
        vocab_size=s["vocab"], head_dim=s["hd"], attn_rope=False,
        ssm_state=s["n"], ssm_expand=int(conf["mamba_expand"]),
        ssm_conv=s["k"], ssm_dt_rank=s["r"], ssm_dt_bc_norms=True,
        attn_period=s["period"], attn_offset=s["offset"],
        norm_eps=float(conf["rms_norm_eps"]), tie_embeddings=True)


def build(conf: dict, seed: int):
    """(engine, params) for a configuration, weights from the seed."""
    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.serving.engine import ServeEngine
    dep = conf["deployment"]
    cfg = model_config(conf)
    params = weights_jamba.init_params(conf, seed, dep["dtype"])
    serve.check_layout(cfg, params)
    rc = RunConfig(param_dtype=dep["dtype"], compute_dtype=dep["dtype"],
                   remat="none", attn_impl=dep["attn_impl"])
    engine = ServeEngine(cfg, rc, params, NO_AXES,
                         max_batch=int(dep["lanes"]),
                         max_seq=int(dep["max_seq"]),
                         mem_arch=dep["mem_arch"], kv_mode="paged",
                         page_len=int(dep["page_len"]))
    return engine, params


def requests_of(conf: dict, traffic: dict, seed: int):
    """``serve.requests_of`` for a file that holds the published config's
    keys at its top level."""
    return serve.requests_of({"config": conf}, traffic, seed)


def serve_window(engine, reqs, seconds: float, spans):
    """One closed-loop day over ``reqs``; returns (scheduler, result)."""
    cls = serve.make_scheduler_class()
    sched = cls(engine.kv_cfg, n_lanes=engine.max_batch,
                max_seq=engine.max_seq, policy="seq-skew",
                n_kv_layers=engine.n_kv_layers,
                n_ssm_layers=engine.n_ssm_layers, seconds=seconds,
                spans=spans)
    res = engine.run_scheduler(reqs, scheduler=sched)
    return sched, res


def run(cell, seed: int, seconds: float, trace: bool, env) -> RunResult:
    conf, traffic = cell.config, cell.traffic
    engine, params = build(conf, seed)
    reqs = requests_of(conf, traffic, seed)
    serve.warm(engine, conf, traffic)
    serve.instrument(engine, env.spans, sync=trace)
    if trace:
        env.start_profile()
    env.mark_setup_done()
    sched, res = serve_window(engine, reqs, seconds, env.spans)
    if trace:
        env.stop_profile()
    if sched.t_close is None:
        raise SetupError("the backlog ran out before the window closed; "
                         "the mix needs more requests")
    window = sched.t_close - sched.t_start
    gaps = token_gaps(sched.tokens)
    peak = env.read_memory_peak()
    e2e = {"serve_tokens_per_s": rate(len(sched.tokens), window),
           "serve_itl_p90_ms": percentile(gaps, 90) * 1e3,
           "serve_peak_hbm_gb": peak / 1e9}
    values = {"decode_step_bytes": hybrid_roofline.step_bytes(conf)}
    picked = serve.sample(reqs, res, traffic, seed, sched.lane_of)
    lanes = len({sched.lane_of[r.rid] for r, _ in picked})
    admitted = sched.admitted
    # the program's state is freed before the reference runs on the device
    del engine, sched, res
    seqs = [(r.tokens, out) for r, out in picked]
    limit = float(conf["correct"]["max_logit_gap"])
    results = reference_jamba.gaps(conf, params, seqs,
                                   int(conf["deployment"]["max_seq"]),
                                   control=env.control)
    n_served = sum(len(out) for _, out in seqs)
    notes = {"sampled_requests": len(picked), "sampled_tokens": n_served,
             "sampled_lanes": lanes,
             "itl_ms": {f"p{q}": percentile(gaps, q) * 1e3
                        for q in (50, 95, 99)}}
    # the control puts the float8 reference's first choices in the served
    # tokens' place, and the same checks decide ``correct``
    pick = 1 if env.control else 0
    if env.control:
        notes["program_max_logit_gap"] = max(
            (g for g, _ in results), default=float("inf"))
    widest = max((r[pick] for r in results), default=float("inf"))
    checks = [Check("max_logit_gap", widest, limit),
              Check("sampled_tokens_short",
                    float(max(0, int(traffic["check"]["min_tokens"])
                              - n_served)), 0.0)]
    return RunResult(end_to_end=e2e, checks=checks, attempted=admitted,
                     failed=sum(r[pick] > limit for r in results),
                     readings=values, notes=notes)
