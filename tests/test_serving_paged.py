"""End-to-end banked paged-KV serving: the ServeEngine decode loop runs all
KV traffic through the registry kernels, matches the dense reference, and
emits AddressTraces whose costs are pinned (ISSUE 3 acceptance gates)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.bench import serving_workload, sweep
from repro.configs import get_smoke_config
from repro.configs.base import RunConfig
from repro.core import arch as A
from repro.launch.sharding import NO_AXES
from repro.models import init_tree, model_specs
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import simulate_serving_trace

RC = RunConfig(remat="none", attn_impl="dense")
CFG = get_smoke_config("llama3.2-1b")
PARAMS = init_tree(model_specs(CFG), jax.random.PRNGKey(0))
PROMPTS = np.random.default_rng(0).integers(
    0, CFG.vocab_size, size=(4, 12)).astype(np.int32)


def _engine(kv_mode, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("page_len", 8)
    return ServeEngine(CFG, RC, PARAMS, NO_AXES, kv_mode=kv_mode, **kw)


def test_paged_generate_matches_dense_reference():
    """Greedy decode through the banked page pools produces exactly the
    dense-cache reference tokens (prompt crosses a page boundary: 12 tokens
    over 8-token pages)."""
    dense = _engine("dense")
    paged = _engine("paged")
    r_d = dense.generate(PROMPTS, max_new_tokens=8)
    r_p = paged.generate(PROMPTS, max_new_tokens=8)
    np.testing.assert_array_equal(r_d.tokens, r_p.tokens)


def test_paged_step_logits_match_dense():
    """Step-by-step logits from the paged decode equal the dense decode to
    float tolerance (same einsums/masks; only the KV storage differs).
    Run in float32 so the bound is tight (bf16 rounds reduction-order
    differences up to ~1%)."""
    rc32 = RunConfig(remat="none", attn_impl="dense",
                     compute_dtype="float32")
    eng = ServeEngine(CFG, rc32, PARAMS, NO_AXES, max_batch=4, max_seq=32,
                      kv_mode="paged", page_len=8)
    plen = PROMPTS.shape[1]
    logits0, cache = eng._prefill(eng.params, jnp.asarray(PROMPTS))
    pools, pages = eng._land_prompts(cache, plen, PROMPTS.shape[0])
    cache_d = eng._pad_cache(cache, plen)
    tok = jnp.argmax(logits0[:, -1, :CFG.vocab_size],
                     axis=-1).astype(jnp.int32)[:, None]
    every_lane = jnp.ones((PROMPTS.shape[0],), bool)
    for i in range(1, 6):
        pos = jnp.asarray(plen + i - 1, jnp.int32)
        ld, cache_d = eng._decode(eng.params, tok, cache_d, pos)
        lane_pos = pages.seq_lens
        pages = eng._grow_pages(pages)
        lp, pools = eng._decode_sched(eng.params, tok, pools,
                                      pages.page_table, lane_pos,
                                      every_lane, 0)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(lp[:, -1], -1)),
            np.asarray(jnp.argmax(ld[:, -1], -1)))
        tok = jnp.argmax(ld[:, -1, :CFG.vocab_size],
                         axis=-1).astype(jnp.int32)[:, None]


def test_prefill_ingest_is_bitexact_across_page_boundary():
    """Pool contents after prefill ingest == the dense prefill cache,
    bit-for-bit, read back through banked_gather (the decode-loop read
    path).  prompt_len=12, page_len=8: the second page is partial."""
    from repro.serving import kvcache as KV
    eng = _engine("paged")
    plen = PROMPTS.shape[1]
    _, cache = eng._prefill(eng.params, jnp.asarray(PROMPTS))
    pools, pages = eng._land_prompts(cache, plen, PROMPTS.shape[0])
    kv = eng.kv_cfg
    n_pref = -(-plen // kv.page_len)
    ids = jnp.maximum(pages.page_table[:, :n_pref], 0).reshape(-1)
    for j, (kind, _) in enumerate(CFG.block_pattern()):
        if kind != "attn":
            continue
        for sb in range(CFG.n_superblocks):
            pool = pools[f"b{j}s{sb}"]
            for side in ("k", "v"):
                got = np.asarray(KV.gather_pages(
                    eng.mem_arch, kv, pool[side], ids)).reshape(
                        PROMPTS.shape[0], n_pref * kv.page_len,
                        kv.kv_heads, kv.head_dim)[:, :plen]
                want = np.asarray(cache["blocks"][f"b{j}"][side][sb])
                np.testing.assert_array_equal(got, want)


def test_step_trace_cost_pinned():
    """The serving-cost acceptance gate: one (arch, batch, context) point's
    decode-step and full-generation cycle counts are pinned, and the live
    engine's trace is identical to the model-free simulated lowering."""
    eng = _engine("paged", mem_arch="16B")
    eng.generate(PROMPTS, max_new_tokens=8)
    step = eng.step_trace()
    full = eng.serving_trace()
    assert A.get("16B").cost(step).total_cycles == 296
    assert A.get("16B").cost(full).total_cycles == 2200
    assert A.get("4R-2W").cost(full).total_cycles == 140
    # live engine trace == simulate_serving_trace on the same point
    sim = simulate_serving_trace("16B", batch=4, prompt_len=12,
                                 decode_steps=7, page_len=8,
                                 n_kv_layers=eng.n_kv_layers, max_seq=32)
    np.testing.assert_array_equal(sim.addrs, full.addrs)
    np.testing.assert_array_equal(sim.kinds, full.kinds)
    np.testing.assert_array_equal(np.asarray(sim.mask),
                                  np.asarray(full.mask))


def test_paged_matches_dense_with_sliding_windows():
    """Local/global sliding-window attention: the pool keeps full history
    and window-masks, the dense path keeps a ring buffer — tokens must
    still agree."""
    cfg = get_smoke_config("gemma2-9b")
    assert cfg.local_global     # the interesting masking case
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    d = ServeEngine(cfg, RC, params, NO_AXES, max_batch=2, max_seq=32,
                    kv_mode="dense")
    p = ServeEngine(cfg, RC, params, NO_AXES, max_batch=2, max_seq=32,
                    kv_mode="paged", page_len=8)
    np.testing.assert_array_equal(
        d.generate(prompts, max_new_tokens=6).tokens,
        p.generate(prompts, max_new_tokens=6).tokens)


@pytest.mark.parametrize("name", ["jamba-v0.1-52b", "falcon-mamba-7b"])
def test_paged_generate_matches_dense_on_ssm_models(name):
    """A model with Mamba layers serves a fixed batch through the same
    step as ``run_scheduler``: each Mamba layer's lane slots start from
    the batch prefill's state and advance every step, and the greedy
    tokens equal the dense reference's."""
    cfg = get_smoke_config(name)
    assert any(kind != "attn" for kind, _ in cfg.block_pattern())
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    d = ServeEngine(cfg, RC, params, NO_AXES, max_batch=2, max_seq=32,
                    kv_mode="dense")
    p = ServeEngine(cfg, RC, params, NO_AXES, max_batch=2, max_seq=32,
                    kv_mode="paged", page_len=8)
    np.testing.assert_array_equal(
        d.generate(prompts, max_new_tokens=6).tokens,
        p.generate(prompts, max_new_tokens=6).tokens)


def test_paged_generate_dispatches_the_scheduler_step():
    """Every decode step of a fixed batch is one call of the jitted
    lane-ragged step that ``run_scheduler`` drives: ``max_new_tokens - 1``
    calls (the first token comes from prefill), each with every lane
    active."""
    eng = _engine("paged")
    calls = []
    step = eng._decode_sched

    def counted(*args):
        calls.append(np.asarray(args[5]))
        return step(*args)

    eng._decode_sched = counted
    eng.generate(PROMPTS, max_new_tokens=7)
    assert len(calls) == 6
    assert all(active.shape == (PROMPTS.shape[0],) and active.all()
               for active in calls)


def test_fixed_batch_step_trace_is_the_all_active_scheduler_step():
    """One decode-step lowering: the fixed batch's ``decode_step_trace``
    with every lane active equals ``scheduler_step_trace`` on the same
    page table and positions, op for op."""
    from repro.serving.kvcache import decode_step_trace
    from repro.serving.scheduler import scheduler_step_trace
    eng = _engine("paged")
    eng.generate(PROMPTS, max_new_tokens=8)
    pt = np.asarray(eng.last_pages.page_table)
    b = pt.shape[0]
    for pos in (12, 16, 18):
        fixed = decode_step_trace(eng.kv_cfg, pt, pos, eng.n_kv_layers)
        sched = scheduler_step_trace(eng.kv_cfg, pt, np.full(b, pos),
                                     np.ones(b, bool), eng.n_kv_layers)
        np.testing.assert_array_equal(fixed.addrs, sched.addrs)
        np.testing.assert_array_equal(fixed.kinds, sched.kinds)
        np.testing.assert_array_equal(np.asarray(fixed.mask),
                                      np.asarray(sched.mask))


def test_dense_mode_has_no_traces():
    eng = _engine("dense")
    eng.generate(PROMPTS, max_new_tokens=4)
    with pytest.raises(RuntimeError):
        eng.step_trace()


def test_paged_requires_banked_arch():
    with pytest.raises(ValueError):
        _engine("paged", mem_arch="4R-2W")


def test_tune_search_over_serving_workload():
    """tune.search ranks the full space on serving traffic; the raw-time
    winner is the multi-port (small traffic — the paper's small-dataset
    regime), while area×time at KV-cache capacity flips to banked (the
    Fig 9 crossover that motivates banked paged-KV serving)."""
    w = serving_workload(batch=4, prompt_len=16, decode_steps=8, page_len=4,
                         n_kv_layers=2)
    ranked = tune.search(workload=w)
    assert len(ranked) == len(tune.PAPER_SPACE.names())
    assert ranked[0].arch == "4R-2W"
    assert all(r.total_cycles > 0 for r in ranked)
    hc = tune.search(workload=w, strategy="hillclimb")
    assert hc[0].arch == ranked[0].arch
    at = tune.search(workload=w, objective="area_time", capacity_kb=256)
    assert at[0].arch.endswith("B") or "-" in at[0].arch  # banked family
    assert at[0].arch in {"4B", "4B-offset", "8B", "8B-offset",
                          "16B", "16B-offset"}
    recs = sweep(["16B", "4R-2W"], w)
    assert {r["arch"] for r in recs} == {"16B", "4R-2W"}
    assert all(r["total_cycles"] > 0 for r in recs)
