"""An admission lands a prompt in a fixed number of jitted calls: the
prefill with its first token, the lowering of every attention layer's K/V
to page rows, and one scatter of those rows into every KV pool, the pools
donated (a hybrid adds its SSM slot write).  The landed pools are pinned
bit for bit against one eager ``KV.scatter_pages`` per pool, the calls
per admission against the model's depth, and faulted and resumed days,
which re-prefill and re-lower into donated pools, against the
uninterrupted day's tokens."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_aux
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig, RunConfig
from repro.launch.sharding import NO_AXES
from repro.models import init_tree, model_specs
from repro.runtime import FaultEvent, FaultPlan, telemetry
from repro.serving import kvcache as KV
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request

RC = RunConfig(remat="none", attn_impl="dense")
LM = get_smoke_config("llama3.2-1b")
LANES, MAX_SEQ, PAGE = 2, 64, 8


def _hybrid(n_layers: int) -> ModelConfig:
    """A tiny Jamba: Mamba then attention, ``n_layers // 2`` of each."""
    return ModelConfig(name=f"tiny-jamba-{n_layers}", family="hybrid",
                       n_layers=n_layers, d_model=32, n_heads=4,
                       n_kv_heads=1, d_ff=64, vocab_size=LM.vocab_size,
                       head_dim=8, ssm_state=4, ssm_dt_rank=4,
                       attn_period=2, attn_offset=1, attn_rope=False,
                       ssm_dt_bc_norms=True)


def _model(kind: str, n_kv_layers: int) -> ModelConfig:
    if kind == "attention":
        return dataclasses.replace(LM, n_layers=n_kv_layers)
    return _hybrid(2 * n_kv_layers)


@functools.lru_cache(maxsize=None)
def _engine(kind: str, n_kv_layers: int = 2) -> ServeEngine:
    cfg = _model(kind, n_kv_layers)
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    return ServeEngine(cfg, RC, params, NO_AXES, max_batch=LANES,
                       max_seq=MAX_SEQ, page_len=PAGE)


def _filled_pools(eng: ServeEngine, seed: int = 0) -> dict:
    """Every KV pool and SSM slot filled with noise, so that a row the
    admission must not touch is checked as well as the rows it lands
    (drawn on the device: a buffer that wraps host memory is not
    donated)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    dtype = jnp.dtype(RC.compute_dtype)
    cfg = eng.cfg
    pools = {}
    for j, (kind, _) in enumerate(cfg.block_pattern()):
        for sb in range(cfg.n_superblocks):
            if kind == "attn":
                shapes = {h: ((eng.kv_cfg.n_pages,) + eng.kv_cfg.page_shape,
                              dtype) for h in ("k", "v")}
            else:
                shapes = {"h": ((LANES, cfg.d_inner, cfg.ssm_state),
                                jnp.float32),
                          "conv": ((LANES, cfg.ssm_conv - 1, cfg.d_inner),
                                   dtype)}
            pools[f"b{j}s{sb}"] = {
                name: jax.random.normal(next(keys), shape, dt)
                for name, (shape, dt) in shapes.items()}
    return pools


def _eager_landing(eng: ServeEngine, pools: dict, prompt, ids, lane: int):
    """The reference: the prefill's K/V lowered to page rows per pool and
    landed with one eager ``KV.scatter_pages`` per pool and K/V; the
    prompt's SSM state set into the lane's slot row."""
    kv = eng.kv_cfg
    plen = len(prompt)
    n_pref = -(-plen // kv.page_len)
    logits, cache = eng._prefill(eng.params, jnp.asarray(prompt)[None])
    first = int(jnp.argmax(logits[0, -1, :eng.cfg.vocab_size]))
    ids = jnp.asarray(ids, jnp.int32)
    out = {}
    for j, (kind, _) in enumerate(eng.cfg.block_pattern()):
        bc = cache["blocks"][f"b{j}"]
        for sb in range(eng.cfg.n_superblocks):
            key = f"b{j}s{sb}"
            if kind != "attn":
                out[key] = {name: slot.at[lane].set(
                    bc[name][sb, 0].astype(slot.dtype))
                    for name, slot in pools[key].items()}
                continue
            pair = {}
            for h in ("k", "v"):
                kc = bc[h][sb]                           # (1, t, KV, HD)
                t = kc.shape[1]
                buf = jnp.zeros((1, n_pref * kv.page_len) + kc.shape[2:],
                                kc.dtype)
                buf = buf.at[:, plen - t:plen].set(kc)
                rows = buf.reshape((n_pref,) + kv.page_shape)
                pair[h] = KV.scatter_pages(eng.mem_arch, kv, pools[key][h],
                                           ids, rows)
            out[key] = pair
    return out, first


def _assert_pools_equal(got: dict, want: dict):
    assert got.keys() == want.keys()
    for key in want:
        assert got[key].keys() == want[key].keys()
        for h in want[key]:
            np.testing.assert_array_equal(
                np.asarray(got[key][h]).view(np.uint8),
                np.asarray(want[key][h]).view(np.uint8), err_msg=key + h)


@pytest.mark.parametrize("chunk", [None, 1, 2],
                         ids=["whole", "chunk1", "chunk2"])
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_landed_pools_are_bit_equal_to_one_eager_scatter_per_pool(kind,
                                                                  chunk):
    eng = _engine(kind)
    prompt = np.random.default_rng(1).integers(
        0, eng.cfg.vocab_size, 21).astype(np.int32)  # 3 pages, last partial
    ids = [9, 2, 14]
    lane = 1
    pools = _filled_pools(eng)
    # the engine donates its input: the reference lands on a copy
    want, first_want = _eager_landing(eng, jax.tree.map(jnp.copy, pools),
                                      prompt, ids, lane)
    if chunk is None:
        got, first = eng._ingest_request(pools, prompt, ids, rid=0,
                                         lane=lane)
    else:
        first, rows, state = eng._prefill_rows(prompt)
        got = pools
        for start in range(0, len(ids), chunk):
            got = eng._scatter_rows(got, rows, ids[start:start + chunk],
                                    start)
        if state:
            got = eng._write_slots(got, state, lane)
    assert first == first_want
    _assert_pools_equal(got, want)
    # the KV pools were donated; the hybrid's slots go through their own
    # donated write
    kv_key = next(k for k in pools if k not in eng._ssm_keys)
    assert pools[kv_key]["k"].is_deleted()


def _day(vocab, spec, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=a, prompt_len=p, max_new_tokens=m,
                    tokens=rng.integers(0, vocab, p).astype(np.int32))
            for i, (a, p, m) in enumerate(spec)]


#: (arrival, prompt_len, max_new): prompts of 2-4 pages over two lanes,
#: so lanes are reused and a 1- or 2-page chunk leaves prompts
#: mid-prefill; request 0 stays resident long enough to be corrupted
DAY = ((0, 28, 9), (0, 20, 5), (1, 9, 3), (2, 26, 3), (3, 17, 4))
#: two prompt lengths, a lane reused
SHORT_DAY = ((0, 12, 2), (0, 20, 3), (1, 12, 2))


@pytest.mark.parametrize("kind,most", [("attention", 3), ("hybrid", 4)])
def test_an_admission_dispatches_the_same_calls_at_any_depth(tmp_path, kind,
                                                             most):
    per_admission = {}
    for n_kv in (2, 4):
        eng = _engine(kind, n_kv)
        assert eng.n_kv_layers == n_kv
        telemetry.reset()
        with jax.profiler.trace(str(tmp_path / f"{kind}{n_kv}")):
            eng.run_scheduler(_day(eng.cfg.vocab_size, SHORT_DAY))
        snap = telemetry.snapshot()
        telemetry.reset()
        admits = snap["spans"]["engine.admit"]["count"]
        assert admits == len(SHORT_DAY)
        per_admission[n_kv] = snap["counters"]["engine.admit_dispatches"] \
            / admits
        # the scatter still counts one banked_scatter per pool and K/V
        assert snap["counters"]["engine.scatter_calls"] == \
            2 * n_kv * admits
    assert per_admission[2] == per_admission[4] == most


@pytest.fixture(scope="module")
def baselines():
    """Each model's uninterrupted day, per chunk size."""
    out = {}
    for kind in ("attention", "hybrid"):
        eng = _engine(kind)
        for chunk in (None, 1, 2):
            res = eng.run_scheduler(_day(eng.cfg.vocab_size, DAY),
                                    prefill_chunk_pages=chunk)
            out[kind, chunk] = {rid: np.asarray(v).copy()
                                for rid, v in res.outputs.items()}
    return out


@pytest.mark.parametrize("chunk", [None, 2], ids=["whole", "chunk2"])
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_a_corrupted_page_is_rebuilt_into_donated_pools(baselines, kind,
                                                        chunk):
    """A resident page fails ECC: its line is zeroed, the request is
    re-prefilled through the admission's donated calls and replayed, and
    every token equals the uninterrupted day's."""
    eng = _engine(kind)
    plan = FaultPlan((FaultEvent(tick=5, kind="page_corrupt", rid=0,
                                 page_idx=1),))
    res = eng.run_scheduler(_day(eng.cfg.vocab_size, DAY), fault_plan=plan,
                            prefill_chunk_pages=chunk)
    assert res.stats["faults"]["recoveries"] == 1
    assert res.outputs.keys() == baselines[kind, chunk].keys()
    for rid, want in baselines[kind, chunk].items():
        np.testing.assert_array_equal(res.outputs[rid], want)


@pytest.mark.parametrize("chunk", [1, 2])
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_a_resume_mid_chunked_prefill_lands_the_held_rows(tmp_path,
                                                          baselines, kind,
                                                          chunk):
    """Preempted while prompts are mid-prefill: the resumed day re-lowers
    the held rows and scatters the remaining chunks into the restored,
    donated pools, with the uninterrupted day's tokens."""
    eng = _engine(kind)
    # after tick 0 both lanes hold a prompt with pages still to land
    plan = FaultPlan((FaultEvent(tick=0, kind="preempt"),))
    ck = str(tmp_path / "ck")
    part1 = eng.run_scheduler(_day(eng.cfg.vocab_size, DAY),
                              fault_plan=plan, checkpoint_dir=ck,
                              prefill_chunk_pages=chunk)
    assert part1.preempted
    assert load_aux(ck, latest_step(ck))["sched"]["prefill_next"]
    part2 = eng.run_scheduler(None, fault_plan=plan, resume_from=ck,
                              prefill_chunk_pages=chunk)
    assert not part2.preempted
    assert part2.outputs.keys() == baselines[kind, chunk].keys()
    for rid, want in baselines[kind, chunk].items():
        np.testing.assert_array_equal(part2.outputs[rid], want)
