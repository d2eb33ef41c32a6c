"""Live serving of Jamba-style hybrids (Mamba-1 + attention) through
``ServeEngine.run_scheduler``: every served token's logits against a plain
float32 forward, across lane reuse, cancel, chunked admission, a prompt
past the SSM chunk, faults and checkpoint/resume; the scheduler's slot
lifecycle; and the attention-only path left as it was."""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig, RunConfig
from repro.launch.sharding import NO_AXES
from repro.models import init_tree, model_specs
from repro.runtime import FaultEvent, FaultPlan
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import (Request, Scheduler,
                                     scheduler_pool_config,
                                     simulate_scheduler_stream)

#: float32 compute, so that the engine and the plain forward differ only
#: in summation order (chunked associative scan against the sequential
#: recurrence, batched against per-sequence matmuls)
RC = RunConfig(remat="none", attn_impl="dense", compute_dtype="float32")
LANES, MAX_SEQ, PAGE = 2, 288, 8


def _hybrid(period: int, n_layers: int) -> ModelConfig:
    """A tiny Jamba: attention at ``i % period == period // 2``, MQA
    without positional encoding, dt/B/C norms in every Mamba mixer."""
    return ModelConfig(
        name=f"tiny-jamba-p{period}", family="hybrid", n_layers=n_layers,
        d_model=32, n_heads=4, n_kv_heads=1, d_ff=64, vocab_size=96,
        head_dim=8, ssm_state=4, ssm_dt_rank=4, attn_period=period,
        attn_offset=period // 2, attn_rope=False, ssm_dt_bc_norms=True,
        norm_eps=1e-6)


CONFIGS = {"period14": _hybrid(14, 14), "period2": _hybrid(2, 4)}
#: leaves ``init_tree`` makes constant (ones / zeros): perturbed, so that
#: every norm weight, bias and decay rate is exercised
_CONSTANT = {"ln1", "ln2", "final_norm", "conv_b", "dt_bias", "A_log",
             "D_skip", "dt_norm", "b_norm", "c_norm"}


def _params(cfg: ModelConfig, seed: int = 0):
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def perturb(path, a):
        a = np.asarray(a, np.float32)
        name = path[-1].key
        if name == "embed":
            a = 0.3 * a
        if name in _CONSTANT:
            a = a + 0.3 * rng.standard_normal(a.shape).astype(np.float32)
        return jax.numpy.asarray(a)

    return jax.tree_util.tree_map_with_path(perturb, params)


@functools.lru_cache(maxsize=None)
def _engine(name: str) -> ServeEngine:
    cfg = CONFIGS[name]
    return ServeEngine(cfg, RC, _params(cfg), NO_AXES, max_batch=LANES,
                       max_seq=MAX_SEQ, page_len=PAGE)


# -- the plain forward ---------------------------------------------------------

def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _mamba(cfg, p, a):
    """Mamba-1 mixer, one token at a time (Jamba: RMSNorm on dt, B, C)."""
    di, n, r, k = cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    u_pre, z = np.split(a @ p["in_proj"], 2, -1)
    s = a.shape[0]
    padded = np.concatenate([np.zeros((k - 1, di), np.float32), u_pre])
    u = _silu(sum(p["conv_w"][i] * padded[i:i + s] for i in range(k))
              + p["conv_b"])
    dt_raw, b, c = np.split(u @ p["x_proj"], [r, r + n], -1)
    dt_raw = _rms(dt_raw, p["dt_norm"], cfg.norm_eps)
    b = _rms(b, p["b_norm"], cfg.norm_eps)
    c = _rms(c, p["c_norm"], cfg.norm_eps)
    delta = np.logaddexp(0.0, dt_raw @ p["dt_proj"] + p["dt_bias"])
    a_mat = -np.exp(p["A_log"])
    h = np.zeros((di, n), np.float32)
    y = np.zeros((s, di), np.float32)
    for t in range(s):
        h = np.exp(delta[t][:, None] * a_mat) * h \
            + (delta[t] * u[t])[:, None] * b[t][None, :]
        y[t] = h @ c[t] + p["D_skip"] * u[t]
    return (y * _silu(z)) @ p["out_proj"]


def _attention(cfg, p, a):
    """Causal multi-query attention, no positional encoding."""
    q = np.einsum("sd,dhk->shk", a, p["wq"])
    k = np.einsum("sd,dhk->shk", a, p["wk"])
    v = np.einsum("sd,dhk->shk", a, p["wv"])
    g = cfg.n_heads // cfg.n_kv_heads
    k, v = np.repeat(k, g, 1), np.repeat(v, g, 1)
    sc = np.einsum("shk,thk->hst", q, k) / np.sqrt(cfg.hd)
    s = a.shape[0]
    sc = np.where(np.tril(np.ones((s, s), bool))[None], sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("shk,hkd->sd", np.einsum("hst,thk->shk", pr, v),
                     p["wo"])


def plain_logits(cfg, params, tokens):
    """(S, vocab) logits at every position: embedding, pre-norm blocks,
    SwiGLU, tied unembedding, in float32 numpy."""
    f32 = functools.partial(np.asarray, dtype=np.float32)
    emb = f32(params["embed"])[:cfg.vocab_size]
    x = emb[np.asarray(tokens)]
    period = cfg.attn_period
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda w: f32(w[i // period]),
                         params["blocks"][f"b{i % period}"])
        a = _rms(x, p["ln1"], cfg.norm_eps)
        attn = i % period == cfg.attn_offset
        x = x + (_attention if attn else _mamba)(cfg, p["mixer"], a)
        a = _rms(x, p["ln2"], cfg.norm_eps)
        f = p["ffn"]
        x = x + (_silu(a @ f["w1"]) * (a @ f["w3"])) @ f["w2"]
    return _rms(x, f32(params["final_norm"]), cfg.norm_eps) @ emb.T


# -- recording what the engine served ---------------------------------------------

class _Recorder(Scheduler):
    """A ``Scheduler`` that notes each tick's admissions and each decoded
    tick's lane -> request map, and cancels ``cancel=(tick, rid)``."""

    def __init__(self, *a, cancel=None, **kw):
        super().__init__(*a, **kw)
        self.cancel_at = cancel
        self.admitted: list = []
        self.decode_rids: list = []

    def run(self, requests=None):
        if requests is not None:
            self.submit(requests)
        while not self.done():
            if self.cancel_at and self.now == self.cancel_at[0]:
                self.cancel(self.cancel_at[1])
            ev = self.tick()
            self.admitted += [a.request.rid for a in ev.admitted]
            if ev.decoded:
                self.decode_rids.append(self.lane_rid.copy())
            yield ev


def _served_logits(eng, reqs, chunk=None, cancel=None):
    """Run a day; return (outputs, [(rid, position, logits)]) with the
    logits of every served token, prefill's first token included."""
    prefill, decode = [], []
    run_prefill, run_decode = eng._prefill_first, eng._decode_sched

    def rec_prefill(params, toks):
        # the admission's call returns the first token alone: its logits
        # are the same prefill's, run again, and the token their argmax
        first, cache = run_prefill(params, toks)
        logits = np.asarray(eng._prefill(params, toks)[0][0, -1])
        assert int(first) == int(np.argmax(logits[:eng.cfg.vocab_size]))
        prefill.append(logits)
        return first, cache

    def rec_decode(params, tok, pools, page_table, pos, active, scratch):
        logits, pools = run_decode(params, tok, pools, page_table, pos,
                                   active, scratch)
        decode.append((np.asarray(logits[:, 0]), np.asarray(pos),
                       np.asarray(active)))
        return logits, pools

    sched = _Recorder(eng.kv_cfg, n_lanes=LANES, max_seq=MAX_SEQ,
                      n_kv_layers=eng.n_kv_layers,
                      n_ssm_layers=eng.n_ssm_layers,
                      prefill_chunk_pages=chunk, cancel=cancel)
    eng._prefill_first, eng._decode_sched = rec_prefill, rec_decode
    try:
        res = eng.run_scheduler(reqs, scheduler=sched)
    finally:
        eng._prefill_first, eng._decode_sched = run_prefill, run_decode
    plen = {r.rid: r.prompt_len for r in reqs}
    served = [(rid, plen[rid] - 1, lg)
              for rid, lg in zip(sched.admitted, prefill)]
    for (logits, pos, active), rids in zip(decode, sched.decode_rids):
        served += [(int(rids[lane]), int(pos[lane]), logits[lane])
                   for lane in np.flatnonzero(active)]
    return res.outputs, served


def _requests(spec, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=a, prompt_len=p, max_new_tokens=m,
                    tokens=rng.integers(0, vocab, p).astype(np.int32))
            for i, (a, p, m) in enumerate(spec)]


#: (arrival, prompt_len, max_new): four requests over two lanes, so both
#: lanes are reused; 261 is past the 256-token SSM chunk and no multiple
#: of it; 5 and 9 are off page boundaries
DAY = ((0, 12, 4), (0, 261, 5), (1, 5, 6), (2, 9, 4))


@pytest.mark.parametrize("reuse", ["completion", "cancel"])
@pytest.mark.parametrize("chunk", [None, 2], ids=["whole", "chunked"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_served_logits_match_the_plain_forward(name, chunk, reuse):
    eng = _engine(name)
    cfg = eng.cfg
    reqs = _requests(DAY, cfg.vocab_size)
    # request 0 leaves its lane mid-decode: the next queued request takes
    # over a lane whose SSM slots hold another request's state
    cancel = (2, 0) if reuse == "cancel" else None
    outputs, served = _served_logits(eng, reqs, chunk=chunk, cancel=cancel)
    for r in reqs:
        n = len(outputs[r.rid])
        if r.rid == 0 and cancel:
            assert 1 <= n < r.max_new_tokens
        else:
            assert n == r.max_new_tokens
    assert len(served) == sum(len(v) for v in outputs.values())
    ref = {r.rid: plain_logits(
        cfg, eng.params, np.concatenate([r.tokens, outputs[r.rid][:-1]]))
        for r in reqs}
    for rid, pos, logits in served:
        want = ref[rid][pos]
        # float32 on both sides: the widest difference, in units of the
        # logits' spread, is 7.9e-5 with 13 Mamba layers behind the
        # 261-token prompt and 4.3e-6 with 2; 1e-3 leaves room for
        # summation order, while a slot left stale moves them by 4-5
        gap = np.abs(logits[:cfg.vocab_size] - want).max() / want.std()
        assert gap < 1e-3, (rid, pos, gap)


def test_lane_reuse_serves_the_tokens_of_a_fresh_engine():
    eng = _engine("period2")
    reqs = _requests(DAY, eng.cfg.vocab_size, seed=1)
    day = eng.run_scheduler(reqs).outputs
    for r in reqs[2:]:                 # both served on a reused lane
        fresh = ServeEngine(eng.cfg, RC, eng.params, NO_AXES,
                            max_batch=LANES, max_seq=MAX_SEQ, page_len=PAGE)
        alone = fresh.run_scheduler([r]).outputs[r.rid]
        np.testing.assert_array_equal(day[r.rid], alone)


# -- faults, preemption and resume -----------------------------------------------

#: a bank loss, a page corruption of a resident request while the other
#: lane's request is mid-decode (its replay must leave that lane's slots
#: as they are) and transient decode faults on one day
CHAOS = FaultPlan((FaultEvent(tick=2, kind="bank_offline", bank=1),
                   FaultEvent(tick=3, kind="page_corrupt", rid=0,
                              page_idx=0),
                   FaultEvent(tick=4, kind="decode_transient", failures=2)))
SHORT_DAY = ((0, 12, 8), (0, 5, 6), (1, 8, 4), (2, 9, 5))


@pytest.fixture(scope="module")
def short_engine():
    cfg = CONFIGS["period2"]
    return ServeEngine(cfg, RC, _params(cfg), NO_AXES, max_batch=LANES,
                       max_seq=32, page_len=PAGE, mem_arch="16B-xor")


@pytest.fixture(scope="module")
def baseline(short_engine):
    reqs = _requests(SHORT_DAY, short_engine.cfg.vocab_size)
    return {k: v.copy() for k, v in
            short_engine.run_scheduler(reqs).outputs.items()}


def test_a_chaos_day_rebuilds_the_slots_and_keeps_every_token(
        short_engine, baseline):
    eng = short_engine
    reqs = _requests(SHORT_DAY, eng.cfg.vocab_size)
    res = eng.run_scheduler(reqs, fault_plan=CHAOS)
    assert res.stats["faults"]["recoveries"] == 1
    assert res.stats["faults"]["migrated_pages"] > 0
    for r in reqs:
        np.testing.assert_array_equal(res.outputs[r.rid], baseline[r.rid])
    # the live trace is the KV traffic of the attention layers only
    live = eng.scheduler_stream().materialize()
    sim = simulate_scheduler_stream(
        eng.mem_arch, reqs, n_lanes=LANES, max_seq=32, page_len=PAGE,
        n_kv_layers=eng.n_kv_layers, fault_plan=CHAOS).materialize()
    np.testing.assert_array_equal(live.addrs, sim.addrs)
    np.testing.assert_array_equal(np.asarray(live.mask),
                                  np.asarray(sim.mask))


@pytest.mark.parametrize("chunk", [None, 1], ids=["whole", "chunked"])
def test_checkpoint_resume_serves_identical_tokens(short_engine, baseline,
                                                   tmp_path, chunk):
    eng = short_engine
    reqs = _requests(SHORT_DAY, eng.cfg.vocab_size)
    plan = FaultPlan((FaultEvent(tick=4, kind="preempt"),))
    full = eng.run_scheduler(reqs, prefill_chunk_pages=chunk).outputs
    ck = str(tmp_path / "ck")
    part = eng.run_scheduler(reqs, fault_plan=plan, checkpoint_dir=ck,
                             prefill_chunk_pages=chunk)
    assert part.preempted
    rest = eng.run_scheduler(None, fault_plan=plan, resume_from=ck,
                             prefill_chunk_pages=chunk)
    for r in reqs:
        np.testing.assert_array_equal(rest.outputs[r.rid], full[r.rid])
    if chunk is None:
        for r in reqs:
            np.testing.assert_array_equal(full[r.rid], baseline[r.rid])


# -- the scheduler's slot lifecycle ------------------------------------------------

def _sched(**kw):
    cfg = scheduler_pool_config("16B", 2, 32, page_len=8)
    return Scheduler(cfg, n_lanes=2, max_seq=32, n_kv_layers=1, **kw)


def test_a_slot_lives_from_admission_to_completion_or_cancel():
    sched = _sched(n_ssm_layers=3)
    sched.submit(_requests(((0, 5, 3), (0, 9, 6), (0, 4, 2)), 50))
    sched.tick()
    assert sched.ssm_slot_live.tolist() == [True, True]
    sched.cancel(1)
    sched.tick()                       # rid 1 leaves lane 1, rid 2 enters
    assert sched.ssm_slot_live.tolist() == [True, True]
    assert sched.lane_rid.tolist() == [0, 2]
    while not sched.done():
        sched.tick()
    assert not sched.ssm_slot_live.any()


def test_a_chunked_admission_takes_its_slot_with_the_last_chunk():
    sched = _sched(n_ssm_layers=1, prefill_chunk_pages=1)
    sched.submit(_requests(((0, 20, 3),), 50))   # three prompt pages
    live = []
    for _ in range(3):
        sched.tick()
        live.append(bool(sched.ssm_slot_live[0]))
    assert live == [False, False, True]


def test_bank_loss_and_checkpoint_keep_the_slots():
    plan = FaultPlan((FaultEvent(tick=1, kind="bank_offline", bank=1),))
    sched = _sched(n_ssm_layers=2, fault_plan=plan)
    sched.submit(_requests(((0, 12, 6), (0, 20, 6)), 50))
    sched.tick()
    before = sched.ssm_slot_live.copy()
    ev = sched.tick()
    assert ev.migrations and (sched.ssm_slot_live == before).all()
    again = _sched(n_ssm_layers=2, fault_plan=plan)
    again.load_state(sched.state_dict())
    np.testing.assert_array_equal(again.ssm_slot_live, sched.ssm_slot_live)


def test_an_attention_only_scheduler_has_no_live_slot():
    sched = _sched()
    sched.submit(_requests(((0, 5, 3),), 50))
    sched.tick()
    assert sched.n_ssm_layers == 0 and not sched.ssm_slot_live.any()


def test_run_scheduler_refuses_a_scheduler_without_the_slots():
    eng = _engine("period2")
    sched = Scheduler(eng.kv_cfg, n_lanes=LANES, max_seq=MAX_SEQ,
                      n_kv_layers=eng.n_kv_layers)
    with pytest.raises(ValueError, match="SSM layers"):
        eng.run_scheduler(_requests(((0, 5, 2),), 96), scheduler=sched)


# -- the attention-only path -------------------------------------------------------

def test_an_attention_only_day_allocates_no_slot_and_steps_as_before():
    cfg = get_smoke_config("llama3.2-1b")
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, RunConfig(remat="none", attn_impl="dense"),
                      params, NO_AXES, max_batch=4, max_seq=32, page_len=8)
    assert eng.n_ssm_layers == 0
    calls = []
    step = eng._decode_sched

    def record(*args):
        calls.append(jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                                  args[1:]))
        return step(*args)

    eng._decode_sched = record
    eng.run_scheduler(_requests(((0, 12, 4), (0, 5, 3)), cfg.vocab_size))
    kv = eng.kv_cfg
    pool = ((kv.n_pages,) + kv.page_shape, "bfloat16")
    n_pt = 32 // 8
    # the step's arguments as chip_smoke.py compiles them: token,
    # K/V pools of every layer and nothing else, page table, positions,
    # active mask, scratch page
    want = (((4, 1), "int32"),
            {f"b{j}s{sb}": {"k": pool, "v": pool}
             for j, _ in enumerate(cfg.block_pattern())
             for sb in range(cfg.n_superblocks)},
            ((4, n_pt), "int32"), ((4,), "int32"), ((4,), "bool"),
            ((), "int32"))
    assert calls and all(c == want for c in calls)
