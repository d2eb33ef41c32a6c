"""The program's own spans and counters (``repro.runtime.telemetry``):
recorded only under a profiler, nested by context, written into the
profiler's trace; and the instrumented paths (scheduler tick, admission,
trace construction, ``cost_many``) count what they did without changing a
single result."""
import glob
import itertools
import os
import time
import tracemalloc

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_smoke_config
from repro.configs.base import RunConfig
from repro.core import cost_engine
from repro.core.cost_engine import cost_many
from repro.launch.sharding import NO_AXES
from repro.models import init_tree, model_specs
from repro.models.trace import _decode_point, model_step_trace
from repro.runtime import telemetry
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import (Request, Scheduler,
                                     scheduler_pool_config)

LM = get_smoke_config("llama3.2-1b")
MOE = get_smoke_config("mixtral-8x22b")
#: (arrival, prompt_len, max_new): staggered arrivals, a page-boundary
#: prompt, a zero-new-token request, more requests than lanes
TRAFFIC = ((0, 12, 8), (0, 5, 6), (1, 8, 4), (2, 3, 0), (2, 9, 5),
           (3, 12, 3))


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _requests(tokens=True):
    rng = np.random.default_rng(0)
    return [Request(rid=i, arrival=a, prompt_len=p, max_new_tokens=m,
                    tokens=(rng.integers(0, LM.vocab_size, p)
                            .astype(np.int32) if tokens else None))
            for i, (a, p, m) in enumerate(TRAFFIC)]


def _busy(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


# -- the registry --------------------------------------------------------------

def test_nothing_is_recorded_while_the_profiler_is_off():
    assert not TraceAnnotation.is_enabled()
    with telemetry.span("outer", rid=1):
        with telemetry.span("inner"):
            telemetry.count("n", 5)
    assert telemetry.snapshot() == {"spans": {}, "counters": {}}
    assert telemetry.records() == []


def test_a_disabled_span_is_one_shared_context_and_allocates_nothing():
    assert telemetry.span("a") is telemetry.span("b")

    def spans(n):
        for _ in itertools.repeat(None, n):
            with telemetry.span("x"):
                telemetry.count("c")

    def peak_of(n):
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        spans(n)
        current, peak = tracemalloc.get_traced_memory()
        assert current == base
        return peak - base

    spans(10)
    tracemalloc.start()
    try:
        # the call's own frame is all there is: no allocation per span
        assert peak_of(10_000) == peak_of(1)
    finally:
        tracemalloc.stop()


def test_nested_spans_record_parents_self_time_attrs_and_counters(
        tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert TraceAnnotation.is_enabled()
        with telemetry.span("admit", rid=7):
            _busy(0.02)
            with telemetry.span("prefill"):
                _busy(0.03)
            with telemetry.span("scatter"):
                _busy(0.01)
            telemetry.count("calls", 4)
        with telemetry.span("admit", rid=8):
            telemetry.count("calls", 4)
        telemetry.count("other")
    recs = telemetry.records()
    assert [r.name for r in recs] == ["admit", "prefill", "scatter",
                                      "admit"]
    assert [r.parent for r in recs] == [None, 0, 0, None]
    assert [r.attrs for r in recs] == [{"rid": 7}, {}, {}, {"rid": 8}]
    snap = telemetry.snapshot()
    assert snap["counters"] == {"calls": 8, "other": 1}
    admit, prefill = snap["spans"]["admit"], snap["spans"]["prefill"]
    assert admit["count"] == 2 and prefill["count"] == 1
    assert prefill["self_s"] == prefill["total_s"] >= 0.03
    children = prefill["total_s"] + snap["spans"]["scatter"]["total_s"]
    assert admit["self_s"] == pytest.approx(admit["total_s"] - children)
    assert 0.02 <= admit["self_s"] < admit["total_s"]


def test_span_names_land_in_the_written_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("engine.admit", rid=3):
            with telemetry.span("engine.prefill"):
                pass
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    names = {ev.name for plane in ProfileData.from_file(files[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"engine.admit", "engine.prefill"} <= names


def test_open_spans_are_left_out_until_they_close(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                pass
            assert [(r.name, r.parent) for r in telemetry.records()] == [
                ("inner", None)]
            assert set(telemetry.snapshot()["spans"]) == {"inner"}
    assert [(r.name, r.parent) for r in telemetry.records()] == [
        ("outer", None), ("inner", 0)]


def test_reset_clears_spans_and_counters(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("a"):
            telemetry.count("n")
        assert telemetry.snapshot()["spans"]
        telemetry.reset()
        assert telemetry.snapshot() == {"spans": {}, "counters": {}}
        with telemetry.span("b"):
            pass
    assert set(telemetry.snapshot()["spans"]) == {"b"}
    assert telemetry.snapshot()["counters"] == {}


# -- the instrumented paths ----------------------------------------------------

def test_a_scheduler_day_counts_its_ticks_and_lowered_traces(tmp_path):
    cfg = scheduler_pool_config("16B", 4, 32, page_len=8)
    sched = Scheduler(cfg, n_lanes=4, max_seq=32, n_kv_layers=2)
    with jax.profiler.trace(str(tmp_path)):
        events = list(sched.run(_requests(tokens=False)))
    spans = telemetry.snapshot()["spans"]
    assert spans["sched.tick"]["count"] == len(events)
    assert spans["sched.lower"]["count"] == sum(len(ev.traces)
                                                for ev in events)
    assert spans["sched.alloc"]["count"] >= len(TRAFFIC)
    parents = {telemetry.records()[r.parent].name
               for r in telemetry.records()
               if r.name in ("sched.lower", "sched.alloc")}
    assert parents == {"sched.tick"}


def test_decode_point_counts_its_allocator_calls(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        *_, page_table, _ = _decode_point(MOE, "16B", batch=2,
                                          prompt_len=16, page_len=8)
    snap = telemetry.snapshot()
    # two prompt pages, then the decode step's page (16 is on a boundary)
    assert snap["counters"]["trace.alloc_calls"] == 3
    # all three rounds run in one jitted device call
    assert snap["counters"]["trace.alloc_dispatches"] == 1
    assert ((page_table >= 0).sum(axis=1) == 3).all()
    assert snap["spans"]["trace.alloc"]["count"] == 1


def test_cost_many_counts_real_and_padded_ops_and_prices_identically(
        tmp_path, monkeypatch):
    archs = ["16B", "4R-1W", "8B-xor-bcast"]
    step = model_step_trace(MOE, "16B", batch=2, prompt_len=16,
                            block_ops=64)
    off = cost_many(archs, step, block_ops=100)
    sizes = []
    pad = cost_engine._pad_ops

    def recorded(addrs, mask, kinds):
        out = pad(addrs, mask, kinds)
        sizes.append(out[0].shape[0])
        return out

    monkeypatch.setattr(cost_engine, "_pad_ops", recorded)
    with jax.profiler.trace(str(tmp_path)):
        on = cost_many(archs, step, block_ops=100)
    assert on == off
    snap = telemetry.snapshot()
    n_ops = off[0].n_load_ops + off[0].n_store_ops + off[0].n_tw_ops
    assert snap["counters"]["cost.ops"] == n_ops
    assert snap["counters"]["cost.padded_ops"] == sum(sizes)
    assert sum(sizes) > n_ops                 # 100-op batches pad to 128
    spans = snap["spans"]
    assert spans["cost.many"]["count"] == 1
    for name in ("cost.pad", "cost.transfer", "cost.dispatch"):
        assert spans[name]["count"] == len(sizes)
    assert spans["cost.coalesce"]["count"] >= 1
    assert spans["cost.fold"]["count"] >= 1
    assert spans["cost.blocks"]["count"] > spans["cost.count"]["count"] > 0
    inside = sum(spans[n]["total_s"] for n in spans if n != "cost.many")
    assert inside <= spans["cost.many"]["total_s"]


def test_cost_many_with_a_cache_folds_its_misses_under_a_span(tmp_path):
    step = model_step_trace(MOE, "16B", batch=2, prompt_len=16,
                            block_ops=64)
    off = cost_many(["16B"], step, cache=cost_engine.BlockCostCache())
    cache = cost_engine.BlockCostCache()
    with jax.profiler.trace(str(tmp_path)):
        on = cost_many(["16B"], step, cache=cache)
    assert on == off
    snap = telemetry.snapshot()
    assert snap["spans"]["cost.fold"]["count"] >= 1
    # only the cache's misses are dispatched
    assert snap["spans"]["cost.dispatch"]["count"] == cache.misses
    assert snap["counters"]["cost.ops"] <= (
        off[0].n_load_ops + off[0].n_store_ops + off[0].n_tw_ops)


@pytest.mark.parametrize("chunk", [None, 1])
def test_run_scheduler_serves_identical_tokens_traced_and_counts_admissions(
        tmp_path, chunk):
    params = init_tree(model_specs(LM), jax.random.PRNGKey(0))
    eng = ServeEngine(LM, RunConfig(remat="none", attn_impl="dense"),
                      params, NO_AXES, max_batch=4, max_seq=32,
                      kv_mode="paged", page_len=8)
    off = eng.run_scheduler(_requests(), prefill_chunk_pages=chunk)
    with jax.profiler.trace(str(tmp_path)):
        on = eng.run_scheduler(_requests(), prefill_chunk_pages=chunk)
    assert on.outputs.keys() == off.outputs.keys()
    for rid in off.outputs:
        np.testing.assert_array_equal(on.outputs[rid], off.outputs[rid])
    snap = telemetry.snapshot()
    spans, recs = snap["spans"], telemetry.records()
    admits = [r for r in recs if r.name == "engine.admit"]
    assert sorted(r.attrs["rid"] for r in admits) == list(
        range(len(TRAFFIC)))
    assert {recs[r.parent].name for r in recs
            if r.name == "engine.prefill"} == {"engine.admit"}
    assert {recs[r.parent].name for r in recs
            if r.name == "engine.rows"} == {"engine.prefill"}
    assert spans["engine.readback"]["count"] == on.stats["decode_ticks"]
    assert spans["engine.decode"]["count"] == on.stats["decode_ticks"]
    scatters = spans["engine.scatter"]["count"]
    assert snap["counters"]["engine.scatter_calls"] == \
        2 * eng.n_kv_layers * scatters
    if chunk is None:
        assert scatters == len(TRAFFIC)
    else:
        assert scatters == sum(-(-p // 8) for _, p, _ in TRAFFIC)


@pytest.mark.parametrize("chunk", [None, 1])
def test_a_hybrid_day_counts_its_ssm_slot_writes_and_occupancy(tmp_path,
                                                                chunk):
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(name="tiny-jamba", family="hybrid", n_layers=4,
                      d_model=32, n_heads=4, n_kv_heads=1, d_ff=64,
                      vocab_size=LM.vocab_size, head_dim=8, ssm_state=4,
                      ssm_dt_rank=4, attn_period=2, attn_offset=1,
                      attn_rope=False, ssm_dt_bc_norms=True)
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, RunConfig(remat="none", attn_impl="dense"),
                      params, NO_AXES, max_batch=4, max_seq=32,
                      kv_mode="paged", page_len=8)
    off = eng.run_scheduler(_requests(), prefill_chunk_pages=chunk)
    with jax.profiler.trace(str(tmp_path)):
        on = eng.run_scheduler(_requests(), prefill_chunk_pages=chunk)
    for rid in off.outputs:
        np.testing.assert_array_equal(on.outputs[rid], off.outputs[rid])
    snap = telemetry.snapshot()
    spans, counters = snap["spans"], snap["counters"]
    # one slot write per admission, two arrays (h, conv) per Mamba layer
    assert eng.n_ssm_layers == 2
    assert spans["engine.ssm_slot"]["count"] == len(TRAFFIC)
    assert counters["engine.ssm_slot_writes"] == 2 * 2 * len(TRAFFIC)
    recs = telemetry.records()
    parents = {recs[r.parent].name if r.parent is not None else None
               for r in recs if r.name == "engine.ssm_slot"}
    assert parents == ({"engine.admit"} if chunk is None else {None})
    # lanes x ticks, and of it the lane-ticks whose slot held a request
    assert counters["sched.ssm_slot_ticks"] == 4 * on.ticks
    live = counters["sched.ssm_slots_live"]
    busy = round(on.stats["lane_occupancy"] * 4 * on.ticks)
    # a chunked admission's slot is live from its last chunk on
    assert live == busy if chunk is None else 0 < live < busy
