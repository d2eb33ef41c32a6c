"""A whole run of a cell, with the look for a chip skipped, at a size the
CPU holds: sound, it comes out correct; with the timed path broken
underneath in each way the cell can fail, ``correct`` comes out false."""
import jax.numpy as jnp
import pytest

import run

SEED = 2**31 + 4242


def run_tiny(checkout, cell, seconds=0.5, **kw):
    return run.run_cell(cell, SEED, seconds, False, require_tpu=False,
                        checkout=checkout, bench_dir=checkout / "chipbench",
                        **kw)


def _serve_fault(kind):
    from repro.serving.engine import ServeEngine
    step = ServeEngine._scheduler_step

    def broken(self, params, tok, pools, page_table, pos, active, scratch):
        if kind == "half_batch":
            b = active.shape[0]
            active = active & (jnp.arange(b) < b // 2)
        logits, new_pools = step(self, params, tok, pools, page_table, pos,
                                 active, scratch)
        if kind == "state_unchanged":
            new_pools = pools
        if kind == "token_altered":
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, new_pools

    return broken


def _price_fault(kind):
    from repro.core import cost_engine
    many, block = cost_engine.cost_many, cost_engine._block_kind_cycles
    first: list = []
    calls = [0]

    def cost_many(*a, **k):
        out = many(*a, **k)
        if kind == "answer_altered":
            out[0].load_cycles += 1
        if kind == "state_unchanged":
            first.append(out)
            return first[0]
        return out

    def half_blocks(*a, **k):
        calls[0] += 1
        out = block(*a, **k)
        return out * 0 if calls[0] % 2 else out

    if kind == "half_blocks":
        return "_block_kind_cycles", half_blocks
    return "cost_many", cost_many


def test_a_sound_serving_run_is_correct(checkout):
    line = run_tiny(checkout, "tiny-lm.tiny-chat")
    assert line["correct"] is True
    assert line["checks"]["max_logit_gap"]["value"] <= \
        line["checks"]["max_logit_gap"]["limit"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell,check", [
    ("tiny-lm.tiny-chat", "max_logit_gap"),
    ("tiny-moe.tiny-price", "mismatched_fields")])
def test_the_control_fails_the_limit_a_sound_run_meets(checkout, cell,
                                                        check):
    # the control: the reference in float8 for the served model, the
    # reference that prices predicated lanes for the pricing cells, put in
    # the timed path's place and judged by the run's own checks
    notes: dict = {}
    line = run_tiny(checkout, cell, notes=notes, control=True)
    c = line["checks"][check]
    assert line["correct"] is False and line["failed"] > 0
    assert notes["program_" + check] <= c["limit"] < c["value"]


@pytest.mark.parametrize("cell", ["tiny-lm.tiny-chat", "tiny-moe.tiny-price"])
def test_control_script_reads_every_seed_not_correct(checkout, cell):
    import control
    lines = list(control.control_lines(
        cell, 0.5, [SEED, SEED + 1], require_tpu=False, checkout=checkout,
        bench_dir=checkout / "chipbench"))
    assert [ln["seed"] for ln in lines] == [SEED, SEED + 1]
    assert all(ln["correct"] is False for ln in lines)


def test_the_sample_holds_a_request_of_every_lane():
    import harness
    from types import SimpleNamespace
    serve = harness.driver("serve")
    reqs = [SimpleNamespace(rid=i, max_new_tokens=10 + i) for i in range(40)]
    res = SimpleNamespace(outputs={r.rid: [0] * r.max_new_tokens
                                   for r in reqs if r.rid != 3})
    lane_of = {r.rid: r.rid % 8 for r in reqs}
    mix = {"check": {"sample_tokens": 1, "max_requests": 2}}
    for seed in (1, SEED):
        picked = serve.sample(reqs, res, mix, seed, lane_of)
        assert picked[0][0].rid == 39           # the longest comes first
        assert {lane_of[r.rid] for r, _ in picked} == set(range(8))
        assert len(picked) == 8 and all(r.rid != 3 for r, _ in picked)
    mix = {"check": {"sample_tokens": 10_000, "max_requests": 12}}
    picked = serve.sample(reqs, res, mix, SEED, lane_of)
    assert len(picked) == 12 and len({r.rid for r, _ in picked}) == 12


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_a_broken_serving_step_is_not_correct(checkout, monkeypatch, kind):
    from repro.serving.engine import ServeEngine
    monkeypatch.setattr(ServeEngine, "_scheduler_step", _serve_fault(kind))
    line = run_tiny(checkout, "tiny-lm.tiny-chat")
    assert line["correct"] is False


def test_a_sound_pricing_run_is_correct(checkout):
    line = run_tiny(checkout, "tiny-moe.tiny-price")
    assert line["correct"] is True
    assert line["checks"]["mismatched_fields"] == {"value": 0.0,
                                                   "limit": 0.0}


@pytest.mark.parametrize("kind", ["answer_altered", "state_unchanged",
                                  "half_blocks"])
def test_a_broken_pricing_pass_is_not_correct(checkout, monkeypatch, kind):
    from repro.core import cost_engine
    name, fn = _price_fault(kind)
    monkeypatch.setattr(cost_engine, name, fn)
    line = run_tiny(checkout, "tiny-moe.tiny-price")
    assert line["correct"] is False
    assert line["checks"]["mismatched_fields"]["value"] > 0
