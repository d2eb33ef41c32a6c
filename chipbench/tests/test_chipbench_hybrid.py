"""The hybrid serving cell on the CPU: its files resolve, a whole run of a
tiny Jamba-style cell comes out correct, its float8 control and a run
whose SSM slots are never written do not, the step's byte count follows
the configuration's shapes, and the hybrid's readers read the program's
spans and counters (None where they are absent)."""
import json

import pytest
from tiny_hybrid import CELL, make_hybrid_checkout
from tiny_cells import REPO

import harness
from repro.runtime import telemetry

SEED = 2**31 + 4243


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_hybrid_checkout(tmp_path_factory.mktemp("hybrid"))


def run_tiny(checkout, **kw):
    import run
    return run.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                        checkout=checkout, bench_dir=checkout / "chipbench",
                        **kw)


def test_the_cell_resolves_to_the_serving_metrics_and_its_own():
    c = harness.find_cell("jamba2-3b.hybrid-chat")
    assert c.config["driver"] == "serve_hybrid"
    assert c.config["max_position_embeddings"] == 512
    assert {m["name"] for m in c.end_to_end} == {
        "setup_s", "serve_tokens_per_s", "serve_itl_p90_ms",
        "serve_peak_hbm_gb"}
    assert {m["name"] for m in c.per_layer} == {
        "ssm_slot_write_ms.hybrid", "ssm_slot_occupancy_pct.hybrid",
        "decode_hbm_roofline_pct.hybrid"}


def test_the_configuration_holds_the_catalog_entry():
    """The published config's keys sit at the file's top level, each with
    its published value but the one context cut that ``reduced`` names."""
    c = json.loads((REPO / "chipbench/configs/jamba2-3b.json").read_text())
    assert "config" not in c
    assert (c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["vocab_size"]) == (28, 2560, 8192, 20, 1, 65536)
    assert (c["mamba_d_state"], c["mamba_d_conv"], c["mamba_expand"],
            c["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert (c["attn_layer_period"], c["attn_layer_offset"],
            c["expert_layer_period"], c["expert_layer_offset"],
            c["num_experts"], c["num_experts_per_tok"]) == (14, 7, 2, 1, 1, 1)
    assert c["sliding_window"] is None and c["rms_norm_eps"] == 1e-06
    assert c["reduced"] == ["max_position_embeddings"]
    assert c["max_position_embeddings"] == 512


def test_a_sound_hybrid_run_is_correct(checkout):
    line = run_tiny(checkout)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "serve_itl_p90_ms", "serve_peak_hbm_gb"}


def test_the_float8_control_fails_the_limit(checkout):
    notes: dict = {}
    line = run_tiny(checkout, notes=notes, control=True)
    c = line["checks"]["max_logit_gap"]
    assert line["correct"] is False and line["failed"] > 0
    assert notes["program_max_logit_gap"] <= c["limit"] < c["value"]


def test_a_run_that_never_writes_the_slots_is_not_correct(checkout,
                                                          monkeypatch):
    from repro.serving.engine import ServeEngine
    monkeypatch.setattr(ServeEngine, "_write_slots",
                        lambda self, pools, state, lane: pools)
    assert run_tiny(checkout)["correct"] is False


def test_step_bytes_follow_the_shapes():
    import hybrid_roofline
    conf = json.loads((REPO / "chipbench/configs/jamba2-3b.json")
                      .read_text())
    # weights: 3.03e9 parameters in bfloat16
    weights = 2 * (65536 * 2560 + 2560 + 28 * (2 * 2560 + 3 * 2560 * 8192)
                   + 26 * (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192
                           + 160 * 5120 + 5120 + 5120 * 16 + 5120
                           + 5120 * 2560 + 160 + 32)
                   + 2 * (2 * 2560 * 20 * 128 + 2 * 2560 * 128))
    state = 2 * 64 * 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    kv = 2 * 2 * 64 * 64 * (8 * 128 * 2)
    assert hybrid_roofline.step_bytes(conf) == weights + state + kv
    assert 7.2e9 < weights + state + kv < 7.35e9


def _span(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": total_s}


HYBRID = {"spans": {"engine.admit": _span(10, 0.9),
                    "engine.ssm_slot": _span(10, 0.02)},
          "counters": {"sched.ssm_slots_live": 630,
                       "sched.ssm_slot_ticks": 640}}
ATTENTION_ONLY = {"spans": {"engine.admit": _span(10, 0.9)},
                  "counters": {"engine.scatter_calls": 800}}


@pytest.mark.parametrize("name,want", [
    ("ssm_slot_write_ms.hybrid", 2.0),
    ("ssm_slot_occupancy_pct.hybrid", 100 * 630 / 640)])
def test_the_slot_readers_read_the_programs_registry(monkeypatch, name,
                                                     want):
    read = harness.metric_reader(name)
    monkeypatch.setattr(telemetry, "snapshot", lambda: HYBRID)
    assert read(None) == pytest.approx(want)
    monkeypatch.setattr(telemetry, "snapshot", lambda: ATTENTION_ONLY)
    assert read(None) is None


def test_the_roofline_reader_reads_the_step_module():
    import run
    from tracing import reduce_events
    ms = 1_000_000
    ops = [("fusion.1", 0, 10 * ms, None), ("fusion.1", 20 * ms, 10 * ms,
                                             None)]
    modules = [("jit__scheduler_step", 0, 10 * ms),
               ("jit__scheduler_step", 20 * ms, 10 * ms)]
    red = reduce_events({"/device:TPU:0": (ops, modules)},
                        [("cb.window", 0, 40 * ms)])
    read = harness.metric_reader("decode_hbm_roofline_pct.hybrid")
    peaks = {"hbm_bytes_per_s": 819e9}
    got = read(run.Readings(red, None, {"decode_step_bytes": 4.095e9},
                            peaks))
    assert got == pytest.approx(50.0)       # 4.095 GB in 10 ms: half
    assert read(run.Readings(red, None, {}, peaks)) is None
    assert read(run.Readings(None, None, {"decode_step_bytes": 1},
                             peaks)) is None
