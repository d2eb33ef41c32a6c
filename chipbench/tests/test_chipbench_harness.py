"""Lookup of cells, configurations, traffic and metrics by name; the shape
of BENCHMARK.json and of the result line; refusal of anything but a TPU."""
import json
import re

import pytest
from tiny_cells import BENCH, REPO

import harness
import run

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = harness.find_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in names
    assert (BENCH / "drivers" / f"{c.config['driver']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_metrics_belong_to_their_configuration(cell):
    names = {m["name"] for m in harness.find_cell(cell).end_to_end}
    if cell.startswith("minicpm-2b."):
        assert names == {"setup_s", "serve_tokens_per_s",
                         "serve_itl_p90_ms", "serve_peak_hbm_gb"}
    else:
        assert names == {"setup_s", "price_op_archs_per_s"}


def test_unknown_cell_is_refused():
    with pytest.raises(harness.SetupError):
        harness.find_cell("no-such.cell")


def test_benchmark_json_keeps_the_contract_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"] and 1 <= b["run_seconds"] <= 51
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    seen = set()
    for c in b["configs"]:
        assert NAME.match(c["name"]) and c["name"] not in seen
        seen.add(c["name"])
        assert c["file"].startswith("chipbench/")
        assert json.loads((REPO / c["file"]).read_text())["name"] == \
            c["name"]
    pairs = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and (w["config"], w["traffic"]) \
            not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for cell in m["workloads"]:
            assert cell in {w["name"] for w in b["workloads"]}
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_a_cell_added_as_new_files_only_resolves(checkout):
    c = harness.find_cell("tiny-lm.tiny-chat", checkout,
                          checkout / "chipbench")
    assert c.config["name"] == "tiny-lm" and c.traffic["n_requests"] == 40
    assert {m["name"] for m in c.end_to_end} >= {"serve_tokens_per_s"}


def test_a_metric_added_as_new_files_only_is_read(checkout, tmp_path):
    import shutil
    root = tmp_path / "co"
    shutil.copytree(checkout, root)
    (root / "chipbench" / "metrics" / "tick_count.serve.py").write_text(
        "def read(r):\n    return float(r.spans.count('tick'))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tick_count.serve", "unit": "ticks", "better": "higher",
        "source": "host_clock", "layer": "serving/scheduler.Scheduler "
        "(host)", "moves": "serve_tokens_per_s",
        "workloads": ["tiny-lm.tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = harness.find_cell("tiny-lm.tiny-chat", root, root / "chipbench")
    assert "tick_count.serve" in {m["name"] for m in c.per_layer}
    read = harness.metric_reader("tick_count.serve", root / "chipbench")
    spans = run.Spans()
    with spans("tick"):
        pass
    assert read(run.Readings(None, spans, {}, None)) == 1.0


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.peaks_for("cpu")


def test_run_refuses_a_platform_that_is_not_a_tpu(capsys):
    with pytest.raises(harness.SetupError, match="no TPU"):
        run.run_cell(CELLS[0], 1, 1.0, False)
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_result_line_has_the_contract_shape():
    cell = harness.find_cell("minicpm-2b.chat-decode")
    res = harness.RunResult(
        end_to_end={"serve_tokens_per_s": 80.0, "serve_itl_p90_ms": 110.0,
                    "serve_peak_hbm_gb": 14.8},
        checks=[harness.Check("max_logit_gap", 0.01, 0.1)],
        attempted=20, failed=0)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 14_800_000_000}
    line = harness.result_line(cell, res, device, False, 50.0)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["setup_s"] == {"value": 50.0, "unit": "s"}
    json.dumps(line)
    res.checks.append(harness.Check("max_logit_gap_2", 0.2, 0.1))
    assert harness.result_line(cell, res, device, False, 50.0)[
        "correct"] is False
    traced = harness.result_line(
        cell, res, dict(device, busy_s=1.0, window_s=2.0), True, 50.0,
        {"device_idle_pct.serve": 50.0},
        {"device_ops": [["fusion", 1.0]], "idle_gaps": [["tick", 1.0]]})
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert traced["metrics"] == {"device_idle_pct.serve": {
        "value": 50.0, "unit": "%"}}
