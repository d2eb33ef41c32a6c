"""The per-layer metrics that read the program's own spans and counters
(``repro.runtime.telemetry``), on a synthetic registry: each reads its
spans, and each reads None where they are absent."""
import sys

import pytest

import harness
from repro.runtime import telemetry


def span(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": total_s}


SERVE = {
    "spans": {"sched.tick": span(200, 1.6), "sched.lower": span(210, 0.5),
              "engine.readback": span(190, 3.8),
              "engine.admit": span(10, 3.3), "engine.prefill": span(10, 0.9),
              "engine.scatter": span(10, 2.2)},
    "counters": {"engine.scatter_calls": 800},
}
PRICE = {
    "spans": {"cost.many": span(2, 50.0), "trace.alloc": span(2, 46.0),
              "cost.count": span(900, 0.4), "cost.coalesce": span(50, 0.2),
              "cost.pad": span(56, 0.1), "cost.transfer": span(56, 0.3),
              "cost.dispatch": span(56, 0.2), "cost.fold": span(2, 0.1)},
    "counters": {"cost.ops": 452256, "cost.padded_ops": 458752},
}
EXPECTED = [
    ("sched_lower_ms.serve", SERVE, 1e3 * 0.5 / 200),
    ("decode_wait_ms.serve", SERVE, 1e3 * 3.8 / 190),
    ("admit_scatter_ms.serve", SERVE, 1e3 * 2.2 / 10),
    ("admit_scatter_calls.serve", SERVE, 80.0),
    ("construct_alloc_ms_per_pass.price", PRICE, 1e3 * 46.0 / 2),
    ("cost_host_ms_per_pass.price", PRICE, 1e3 * 1.0 / 2),
    ("cost_pad_waste_pct.price", PRICE, 100 * (1 - 452256 / 458752)),
]
NAMES = [name for name, _, _ in EXPECTED]


def read(name):
    return harness.metric_reader(name)(None)


@pytest.mark.parametrize("name,snap,want", EXPECTED, ids=NAMES)
def test_each_reader_reads_the_programs_registry(monkeypatch, name, snap,
                                                 want):
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert read(name) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_reads_none_where_its_spans_are_absent(monkeypatch,
                                                           name):
    telemetry.reset()
    assert read(name) is None                 # an empty registry
    other = PRICE if name.endswith(".serve") else SERVE
    monkeypatch.setattr(telemetry, "snapshot", lambda: other)
    assert read(name) is None                 # the other kind of cell's spans


@pytest.mark.parametrize("name,snap,want", EXPECTED, ids=NAMES)
def test_each_reader_reads_none_from_a_program_without_telemetry(
        monkeypatch, name, snap, want):
    import repro.runtime
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert read(name) == pytest.approx(want)
    # the program as it was before it recorded spans: no such module
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert read(name) is None


def test_construction_reads_none_where_passes_price_a_recorded_step(
        monkeypatch):
    lattice = {"spans": {k: v for k, v in PRICE["spans"].items()
                         if k != "trace.alloc"},
               "counters": PRICE["counters"]}
    monkeypatch.setattr(telemetry, "snapshot", lambda: lattice)
    assert read("construct_alloc_ms_per_pass.price") is None
    assert read("cost_host_ms_per_pass.price") == pytest.approx(500.0)
