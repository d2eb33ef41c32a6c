"""``admit_dispatches.serve``: the program's ``engine.admit_dispatches``
counter per ``engine.admit`` span, and None where the program does not
count them (a program from before the counter) or admitted nothing."""
import sys

import pytest

import harness
from repro.runtime import telemetry

NAME = "admit_dispatches.serve"


def span(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": total_s}


#: 10 whole-prompt admissions of three calls each
COUNTED = {"spans": {"engine.admit": span(10, 0.3),
                     "engine.prefill": span(10, 0.2),
                     "engine.scatter": span(10, 0.01)},
           "counters": {"engine.scatter_calls": 800,
                        "engine.admit_dispatches": 30}}
#: the same window from a program that does not count its dispatches
UNCOUNTED = {"spans": COUNTED["spans"],
             "counters": {"engine.scatter_calls": 800}}
#: a window with no admission
IDLE = {"spans": {"sched.tick": span(200, 1.6)},
        "counters": {"engine.admit_dispatches": 0}}


def read():
    return harness.metric_reader(NAME)(None)


@pytest.mark.parametrize("snap,want", [(COUNTED, 3.0), (UNCOUNTED, None),
                                       (IDLE, None)],
                         ids=["counted", "uncounted", "no-admission"])
def test_reads_the_dispatches_per_admission(monkeypatch, snap, want):
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert read() == want


def test_reads_none_from_an_empty_registry_or_no_telemetry(monkeypatch):
    telemetry.reset()
    assert read() is None
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert read() is None


def test_reads_a_served_days_counter(tmp_path):
    """A traced day of the program itself: three calls per whole-prompt
    admission of an attention-only model."""
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.models import init_tree, model_specs
    from repro.serving.engine import ServeEngine
    from repro.serving.scheduler import Request
    cfg = get_smoke_config("llama3.2-1b")
    eng = ServeEngine(cfg, RunConfig(remat="none", attn_impl="dense"),
                      init_tree(model_specs(cfg), jax.random.PRNGKey(0)),
                      NO_AXES, max_batch=2, max_seq=32, page_len=8)
    reqs = [Request(rid=i, arrival=0, prompt_len=p, max_new_tokens=2,
                    tokens=np.arange(p, dtype=np.int32))
            for i, p in enumerate((12, 5, 12))]
    telemetry.reset()
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run_scheduler(reqs)
        assert read() == 3.0
    finally:
        telemetry.reset()
