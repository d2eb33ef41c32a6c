#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout root; its
configuration, traffic and per-layer metrics come from files of their own
(see ``harness.py``).  The run refuses any platform but a TPU with the chips
the cell asks for, keeps JAX's compilation cache inside the checkout, makes
its inputs and weights from ``--seed``, warms up every shape it will use
(counted in ``setup_s``), measures for ``--seconds``, checks what the timed
path produced against a plain reference, and prints one JSON object as the
last line of standard output.  With ``--trace 1`` the window is traced by
the profiler and the line carries the cell's per-layer metrics instead of
its end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
from tracing import Profiler, Spans, breakdown, reduce_profile  # noqa: E402

#: JAX's event for every program the process compiles or loads from the
#: persistent cache; none may fall inside the window
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


class Env:
    """What a driver may ask of the harness: spans, the profiler, the end
    of set-up and the memory peak."""

    def __init__(self, trace: bool, chips: int, tmpdir: str,
                 require_tpu: bool = True, control: bool = False):
        self.spans = Spans(annotate=trace)
        self.control = control            # checks judge the control
        self.chips = chips
        self.require_tpu = require_tpu
        self.memory_peak = None
        self.profiler = Profiler(tmpdir) if trace else None
        self.profile = None
        self.setup_s = None
        self.in_window = False
        self.window_compiles = 0          # programs compiled or loaded

    def on_jax_event(self, event: str, **_) -> None:
        if self.in_window and event == COMPILE_EVENT:
            self.window_compiles += 1

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_PROCESS
        self.spans.reset()
        self.in_window = True

    def start_profile(self) -> None:
        self.profiler.start()

    def stop_profile(self) -> None:
        self.profile = self.profiler.stop()

    def read_memory_peak(self) -> int:
        """Read once the window has closed, before the reference runs."""
        self.in_window = False
        peak = harness.memory_peak_bytes(self.chips)
        if peak is None:
            if self.require_tpu:
                raise harness.SetupError("the device reports no peak "
                                         "memory")
            peak = 0
        self.memory_peak = peak
        return peak


class Readings:
    """What a per-layer metric's reader reads: the trace reduction, the
    window's host spans, the driver's counts and the device's peaks."""

    def __init__(self, reduction, spans, values, peaks):
        self.reduction = reduction
        self.spans = spans
        self.values = values or {}
        self.peaks = peaks


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, checkout: Path = harness.CHECKOUT,
             bench_dir: Path = harness.BENCH_DIR,
             notes: dict | None = None, control: bool = False) -> dict:
    """One run of one cell; returns the result line (a dict).  ``notes``,
    where given, receives what the run noted beside its metrics (samples
    compared, programs compiled inside the window).  With ``control`` the
    control stands in for what the timed path produced, the checks judge
    it, and ``notes`` holds the program's own reading of each number."""
    cell = harness.find_cell(name, checkout, bench_dir)
    device = harness.device_info(cell.chips, require_tpu)
    peaks = harness.peaks_for(device["kind"], bench_dir) if require_tpu \
        else None
    readers = {m["name"]: harness.metric_reader(m["name"], bench_dir)
               for m in cell.per_layer} if trace else {}
    import jax
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drv = harness.driver(cell.config["driver"], bench_dir)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        env = Env(trace, cell.chips, tmp, require_tpu, control)
        jax.monitoring.register_event_listener(env.on_jax_event)
        try:
            res = drv.run(cell, seed, seconds, trace, env)
        finally:
            jax.monitoring.unregister_event_listener(env.on_jax_event)
        if env.memory_peak is None:
            env.read_memory_peak()
        reduction = reduce_profile(env.profile) if trace else None
    device = dict(device, memory_peak_bytes=env.memory_peak)
    per_layer, bd = None, None
    if trace:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        rd = Readings(reduction, env.spans, res.readings, peaks)
        per_layer = {}
        for mname, read in readers.items():
            value = read(rd)
            if value is not None:
                per_layer[mname] = value
        bd = breakdown(reduction)
    if notes is not None:
        notes.update(res.notes, window_compiles=env.window_compiles)
    return harness.result_line(cell, res, device, trace, env.setup_s,
                               per_layer, bd)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    notes: dict = {}
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), notes=notes)
    except harness.SetupError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(f"notes: {json.dumps(notes)}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
