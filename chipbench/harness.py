"""Cell lookup, device checks and the result line of the chip benchmark.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    <bench>/configs/<config>.json     sizes, source and deployment
    <bench>/traffic/<traffic>.json    parameters of the traffic generator
    <bench>/metrics/<metric>.py       ``read(readings) -> float | None``
    <bench>/drivers/<driver>.py       one driver per kind of system path
    <bench>/peaks.json                published peaks keyed by device_kind

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one that exists.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent


class SetupError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown cell, ...)."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


@dataclass
class Check:
    """One number the run compares, with its limit: correct iff
    ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class RunResult:
    end_to_end: dict                  # metric name -> value (host clock)
    checks: list                      # [Check]
    attempted: int
    failed: int
    readings: object = None           # what the per-layer readers read
    notes: dict = field(default_factory=dict)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(checkout: Path = CHECKOUT) -> dict:
    path = checkout / "BENCHMARK.json"
    if not path.is_file():
        raise SetupError(f"no BENCHMARK.json at {checkout}")
    return load_json(path)


def _applies(metric: dict, cell: str, e2e_names: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is None:                # an end-to-end metric of every cell
        return True
    return metric["moves"] in e2e_names


def find_cell(name: str, checkout: Path = CHECKOUT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` to its configuration, traffic
    and metrics, each read from its own file."""
    bench = load_benchmark(checkout)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = load_json(checkout / entry["file"])
    if config.get("name") != entry["name"]:
        raise SetupError(f"{entry['file']} names {config.get('name')!r}, "
                         f"BENCHMARK.json {entry['name']!r}")
    traffic_file = bench_dir / "traffic" / f"{w['traffic']}.json"
    traffic = load_json(traffic_file)
    if traffic.get("driver") != config.get("driver"):
        raise SetupError(f"traffic {w['traffic']!r} is for driver "
                         f"{traffic.get('driver')!r}, config "
                         f"{entry['name']!r} for {config.get('driver')!r}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise SetupError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    mod = _load_module(bench_dir / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_"))
    return mod.read


def driver(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``drivers/<name>.py`` (it defines ``run``)."""
    return _load_module(bench_dir / "drivers" / f"{name}.py",
                        "chipbench_driver_" + name)


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one device kind; an unknown kind is an error,
    never a default."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"no peaks for device kind {kind!r} in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them; refuses anything but
    a TPU with at least ``chips`` devices unless ``require_tpu`` is off
    (the CPU tests of the harness)."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}
    if require_tpu:
        if info["platform"] != "tpu":
            raise SetupError(f"no TPU: JAX found platform "
                             f"{info['platform']!r}")
        if len(devices) < chips:
            raise SetupError(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devices)}")
    return info


def memory_peak_bytes(chips: int) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip the cell uses (None where
    the backend does not report it)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def result_line(cell: Cell, res: RunResult, device: dict, trace: bool,
                setup_s: float, per_layer: dict | None = None,
                breakdown: dict | None = None) -> dict:
    """The contract's last line: ``correct``, ``attempted``, ``failed``,
    ``metrics``, ``device``, optionally ``breakdown``, and the compared
    numbers with their limits under ``checks``, which comes last."""
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        values = per_layer or {}
    else:
        values = dict(res.end_to_end)
        values["setup_s"] = setup_s
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise SetupError(f"the driver measured no {missing}")
        values = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    line = {
        "correct": all(c.ok for c in res.checks) and bool(res.checks),
        "attempted": int(res.attempted),
        "failed": int(res.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": float(c.value),
                               "limit": float(c.limit)} for c in res.checks}
    return line
