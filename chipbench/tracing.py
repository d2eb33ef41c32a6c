"""Host spans, the profiler, and the reduction from a profiler trace to the
numbers the per-layer metrics read.

Spans come from the benchmark's own files, around the calls into each layer
of the program.  In a traced run each span is also a
``jax.profiler.TraceAnnotation`` named ``cb.<name>``, so it lands in the
profiler's trace on the same clock as the device's operations, and every
idle gap of the device can be put down to what the host was doing.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field

SPAN_PREFIX = "cb."
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


class Spans:
    """Durations of named host spans (seconds), kept in memory; with
    ``annotate`` each span is also a profiler ``TraceAnnotation``."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.durations: dict = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.durations[name].append(time.perf_counter() - t0)

    def reset(self) -> None:
        """Forget what was recorded (set-up's spans are not the window's)."""
        self.durations.clear()

    def total(self, name: str) -> float:
        return float(sum(self.durations.get(name, ())))

    def count(self, name: str) -> int:
        return len(self.durations.get(name, ()))


class Profiler:
    """``jax.profiler`` over one window, written under a temporary directory
    (inside ``TMPDIR``) that is removed once the trace is read."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        """Trace the device and the host's annotations; Python function
        events are off (millions of them in a construction pass, and the
        reduction reads none)."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self):
        """Stop tracing and return the ``ProfileData`` of the trace."""
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.directory, "**", "*.xplane.pb"), recursive=True))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.directory}")
        return ProfileData.from_file(files[-1])


_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<shape>.+?) "
                  r"(?P<opcode>[\w\-]+)\((?P<args>.*)$", re.S)
_LAYOUT = re.compile(r"\{[^{}]*\}")


def hlo_parts(text: str):
    """``(instruction, shape, opcode, operands...)`` of a device op event,
    whose name is the HLO instruction's text (``%copy.3 = bf16[4,8]{1,0}
    copy(bf16[4,8]{1,0} %x)``); None where it does not parse."""
    m = _HLO.match(text)
    return None if m is None else (m["name"], m["shape"], m["opcode"],
                                   m["args"])


def op_label(text: str) -> str:
    """A short name for an op that groups its instances: the instruction
    name without its number, and its shape without the layout."""
    parts = hlo_parts(text)
    if parts is None:
        return text[:120]
    base = re.sub(r"\.\d+$", "", parts[0])
    return f"{base} {_LAYOUT.sub('', parts[1])}"[:120]


@dataclass
class DeviceOp:
    name: str                             # the HLO instruction's text
    module: str
    start_ns: float
    dur_ns: float

    @property
    def opcode(self) -> str:
        parts = hlo_parts(self.name)
        return parts[2] if parts else ""


@dataclass
class Reduction:
    """What one traced window holds, in seconds."""
    window_s: float
    busy_s: float                         # union of device op intervals,
                                          # averaged over the devices
    n_devices: int
    ops: list = field(default_factory=list)          # [DeviceOp] in window
    op_seconds: dict = field(default_factory=dict)   # op_label -> s
    module_seconds: dict = field(default_factory=dict)
    module_counts: dict = field(default_factory=dict)
    idle_by_span: dict = field(default_factory=dict)  # host span -> idle s
    span_seconds: dict = field(default_factory=dict)
    span_counts: dict = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _stat(ev, key):
    try:
        return dict(ev.stats).get(key)
    except (TypeError, ValueError):
        return None


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_events(device_planes: dict, host_spans: list,
                  window=None) -> Reduction:
    """The reduction proper, over plain data so that it can be checked on a
    small synthetic trace.

    ``device_planes`` maps a device name to ``(ops, modules)``: lists of
    ``(name, start_ns, dur_ns, module_or_None)`` and ``(name, start_ns,
    dur_ns)``.  ``host_spans`` is a list of ``(name, start_ns, dur_ns)``.
    ``window`` is ``(start_ns, end_ns)``; by default the ``cb.window``
    span's extent.
    """
    if window is None:
        ws = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
        if not ws:
            raise ValueError("no window span in the trace")
        window = (min(s for s, _ in ws), max(e for _, e in ws))
    w0, w1 = window
    if w1 <= w0:
        raise ValueError(f"empty trace window {window}")
    ops_in, busy_total = [], 0.0
    merged_all = []
    for dev, (ops, modules) in sorted(device_planes.items()):
        mods = sorted((s, s + d, n) for n, s, d in modules)
        mstarts = [m[0] for m in mods]
        ivs = []
        for name, s, d, module in ops:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            if module is None:
                i = bisect.bisect_right(mstarts, s) - 1
                module = (mods[i][2] if i >= 0 and mods[i][1] >= e
                          else "")
            cs, ce = max(s, w0), min(e, w1)
            ops_in.append(DeviceOp(name, module, cs, ce - cs))
            ivs.append((cs, ce))
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged)
        merged_all.append(merged)
    n_dev = max(1, len(device_planes))
    op_s: dict = defaultdict(float)
    mod_s: dict = defaultdict(float)
    mod_n: dict = defaultdict(int)
    for op in ops_in:
        op_s[op_label(op.name)] += op.dur_ns * 1e-9
        mod_s[op.module] += op.dur_ns * 1e-9
    for dev, (_, modules) in device_planes.items():
        for n, s, d in modules:
            if s >= w0 and s < w1:
                mod_n[n] += 1
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN]
    span_s: dict = defaultdict(float)
    span_n: dict = defaultdict(int)
    for n, s, e in spans:
        if e > w0 and s < w1:
            span_s[n[len(SPAN_PREFIX):]] += (min(e, w1) - max(s, w0)) * 1e-9
            span_n[n[len(SPAN_PREFIX):]] += 1
    idle: dict = defaultdict(float)
    index = _SpanIndex(spans)
    for merged in merged_all:
        cursor = w0
        gaps = []
        for s, e in merged + [[w1, w1]]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        for g0, g1 in gaps:
            _attribute(index, g0, g1, idle, 1.0 / n_dev)
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n_dev,
        n_devices=n_dev, ops=ops_in, op_seconds=dict(op_s),
        module_seconds=dict(mod_s), module_counts=dict(mod_n),
        idle_by_span=dict(idle), span_seconds=dict(span_s),
        span_counts=dict(span_n))


class _SpanIndex:
    """Host spans sorted by start, for finding those that overlap a gap."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0)

    def overlapping(self, g0, g1):
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_left(self.starts, g1)
        return [(s, e, n) for n, s, e in self.spans[lo:hi] if e > g0]


def _attribute(index: _SpanIndex, g0, g1, idle: dict,
               weight: float) -> None:
    """Split a device idle gap at the host spans' edges and put each piece
    down to the innermost (shortest) span that covers it."""
    live = index.overlapping(g0, g1)
    edges = sorted({g0, g1} | {x for s, e, _ in live for x in (s, e)
                               if g0 < x < g1})
    for a, b in zip(edges, edges[1:]):
        inner = [(e - s, n) for s, e, n in live if s <= a and e >= b]
        label = (min(inner)[1][len(SPAN_PREFIX):] if inner
                 else "host outside any span")
        idle[label] += (b - a) * 1e-9 * weight


def reduce_profile(profile) -> Reduction:
    """Read a ``jax.profiler.ProfileData`` into plain events and reduce."""
    device_planes, host_spans = {}, []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((ev.name, ev.start_ns, ev.duration_ns,
                                    _stat(ev, "hlo_module")))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((ev.name, ev.start_ns,
                                        ev.duration_ns))
            device_planes[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.duration_ns))
    if not device_planes:
        raise RuntimeError("the trace holds no TPU device plane")
    return reduce_events(device_planes, host_spans)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The ledger's ``breakdown``: the device operations that took most
    time, and the device's idle time by what the host was doing."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
