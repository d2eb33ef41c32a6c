"""Bytes that the paged-KV Pallas kernels move per call, worked out from the
shapes in the call's HLO text, and their share of the HBM roofline.

``banked_gather`` reads ``n`` rows of a bank-major table and writes them out:
``idx: s32[n]``, ``table: T[V, r, d]`` -> ``T[n, r, d]``.  ``banked_scatter``
reads ``n`` update rows and writes them into the table it aliases:
``idx: s32[n]``, ``updates: T[n, r, d]``, ``table: T[V, r, d]`` ->
``T[V, r, d]``.  Each moves its rows twice (read and write) plus the index
vector; neither computes, so the HBM bandwidth is the roofline.
"""
from __future__ import annotations

import math
import re

from tracing import hlo_parts

_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\]")
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


def _shapes(text: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def _nbytes(shape) -> int:
    dt, dims = shape
    return _ITEM[dt] * math.prod(dims)


def kv_kernel_call(text: str):
    """``("banked_gather" | "banked_scatter", bytes moved)`` for a device op
    that is one of the two kernels' calls, else None.  The op is a
    ``tpu_custom_call`` recognised by the shapes of its operands and
    result, whatever the compiler named the instruction."""
    parts = hlo_parts(text)
    if parts is None or parts[2] != "custom-call":
        return None
    _, result, _, args = parts
    if "custom_call_target" in args and \
            'custom_call_target="tpu_custom_call"' not in args:
        return None
    out, ins = _shapes(result), _shapes(args)
    if len(out) != 1 or len(ins) < 2 or ins[0][0] != "s32":
        return None
    (dt, dims), idx = out[0], ins[0]
    if len(ins) >= 3 and ins[2] == out[0] and ins[1][0] == dt \
            and idx[1] == ins[1][1][:1] and ins[1][1][1:] == dims[1:]:
        return "banked_scatter", 2 * _nbytes(ins[1]) + _nbytes(idx)
    if ins[1][0] == dt and idx[1] == dims[:1] and ins[1][1][1:] == dims[1:]:
        return "banked_gather", 2 * _nbytes(out[0]) + _nbytes(idx)
    return None


def roofline_pct(reduction, kernel: str, hbm_bytes_per_s: float):
    """The kernel's share of its roofline over the traced window: the least
    time its calls could take at the HBM peak over the time they took.
    None where the window holds no call of it."""
    moved, seconds = 0, 0.0
    for op in reduction.ops:
        call = kv_kernel_call(op.name)
        if call is not None and call[0] == kernel:
            moved += call[1]
            seconds += op.dur_ns * 1e-9
    if not seconds:
        return None
    return 100.0 * moved / hbm_bytes_per_s / seconds
