"""The program's own spans and counters (``repro.runtime.telemetry``) as
the per-layer readers read them.

The program records them only while a profiler session records: in a
traced run, the window and nothing else; in an untraced run, nothing.  A
program without the module, or one that recorded nothing, reads None.
"""


def snapshot():
    """The program's registry (``{"spans": ..., "counters": ...}``), or
    None where there is nothing to read."""
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    return snap if snap["spans"] or snap["counters"] else None


def count(snap, name: str) -> int:
    """How many ``name`` spans closed."""
    return snap["spans"].get(name, {}).get("count", 0) if snap else 0


def total_s(snap, name: str) -> float:
    """Seconds inside ``name`` spans."""
    return snap["spans"].get(name, {}).get("total_s", 0.0) if snap else 0.0


def counter(snap, name: str) -> int:
    """The counter ``name``'s total."""
    return snap["counters"].get(name, 0) if snap else 0
