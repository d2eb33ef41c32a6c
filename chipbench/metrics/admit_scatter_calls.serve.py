"""``banked_scatter`` calls per admission (pools × K/V of every
``_scatter_rows``), from the program's ``engine.scatter_calls`` counter
and ``engine.admit`` spans.  None in a window with no admission."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    n = ps.count(snap, "engine.admit")
    return ps.counter(snap, "engine.scatter_calls") / n if n else None
