"""Jitted calls an admission dispatches (``ServeEngine`` admission: the
prefill with its first token, the row lowering, each scatter of held rows,
a hybrid's slot write), from the program's ``engine.admit_dispatches``
counter and ``engine.admit`` spans.  None in a window with no admission,
and from a program that does not count them."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    n = ps.count(snap, "engine.admit")
    calls = ps.counter(snap, "engine.admit_dispatches")
    return calls / n if n and calls else None
