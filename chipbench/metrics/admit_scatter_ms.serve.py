"""Host milliseconds per admission spent scattering the prompt's pages
into the pools (``ServeEngine._scatter_rows``: one ``banked_scatter`` per
pool and K/V), from the program's ``engine.scatter`` and ``engine.admit``
spans.  None in a window with no admission."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    n = ps.count(snap, "engine.admit")
    return 1e3 * ps.total_s(snap, "engine.scatter") / n if n else None
