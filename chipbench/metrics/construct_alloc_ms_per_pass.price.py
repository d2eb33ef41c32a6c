"""Host milliseconds per pricing pass spent allocating the step's KV pages
(``models/trace._decode_point``'s ``allocate_pages`` loop, up to the page
table's readback), from the program's ``trace.alloc`` and ``cost.many``
spans.  None where the passes allocate nothing."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    passes = ps.count(snap, "cost.many")
    if not passes or not ps.count(snap, "trace.alloc"):
        return None
    return 1e3 * ps.total_s(snap, "trace.alloc") / passes
