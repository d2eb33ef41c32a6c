"""Host milliseconds per ``cost_many`` call spent preparing blocks for the
device: counting instructions, coalescing, padding and transferring, from
the program's ``cost.count``, ``cost.coalesce``, ``cost.pad``,
``cost.transfer`` and ``cost.many`` spans.  None where nothing was
priced."""
import program_spans as ps

PARTS = ("cost.count", "cost.coalesce", "cost.pad", "cost.transfer")


def read(r):
    snap = ps.snapshot()
    passes = ps.count(snap, "cost.many")
    if not passes:
        return None
    return 1e3 * sum(ps.total_s(snap, p) for p in PARTS) / passes
