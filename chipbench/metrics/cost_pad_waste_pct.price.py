"""Share of the op slots ``cost_many`` sent to the device that were
padding (batches padded to a power of two), from the program's
``cost.ops`` and ``cost.padded_ops`` counters.  None where nothing was
dispatched."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    slots = ps.counter(snap, "cost.padded_ops")
    if not slots:
        return None
    return 100.0 * (1.0 - ps.counter(snap, "cost.ops") / slots)
