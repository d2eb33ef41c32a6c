"""Host milliseconds per decode tick spent waiting for the step's tokens
(the readback of the tick's argmax in ``run_scheduler``), from the
program's ``engine.readback`` spans.  None where no step was read back."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    n = ps.count(snap, "engine.readback")
    return 1e3 * ps.total_s(snap, "engine.readback") / n if n else None
