"""Host milliseconds per ``Scheduler.tick`` spent lowering the tick's
address traces (``scheduler_step_trace`` / ``admission_prefill_trace``),
from the program's ``sched.lower`` and ``sched.tick`` spans.  None where
the program recorded no tick."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    ticks = ps.count(snap, "sched.tick")
    return 1e3 * ps.total_s(snap, "sched.lower") / ticks if ticks else None
