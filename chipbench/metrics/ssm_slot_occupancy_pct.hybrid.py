"""Share of lane-ticks in which a lane's SSM slots held a resident
request's state, from the program's ``sched.ssm_slots_live`` and
``sched.ssm_slot_ticks`` counters.  None where the scheduler carried no
slot."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    ticks = ps.counter(snap, "sched.ssm_slot_ticks")
    if not ticks:
        return None
    return 100.0 * ps.counter(snap, "sched.ssm_slots_live") / ticks
