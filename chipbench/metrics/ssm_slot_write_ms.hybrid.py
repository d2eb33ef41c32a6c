"""Host milliseconds per admission spent landing the prompt's SSM state in
the lane's slots (``ServeEngine._write_slots``, up to the dispatch of its
one jitted call), from the program's ``engine.ssm_slot`` and
``engine.admit`` spans.  None in a window with no slot write."""
import program_spans as ps


def read(r):
    snap = ps.snapshot()
    n = ps.count(snap, "engine.admit")
    if not n or not ps.count(snap, "engine.ssm_slot"):
        return None
    return 1e3 * ps.total_s(snap, "engine.ssm_slot") / n
