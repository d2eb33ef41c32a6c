"""Share of lane-ticks in which a lane held a request, from the program's
own counter (``Scheduler.stats()["lane_occupancy"]``) over the window's
day: a closed loop with a backlog should keep it near 100."""


def read(r):
    occ = r.values.get("lane_occupancy")
    return None if occ is None else 100.0 * occ
