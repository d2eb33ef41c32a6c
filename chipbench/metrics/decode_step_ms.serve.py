"""Device milliseconds per decode tick of the jitted ``_scheduler_step``:
its operations' device time in the traced window over its executions."""

MODULE = "_scheduler_step"


def read(r):
    red = r.reduction
    if red is None:
        return None
    runs = sum(n for m, n in red.module_counts.items() if MODULE in m)
    busy = sum(s for m, s in red.module_seconds.items() if MODULE in m)
    return 1e3 * busy / runs if runs else None
