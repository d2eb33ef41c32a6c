"""Host milliseconds per ``Scheduler.tick`` (admission, page allocation,
trace lowering), from the benchmark's span around each tick."""


def read(r):
    n = r.spans.count("tick")
    return 1e3 * r.spans.total("tick") / n if n else None
