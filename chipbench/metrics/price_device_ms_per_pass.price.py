"""Device milliseconds per pass: the time in which an operation ran on the
device during the traced window, over the passes priced in it."""


def read(r):
    red, passes = r.reduction, r.values.get("passes")
    if red is None or not passes:
        return None
    return 1e3 * red.busy_s / passes
