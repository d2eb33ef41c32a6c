"""Share of the traced window in which no operation ran on the device."""


def read(r):
    red = r.reduction
    return None if red is None else 100.0 * red.idle_share
