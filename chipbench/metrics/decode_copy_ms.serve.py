"""Device milliseconds per decode tick spent in ``copy`` operations inside
the jitted ``_scheduler_step`` (the whole-pool copies the step makes
because its pools are not donated)."""

MODULE = "_scheduler_step"


def read(r):
    red = r.reduction
    if red is None:
        return None
    runs = sum(n for m, n in red.module_counts.items() if MODULE in m)
    copy = sum(op.dur_ns for op in red.ops
               if MODULE in op.module and op.opcode == "copy")
    return 1e-6 * copy / runs if runs else None
