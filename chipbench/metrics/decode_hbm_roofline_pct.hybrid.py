"""The hybrid decode step's share of its HBM roofline: the bytes each
executed ``_scheduler_step`` must move (``hybrid_roofline.step_bytes``:
weights once, every lane's SSM state read and written, the KV pages
gathered) at the chip's peak bandwidth, over the step's device time in the
traced window.  None where the driver counted no bytes or the window holds
no step."""
from hybrid_roofline import roofline_pct

MODULE = "_scheduler_step"


def read(r):
    moved = r.values.get("decode_step_bytes")
    if r.reduction is None or not moved:
        return None
    return roofline_pct(r.reduction, MODULE, moved,
                        r.peaks["hbm_bytes_per_s"])
