"""``banked_gather``'s share of its HBM roofline in the traced window: the
bytes its calls move (from their shapes, ``kv_roofline.kv_kernel_call``)
at the chip's peak bandwidth, over the device time of those calls.  None
where the window holds no call."""
from kv_roofline import roofline_pct


def read(r):
    if r.reduction is None:
        return None
    return roofline_pct(r.reduction, "banked_gather",
                        r.peaks["hbm_bytes_per_s"])
