"""The whole decode step's share of the chip's bf16 peak: the FLOPs the
model needs for the tokens served in the traced window (two per weight of
every matmul, unembedding included, plus attention over each token's
context), over the window, over the peak."""


def read(r):
    red = r.reduction
    flops = r.values.get("model_flops")
    if red is None or not flops:
        return None
    return 100.0 * flops / red.window_s / r.peaks["bf16_flops"]
