"""Bytes that one decode step of a served Jamba-style hybrid must move,
worked out from the configuration's shapes, and the step's share of the
HBM roofline.

Per executed step (``ServeEngine._scheduler_step``): every stored weight
once (the tied embedding is read whole by the unembedding); every lane's
SSM state of every Mamba layer, read and written (``h`` in float32,
(d_inner, d_state); the conv window in the served type, (d_conv - 1,
d_inner)); and the KV pages that the two attention layers gather, K and V,
every page of every lane's page list, as the step's gather reads them.
The step computes little per byte at this batch, so HBM bandwidth is its
roofline.
"""
from __future__ import annotations

import math

from weights_jamba import is_attention, n_params, sizes

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_bytes(conf: dict) -> int:
    s, dep = sizes(conf), conf["deployment"]
    item = _ITEM[dep["dtype"]]
    lanes = int(dep["lanes"])
    n_attn = sum(is_attention(conf, i) for i in range(s["layers"]))
    n_ssm = s["layers"] - n_attn
    weights = n_params(conf) * item
    state = 2 * lanes * n_ssm * (s["di"] * s["n"] * 4
                                 + (s["k"] - 1) * s["di"] * item)
    pages = math.ceil(int(dep["max_seq"]) / int(dep["page_len"]))
    page = int(dep["page_len"]) * s["kv"] * s["hd"] * item
    kv = 2 * n_attn * lanes * pages * page
    return weights + state + kv


def roofline_pct(reduction, module: str, moved_per_run: float,
                 hbm_bytes_per_s: float):
    """The least time the module's runs in the traced window could take at
    the HBM peak, over the device time they took; None where the window
    holds no run."""
    runs = sum(c for m, c in reduction.module_counts.items() if module in m)
    busy = sum(t for m, t in reduction.module_seconds.items() if module in m)
    if not runs or not busy:
        return None
    return 100.0 * moved_per_run * runs / hbm_bytes_per_s / busy
