"""Random weights of a served Jamba-style hybrid, made on the device in one
jitted call from the seed, in the type they are served in.

The tensors are those of the published model in the layout the serving
program loads: the embedding (tied), and per layer two norms, a SwiGLU
MLP and a mixer, which is multi-query attention (q/k/v/o) at the layers
where ``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer
(in/out projections, depthwise conv with bias, x/dt projections, dt bias,
A, D and the RMSNorm weights of dt, B and C) everywhere else.  The layers
are grouped as the program scans them: one period of the layer pattern
per group, block ``b{j}`` holding layer ``j`` of every period, stacked.

Projections draw N(0, 1/fan_in); norms, D and the dt/B/C norm weights are
ones; A and the dt bias follow Mamba's own initialisation (A = -[1..N] per
channel; dt between 1e-3 and 1e-1 through the inverse softplus), so the
recurrence keeps a memory over tens of tokens, as a trained one does.  The
embedding draws N(0, EMBED_STD^2), for the reason ``weights.layout`` gives.
"""
from __future__ import annotations

import math

from weights import EMBED_STD, _leaves

#: the range Mamba draws each channel's initial time step from
DT_MIN, DT_MAX = 1e-3, 1e-1


def sizes(conf: dict) -> dict:
    """The shapes of a configuration file, whose top level holds the
    published config's keys."""
    d = int(conf["hidden_size"])
    h = int(conf["num_attention_heads"])
    di = int(conf["mamba_expand"]) * d
    period = int(conf["attn_layer_period"])
    layers = int(conf["num_hidden_layers"])
    return {"d": d, "h": h, "kv": int(conf["num_key_value_heads"]),
            "hd": d // h, "f": int(conf["intermediate_size"]),
            "layers": layers, "period": period,
            "offset": int(conf["attn_layer_offset"]),
            "groups": layers // period, "di": di,
            "n": int(conf["mamba_d_state"]), "r": int(conf["mamba_dt_rank"]),
            "k": int(conf["mamba_d_conv"]),
            "vocab": int(conf["vocab_size"]),
            "vocab_padded": -(-int(conf["vocab_size"]) // 256) * 256}


def is_attention(conf: dict, i: int) -> bool:
    s = sizes(conf)
    return i % s["period"] == s["offset"]


def layout(conf: dict) -> dict:
    """(shape, init) of every stored tensor in the program's tree: init is
    a standard deviation, ``"ones"``, ``"a_log"`` or ``"dt_bias"``."""
    s = sizes(conf)
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    di, n, r, k, g = s["di"], s["n"], s["r"], s["k"], s["groups"]
    blocks = {}
    for j in range(s["period"]):
        if is_attention(conf, j):
            mixer = {"wq": ((g, d, h, hd), 1 / math.sqrt(d)),
                     "wk": ((g, d, kv, hd), 1 / math.sqrt(d)),
                     "wv": ((g, d, kv, hd), 1 / math.sqrt(d)),
                     "wo": ((g, h, hd, d), 1 / math.sqrt(h * hd))}
        else:
            mixer = {"in_proj": ((g, d, 2 * di), 1 / math.sqrt(d)),
                     "conv_w": ((g, k, di), 1 / math.sqrt(k)),
                     "conv_b": ((g, di), 1 / math.sqrt(k)),
                     "x_proj": ((g, di, r + 2 * n), 1 / math.sqrt(di)),
                     "dt_proj": ((g, r, di), 1 / math.sqrt(r)),
                     "dt_bias": ((g, di), "dt_bias"),
                     "A_log": ((g, di, n), "a_log"),
                     "D_skip": ((g, di), "ones"),
                     "out_proj": ((g, di, d), 1 / math.sqrt(di)),
                     "dt_norm": ((g, r), "ones"),
                     "b_norm": ((g, n), "ones"),
                     "c_norm": ((g, n), "ones")}
        blocks[f"b{j}"] = {
            "ln1": ((g, d), "ones"), "ln2": ((g, d), "ones"),
            "mixer": mixer,
            "ffn": {"w1": ((g, d, f), 1 / math.sqrt(d)),
                    "w3": ((g, d, f), 1 / math.sqrt(d)),
                    "w2": ((g, f, d), 1 / math.sqrt(f))}}
    return {"embed": ((s["vocab_padded"], d), EMBED_STD),
            "final_norm": ((d,), "ones"), "blocks": blocks}


def n_params(conf: dict) -> int:
    """Stored parameters (the padded embedding rows included)."""
    return sum(math.prod(shape) for _, (shape, _) in _leaves(layout(conf)))


def init_params(conf: dict, seed: int, dtype: str = "bfloat16"):
    """All weights in one jitted call on the default device; rows of the
    embedding past the vocabulary (padding) are zero."""
    import jax
    import jax.numpy as jnp
    s = sizes(conf)
    leaves = list(_leaves(layout(conf)))
    dt = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (path, (shape, init)) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if init == "ones":
                x = jnp.ones(shape, jnp.float32)
            elif init == "a_log":           # A = -[1..N] in every channel
                x = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[-1] + 1, dtype=jnp.float32)), shape)
            elif init == "dt_bias":         # softplus(bias) = dt
                step = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(DT_MIN),
                    math.log(DT_MAX)))
                x = step + jnp.log(-jnp.expm1(-step))
            else:
                x = jax.random.normal(k, shape, jnp.float32) * init
            x = x.astype(dt)
            if path == ("embed",):
                x = jnp.where(jnp.arange(shape[0])[:, None] < s["vocab"],
                              x, jnp.zeros((), dt))
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return out

    from traffic import derived_seed
    key = jax.random.PRNGKey(derived_seed(seed, 0))
    return jax.block_until_ready(jax.jit(make)(key))
