"""Random weights of a served configuration, made on the device in one
jitted call from the seed, in the type they are served in.

The tensors are those of the published model (embedding, per-layer q/k/v/o,
gate/up/down and two norms, final norm) in the layout the serving program
loads.  MiniCPM's muP scalars are folded in the way a converted checkpoint
carries them: the embedding is stored times ``scale_emb`` and the two
residual-branch outputs (o and down) times ``scale_depth / sqrt(layers)``,
so a llama-style block computes the published model exactly, up to a
positive scale of the logits that greedy decoding does not see.  The plain
reference (``reference_lm``) undoes the folding and applies the published
scalars itself.
"""
from __future__ import annotations

import math

EMBED_STD = 0.02


def sizes(conf: dict) -> dict:
    c = conf["config"]
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(c["num_key_value_heads"]),
            "hd": d // h, "f": int(c["intermediate_size"]),
            "layers": int(c["num_hidden_layers"]),
            "vocab": int(c["vocab_size"]),
            "vocab_padded": -(-int(c["vocab_size"]) // 256) * 256}


def fold_scales(conf: dict) -> dict:
    """The published scalars that the stored weights carry."""
    c = conf["config"]
    s = sizes(conf)
    return {"embed": float(c.get("scale_emb", 1.0)),
            "branch": (float(c["scale_depth"]) / math.sqrt(s["layers"])
                       if "scale_depth" in c else 1.0),
            "logit_div": (s["d"] / float(c["dim_model_base"])
                          if "dim_model_base" in c else 1.0)}


def layout(conf: dict) -> dict:
    """(shape, std) of every stored tensor, in the program's tree layout.
    Input projections draw N(0, 1/fan_in) and the branch outputs carry the
    folded depth scale.  The stored embedding draws N(0, EMBED_STD^2),
    small beside what the residual branches add: with tied embeddings and
    random weights a larger embedding makes each token's own row win the
    unembedding, so the model would repeat its input whatever the context
    (and a broken KV cache would go unseen)."""
    s = sizes(conf)
    fs = fold_scales(conf)
    d, h, kv, hd, f, n = s["d"], s["h"], s["kv"], s["hd"], s["f"], s["layers"]
    r = 1.0 / math.sqrt(d)
    return {
        "embed": ((s["vocab_padded"], d), EMBED_STD),
        "final_norm": ((d,), None),
        "blocks": {"b0": {
            "ln1": ((n, d), None),
            "ln2": ((n, d), None),
            "mixer": {"wq": ((n, d, h, hd), r),
                      "wk": ((n, d, kv, hd), r),
                      "wv": ((n, d, kv, hd), r),
                      "wo": ((n, h, hd, d),
                             fs["branch"] / math.sqrt(h * hd))},
            "ffn": {"w1": ((n, d, f), r),
                    "w3": ((n, d, f), r),
                    "w2": ((n, f, d), fs["branch"] / math.sqrt(f))},
        }},
    }


def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def init_params(conf: dict, seed: int, dtype: str = "bfloat16"):
    """All weights in one jitted call on the default device.  Norm weights
    are ones (as published checkpoints start); rows of the embedding past
    the vocabulary (padding) are zero."""
    import jax
    import jax.numpy as jnp
    s = sizes(conf)
    leaves = list(_leaves(layout(conf)))
    dt = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (path, (shape, std)) in enumerate(leaves):
            if std is None:
                x = jnp.ones(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                x = (jax.random.normal(k, shape, jnp.float32)
                     * std).astype(dt)
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
