"""Plain reference of a served MiniCPM-style model: the published forward
pass in float32 at the highest matmul precision, written from the model's
description with ``jax.numpy`` alone (no kernels, cache, batching or code of
the program under test).

MiniCPM (arXiv:2404.06395, openbmb/MiniCPM-2B-sft-bf16 ``config.json``):
pre-norm RMSNorm blocks, rotary attention (half-split rotation, theta
``rope_theta``), SwiGLU MLP, tied embeddings, and three muP scalars: the
input embedding is multiplied by ``scale_emb``, each residual branch by
``scale_depth / sqrt(num_hidden_layers)``, and the final hidden state is
divided by ``hidden_size / dim_model_base`` before the tied unembedding.
The stored weights carry these scalars folded in (``weights.py``); they are
unfolded here so that this file computes the published model.

``gaps`` compares served tokens with the reference: at every position where
a token was served, the amount by which the reference's logit of that token
lies below the reference's best logit, in units of the standard deviation
of the reference's logits at that position (so that the number does not
depend on the logits' scale).  Greedy serving at full precision gives 0
there; rounding gives small gaps where the best two logits are close.  With ``control=True`` it also reads the same number for the token
that the reference computed in float8 (e4m3, per-tensor scales on weights
and activations) puts first: the control that has to fail the limit.
"""
from __future__ import annotations

import math

import numpy as np

from weights import fold_scales, sizes

_F8_MAX = 448.0                   # largest finite float8_e4m3fn


def _q8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _forward(conf, params, tokens, fp8: bool):
    """Final-norm hidden states (S, d) and the published unembedding."""
    import jax
    import jax.numpy as jnp
    c = conf["config"]
    s, fs = sizes(conf), fold_scales(conf)
    hi = jax.lax.Precision.HIGHEST
    q = _q8 if fp8 else (lambda x: x)
    eps = float(c["rms_norm_eps"])
    theta = float(c.get("rope_theta", 10000.0))
    h, kv, hd = s["h"], s["kv"], s["hd"]
    n = tokens.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32)

    def f32(a):
        return a.astype(jnp.float32)

    def mm(spec, a, w):
        return jnp.einsum(spec, q(a), q(w), precision=hi)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * f32(w)

    def rope(x):                                 # (S, H, hd)
        freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                / hd)
        ang = pos[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    emb = f32(params["embed"][:s["vocab"]]) / fs["embed"]   # published E
    x = fs["embed"] * emb[tokens]
    causal = jnp.tril(jnp.ones((n, n), bool))
    br = fs["branch"]

    def layer(x, p):
        a = rms(x, p["ln1"])
        qh = rope(mm("sd,dhk->shk", a, f32(p["mixer"]["wq"])))
        kh = rope(mm("sd,dhk->shk", a, f32(p["mixer"]["wk"])))
        vh = mm("sd,dhk->shk", a, f32(p["mixer"]["wv"]))
        rep = h // kv
        kh, vh = jnp.repeat(kh, rep, 1), jnp.repeat(vh, rep, 1)
        sc = mm("shk,thk->hst", qh, kh) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = mm("hst,thk->shk", jax.nn.softmax(sc, -1), vh)
        x = x + br * mm("shk,hkd->sd", o, f32(p["mixer"]["wo"]) / br)
        a = rms(x, p["ln2"])
        g = jax.nn.silu(mm("sd,df->sf", a, f32(p["ffn"]["w1"])))
        u = mm("sd,df->sf", a, f32(p["ffn"]["w3"]))
        x = x + br * mm("sf,fd->sd", g * u, f32(p["ffn"]["w2"]) / br)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["b0"])
    x = rms(x, params["final_norm"]) / fs["logit_div"]
    return mm("sd,vd->sv", x, emb)


def _gaps_fn(conf: dict, control: bool):
    import jax
    import jax.numpy as jnp

    def fn(params, tokens, targets, served):
        ref = _forward(conf, params, tokens, fp8=False)
        best, scale = ref.max(-1), ref.std(-1)
        got = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        gap = jnp.where(served, (best - got) / scale, 0.0)
        if not control:
            return gap, gap
        low = _forward(conf, params, tokens, fp8=True)
        pick = jnp.argmax(low, -1)
        lgot = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return gap, jnp.where(served, (best - lgot) / scale, 0.0)

    return jax.jit(fn)


def gaps(conf: dict, params, sequences, length: int,
         control: bool = False) -> list:
    """Per sequence ``(prompt ids, served ids)``: the widest gap of a
    served token (and, with ``control``, of the float8 reference's first
    choice) over the served positions.  Every sequence is padded to
    ``length`` so that one program serves them all; padding sits after the
    sequence and a causal model never reads it."""
    import jax.numpy as jnp
    fn = _gaps_fn(conf, control)
    out = []
    for prompt, served in sequences:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        p, m = prompt.shape[0], served.shape[0]
        if p + m - 1 > length or m < 1:
            raise ValueError(f"sequence of {p}+{m} tokens does not fit "
                             f"{length}")
        toks = np.zeros(length, np.int32)
        toks[:p] = prompt
        toks[p:p + m - 1] = served[:-1]
        tgt = np.zeros(length, np.int32)
        tgt[p - 1:p - 1 + m] = served
        mask = np.zeros(length, bool)
        mask[p - 1:p - 1 + m] = True
        g, cg = fn(params, jnp.asarray(toks), jnp.asarray(tgt),
                   jnp.asarray(mask))
        out.append((float(np.max(np.asarray(g))),
                    float(np.max(np.asarray(cg)))))
    return out
