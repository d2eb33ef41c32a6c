"""Plain reference of a served Jamba-style hybrid: the published forward
pass in float32 at the highest matmul precision, written from the model's
description with ``jax.numpy`` alone (no kernels, cache, batching or code of
the program under test).

Jamba (arXiv:2403.19887; ai21labs/AI21-Jamba2-3B ``config.json`` and the
``transformers`` Jamba model): token embedding; pre-norm RMSNorm blocks,
each a mixer and a feed-forward branch added to the residual; the mixer is
causal multi-query attention with no positional encoding at layers where
``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer
elsewhere; a SwiGLU MLP in every layer (``num_experts`` 1); a final RMSNorm
and the tied unembedding.  The Mamba mixer: ``in_proj`` to x and the gate
z; a causal depthwise convolution of x (width ``mamba_d_conv``, with bias)
and SiLU; ``x_proj`` to dt, B and C, each put through its own RMSNorm
(Jamba's ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); dt through
``dt_proj`` with its bias and softplus; then, token by token,
``h = exp(dt A) h + dt x B`` and ``y = C h + D x``, gated by SiLU(z), and
``out_proj``.  Departures: the weights are stored in the program's layout
(layers grouped by period, ``conv_w`` as (width, channels), ``A_log`` =
log(-A)), which this file reads by name; nothing else.

The forward runs one jitted call per layer, so that no more than one
layer's weights are in float32 at a time.  ``gaps`` is
``reference_lm.gaps``'s number: at every served position, how far the
reference's logit of the served token lies below the reference's best, in
units of the standard deviation of the reference's logits there.  With
``control=True`` it also reads that number for the first choice of the
reference computed in float8 (e4m3, per-tensor scales on the weights and
activations of every matrix product).
"""
from __future__ import annotations

import functools
import math

import numpy as np

from reference_lm import _q8
from weights_jamba import is_attention, sizes


@functools.lru_cache(maxsize=None)
def _layer_fns(conf_key: str, fp8: bool):
    """Jitted (attention block, Mamba block, head) of one configuration."""
    import json

    import jax
    import jax.numpy as jnp
    conf = json.loads(conf_key)
    s = sizes(conf)
    hi = jax.lax.Precision.HIGHEST
    q = _q8 if fp8 else (lambda x: x)
    eps = float(conf["rms_norm_eps"])
    h, kv, hd, r, n, k = s["h"], s["kv"], s["hd"], s["r"], s["n"], s["k"]

    def f32(a):
        return a.astype(jnp.float32)

    def mm(spec, a, w):
        return jnp.einsum(spec, q(a), q(f32(w)), precision=hi)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * f32(w)

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def mlp(x, p):
        a = rms(x, p["ln2"])
        g = silu(mm("sd,df->sf", a, p["ffn"]["w1"]))
        u = mm("sd,df->sf", a, p["ffn"]["w3"])
        return x + mm("sf,fd->sd", g * u, p["ffn"]["w2"])

    def attention(x, blk, g):
        p = jax.tree.map(lambda a: a[g], blk)
        m = p["mixer"]
        a = rms(x, p["ln1"])
        qh = mm("sd,dhk->shk", a, m["wq"])
        kh = jnp.repeat(mm("sd,dhk->shk", a, m["wk"]), h // kv, 1)
        vh = jnp.repeat(mm("sd,dhk->shk", a, m["wv"]), h // kv, 1)
        sc = mm("shk,thk->hst", qh, kh) / math.sqrt(hd)
        t = x.shape[0]
        sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], sc, -jnp.inf)
        o = mm("hst,thk->shk", jax.nn.softmax(sc, -1), vh)
        return mlp(x + mm("shk,hkd->sd", o, m["wo"]), p)

    def mamba(x, blk, g):
        p = jax.tree.map(lambda a: a[g], blk)
        m = p["mixer"]
        a = rms(x, p["ln1"])
        xz = mm("sd,de->se", a, m["in_proj"])
        xin, z = jnp.split(xz, 2, -1)
        t = x.shape[0]
        # causal depthwise conv: output t sees inputs t-k+1 .. t, the
        # weight of lag k-1-i in row i
        pad = jnp.concatenate([jnp.zeros((k - 1, xin.shape[1])), xin])
        conv = sum(f32(m["conv_w"][i]) * pad[i:i + t] for i in range(k))
        u = silu(conv + f32(m["conv_b"]))
        dt, b, cc = jnp.split(mm("si,ik->sk", u, m["x_proj"]),
                              [r, r + n], -1)
        dt, b, cc = (rms(dt, m["dt_norm"]), rms(b, m["b_norm"]),
                     rms(cc, m["c_norm"]))
        delta = jax.nn.softplus(mm("sr,ri->si", dt, m["dt_proj"])
                                + f32(m["dt_bias"]))
        amat = -jnp.exp(f32(m["A_log"]))                 # (channels, n)

        def step(state, inp):
            d_t, u_t, b_t, c_t = inp
            state = jnp.exp(d_t[:, None] * amat) * state \
                + (d_t * u_t)[:, None] * b_t[None, :]
            return state, jnp.sum(state * c_t[None, :], -1)

        _, y = jax.lax.scan(step, jnp.zeros(amat.shape),
                            (delta, u, b, cc))
        y = (y + f32(m["D_skip"]) * u) * silu(z)
        return mlp(x + mm("si,id->sd", y, m["out_proj"]), p)

    def head(x, final_norm, embed):
        return mm("sd,vd->sv", rms(x, final_norm), embed[:s["vocab"]])

    return jax.jit(attention), jax.jit(mamba), jax.jit(head)


def logits(conf: dict, params, tokens, fp8: bool = False):
    """(S, vocab) reference logits of a token sequence."""
    import json

    import jax
    import jax.numpy as jnp
    s = sizes(conf)
    attention, mamba, head = _layer_fns(
        json.dumps(conf, sort_keys=True), fp8)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(s["layers"]):
            blk = params["blocks"][f"b{i % s['period']}"]
            fn = attention if is_attention(conf, i) else mamba
            x = fn(x, blk, i // s["period"])
        return head(x, params["final_norm"], params["embed"])


@functools.lru_cache(maxsize=None)
def _gap_fn():
    import jax
    import jax.numpy as jnp

    def fn(ref, low, targets, served):
        best, scale = ref.max(-1), ref.std(-1)
        got = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        gap = jnp.where(served, (best - got) / scale, 0.0)
        pick = jnp.argmax(low, -1)
        lgot = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return gap, jnp.where(served, (best - lgot) / scale, 0.0)

    return jax.jit(fn)


def gaps(conf: dict, params, sequences, length: int,
         control: bool = False) -> list:
    """Per sequence ``(prompt ids, served ids)``: the widest gap of a
    served token (and, with ``control``, of the float8 reference's first
    choice) over the served positions.  Every sequence is padded to
    ``length``, after its end, which a causal model never reads."""
    import jax.numpy as jnp
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
        ref = logits(conf, params, toks)
        low = logits(conf, params, toks, fp8=True) if control else ref
        g, cg = _gap_fn()(ref, low, jnp.asarray(tgt), jnp.asarray(mask))
        out.append((float(np.max(np.asarray(g))),
                    float(np.max(np.asarray(cg)))))
    return out
