"""Transformer building blocks: norms, RoPE, GQA attention (dense, flash,
decode; SWA / local-global / softcap / qkv-bias variants), gated MLPs.

All math is written *globally* (full logical shapes); distribution comes from
GSPMD via the sharding constraints in ``launch.sharding.Axes``.  Attention
never materializes repeated KV heads: queries are shaped (B, S, KV, G, HD)
with G = H / KV so the GQA einsums contract against (B, T, KV, HD) directly.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.launch.sharding import Axes
from repro.models.params import Leaf, fan_in_scale

Array = jnp.ndarray
NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# norms / rope / softcap
# ---------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> Leaf:
    return Leaf((d,), ("embed",), init="ones")


def rmsnorm(w: Array, x: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def softcap(x: Array, cap: float) -> Array:
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, ..., HD); positions: (S,) or (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (HD/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, HD/2)
    angles = angles[..., :, None, :]                    # head axis: (.., S, 1, HD/2)
    while angles.ndim < x.ndim:
        angles = angles[None]                           # leading batch dims
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = fan_in_scale(d)
    p = {
        "wq": Leaf((d, h, hd), ("embed", "heads", "head_dim"), scale=s),
        "wk": Leaf((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=s),
        "wv": Leaf((d, kv, hd), ("embed", "kv_heads", "head_dim"), scale=s),
        "wo": Leaf((h, hd, d), ("heads", "head_dim", "embed"),
                   scale=fan_in_scale(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = Leaf((h, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = Leaf((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = Leaf((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return p


def _qkv(cfg: ModelConfig, p: dict, x: Array, positions: Array, ax: Axes):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // kv
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.attn_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = ax.heads_act(q)
    k = ax.heads_act(k)
    v = ax.heads_act(v)
    b, s = x.shape[:2]
    q = q.reshape(b, s, kv, g, hd)
    return q, k, v


def _mask(qpos: Array, kpos: Array, window: int) -> Array:
    """(…, Sq, Sk) boolean mask: causal + optional sliding window."""
    m = kpos[None, :] <= qpos[:, None]
    if window:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def attention_dense(cfg: ModelConfig, q: Array, k: Array, v: Array,
                    qpos: Array, kpos: Array, window: int) -> Array:
    """Materialized-scores GQA attention (training / short context)."""
    hd = cfg.hd
    s = jnp.einsum("bskgh,btkh->bkgst", q, k) / math.sqrt(hd)
    s = softcap(s, cfg.attn_softcap)
    s = jnp.where(_mask(qpos, kpos, window), s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v)
    return o


def attention_flash(cfg: ModelConfig, q: Array, k: Array, v: Array,
                    qpos: Array, kpos: Array, window: int,
                    block_q: int, block_k: int) -> Array:
    """Online-softmax blocked attention, Python-unrolled (exact HLO flop
    accounting — no inner lax loops; see DESIGN.md §5).  Causal."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    scale = 1.0 / math.sqrt(hd)
    outs = []
    for qi in range(nq):
        q_blk = q[:, qi * block_q:(qi + 1) * block_q]
        qp = qpos[qi * block_q:(qi + 1) * block_q]
        bq = q_blk.shape[1]
        m = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, kvh, g, bq), jnp.float32)
        acc = jnp.zeros((b, kvh, g, bq, hd), jnp.float32)
        q_lo, q_hi = qi * block_q, (qi + 1) * block_q - 1
        for kj in range(nk):
            k_lo, k_hi = kj * block_k, (kj + 1) * block_k - 1
            if k_lo > q_hi:                       # strictly future block
                continue
            if window and k_hi < q_lo - window + 1:
                continue                          # fully out of window
            k_blk = k[:, k_lo:k_lo + block_k]
            v_blk = v[:, k_lo:k_lo + block_k]
            kp = kpos[k_lo:k_lo + block_k]
            s = jnp.einsum("bqkgh,btkh->bkgqt", q_blk, k_blk) * scale
            s = softcap(s, cfg.attn_softcap).astype(jnp.float32)
            s = jnp.where(_mask(qp, kp, window), s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l = l * corr + pexp.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", pexp, v_blk.astype(jnp.float32))
            m = m_new
        o = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(jnp.einsum("bkgqh->bqkgh", o).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def attention(cfg: ModelConfig, rc: RunConfig, p: dict, x: Array,
              ax: Axes, *, window: int = 0,
              positions: Optional[Array] = None, return_kv: bool = False):
    """Full-sequence (train / prefill) attention; returns (B, S, D)
    (and the roped K/V when return_kv, for prefill cache capture)."""
    b, s, _ = x.shape
    positions = jnp.arange(s) if positions is None else positions
    q, k, v = _qkv(cfg, p, x, positions, ax)
    impl = rc.attn_impl
    if impl == "auto":
        impl = "flash" if s > 2 * rc.flash_block else "dense"
    if impl == "flash":
        o = attention_flash(cfg, q, k, v, positions, positions, window,
                            rc.flash_block, rc.flash_block)
    else:
        o = attention_dense(cfg, q, k, v, positions, positions, window)
    o = o.reshape(b, s, cfg.n_heads, cfg.hd)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(cfg: ModelConfig, p: dict, x: Array, cache: dict,
                     pos: Array, ax: Axes, *, window: int = 0):
    """Single-token decode against a (ring-)buffered KV cache.

    x: (B, 1, D); cache: {"k","v"}: (B, T, KV, HD) with T = seq_len (full
    cache) or window size (SWA ring buffer).  pos: () int32 current position.
    Returns (out (B,1,D), new_cache).
    """
    b = x.shape[0]
    kvh, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    t = cache["k"].shape[1]
    q, k_new, v_new = _qkv(cfg, p, x, pos[None], ax)
    slot = pos % t if window else pos               # ring buffer under SWA
    # One-hot masked update, NOT dynamic_update_slice: a traced-index DUS on
    # the sequence axis forces GSPMD to all-gather the sharded cache every
    # token (measured 2.4 GiB/layer on qwen decode — §Perf D1); the one-hot
    # write is elementwise over the sharded dim and costs zero collectives.
    hot = (jnp.arange(t) == slot)[None, :, None, None]
    ck = jnp.where(hot, k_new.astype(cache["k"].dtype), cache["k"])
    cv = jnp.where(hot, v_new.astype(cache["v"].dtype), cache["v"])
    # keep the cache in its banked layout: seq stays on the model axis
    # (constraining heads here would silently unshard seq — §Perf D1)
    ck = ax.shard(ck, ax.batch, ax.tp, None, None)
    cv = ax.shard(cv, ax.batch, ax.tp, None, None)
    idx = jnp.arange(t)
    if window:
        # ring: entry i holds absolute position  i + floor((pos-i)/t +1)*?  —
        # valid iff it was written within the last `t` steps
        age = (slot - idx) % t
        valid = age <= jnp.minimum(pos, t - 1)
    else:
        valid = idx <= pos
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, ck.astype(q.dtype)) / math.sqrt(hd)
    s = softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqt,btkh->bqkgh", pr, cv.astype(q.dtype))
    o = o.reshape(b, 1, cfg.n_heads, hd)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": Leaf((d, f), ("embed", "ffn"), scale=fan_in_scale(d)),
        "w3": Leaf((d, f), ("embed", "ffn"), scale=fan_in_scale(d)),
        "w2": Leaf((f, d), ("ffn", "embed"), scale=fan_in_scale(f)),
    }


def _act(name: str, x: Array) -> Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def mlp(cfg: ModelConfig, p: dict, x: Array, ax: Axes) -> Array:
    dt = x.dtype
    h = _act(cfg.act, jnp.einsum("bsd,df->bsf", x, p["w1"].astype(dt)))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w3"].astype(dt))
    h = ax.shard(h, ax.batch, None, ax.tp)
    return jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(dt))
