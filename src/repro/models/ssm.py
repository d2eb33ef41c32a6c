"""Mamba-1 selective SSM block (falcon-mamba, jamba's SSM layers).

Prefill uses a chunked parallel scan: the sequence is cut into chunks; inside
a chunk the recurrence h_t = a_t * h_{t-1} + b_t runs as a
``jax.lax.associative_scan`` (materializing only (B, chunk, D_inner, N)),
and the chunk boundary state is carried by an outer ``lax.scan``.  Decode is
the O(1) recurrent update against an (B, D_inner, N) state cache plus a
rolling depthwise-conv window.

The elementwise recurrence carries no collectives (d_inner is TP-sharded,
the scan is pointwise over it), so scan-body cost under-counting is bounded
by the tiny state math — see DESIGN.md §5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import Axes
from repro.models.layers import rmsnorm
from repro.models.params import Leaf, fan_in_scale

Array = jnp.ndarray


def ssm_specs(cfg: ModelConfig) -> dict:
    d, di, n, r, k = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                      cfg.ssm_conv)
    norms = {
        "dt_norm": Leaf((r,), ("dt_rank",), init="ones"),
        "b_norm": Leaf((n,), ("state",), init="ones"),
        "c_norm": Leaf((n,), ("state",), init="ones"),
    } if cfg.ssm_dt_bc_norms else {}
    return {
        "in_proj": Leaf((d, 2 * di), ("embed", "dinner"), scale=fan_in_scale(d)),
        "conv_w": Leaf((k, di), ("conv", "dinner"), scale=fan_in_scale(k)),
        "conv_b": Leaf((di,), ("dinner",), init="zeros"),
        "x_proj": Leaf((di, r + 2 * n), ("dinner", None),
                       scale=fan_in_scale(di)),
        "dt_proj": Leaf((r, di), ("dt_rank", "dinner"), scale=fan_in_scale(r)),
        "dt_bias": Leaf((di,), ("dinner",), init="zeros"),
        "A_log": Leaf((di, n), ("dinner", "state"), init="ones"),
        "D_skip": Leaf((di,), ("dinner",), init="ones"),
        "out_proj": Leaf((di, d), ("dinner", "embed"), scale=fan_in_scale(di)),
        **norms,
    }


def _conv_causal(x: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv over (B, S, Di) with kernel (K, Di)."""
    k = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(k):
        shift = k - 1 - i
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        out = out + xs * w[i]
    return out + b


def _ssm_inputs(cfg: ModelConfig, p: dict, u: Array):
    """u: (..., S, Di) post-conv activations -> (dt, B, C, A)."""
    n, r = cfg.ssm_state, cfg.dt_rank
    dt = u.dtype
    proj = jnp.einsum("...sd,dk->...sk", u, p["x_proj"].astype(dt))
    dt_raw, bmat, cmat = jnp.split(proj, [r, r + n], axis=-1)
    if cfg.ssm_dt_bc_norms:                  # jamba's mixer
        dt_raw = rmsnorm(p["dt_norm"], dt_raw, cfg.norm_eps)
        bmat = rmsnorm(p["b_norm"], bmat, cfg.norm_eps)
        cmat = rmsnorm(p["c_norm"], cmat, cfg.norm_eps)
    delta = jax.nn.softplus(
        jnp.einsum("...sr,rd->...sd", dt_raw, p["dt_proj"].astype(dt))
        + p["dt_bias"].astype(dt))                              # (...,S,Di)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                # (Di, N)
    return delta, bmat, cmat, a


def _scan_chunk(carry_h: Array, abar: Array, bbar: Array) -> tuple:
    """Associative scan of h_t = abar_t h_{t-1} + bbar_t inside one chunk.

    abar/bbar: (B, L, Di, N) fp32; carry_h: (B, Di, N).
    """
    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2
    a_cum, b_cum = jax.lax.associative_scan(combine, (abar, bbar), axis=1)
    h = a_cum * carry_h[:, None] + b_cum                        # (B,L,Di,N)
    return h[:, -1], h


def mamba_prefill(cfg: ModelConfig, p: dict, x: Array, ax: Axes,
                  chunk: int = 256):
    """x: (B, S, D) -> (y (B, S, D), decode-ready state cache).

    Any S: a last chunk that S does not fill is padded with identity steps
    (``delta`` = 0, so ``abar`` = 1 and ``bbar`` = 0), which leave the
    carried state as the last token left it and whose outputs are dropped.
    """
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.ssm_state
    dt = x.dtype
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt))
    u_pre, z = jnp.split(xz, 2, axis=-1)
    u_pre = ax.shard(u_pre, ax.batch, None, ax.tp)
    u = jax.nn.silu(_conv_causal(u_pre, p["conv_w"].astype(dt),
                                 p["conv_b"].astype(dt)))
    delta, bmat, cmat, a = _ssm_inputs(cfg, p, u)

    chunk = min(chunk, s)
    nchunks = -(-s // chunk)
    pad = nchunks * chunk - s

    def body(h, args):
        u_c, delta_c, b_c, c_c = args
        abar = jnp.exp(delta_c.astype(jnp.float32)[..., None] * a)
        bbar = (delta_c.astype(jnp.float32) * u_c.astype(jnp.float32)
                )[..., None] * b_c.astype(jnp.float32)[..., None, :]
        h_last, hs = _scan_chunk(h, abar, bbar)
        y = jnp.einsum("blin,bln->bli", hs, c_c.astype(jnp.float32))
        return h_last, y.astype(dt)

    def split_chunks(t):
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(b, nchunks, chunk, *t.shape[2:]).swapaxes(0, 1)

    h0 = jnp.zeros((b, di, n), jnp.float32)
    h_final, ys = jax.lax.scan(
        body, h0, (split_chunks(u), split_chunks(delta),
                   split_chunks(bmat), split_chunks(cmat)))
    y = ys.swapaxes(0, 1).reshape(b, nchunks * chunk, di)
    if pad:
        y = y[:, :s]
    y = y + u * p["D_skip"].astype(dt)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"].astype(dt))
    k = cfg.ssm_conv
    tail = u_pre[:, -(k - 1):]
    if s < k - 1:                # a shorter prompt's window is zero-led
        tail = jnp.pad(tail, ((0, 0), (k - 1 - s, 0), (0, 0)))
    cache = {"h": h_final,                                   # (B, Di, N)
             "conv": tail}                                   # (B, K-1, Di)
    return out, cache


def mamba_decode(cfg: ModelConfig, p: dict, x: Array, cache: dict, ax: Axes):
    """One-token recurrent step.  x: (B, 1, D); cache: {h, conv}."""
    b = x.shape[0]
    dt = x.dtype
    k = cfg.ssm_conv
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt))
    u_new, z = jnp.split(xz, 2, axis=-1)                     # (B,1,Di)
    window = jnp.concatenate([cache["conv"].astype(dt), u_new], axis=1)
    u = jnp.einsum("bki,ki->bi", window, p["conv_w"].astype(dt)) \
        + p["conv_b"].astype(dt)
    u = jax.nn.silu(u)[:, None]                              # (B,1,Di)
    delta, bmat, cmat, a = _ssm_inputs(cfg, p, u)
    abar = jnp.exp(delta.astype(jnp.float32)[..., None] * a)[:, 0]  # (B,Di,N)
    bbar = ((delta * u).astype(jnp.float32)[..., None]
            * bmat.astype(jnp.float32)[..., None, :])[:, 0]
    h = abar * cache["h"] + bbar
    y = jnp.einsum("bin,bn->bi", h, cmat[:, 0].astype(jnp.float32))
    y = y.astype(dt)[:, None] + u * p["D_skip"].astype(dt)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"].astype(dt))
    new_cache = {"h": h, "conv": window[:, 1:]}
    return out, new_cache
