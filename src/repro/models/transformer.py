"""Model assembly: parameter/cache spec trees, scanned super-block stacks,
train forward + loss, prefill, and single-token decode for every assigned
architecture family (dense / moe / ssm / hybrid / audio / vlm).

The layer stack is organized as ``n_superblocks`` scanned repetitions of the
config's ``block_pattern()`` (e.g. jamba: 7×mamba+1×attn with MoE every 2nd
layer => an 8-layer pattern scanned 4×; gemma2: (local, global) scanned 21×).
Scanning keeps HLO compact; the dry-run's cost accounting compensates for
while-body single-counting (launch/roofline.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.launch.sharding import Axes
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.params import Leaf, fan_in_scale, stack_specs

Array = jnp.ndarray
AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# parameter / cache specs
# ---------------------------------------------------------------------------

def block_specs(cfg: ModelConfig, kind: str, is_moe: bool) -> dict:
    p = {"ln1": L.rmsnorm_spec(cfg.d_model),
         "ln2": L.rmsnorm_spec(cfg.d_model)}
    p["mixer"] = L.attn_specs(cfg) if kind == "attn" else S.ssm_specs(cfg)
    p["ffn"] = M.moe_specs(cfg) if is_moe else L.mlp_specs(cfg)
    if cfg.post_block_norms:
        p["ln1_post"] = L.rmsnorm_spec(cfg.d_model)
        p["ln2_post"] = L.rmsnorm_spec(cfg.d_model)
    return p


def model_specs(cfg: ModelConfig) -> dict:
    vp, d = cfg.padded_vocab(), cfg.d_model
    specs = {
        "embed": Leaf((vp, d), ("vocab", "embed"), scale=1.0),
        "final_norm": L.rmsnorm_spec(d),
        "blocks": {},
    }
    for j, (kind, is_moe) in enumerate(cfg.block_pattern()):
        specs["blocks"][f"b{j}"] = stack_specs(
            block_specs(cfg, kind, is_moe), cfg.n_superblocks)
    if not cfg.tie_embeddings:
        specs["lm_head"] = Leaf((d, vp), ("embed", "vocab"),
                                scale=fan_in_scale(d))
    return specs


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                stacked: bool = True) -> dict:
    """Decode-state spec tree (KV / SSM caches), logical-axes tagged.
    stacked=False returns one superblock's slice (dry-run block module)."""
    kvh, hd = cfg.n_kv_heads, cfg.hd
    di, n, k = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    blocks = {}
    for j, (kind, _) in enumerate(cfg.block_pattern()):
        if kind == "attn":
            t = min(seq_len, cfg.sliding_window) if cfg.sliding_window \
                else seq_len
            if cfg.local_global and j % 2 == 0:
                t = min(seq_len, cfg.local_window)
            leaf = {"k": Leaf((batch, t, kvh, hd),
                              ("batch", "seq", "kv_heads", "head_dim"),
                              init="zeros"),
                    "v": Leaf((batch, t, kvh, hd),
                              ("batch", "seq", "kv_heads", "head_dim"),
                              init="zeros")}
        else:
            leaf = {"h": Leaf((batch, di, n), ("batch", "dinner", "state"),
                              init="zeros"),
                    "conv": Leaf((batch, k - 1, di),
                                 ("batch", "conv", "dinner"), init="zeros")}
        if stacked:
            leaf = stack_specs(leaf, cfg.n_superblocks)
        blocks[f"b{j}"] = leaf
    return {"blocks": blocks}


def superblock_param_specs(cfg: ModelConfig) -> tuple:
    """One (unstacked) superblock's parameter slice, as scanned xs see it."""
    return tuple(block_specs(cfg, kind, is_moe)
                 for kind, is_moe in cfg.block_pattern())


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _block_window(cfg: ModelConfig, j: int) -> int:
    if cfg.local_global:
        return cfg.local_window if j % 2 == 0 else 0
    return cfg.sliding_window


def apply_block(cfg: ModelConfig, rc: RunConfig, p: dict, x: Array, ax: Axes,
                kind: str, is_moe: bool, j: int,
                positions: Optional[Array] = None):
    """Pre-norm residual block; returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        h = L.attention(cfg, rc, p["mixer"], h, ax,
                        window=_block_window(cfg, j), positions=positions)
    else:
        h, _ = S.mamba_prefill(cfg, p["mixer"], h, ax)
    if cfg.post_block_norms:
        h = L.rmsnorm(p["ln1_post"], h, cfg.norm_eps)
    x = ax.act(x + h)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if is_moe:
        h, aux = M.moe(cfg, rc, p["ffn"], h, ax)
    else:
        h = L.mlp(cfg, p["ffn"], h, ax)
    if cfg.post_block_norms:
        h = L.rmsnorm(p["ln2_post"], h, cfg.norm_eps)
    x = ax.act(x + h)
    return x, aux


def apply_block_decode(cfg: ModelConfig, rc: RunConfig, p: dict, x: Array,
                       cache: dict, pos: Array, ax: Axes,
                       kind: str, is_moe: bool, j: int, attn_fn=None):
    """One block's decode step.  ``attn_fn`` swaps the attention-cache
    implementation (same signature as ``L.attention_decode``) — the serving
    engine's banked paged-KV path plugs in here, reusing the block's
    residual/FFN structure unchanged."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        h, new_cache = (attn_fn or L.attention_decode)(
            cfg, p["mixer"], h, cache, pos, ax,
            window=_block_window(cfg, j))
    else:
        h, new_cache = S.mamba_decode(cfg, p["mixer"], h, cache, ax)
    if cfg.post_block_norms:
        h = L.rmsnorm(p["ln1_post"], h, cfg.norm_eps)
    x = x + h
    with jax.named_scope("mlp"):
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        h = M.moe(cfg, rc, p["ffn"], h, ax)[0] if is_moe \
            else L.mlp(cfg, p["ffn"], h, ax)
        if cfg.post_block_norms:
            h = L.rmsnorm(p["ln2_post"], h, cfg.norm_eps)
    return x + h, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _remat(rc: RunConfig, fn):
    if rc.remat == "none":
        return fn
    if rc.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _embed(cfg: ModelConfig, params: dict, tokens: Array,
           frontend: Optional[Array], dtype) -> Array:
    x = params["embed"].astype(dtype)[tokens]
    if cfg.frontend:
        assert frontend is not None, f"{cfg.name} needs frontend embeddings"
        x = jnp.concatenate([frontend.astype(dtype), x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return x


def _unembed(cfg: ModelConfig, params: dict, x: Array) -> Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["lm_head"].astype(x.dtype))
    logits = L.softcap(logits, cfg.final_softcap)
    vp = cfg.padded_vocab()
    if vp != cfg.vocab_size:  # mask padded vocab rows
        mask = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(mask, logits, L.NEG_INF)
    return logits


def forward(cfg: ModelConfig, rc: RunConfig, params: dict, tokens: Array,
            ax: Axes, frontend: Optional[Array] = None):
    """Training/scoring forward pass -> (logits (B, S, Vp), aux_loss)."""
    x, aux = hidden_states(cfg, rc, params, tokens, ax, frontend)
    return _unembed(cfg, params, x), aux


def hidden_states(cfg: ModelConfig, rc: RunConfig, params: dict,
                  tokens: Array, ax: Axes,
                  frontend: Optional[Array] = None):
    """Shared trunk: final-norm'd hidden states (B, S, D) + MoE aux."""
    dtype = jnp.dtype(rc.compute_dtype)
    x = ax.act(_embed(cfg, params, tokens, frontend, dtype))
    pattern = cfg.block_pattern()
    positions = jnp.arange(x.shape[1])

    def superblock(carry, block_params):
        x, aux = carry
        for j, (kind, is_moe) in enumerate(pattern):
            x, a = apply_block(cfg, rc, block_params[j], x, ax, kind,
                               is_moe, j, positions)
            aux = aux + a
        return (x, aux), None

    sb = _remat(rc, superblock)
    xs = tuple(params["blocks"][f"b{j}"] for j in range(len(pattern)))
    (x, aux), _ = jax.lax.scan(sb, (x, jnp.zeros((), jnp.float32)), xs)
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def _sharded_ce(cfg: ModelConfig, params: dict, h: Array, target: Array,
                ax: Axes) -> Array:
    """Vocab-TP cross-entropy: logits stay sharded over the model axis; the
    target logit comes from a row-gather, never from full-logit indexing.
    Memory: O(B·T·V/tp) transient instead of O(B·T·V) (§Perf iteration 1)."""
    if cfg.tie_embeddings:
        w = params["embed"]                      # (Vp, D)
        logits = jnp.einsum("btd,vd->btv", h, w.astype(h.dtype))
        tvec = w[target].astype(h.dtype)         # (B, T, D)
    else:
        w = params["lm_head"]                    # (D, Vp)
        logits = jnp.einsum("btd,dv->btv", h, w.astype(h.dtype))
        tvec = w.T[target].astype(h.dtype)
    logits = ax.shard(logits, ax.batch, None, ax.tp)   # vocab stays sharded
    logits = L.softcap(logits, cfg.final_softcap).astype(jnp.float32)
    vp = cfg.padded_vocab()
    if vp != cfg.vocab_size:
        logits = logits + jnp.where(jnp.arange(vp) < cfg.vocab_size,
                                    0.0, L.NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)            # (B, T) — psum'd stats
    tl = jnp.sum(h.astype(jnp.float32) * tvec.astype(jnp.float32), axis=-1)
    tl = L.softcap(tl, cfg.final_softcap) if cfg.final_softcap else tl
    return (lse - tl).mean()


def loss_fn(cfg: ModelConfig, rc: RunConfig, params: dict, batch: dict,
            ax: Axes):
    """Next-token cross-entropy (+ MoE aux) over the text region."""
    tokens = batch["tokens"]
    f = cfg.n_frontend_tokens if cfg.frontend else 0
    if rc.ce_impl == "sharded":
        h, aux = hidden_states(cfg, rc, params, tokens, ax,
                               batch.get("frontend"))
        pred_h = h[:, f - 1:-1] if f else h[:, :-1]
        target = tokens if f else tokens[:, 1:]
        loss = _sharded_ce(cfg, params, pred_h, target, ax)
    else:
        logits, aux = forward(cfg, rc, params, tokens, ax,
                              batch.get("frontend"))
        pred = logits[:, f - 1:-1] if f else logits[:, :-1]
        target = tokens if f else tokens[:, 1:]
        logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, target[..., None],
                                    axis=-1)[..., 0].mean()
    return loss + AUX_LOSS_COEF * aux, {"loss": loss, "aux": aux}


def prefill(cfg: ModelConfig, rc: RunConfig, params: dict, tokens: Array,
            ax: Axes, frontend: Optional[Array] = None):
    """Inference prefill: returns (last-position logits, decode cache)."""
    dtype = jnp.dtype(rc.compute_dtype)
    x = ax.act(_embed(cfg, params, tokens, frontend, dtype))
    pattern = cfg.block_pattern()
    positions = jnp.arange(x.shape[1])
    b, s = x.shape[:2]

    def superblock(x, block_params):
        caches = {}
        for j, (kind, is_moe) in enumerate(pattern):
            p = block_params[j]
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            if kind == "attn":
                w = _block_window(cfg, j)
                t = min(s, w) if w else s
                assert s % t == 0, "ring cache needs seq % window == 0"
                h, (k, v) = L.attention(cfg, rc, p["mixer"], h, ax, window=w,
                                        positions=positions, return_kv=True)
                caches[f"b{j}"] = {"k": k[:, -t:], "v": v[:, -t:]}
            else:
                h, sc = S.mamba_prefill(cfg, p["mixer"], h, ax)
                caches[f"b{j}"] = sc
            if cfg.post_block_norms:
                h = L.rmsnorm(p["ln1_post"], h, cfg.norm_eps)
            x = ax.act(x + h)
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            h = M.moe(cfg, rc, p["ffn"], h, ax)[0] if is_moe \
                else L.mlp(cfg, p["ffn"], h, ax)
            if cfg.post_block_norms:
                h = L.rmsnorm(p["ln2_post"], h, cfg.norm_eps)
            x = ax.act(x + h)
        return x, caches

    xs = tuple(params["blocks"][f"b{j}"] for j in range(len(pattern)))
    x, caches = jax.lax.scan(superblock, x, xs)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(cfg, params, x[:, -1:])
    return logits, {"blocks": caches}


def decode_step(cfg: ModelConfig, rc: RunConfig, params: dict, token: Array,
                cache: dict, pos: Array, ax: Axes):
    """One decode step.  token: (B, 1) int32; pos: () int32 current position.
    Returns (logits (B, 1, Vp), new cache)."""
    dtype = jnp.dtype(rc.compute_dtype)
    x = params["embed"].astype(dtype)[token]
    pattern = cfg.block_pattern()

    def superblock(x, args):
        block_params, block_cache = args
        new_caches = {}
        for j, (kind, is_moe) in enumerate(pattern):
            x, nc = apply_block_decode(cfg, rc, block_params[j], x,
                                       block_cache[f"b{j}"], pos, ax,
                                       kind, is_moe, j)
            new_caches[f"b{j}"] = nc
        return x, new_caches

    xs_p = tuple(params["blocks"][f"b{j}"] for j in range(len(pattern)))
    x, new_cache = jax.lax.scan(superblock, x, (xs_p, cache["blocks"]))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _unembed(cfg, params, x), {"blocks": new_cache}
