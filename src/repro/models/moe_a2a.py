"""Expert-parallel MoE via shard_map all-to-all — the §Perf A2 lesson
("index dispatch needs a real all-to-all, not GSPMD scatter") implemented.

Layout (requires E % tp == 0 and S % tp == 0):

  * tokens arrive (B, S, D); inside shard_map each (data=i, model=j) device
    owns the (i, j) tile of a (batch × sequence) split — the model axis
    shards the SEQUENCE here (free sequence-parallelism at MoE boundaries);
  * each device routes its T_loc tokens with the arbiter math
    (grant_positions), scatters them into an (E, C, D) send buffer
    — banks = experts, exactly the paper's controller;
  * ``lax.all_to_all(split_axis=0, concat_axis=1)`` exchanges expert
    slices: every device ends with (E_loc, tp·C, D) — the tokens of ALL
    model-shards for ITS E/tp experts;
  * local expert FFN (weights FSDP-gathered over 'data'), reverse
    all_to_all, weighted combine.

Collective cost per layer ≈ 2 all-to-alls of (E, C_loc, D) + weight
gathers — no (G, S, E, C) dispatch products on the wire.  Equivalence vs
moe_gshard is asserted on a 4-device mesh in tests/test_moe_a2a.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.arbiter import grant_positions
from repro.launch.sharding import Axes
from repro.models.moe import capacity


def _smap(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


Array = jnp.ndarray


def a2a_applicable(cfg: ModelConfig, ax: Axes, seq_len: int) -> bool:
    tp = ax.size(ax.tp)
    if ax.mesh is None or tp <= 1 or seq_len % tp != 0:
        return False
    # E ≥ tp: E/tp experts per device; E < tp: tp/E devices co-own one
    # expert via capacity-split virtual experts.
    return cfg.n_experts % tp == 0 or tp % cfg.n_experts == 0


def moe_a2a(cfg: ModelConfig, p: dict, x: Array, ax: Axes):
    """x: (B, S, D) -> ((B, S, D), aux).  Caller guards a2a_applicable.

    E ≥ tp: classic EP (E/tp experts per device).  E < tp: each expert is
    co-owned by r = tp/E devices as r *virtual experts* that split its
    capacity (request pos c goes to virtual copy c % r at slot c // r) —
    the arbiter math untouched, weights replicated r-ways (sliced before
    the FSDP row-gather, so only ONE expert's weights materialize).
    """
    mesh = ax.mesh
    tp_axis = ax.tp
    tp = ax.size(tp_axis)
    e, k = cfg.n_experts, cfg.experts_per_token
    r = max(1, tp // e)                      # devices per expert
    b, s, d = x.shape
    bspec = ax.resolve(("batch",), (b,))[0]
    all_axes = tuple(mesh.axis_names)
    split_experts = r > 1

    def inner(router, w1, w2, w3, x_loc):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        cap = capacity(cfg, t)
        cap = -(-cap // r) * r               # divisible by the split
        cap_v = cap // r
        dt = x_loc.dtype
        xt = x_loc.reshape(t, d)

        # ---- routing (router rows are FSDP-sharded on 'data') ----
        router_f = lax.all_gather(router, "data", axis=0, tiled=True)
        logits = xt.astype(jnp.float32) @ router_f.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        # Switch aux loss with globally-pmean'd statistics (frac, mean_p
        # averaged over ALL tokens before the product — matches gshard)
        frac = lax.pmean(jnp.mean(
            jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0),
            all_axes)
        mean_p = lax.pmean(jnp.mean(probs, axis=0), all_axes)
        aux = e * jnp.sum(frac * mean_p)

        # ---- arbiter dispatch into the (E·r, C/r, D) send buffer ----
        req_e = jnp.transpose(top_e, (1, 0)).reshape(k * t)  # priority order
        pos = grant_positions(req_e, e)
        kept = pos < cap
        vexp = req_e * r + pos % r           # virtual expert (r=1: = req_e)
        vpos = pos // r
        n_v = e * r                          # == tp when split
        slot = jnp.where(kept, vexp * cap_v + vpos, n_v * cap_v)
        xrep = jnp.tile(xt, (k, 1))                          # (k·t, D)
        buf = jnp.zeros((n_v * cap_v + 1, d), dt).at[slot].set(
            xrep, mode="drop")[:-1].reshape(n_v, cap_v, d)

        # ---- exchange: (V, Cv, D) -> (V/tp, tp·Cv, D) ----
        recv = lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=1,
                              tiled=True)

        # ---- local expert FFN ----
        act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
        if split_experts:
            # device j serves expert j // r: slice BEFORE the row-gather so
            # only one expert's weights materialize per device
            j = lax.axis_index(tp_axis)
            own = j // r
            w1o = lax.dynamic_index_in_dim(w1, own, 0, keepdims=False)
            w3o = lax.dynamic_index_in_dim(w3, own, 0, keepdims=False)
            w2o = lax.dynamic_index_in_dim(w2, own, 0, keepdims=False)
            w1f = lax.all_gather(w1o, "data", axis=0, tiled=True).astype(dt)
            w3f = lax.all_gather(w3o, "data", axis=0, tiled=True).astype(dt)
            w2f = lax.all_gather(w2o, "data", axis=1, tiled=True).astype(dt)
            xin = recv.reshape(tp * cap_v, d)       # (tp·Cv, D) one vexpert
            h = act(xin @ w1f) * (xin @ w3f)
            out = (h @ w2f).reshape(1, tp * cap_v, d)
        else:
            w1f = lax.all_gather(w1, "data", axis=1, tiled=True).astype(dt)
            w3f = lax.all_gather(w3, "data", axis=1, tiled=True).astype(dt)
            w2f = lax.all_gather(w2, "data", axis=2, tiled=True).astype(dt)
            h = act(jnp.einsum("ecd,edf->ecf", recv, w1f))
            h = h * jnp.einsum("ecd,edf->ecf", recv, w3f)
            out = jnp.einsum("ecf,efd->ecd", h, w2f)

        # ---- reverse exchange + combine ----
        back = lax.all_to_all(out, tp_axis, split_axis=1, concat_axis=0,
                              tiled=True)
        flat = jnp.concatenate(
            [back.reshape(n_v * cap_v, d), jnp.zeros((1, d), dt)], axis=0)
        got = flat[slot].reshape(k, t, d)
        w = (top_p * kept.reshape(k, t).T).astype(dt)        # (t, k)
        y = jnp.einsum("ktd,tk->td", got, w)
        return y.reshape(bl, sl, d), aux

    if split_experts:
        # weights replicated over 'model' (sliced per-device inside),
        # FSDP rows on 'data'
        wspecs = (P(None, "data", None), P(None, None, "data"),
                  P(None, "data", None))
    else:
        wspecs = (P(tp_axis, "data", None), P(tp_axis, None, "data"),
                  P(tp_axis, "data", None))
    in_specs = (P("data", None), wspecs[0], wspecs[1], wspecs[2],
                P(bspec, tp_axis, None))
    out_specs = (P(bspec, tp_axis, None), P())
    y, aux = _smap(inner, mesh, in_specs, out_specs)(
        p["router"], p["w1"], p["w2"], p["w3"], x)
    return y, aux
