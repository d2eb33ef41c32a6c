"""The two Jamba pieces of ``ModelConfig`` against hand-written float32
formulas (RMSNorms on the Mamba mixer's dt, B and C; attention with no
positional encoding), and ``mamba_prefill`` at lengths its chunk does not
divide."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, RunConfig
from repro.launch.sharding import NO_AXES
from repro.models import init_tree
from repro.models import layers as L
from repro.models.ssm import _ssm_inputs, mamba_decode, mamba_prefill, \
    ssm_specs

CFG = ModelConfig(name="tiny-jamba", family="hybrid", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=1, d_ff=64,
                  vocab_size=64, head_dim=8, ssm_state=4, ssm_dt_rank=4,
                  attn_period=2, attn_offset=1, attn_rope=False,
                  ssm_dt_bc_norms=True, norm_eps=1e-6)
RC = RunConfig(remat="none", attn_impl="dense", compute_dtype="float32")


def _random(specs, seed):
    """Every leaf random (norm weights and biases included)."""
    tree = init_tree(specs, jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, jnp.float32) * 0.3 + (a if a.ndim > 1
                                                             else 0.0)
        for k, a in zip(keys, leaves)])


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def test_mixer_norms_follow_the_jamba_formulas():
    p = jax.tree.map(np.asarray, _random(ssm_specs(CFG), 0))
    assert {"dt_norm", "b_norm", "c_norm"} <= set(p)
    assert p["dt_norm"].shape == (CFG.dt_rank,)
    assert p["b_norm"].shape == p["c_norm"].shape == (CFG.ssm_state,)
    u = np.random.default_rng(0).standard_normal(
        (2, 5, CFG.d_inner)).astype(np.float32)
    delta, b, c, a = _ssm_inputs(CFG, p, jnp.asarray(u))
    r, n, eps = CFG.dt_rank, CFG.ssm_state, CFG.norm_eps
    proj = u @ p["x_proj"]
    dt = _rms(proj[..., :r], p["dt_norm"], eps)
    want_delta = np.logaddexp(0.0, dt @ p["dt_proj"] + p["dt_bias"])
    np.testing.assert_allclose(delta, want_delta, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(b, _rms(proj[..., r:r + n], p["b_norm"], eps),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c, _rms(proj[..., r + n:], p["c_norm"], eps),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a, -np.exp(p["A_log"]), rtol=1e-6)


def test_without_the_switch_the_mixer_has_no_norms():
    plain = dataclasses.replace(CFG, ssm_dt_bc_norms=False)
    assert not {"dt_norm", "b_norm", "c_norm"} & set(ssm_specs(plain))
    assert plain.param_counts()["total"] + CFG.n_layers // 2 * (
        CFG.dt_rank + 2 * CFG.ssm_state) == CFG.param_counts()["total"]


def test_attention_without_rope_is_plain_causal_mqa():
    p = jax.tree.map(np.asarray, _random(L.attn_specs(CFG), 2))
    x = np.random.default_rng(1).standard_normal(
        (1, 7, CFG.d_model)).astype(np.float32)
    got = np.asarray(L.attention(CFG, RC, p, jnp.asarray(x), NO_AXES))[0]
    q = np.einsum("sd,dhk->shk", x[0], p["wq"])
    k = np.einsum("sd,dhk->shk", x[0], p["wk"])[:, [0] * CFG.n_heads]
    v = np.einsum("sd,dhk->shk", x[0], p["wv"])[:, [0] * CFG.n_heads]
    s = np.einsum("shk,thk->hst", q, k) / math.sqrt(CFG.hd)
    s = np.where(np.tril(np.ones((7, 7), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("shk,hkd->sd", np.einsum("hst,thk->shk", pr, v),
                     p["wo"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # with RoPE the same weights give another answer: the switch is live
    roped = dataclasses.replace(CFG, attn_rope=True)
    other = np.asarray(L.attention(roped, RC, p, jnp.asarray(x), NO_AXES))
    assert np.abs(other[0] - want).max() > 1e-2


def _sequential(cfg, p, x):
    """The decode step applied token by token from a zero state."""
    b = x.shape[0]
    cache = {"h": jnp.zeros((b, cfg.d_inner, cfg.ssm_state), jnp.float32),
             "conv": jnp.zeros((b, cfg.ssm_conv - 1, cfg.d_inner),
                               x.dtype)}
    ys = []
    for t in range(x.shape[1]):
        y, cache = mamba_decode(cfg, p, x[:, t:t + 1], cache, NO_AXES)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), cache


@pytest.mark.parametrize("s,chunk", [(20, 8), (13, 4), (2, 8)])
def test_prefill_at_a_length_the_chunk_does_not_divide(s, chunk):
    """The padded last chunk's identity steps leave the state and every
    real output as the unpadded scan gives them (a prompt shorter than
    the conv window included)."""
    p = _random(ssm_specs(CFG), 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, s, CFG.d_model)) * 0.5
    y, cache = mamba_prefill(CFG, p, x, NO_AXES, chunk=chunk)
    y1, cache1 = mamba_prefill(CFG, p, x, NO_AXES, chunk=s)   # one chunk
    ys, caches = _sequential(CFG, p, x)
    for got in ((y, cache), (ys, caches)):
        np.testing.assert_allclose(got[0], y1, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(got[1]["h"], cache1["h"], rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(got[1]["conv"], cache1["conv"],
                                   rtol=1e-5, atol=1e-6)
