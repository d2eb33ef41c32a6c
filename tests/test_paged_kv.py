"""Banked paged-KV cache: allocation arbitration, logical page-id bijection,
roundtrip, bank balance, kernel-path equivalence."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.kvcache import (PagedKVConfig, allocate_pages,
                                   append_token, bank_load_stats, gather_kv,
                                   gather_pages, init_pages, init_state,
                                   pool_rows, scatter_pages,
                                   simulate_serving_trace)

CFG = PagedKVConfig(n_pages=64, page_len=4, n_banks=8, kv_heads=2, head_dim=4)


def test_append_gather_roundtrip():
    b, steps = 3, 10
    state = init_state(CFG, batch=b, max_seq=32, dtype=jnp.float32)
    ks = np.random.default_rng(0).standard_normal(
        (steps, b, CFG.kv_heads, CFG.head_dim)).astype(np.float32)
    for t in range(steps):
        state = append_token(CFG, state, jnp.asarray(ks[t]),
                             jnp.asarray(ks[t] * 2))
    k, v, valid = gather_kv(CFG, state, max_seq=16)
    assert k.shape == (b, 16, CFG.kv_heads, CFG.head_dim)
    np.testing.assert_array_equal(np.asarray(valid[:, :steps]), True)
    np.testing.assert_array_equal(np.asarray(valid[:, steps:]), False)
    got = np.asarray(k)[:, :steps]                      # (B, T, KV, HD)
    want = np.moveaxis(ks, 0, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v)[:, :steps], want * 2, rtol=1e-6)


def test_allocation_spreads_across_banks():
    """Same logical page index across a batch prefers ONE bank; the arbiter
    grants in order and capacity spills keep the pool balanced."""
    b = 16
    pages = init_pages(CFG, batch=b, max_seq=32)
    pages, ids = allocate_pages(CFG, pages, jnp.ones((b,), bool))
    assert int((ids >= 0).sum()) == b
    assert len(set(np.asarray(ids).tolist())) == b      # all distinct pages
    stats = bank_load_stats(pages)
    # 16 requests, all preferring bank 0 (logical page 0): 8 land in bank 0
    # up to capacity, the rest spill -> serialization bounded by capacity
    assert float(stats["max"]) <= CFG.pages_per_bank


def test_page_ids_are_bank_map_consistent():
    """The minted logical page id must map (via the arch bank map the cost
    model uses) to exactly the bank the arbiter granted — the invariant
    that makes serving AddressTraces honest."""
    for mapping in ("lsb", "offset", "xor", "fold"):
        cfg = PagedKVConfig(n_pages=64, page_len=4, n_banks=8,
                            mapping=mapping, kv_heads=1, head_dim=1,
                            map_shift=1)
        pages = init_pages(cfg, batch=12, max_seq=32)
        used_before = np.asarray(pages.bank_used)
        pages, ids = allocate_pages(cfg, pages, jnp.ones((12,), bool))
        got_banks = np.asarray(cfg.layout.bank_slot(jnp.asarray(ids))[0])
        counts = np.bincount(got_banks, minlength=cfg.n_banks)
        np.testing.assert_array_equal(
            counts, np.asarray(pages.bank_used) - used_before)


def test_page_table_unique_physical_pages():
    b = 4
    state = init_state(CFG, batch=b, max_seq=32)
    for t in range(24):     # 6 pages per sequence = 24 pages total
        k = jnp.ones((b, CFG.kv_heads, CFG.head_dim))
        state = append_token(CFG, state, k, k)
    pt = np.asarray(state.pages.page_table)
    mapped = pt[pt >= 0]
    assert len(mapped) == 4 * 6
    assert len(set(mapped.tolist())) == len(mapped)     # no aliasing
    # paper-style balance: 24 pages over 8 banks -> max 3-4 per bank
    assert float(bank_load_stats(state)["serialization"]) <= 1.5


@given(st.integers(1, 12), st.integers(1, 20))
@settings(max_examples=15, deadline=None)
def test_property_no_aliasing(batch, steps):
    cfg = PagedKVConfig(n_pages=128, page_len=2, n_banks=8, kv_heads=1,
                        head_dim=2)
    state = init_state(cfg, batch=batch, max_seq=64)
    for _ in range(steps):
        k = jnp.zeros((batch, 1, 2))
        state = append_token(cfg, state, k, k)
    pt = np.asarray(state.pages.page_table)
    mapped = pt[pt >= 0]
    assert len(set(mapped.tolist())) == len(mapped)
    assert int(state.pages.bank_used.sum()) == len(mapped)


def test_config_from_arch_derives_layout_from_core_arch():
    """Serving-side layout decisions come from repro.core.arch: the page
    pool's bank count / map / shift are the architecture's BankedLayout."""
    from repro.core import arch
    cfg = PagedKVConfig.from_arch("8B-xor", n_pages=64, page_len=4,
                                  kv_heads=2, head_dim=4)
    assert cfg.n_banks == 8 and cfg.mapping == "xor"
    lay = arch.get("8B-xor").layout
    r = jnp.arange(64)
    np.testing.assert_array_equal(np.asarray(cfg.layout.bank_slot(r)[0]),
                                  np.asarray(lay.bank_slot(r)[0]))
    # offset maps carry the architecture's calibrated shift (1, not the
    # bankmap default of 2)
    off = PagedKVConfig.from_arch("16B-offset", n_pages=64, page_len=4)
    assert off.map_shift == 1
    with pytest.raises(ValueError):
        PagedKVConfig.from_arch("4R-2W", n_pages=64, page_len=4)


def test_from_arch_pool_allocates_and_roundtrips():
    cfg = PagedKVConfig.from_arch("8B", n_pages=64, page_len=4, kv_heads=2,
                                  head_dim=4)
    state = init_state(cfg, batch=4, max_seq=16, dtype=jnp.float32)
    k = jnp.ones((4, 2, 4))
    for _ in range(6):
        state = append_token(cfg, state, k, k * 3)
    got_k, got_v, valid = gather_kv(cfg, state, max_seq=8)
    np.testing.assert_allclose(np.asarray(got_k[:, :6]), 1.0)
    np.testing.assert_allclose(np.asarray(got_v[:, :6]), 3.0)
    np.testing.assert_array_equal(np.asarray(valid[:, :6]), True)


@pytest.mark.parametrize("arch_name", ["8B-xor", "16B-offset", "4B"])
def test_kernel_gather_matches_reference_bitexact(arch_name):
    """The serving hot path (banked_gather on the bank-major page-slab view)
    returns bit-identical page lines to the reference 4-D pool — across
    page boundaries and for every bank map."""
    from repro.core import arch as A
    a = A.get(arch_name)
    cfg = PagedKVConfig.from_arch(a, n_pages=32, page_len=4, kv_heads=2,
                                  head_dim=4)
    state = init_state(cfg, batch=3, max_seq=24, dtype=jnp.float32)
    rng = np.random.default_rng(7)
    for _ in range(11):                       # crosses 2 page boundaries
        k = jnp.asarray(rng.standard_normal((3, 2, 4)), jnp.float32)
        state = append_token(cfg, state, k, k + 1)
    ref_k, ref_v, valid = gather_kv(cfg, state, max_seq=12)
    pt = state.pages.page_table[:, :3]
    ids = jnp.maximum(pt, 0).reshape(-1)
    got_k = np.asarray(gather_pages(a, cfg, pool_rows(state.k_pool), ids)
                       ).reshape(3, 12, 2, 4)
    got_v = np.asarray(gather_pages(a, cfg, pool_rows(state.v_pool), ids)
                       ).reshape(3, 12, 2, 4)
    np.testing.assert_array_equal(got_k, np.asarray(ref_k))   # bit-exact
    np.testing.assert_array_equal(got_v, np.asarray(ref_v))


def test_kernel_scatter_then_gather_roundtrip():
    """scatter_pages is the exact inverse path of gather_pages on the
    persistent bank-major pool."""
    from repro.core import arch as A
    a = A.get("8B-offset")
    cfg = PagedKVConfig.from_arch(a, n_pages=16, page_len=2, kv_heads=1,
                                  head_dim=4)
    pages = init_pages(cfg, batch=4, max_seq=8)
    pages, ids = allocate_pages(cfg, pages, jnp.ones((4,), bool))
    rows = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4,) + cfg.page_shape), jnp.float32)
    pool = jnp.zeros((cfg.n_pages,) + cfg.page_shape, jnp.float32)
    pool = scatter_pages(a, cfg, pool, ids, rows)
    back = gather_pages(a, cfg, pool, ids)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(rows))


def test_simulated_serving_trace_is_costable_everywhere():
    tr = simulate_serving_trace("16B", batch=4, prompt_len=16,
                                decode_steps=8, page_len=4, n_kv_layers=2)
    from repro.core import arch as A
    for name in ("16B", "16B-offset", "4R-1W", "4R-2W"):
        c = A.get(name).cost(tr)
        assert c.total_cycles > 0
    # non-banked archs lower through the canonical 16B-lsb pool
    tr_mp = simulate_serving_trace("4R-2W", batch=4, prompt_len=16,
                                   decode_steps=8, page_len=4)
    assert tr_mp.n_ops > 0
