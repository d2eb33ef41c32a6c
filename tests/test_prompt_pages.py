"""The jitted page allocation (``kvcache.allocate_prompt_pages`` and
``models/trace._decode_point_pages``) against the eager ``allocate_pages``
loop it replaced: the same arbiter rounds, the same page table, bit for
bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.trace import _decode_point_pages
from repro.serving.kvcache import (PagedKVConfig, allocate_pages,
                                   allocate_prompt_pages, init_pages,
                                   pool_pages)

BATCH, PAGE_LEN = 8, 4


def _eager(cfg, batch, max_seq, prompt_len):
    """One eager ``allocate_pages`` call per prompt page, then the decode
    step's page: returns (state after the prompt, state after the step)."""
    state = init_pages(cfg, batch, max_seq)
    ones = jnp.ones((batch,), bool)
    for p in range(-(-prompt_len // cfg.page_len)):
        state = state._replace(
            seq_lens=jnp.full((batch,), p * cfg.page_len, jnp.int32))
        state, _ = allocate_pages(cfg, state, ones)
    prompt = state._replace(
        seq_lens=jnp.full((batch,), prompt_len, jnp.int32))
    need = (prompt.seq_lens % cfg.page_len) == 0
    return prompt, allocate_pages(cfg, prompt, need)[0]


def _assert_same(got, want):
    for field in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)


@pytest.mark.parametrize("pool", ["roomy", "tight"])
@pytest.mark.parametrize("prompt_len", [12, 13])
@pytest.mark.parametrize("n_banks", [4, 8, 16])
@pytest.mark.parametrize("mapping", ["lsb", "offset", "xor", "fold"])
def test_jitted_allocation_equals_the_eager_loop(mapping, n_banks,
                                                 prompt_len, pool):
    max_seq = prompt_len + 1
    # a tight pool holds half a batch per bank: every lane asks for page p
    # in the same bank, so phase 1 grants half of each round and the rest
    # spills (and, on few banks, later rounds run the pool dry)
    n_pages = (pool_pages(n_banks, BATCH, max_seq, PAGE_LEN)
               if pool == "roomy" else n_banks * (BATCH // 2))
    cfg = PagedKVConfig(n_pages=n_pages, page_len=PAGE_LEN, n_banks=n_banks,
                        mapping=mapping, kv_heads=1, head_dim=1, map_shift=1)
    want_prompt, want = _eager(cfg, BATCH, max_seq, prompt_len)
    _assert_same(allocate_prompt_pages(cfg, BATCH, max_seq, prompt_len),
                 want_prompt)
    _assert_same(_decode_point_pages(cfg, BATCH, max_seq, prompt_len), want)
    pt = np.asarray(want.page_table)
    placed = pt >= 0
    if pool == "roomy":
        # every prompt page, and the decode step's on a page boundary
        assert (placed.sum(axis=1) == -(-prompt_len // PAGE_LEN)
                + (prompt_len % PAGE_LEN == 0)).all()
    else:
        lay = cfg.layout
        cols = np.broadcast_to(np.arange(pt.shape[1]), pt.shape)
        pref = np.asarray(lay.bank_slot(jnp.asarray(cols))[0])
        got = np.asarray(lay.bank_slot(jnp.asarray(np.maximum(pt, 0)))[0])
        assert (placed & (got != pref)).any()           # phase 2 ran


def test_price_step_shape_equals_the_eager_loop():
    """Batch 32 at position 4000 on ``16B-offset``: 500 prompt rounds and
    the decode step's page, one (32, 501) table."""
    cfg = PagedKVConfig.from_arch(
        "16B-offset", n_pages=pool_pages(16, 32, 4001, 8), page_len=8,
        kv_heads=1, head_dim=1)
    got = _decode_point_pages(cfg, 32, 4001, 4000)
    _assert_same(got, _eager(cfg, 32, 4001, 4000)[1])
    assert (np.asarray(got.page_table) >= 0).all()
