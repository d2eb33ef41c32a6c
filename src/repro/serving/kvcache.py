"""Banked paged KV cache — the paper's shared-memory banking applied to
serving state, end-to-end (docs/SERVING.md is the narrative version).

Pages are the banked unit.  The cache is a pool of fixed-size pages stored
*bank-major* (physical page ``bank · pages_per_bank + slot``), exactly the
storage layout ``repro.core.arch.BankedLayout`` defines for the FPGA memory
and the Pallas kernels.  A page table maps (sequence, logical-in-sequence
page) → *logical pool page id*; the id is minted with
``BankedLayout.logical_row(bank, slot)`` — the inverse bank map — so that

  * ``kernels.get("banked_gather") / banked_scatter`` resolve the id to the
    physical page through the very same index-map math, and
  * the cost model's bank maps (``arch.cost`` on an ``AddressTrace`` of page
    ids) see the bank the allocator actually placed the page in.

Allocation is the carry-chain arbiter at page granularity: a batch of
sequences requesting new pages forms a request vector per bank; grant order
(= exclusive cumsum) assigns each request the next free slot in its bank,
and requests beyond a bank's free capacity spill to the least-loaded bank
(the TPU can't stall — same capacity reasoning as MoE dispatch).

Three access paths share the layout:

  * kernel path (the serving hot path): ``gather_pages`` / ``scatter_pages``
    call the registry kernels on a persistent bank-major page-slab pool
    (``table_banked=True`` — no per-call relayout);
  * reference path: ``append_token`` / ``gather_kv`` are the pure-jnp oracle
    on a 4-D pool, used by tests to pin the kernel path bit-exactly;
  * trace path: ``decode_step_trace`` / ``prefill_trace`` /
    ``simulate_serving_trace`` lower the same request streams to
    ``repro.core.trace.AddressTrace`` via the kernels' own trace generators,
    so ``arch.cost(trace)`` prices serving traffic the same way it prices
    the Table II/III kernels.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arbiter import grant_positions
from repro.core.conflicts import bank_counts

Array = jnp.ndarray

__all__ = [
    "PagedKVConfig", "PageTableState", "PagedKVState",
    "pool_pages", "init_pages", "init_state", "allocate_pages",
    "allocate_prompt_pages", "append_token", "gather_kv", "bank_load_stats",
    "pool_rows", "gather_pages", "scatter_pages",
    "kv_read_stream", "decode_step_trace", "prefill_trace",
    "simulate_serving_trace", "simulate_serving_stream",
    "ALLOC_POLICIES", "preferred_banks", "resolve_policy",
]


# --------------------------------------------------------------------------
# preferred-bank allocation policies
# --------------------------------------------------------------------------

#: preferred-bank policies: ``(map_bank, seq_key, n_banks) -> bank``.
#: ``map_bank`` is the architecture's bank map applied to the in-sequence
#: page index; ``seq_key`` identifies the requesting sequence (lane index in
#: the fixed-batch allocator, request id in the continuous-batching
#: scheduler).  Works on python ints, numpy and jnp arrays alike — the same
#: formula drives both the jit'd batch allocator and the host-side scheduler
#: pool (repro/serving/scheduler.py).
#:
#:   * ``"paper"``    — every sequence prefers ``map_bank`` for page index k
#:     (the pre-scheduler behavior): same-index pages of concurrent
#:     sequences all contend for one bank at allocation time, so the
#:     same-position page scatter of a batch decode step serializes.
#:   * ``"seq-skew"`` — rotate the preferred bank by the sequence key:
#:     same-index pages of different sequences land ``seq_key`` banks apart,
#:     de-conflicting both the allocation batch and the same-position
#:     read/write ops (docs/SERVING.md has the 16B-xor worked example).
ALLOC_POLICIES = {
    "paper": lambda bank, seq_key, n_banks: bank,
    "seq-skew": lambda bank, seq_key, n_banks: (bank + seq_key) % n_banks,
}


def resolve_policy(policy):
    """A policy name or callable -> the ``(bank, seq_key, n_banks) -> bank``
    callable (names come from ``ALLOC_POLICIES``)."""
    if callable(policy):
        return policy
    try:
        return ALLOC_POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown allocation policy {policy!r}; choose from "
            f"{tuple(ALLOC_POLICIES)} or pass a callable") from None


def preferred_banks(layout, page_idx, seq_key, policy="paper"):
    """The bank each (sequence, in-sequence page index) request prefers:
    the arch's bank map on the page index, skewed by the policy.  Pure
    arithmetic — vectorized over numpy or jnp inputs."""
    bank, _ = layout.bank_slot(page_idx)
    return resolve_policy(policy)(bank, seq_key, layout.n_banks)


def pool_pages(n_banks: int, batch: int, max_seq: int, page_len: int,
               slack: int = 2) -> int:
    """Physical pool size: ``slack``× the worst-case live pages of a
    (batch, max_seq) budget, rounded up to a whole number of banks."""
    pages_per_seq = -(-max_seq // page_len)
    n = slack * batch * pages_per_seq
    return -(-n // n_banks) * n_banks


@dataclass(frozen=True)
class PagedKVConfig:
    n_pages: int            # physical pool size (multiple of n_banks)
    page_len: int           # tokens per page
    n_banks: int = 16
    mapping: str = "lsb"
    kv_heads: int = 8
    head_dim: int = 128
    map_shift: int = 2      # offset-map bank-bit position (bankmap default)

    @classmethod
    def from_arch(cls, arch, n_pages: int, page_len: int,
                  kv_heads: int = 8, head_dim: int = 128) -> "PagedKVConfig":
        """Derive the page-pool banking from a ``MemoryArchitecture`` (name,
        spec, or object) — the serving-side layout decision comes from
        ``repro.core.arch``, not local constants."""
        from repro.core import arch as _arch
        a = _arch.resolve(arch)
        lay = a.layout
        if lay is None:
            raise ValueError(
                f"{a.name} has no banked layout to derive a KV page map "
                f"from; use a banked architecture (e.g. '16B-offset')")
        return cls(n_pages=n_pages, page_len=page_len, n_banks=lay.n_banks,
                   mapping=lay.mapping, kv_heads=kv_heads, head_dim=head_dim,
                   map_shift=lay.shift)

    @property
    def layout(self):
        """The ``BankedLayout`` this pool implements (single source of truth
        for page↔(bank, slot) math, shared with the FPGA simulator and the
        Pallas kernels)."""
        from repro.core.arch import BankedLayout
        return BankedLayout(self.n_banks, self.mapping, self.map_shift)

    @property
    def pages_per_bank(self) -> int:
        return self.n_pages // self.n_banks

    @property
    def page_shape(self) -> tuple[int, int]:
        """One page of a serving pool: ``page_len`` token rows of
        ``kv_heads·head_dim`` words.  Pools are ``(n_pages,) + page_shape``,
        the banked kernels' row-slab view, which a TPU kernel reads in the
        array's default layout (no relayout copy per call)."""
        return (self.page_len, self.kv_heads * self.head_dim)


class PageTableState(NamedTuple):
    """Allocation state (a pytree — lives inside the jit'd decode step).

    ``page_table`` holds *logical pool page ids* (-1 = unmapped): the very
    addresses the gather/scatter kernels and the cost model consume.
    """
    page_table: Array       # (B, max_pages) int32 logical ids (-1 unmapped)
    seq_lens: Array         # (B,) int32 tokens written per sequence
    bank_used: Array        # (n_banks,) int32 allocated pages per bank


class PagedKVState(NamedTuple):
    """Reference-path cache state: dense 4-D pools + the page table."""
    k_pool: Array           # (n_pages, page_len, KV, HD) bank-major pages
    v_pool: Array
    pages: PageTableState


def init_pages(cfg: PagedKVConfig, batch: int,
               max_seq: int) -> PageTableState:
    assert cfg.n_pages % cfg.n_banks == 0
    max_pages = -(-max_seq // cfg.page_len)
    return PageTableState(
        page_table=jnp.full((batch, max_pages), -1, jnp.int32),
        seq_lens=jnp.zeros((batch,), jnp.int32),
        bank_used=jnp.zeros((cfg.n_banks,), jnp.int32),
    )


def init_state(cfg: PagedKVConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> PagedKVState:
    shape = (cfg.n_pages, cfg.page_len, cfg.kv_heads, cfg.head_dim)
    return PagedKVState(
        k_pool=jnp.zeros(shape, dtype),
        v_pool=jnp.zeros(shape, dtype),
        pages=init_pages(cfg, batch, max_seq),
    )


def allocate_pages(cfg: PagedKVConfig, state: PageTableState,
                   need: Array, policy="paper") -> tuple[PageTableState,
                                                         Array]:
    """Allocate one page for every sequence with need[b]=True.

    Phase 1 (the arbiter): preferred bank = ``policy`` applied to
    bank_map(in-sequence page index) and the lane index (the free-page
    selection hook — ``"paper"`` keeps the pre-policy behavior, every lane
    preferring the same bank for page k; ``"seq-skew"`` rotates by lane so
    concurrent same-index pages stop contending; see ``ALLOC_POLICIES``);
    grant order = exclusive cumsum per bank; grants within the bank's free
    capacity succeed.  Phase 2 (capacity spill — TPUs can't stall): the
    remaining requests take slots from the global free list, least-loaded
    banks first, via a searchsorted over cumulative free counts (the sort
    is stable, so equal-load ties always break toward the lowest bank
    index — allocation is fully deterministic).  Succeeds while any free
    page exists.

    Returns (new state, (B,) logical pool page ids or -1).  The id is
    minted via ``BankedLayout.logical_row(bank, slot)``, so the arch's bank
    map on the id recovers exactly the bank the arbiter granted.
    """
    b = need.shape[0]
    cap = cfg.pages_per_bank
    lay = cfg.layout
    logical = state.seq_lens // cfg.page_len            # next in-seq page
    pref_bank = preferred_banks(lay, logical, jnp.arange(b), policy)
    need_i = need.astype(jnp.int32)

    # phase 1: arbiter grants at the preferred bank
    pos1 = grant_positions(pref_bank, cfg.n_banks, mask=need_i)
    slot1 = state.bank_used[pref_bank] + pos1
    ok1 = need & (slot1 < cap)
    used1 = state.bank_used + bank_counts(pref_bank, cfg.n_banks,
                                          mask=ok1.astype(jnp.int32))

    # phase 2: spill to the global free list (least-loaded banks first)
    overflow = need & ~ok1
    rank = jnp.cumsum(overflow.astype(jnp.int32)) - overflow  # 0-based
    order = jnp.argsort(used1, stable=True)             # ascending load
    free_sorted = (cap - used1)[order]
    cum = jnp.cumsum(free_sorted)
    sidx = jnp.searchsorted(cum, rank, side="right")
    sidx_c = jnp.clip(sidx, 0, cfg.n_banks - 1)
    bank2 = order[sidx_c]
    prev = cum[sidx_c] - free_sorted[sidx_c]
    slot2 = used1[bank2] + (rank - prev)
    ok2 = overflow & (rank < cum[-1]) & (slot2 < cap)

    bank = jnp.where(ok1, pref_bank, bank2)
    slot = jnp.where(ok1, slot1, slot2)
    ok = ok1 | ok2
    page_id = jnp.where(ok, lay.logical_row(bank, slot), -1)

    counts = bank_counts(bank, cfg.n_banks, mask=ok.astype(jnp.int32))
    new_used = state.bank_used + counts
    pt = state.page_table.at[jnp.arange(b), logical].set(
        jnp.where(ok, page_id, state.page_table[jnp.arange(b), logical]))
    return PageTableState(pt, state.seq_lens, new_used), page_id


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def allocate_prompt_pages(cfg: PagedKVConfig, batch: int, max_seq: int,
                          prompt_len: int) -> PageTableState:
    """A fresh page table with every prompt page of ``batch`` sequences of
    ``prompt_len`` tokens allocated, and ``seq_lens = prompt_len``.

    Round ``p`` asks ``allocate_pages`` for every lane's page ``p`` (at
    ``seq_lens = p · page_len``); the rounds run as a ``lax.fori_loop`` in
    one jitted call: one device dispatch, one compile per (config, batch,
    max_seq, prompt_len)."""
    ones = jnp.ones((batch,), bool)

    def round_(p, state):
        state = state._replace(
            seq_lens=jnp.full((batch,), p * cfg.page_len, jnp.int32))
        return allocate_pages(cfg, state, ones)[0]

    state = jax.lax.fori_loop(0, -(-prompt_len // cfg.page_len), round_,
                              init_pages(cfg, batch, max_seq))
    return state._replace(seq_lens=jnp.full((batch,), prompt_len,
                                            jnp.int32))


def _physical(cfg: PagedKVConfig, page_id: Array) -> Array:
    """Logical pool page id -> bank-major physical page (storage row)."""
    return cfg.layout.physical_row(page_id, cfg.n_pages)


# --------------------------------------------------------------------------
# reference path (pure jnp; the oracle the kernel path is pinned against)
# --------------------------------------------------------------------------

def append_token(cfg: PagedKVConfig, state: PagedKVState, k: Array,
                 v: Array) -> PagedKVState:
    """Write one token's (B, KV, HD) K/V at each sequence's current position,
    allocating pages on page boundaries (reference write path)."""
    bsz = k.shape[0]
    pages = state.pages
    need = (pages.seq_lens % cfg.page_len) == 0
    pages, _ = allocate_pages(cfg, pages, need)
    logical = pages.seq_lens // cfg.page_len
    page_id = pages.page_table[jnp.arange(bsz), logical]
    phys = _physical(cfg, page_id)
    off = pages.seq_lens % cfg.page_len
    k_pool = state.k_pool.at[phys, off].set(k.astype(state.k_pool.dtype))
    v_pool = state.v_pool.at[phys, off].set(v.astype(state.v_pool.dtype))
    return PagedKVState(k_pool, v_pool,
                        PageTableState(pages.page_table, pages.seq_lens + 1,
                                       pages.bank_used))


def gather_kv(cfg: PagedKVConfig, state: PagedKVState,
              max_seq: int) -> tuple[Array, Array, Array]:
    """Materialize (B, max_seq, KV, HD) K/V + validity mask from the pool
    (the jnp reference path; ``gather_pages`` is the kernel hot path for
    the same physical layout)."""
    pages = state.pages
    bsz, max_pages = pages.page_table.shape
    n_pages_needed = -(-max_seq // cfg.page_len)
    pt = pages.page_table[:, :n_pages_needed]           # (B, P) logical ids
    phys = _physical(cfg, jnp.maximum(pt, 0))
    k = state.k_pool[phys]                              # (B, P, L, KV, HD)
    v = state.v_pool[phys]
    k = k.reshape(bsz, n_pages_needed * cfg.page_len, cfg.kv_heads,
                  cfg.head_dim)[:, :max_seq]
    v = v.reshape(bsz, n_pages_needed * cfg.page_len, cfg.kv_heads,
                  cfg.head_dim)[:, :max_seq]
    idx = jnp.arange(max_seq)
    valid = idx[None, :] < pages.seq_lens[:, None]
    mapped = jnp.repeat(pt >= 0, cfg.page_len, axis=1)[:, :max_seq]
    return k, v, valid & mapped


def bank_load_stats(state) -> dict:
    """Paper-style bank efficiency of the current allocation, plus the
    per-bank occupancy-skew measures the preferred-bank policies are judged
    on.  Accepts a ``PageTableState``, anything carrying ``.pages``, a
    scheduler pool (anything with ``.bank_used``), or a raw per-bank
    occupancy vector.

    Keys: ``max`` / ``min`` / ``mean`` occupancy, ``serialization``
    (max/mean — the batch allocator's cycle multiplier),
    ``max_min_ratio`` (max over the emptiest bank, ∞-free: min clamped to
    1 page) and ``mad`` (mean absolute deviation from the mean — 0 for a
    perfectly level pool)."""
    pages = getattr(state, "pages", state)
    used = getattr(pages, "bank_used", pages)
    used = jnp.asarray(used).astype(jnp.float32)
    mean = used.mean()
    return {"max": used.max(), "min": used.min(), "mean": mean,
            "serialization": used.max() / jnp.maximum(mean, 1e-9),
            "max_min_ratio": used.max() / jnp.maximum(used.min(), 1.0),
            "mad": jnp.abs(used - mean).mean()}


# --------------------------------------------------------------------------
# kernel path (the serving hot path: registry kernels on a bank-major pool)
# --------------------------------------------------------------------------

def pool_rows(pool: Array) -> Array:
    """(n_pages, L, KV, HD) pool -> (n_pages, L, KV·HD) kernel view (one page
    = one bank-major table row)."""
    return pool.reshape(pool.shape[:2] + (-1,))


def gather_pages(arch, cfg: PagedKVConfig, pool: Array,
                 page_ids: Array) -> Array:
    """Gather page lines by *logical* pool page id through
    ``kernels.get("banked_gather")`` (bank-major persistent pool — no
    relayout).  page_ids: (N,) int32, already clamped ≥ 0."""
    from repro.kernels import registry
    return registry.get("banked_gather").run(
        arch, pool, page_ids, table_banked=True)


def scatter_pages(arch, cfg: PagedKVConfig, pool: Array, page_ids: Array,
                  rows: Array) -> Array:
    """Scatter page lines into *logical* pool page ids through
    ``kernels.get("banked_scatter")``; returns the updated bank-major pool."""
    from repro.kernels import registry
    return registry.get("banked_scatter").run(
        arch, pool, page_ids, rows, table_banked=True)


# --------------------------------------------------------------------------
# trace path (what the decode loop costs under arch.cost)
# --------------------------------------------------------------------------

def kv_read_stream(page_table) -> tuple[np.ndarray, np.ndarray]:
    """The decode-step read stream: every sequence requests its whole page
    list (the paged-attention gather).  Returns (ids, active-lane mask) —
    unmapped (-1) entries are clamped to page 0 but predicated off, exactly
    what the jit'd gather does with its static page-table width."""
    pt = np.asarray(page_table)
    return np.maximum(pt, 0).reshape(-1), (pt >= 0).reshape(-1)


def decode_step_trace(cfg: PagedKVConfig, page_table, pos: int,
                      n_kv_layers: int = 1):
    """One decode step's exact ``AddressTrace``.

    Per KV layer, in kernel-call order: a K-pool page gather, a V-pool page
    gather (the paged-attention read), then a K and a V scatter of the
    sequence's *current* page (the read-modify-write append).  Addresses are
    logical pool page ids — the banked unit — produced by the registry
    kernels' own trace generators, so ``arch.cost`` prices serving exactly
    like any other kernel.
    """
    from repro.core.trace import AddressTrace
    from repro.kernels.banked_gather.ops import banked_gather_trace
    from repro.kernels.banked_scatter.ops import banked_scatter_trace
    pt = np.asarray(page_table)
    b = pt.shape[0]
    read_ids, read_mask = kv_read_stream(pt)
    cur = pt[np.arange(b), int(pos) // cfg.page_len]
    cur_ids, cur_mask = np.maximum(cur, 0), cur >= 0
    chunks = []
    for _ in range(n_kv_layers):
        chunks.append(banked_gather_trace(None, None, read_ids,
                                          mask=read_mask))
        chunks.append(banked_gather_trace(None, None, read_ids,
                                          mask=read_mask))
        chunks.append(banked_scatter_trace(None, None, cur_ids,
                                           mask=cur_mask))
        chunks.append(banked_scatter_trace(None, None, cur_ids,
                                           mask=cur_mask))
    t = AddressTrace.concat(*chunks)
    t.meta.update({"what": "decode_step", "pos": int(pos),
                   "n_kv_layers": n_kv_layers})
    return t


def prefill_trace(cfg: PagedKVConfig, page_table, prompt_len: int,
                  n_kv_layers: int = 1):
    """The prefill ingest's ``AddressTrace``: one K and one V page scatter
    per layer covering every prompt page (prefill K/V is computed once by
    the model and written to the pool page-at-a-time)."""
    from repro.core.trace import AddressTrace
    from repro.kernels.banked_scatter.ops import banked_scatter_trace
    pt = np.asarray(page_table)
    n_pref = -(-prompt_len // cfg.page_len)
    ids = pt[:, :n_pref]
    ids_flat, mask = np.maximum(ids, 0).reshape(-1), (ids >= 0).reshape(-1)
    chunks = []
    for _ in range(n_kv_layers):
        chunks.append(banked_scatter_trace(None, None, ids_flat, mask=mask))
        chunks.append(banked_scatter_trace(None, None, ids_flat, mask=mask))
    t = AddressTrace.concat(*chunks)
    t.meta.update({"what": "prefill", "prompt_len": int(prompt_len),
                   "n_kv_layers": n_kv_layers})
    return t


def simulate_serving_stream(arch, batch: int, prompt_len: int,
                            decode_steps: int, page_len: int = 8,
                            n_kv_layers: int = 1, max_seq: int | None = None,
                            include_prefill: bool = True):
    """The serving traffic of a (batch, context) point as a lazy
    ``repro.core.trace.TraceStream`` — the unified ``Trace`` protocol every
    cost consumer speaks: one source block per prefill ingest / decode
    step, produced on demand with pages allocated by the same arbiter the
    live engine uses.

    This is the O(block)-memory lowering — ``cost_many(archs, stream)``
    (and ``bench.serving_workload``, whose cached lowering is this stream)
    prices million-op serving traces without ever materializing the dense
    (ops × 16) matrix that ``simulate_serving_trace`` (the materialization
    of this stream) builds.  The stream is re-iterable: each iteration
    replays the allocator from scratch, so blocks need not be held alive.

    The traffic is architecture-DEPENDENT (the allocator places pages per
    the arch's bank map), which is why ``bench.TraceWorkload`` re-lowers it
    per sweep cell.  Non-banked architectures price the canonical 16-bank
    LSB pool's stream (multi-port issue cost depends only on lane activity).
    """
    from repro.core import arch as _arch
    from repro.core.trace import TraceStream
    a = _arch.resolve(arch)
    max_seq = max_seq or (prompt_len + decode_steps)
    if a.layout is not None:
        cfg = PagedKVConfig.from_arch(
            a, n_pages=pool_pages(a.layout.n_banks, batch, max_seq, page_len),
            page_len=page_len, kv_heads=1, head_dim=1)
    else:
        cfg = PagedKVConfig(
            n_pages=pool_pages(16, batch, max_seq, page_len),
            page_len=page_len, n_banks=16, mapping="lsb", kv_heads=1,
            head_dim=1, map_shift=1)

    def blocks():
        state = allocate_prompt_pages(cfg, batch, max_seq, prompt_len)
        if include_prefill:
            yield prefill_trace(cfg, state.page_table, prompt_len,
                                n_kv_layers)
        for i in range(decode_steps):                   # decode appends
            pos = prompt_len + i
            need = (state.seq_lens % page_len) == 0
            state, _ = allocate_pages(cfg, state, need)
            yield decode_step_trace(cfg, state.page_table, pos,
                                    n_kv_layers)
            state = state._replace(seq_lens=state.seq_lens + 1)

    return TraceStream(blocks, meta={
        "what": "serving", "arch": a.name, "batch": batch,
        "prompt_len": prompt_len, "decode_steps": decode_steps,
        "page_len": page_len, "n_kv_layers": n_kv_layers})


def simulate_serving_trace(arch, batch: int, prompt_len: int,
                           decode_steps: int, page_len: int = 8,
                           n_kv_layers: int = 1, max_seq: int | None = None,
                           include_prefill: bool = True):
    """The full serving ``AddressTrace`` of a (batch, context) point without
    running a model: prefill page writes + ``decode_steps`` decode steps —
    the dense concatenation of ``simulate_serving_stream`` (use the stream
    directly for traces too big to materialize)."""
    return simulate_serving_stream(
        arch, batch, prompt_len, decode_steps, page_len=page_len,
        n_kv_layers=n_kv_layers, max_seq=max_seq,
        include_prefill=include_prefill).materialize()  # lint: allow-materialize
