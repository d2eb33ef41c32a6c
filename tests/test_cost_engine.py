"""The batched streaming cost engine (repro.core.cost_engine).

Acceptance gates of the engine PR:

  * ``cost_many`` is bit-equal to the per-architecture legacy loop
    (``MemoryArchitecture._cost_loop`` — the pre-engine costing path, kept
    as the independent reference) on every Table II/III point and on the
    16-bank serving trace;
  * chunked (``block_ops``) and streamed (``TraceStream``) costing are
    bit-equal to dense costing at any block size, including blocks that cut
    instructions in half;
  * the streaming path prices a >1e6-op synthetic serving stream while only
    ever holding one block at a time (no dense (ops × 16) matrix).
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.bench import fft_workload, serving_workload, transpose_workload
from repro.core import arch
from repro.core.arch import PAPER_ARCHITECTURES, TRANSPOSE_ARCHITECTURES
from repro.core.cost_engine import cost_many, lower_archs
from repro.core.memsim import LANES
from repro.core.trace import AddressTrace, TraceStream
from repro.serving.kvcache import (simulate_serving_stream,
                                   simulate_serving_trace)

#: beyond-paper points exercising every generic-formula term: xor/fold maps,
#: broadcast coalescing, wide banking, and the whole multi-port family
EXTRA_ARCHS = ("16B-bcast", "8B-xor", "8B-fold", "32B-xor", "4B-offset",
               "4R-1W", "4R-2W", "4R-1W-VB")


def _rand_trace(rng, n_ops=64, n_words=512, masked=True) -> AddressTrace:
    addrs = rng.integers(0, n_words, (n_ops, LANES))
    kinds = rng.integers(0, 3, n_ops).astype(np.int8)
    instr = np.sort(rng.integers(0, max(1, n_ops // 3), n_ops)).astype(
        np.int32)
    mask = (rng.random((n_ops, LANES)) > 0.25) if masked else None
    return AddressTrace(addrs, kinds, instr, mask)


# ------------------------------------------------- (a) batched == loop --

@pytest.mark.parametrize("n", (32, 64, 128))
def test_cost_many_equals_loop_on_table2(n):
    """Every Table II point: one fused pass == the per-arch legacy loop ==
    the arch.cost shim (full TraceCost equality, not just totals)."""
    t = transpose_workload(n).trace()
    costs = cost_many(TRANSPOSE_ARCHITECTURES, t)
    for a, c in zip(TRANSPOSE_ARCHITECTURES, costs):
        assert c == a._cost_loop(t), (n, a.name)
        assert c == a.cost(t), (n, a.name)


@pytest.mark.parametrize("radix", (4, 8, 16))
def test_cost_many_equals_loop_on_table3(radix):
    t = fft_workload(4096, radix).trace()
    costs = cost_many(PAPER_ARCHITECTURES, t)
    for a, c in zip(PAPER_ARCHITECTURES, costs):
        assert c == a._cost_loop(t), (radix, a.name)


def test_cost_many_equals_loop_on_serving_trace():
    """The 16B serving trace (paged-KV prefill + decode traffic, masked
    ragged streams) priced under all nine paper memories at once."""
    t = simulate_serving_trace("16B", batch=4, prompt_len=16, decode_steps=8,
                               page_len=4, n_kv_layers=2)
    costs = cost_many(PAPER_ARCHITECTURES, t)
    for a, c in zip(PAPER_ARCHITECTURES, costs):
        assert c == a._cost_loop(t), a.name


def test_cost_many_covers_beyond_paper_points():
    """xor/fold maps, broadcast reads, 32-bank lattice, and VB writes all
    lower into the same generic parameter formula."""
    rng = np.random.default_rng(7)
    t = _rand_trace(rng, n_ops=96)
    archs = [arch.get(n) for n in EXTRA_ARCHS]
    for a, c in zip(archs, cost_many(archs, t)):
        assert c == a._cost_loop(t), a.name


def test_cost_many_empty_and_compute_only_traces():
    a16 = arch.get("16B")
    empty = AddressTrace.empty()
    assert cost_many([a16], empty)[0] == a16._cost_loop(empty)
    compute = AddressTrace.empty().with_compute(100, {"fp": 60, "imm": 40})
    got = cost_many([a16], compute)[0]
    assert got == a16._cost_loop(compute)
    assert got.total_cycles == 100 and got.fp_ops == 60


def test_lower_archs_is_cached_per_spec_list():
    names = ("16B", "8B-offset", "4R-2W")
    assert lower_archs(names) is lower_archs([arch.get(n) for n in names])


# --------------------------------------- (b) chunked / streamed == dense --

@pytest.mark.parametrize("block_ops", (1, 7, 64, None))
def test_chunked_costing_bit_equal_to_dense(block_ops):
    """block_ops ∈ {1, 7, 64, n_ops}: instruction overheads are charged
    from global instruction ids, so blocks that cut an instruction in half
    still charge it exactly once."""
    t = fft_workload(4096, 4).trace()          # loads + stores + TW kinds
    block = t.n_ops if block_ops is None else block_ops
    archs = list(TRANSPOSE_ARCHITECTURES[:4])
    assert cost_many(archs, t, block_ops=block) == cost_many(archs, t)


def test_chunked_costing_masked_serving_trace():
    t = simulate_serving_trace("8B-offset", batch=4, prompt_len=16,
                               decode_steps=8, page_len=4)
    archs = [arch.get(n) for n in ("8B-offset", "16B-bcast", "4R-1W-VB")]
    dense = cost_many(archs, t)
    for block in (1, 7, 64, t.n_ops):
        assert cost_many(archs, t, block_ops=block) == dense


def test_raw_iter_blocks_iterator_is_a_valid_stream_source():
    """Tentpole invariant: the unified Trace protocol removed the old
    iter_blocks-view rejection.  Views are instr_carry-marked at cut
    boundaries, so feeding the raw iterator to cost_many charges the cut
    instruction's overhead once and is memory-side bit-equal to dense
    costing (views carry no compute — ``blocks()`` carries it too)."""
    t = AddressTrace.from_stream(np.arange(48), "load").with_compute(
        100, {"fp": 60})
    a16 = arch.get("16B")
    dense = cost_many([a16], t)[0]
    via_views = cost_many([a16], t.iter_blocks(2))[0]
    assert via_views.load_cycles == dense.load_cycles
    assert via_views.n_load_ops == dense.n_load_ops
    # the full protocol (blocks) additionally preserves compute metadata
    assert cost_many([a16], t.blocks(2))[0] == dense
    blk = next(t.iter_blocks(2))
    assert a16.cost(blk).load_cycles == a16.cost(t[:2]).load_cycles


def test_iter_blocks_preserves_global_instruction_ids():
    t = AddressTrace.concat(AddressTrace.from_stream(np.arange(48), "load"),
                            AddressTrace.from_stream(np.arange(32), "store"))
    blocks = list(t.iter_blocks(2))
    assert sum(b.n_ops for b in blocks) == t.n_ops
    # the load instruction spans blocks 0-1: same id on both sides of the cut
    assert blocks[0].instr[-1] == blocks[1].instr[0]
    with pytest.raises(ValueError):
        next(t.iter_blocks(0))


def test_stream_costing_equals_materialized_dense():
    """A TraceStream prices bit-equal to its dense concatenation — on the
    exact serving lowering the sweep uses (overlapping-size check)."""
    kw = dict(batch=4, prompt_len=16, decode_steps=16, page_len=4,
              n_kv_layers=2)
    stream = simulate_serving_stream("16B", **kw)
    dense = simulate_serving_trace("16B", **kw)
    archs = list(PAPER_ARCHITECTURES)
    assert cost_many(archs, stream) == cost_many(archs, dense)
    # re-iterable: a second pass replays the allocator and agrees
    assert cost_many(archs, stream, block_ops=8) == cost_many(archs, dense)
    assert stream.materialize().n_ops == dense.n_ops


def test_streaming_million_op_trace_stays_block_bounded():
    """A >1e6-op synthetic serving stream is priced while at most one
    block's ops are ever materialized (tracked via a peeking generator) —
    and the cycle math agrees with dense costing on a truncated prefix."""
    n_blocks, ops_per_block = 260, 4096        # > 1e6 ops total
    rng = np.random.default_rng(3)
    base = _rand_trace(rng, n_ops=ops_per_block, n_words=1 << 16)
    peak = {"alive": 0, "max_alive": 0, "blocks": 0}

    def blocks(n):
        def gen():
            for _ in range(n):
                peak["alive"] += 1
                peak["blocks"] += 1
                peak["max_alive"] = max(peak["max_alive"], peak["alive"])
                yield base                     # O(block) live data
                peak["alive"] -= 1
        return gen

    a16 = arch.get("16B")
    total = cost_many([a16], TraceStream(blocks(n_blocks)))[0]
    assert peak["blocks"] == n_blocks
    assert n_blocks * ops_per_block > 1_000_000
    # every yielded block was released before the next was drawn
    assert peak["max_alive"] == 1
    # linearity: the per-block cost × n_blocks == the streamed total
    one = cost_many([a16], base)[0]
    assert total.total_cycles == n_blocks * one.total_cycles
    assert total.n_load_ops == n_blocks * one.n_load_ops


# -------------------------------- (b2) streamed CONSTRUCTION == dense --
# Block-size invariance of kernel-GENERATED streams (the tentpole's
# construction-side counterpart of the chunked-costing tests above).

def test_kernel_stream_construction_bit_equal_transpose_table2():
    """Every Table II point: the banked_transpose kernel's native
    trace_blocks stream (block_ops ∈ {1, 7, 64, n}) costs bit-equal to its
    dense trace() under all eight Table II memories — and the stream is
    re-iterable (a second pass agrees)."""
    from repro import kernels
    k = kernels.get("banked_transpose")
    archs = list(TRANSPOSE_ARCHITECTURES)
    for n in (32, 64, 128):
        x = np.zeros((n, n), np.float32)
        dense_t = k.address_trace(archs[0], x)
        dense = cost_many(archs, dense_t)
        blocks = (1, 7, 64, dense_t.n_ops) if n == 32 else (64, None)
        for bo in blocks:
            s = k.trace_blocks(archs[0], x, block_ops=bo)
            assert cost_many(archs, s) == dense, (n, bo)
        if n == 32:     # re-iterability: generator-function-backed stream
            s = k.trace_blocks(archs[0], x, block_ops=7)
            assert cost_many(archs, s) == cost_many(archs, s)


def test_kernel_stream_construction_bit_equal_fft_radix4():
    from repro import kernels
    k = kernels.get("fft_stage")
    archs = list(PAPER_ARCHITECTURES)
    x = np.zeros((1, 4096), np.complex64)
    dense = cost_many(archs, k.address_trace(archs[0], x))
    for bo in (7, 64, None):
        assert cost_many(archs, k.trace_blocks(archs[0], x, block_ops=bo)) \
            == dense, bo


@pytest.mark.parametrize("radix", (4, 8, 16))
def test_program_stream_construction_bit_equal_table3(radix):
    """Every Table III point: the VM's streaming lowering
    (program_trace_stream — what run_program and bench.sweep now cost)
    equals the dense AddressTrace.from_program path bit-exactly."""
    from repro.isa.vm import program_trace, program_trace_stream
    prog = fft_workload(4096, radix).program
    archs = list(PAPER_ARCHITECTURES)
    dense = cost_many(archs, program_trace(prog))
    for bo in (64, None):
        assert cost_many(archs, program_trace_stream(prog, bo)) == dense, bo


def test_row_stream_kernels_stream_bit_equal():
    """gather/scatter/moe/popcount/arbiter: native block generators chunk
    ONE instruction (instr_carry continuation) and cost bit-equal to the
    dense row-stream trace, masks included."""
    from repro import kernels
    rng = np.random.default_rng(11)
    idx = rng.integers(0, 512, 1000)
    mask = rng.random(1000) > 0.2
    cases = [("banked_gather", (None, idx), {"mask": mask}),
             ("banked_scatter", (None, idx), {"mask": mask}),
             ("moe_dispatch", (idx % 16, 16, 64), {}),
             ("conflict_popcount", (rng.integers(0, 16, (37, 16)),), {}),
             ("carry_arbiter",
              (rng.integers(1, 2 ** 16, (23, 16)).astype(np.uint32),), {})]
    archs = [arch.get(n) for n in ("16B", "8B-offset", "4R-2W", "4R-1W-VB")]
    for name, args, kw in cases:
        k = kernels.get(name)
        dense_t = k.address_trace(archs[0], *args, **kw)
        dense = cost_many(archs, dense_t)
        # one instruction regardless of chunking
        assert dense_t.n_instructions == 1
        for bo in (1, 7, 64, None):
            got = cost_many(archs, k.trace_blocks(archs[0], *args,
                                                  block_ops=bo, **kw))
            assert got == dense, (name, bo)


def test_stream_generators_reject_nonpositive_block_ops():
    """Every streaming path raises on block_ops <= 0 — none silently yields
    empty blocks (which would cost 0 cycles without an error)."""
    from repro import kernels
    from repro.core.trace import iter_op_chunks
    req = np.ones((4, 16), np.uint32)
    with pytest.raises(ValueError):
        list(kernels.get("carry_arbiter").trace_blocks(
            "16B", req, block_ops=0).blocks())
    with pytest.raises(ValueError):
        list(iter_op_chunks(np.arange(32), block_ops=0))
    with pytest.raises(ValueError):
        list(AddressTrace.from_stream(np.arange(32)).blocks(-1))


def test_kernel_stream_blocks_are_block_bounded():
    """Structural O(block) check: no yielded block exceeds block_ops ops,
    and the blocks partition the dense op stream exactly."""
    from repro import kernels
    k = kernels.get("banked_transpose")
    x = np.zeros((256, 256), np.float32)
    s = k.trace_blocks("16B", x, block_ops=64)
    sizes = [b.n_ops for b in s.blocks()]
    assert max(sizes) <= 64
    assert sum(sizes) == k.address_trace("16B", x).n_ops


def test_trace_stream_one_shot_iterator_stays_lazy_but_loud():
    """Satellite regression: a bare generator (one-shot iterator) passed to
    TraceStream used to silently yield nothing on the second iteration (a
    0-cycle cost with no error).  It now stays LAZY — blocks are drawn one
    at a time, preserving the O(block) contract — and a second pass raises
    instead of lying; sequence- and callable-backed streams re-iterate."""
    from repro.core.trace import TraceStream as TS

    drawn = []

    def gen():
        for i in range(3):
            drawn.append(i)
            yield AddressTrace.from_stream(np.arange(32) + i, "load")

    s = TS(gen())                       # called generator: one-shot source
    assert drawn == []                  # construction consumed nothing
    a16 = arch.get("16B")
    first = cost_many([a16], s)[0]
    assert first.n_load_ops == 6        # 3 blocks × 2 ops — not 0
    assert drawn == [0, 1, 2]
    with pytest.raises(RuntimeError, match="one-shot"):
        cost_many([a16], s)
    # sequence- and callable-backed streams are re-iterable
    seq = TS(tuple(TS(gen()).blocks()))
    assert cost_many([a16], seq)[0] == first == cost_many([a16], seq)[0]
    assert seq.n_ops == 6 and seq.materialize().n_ops == 6
    fn = TS(gen)                        # generator FUNCTION: lazy + re-iter
    assert cost_many([a16], fn)[0] == first == cost_many([a16], fn)[0]
    with pytest.raises(TypeError):
        TS(42)


def test_trace_stream_concat_and_kind_filter_parity():
    """TraceStream parity satellites: concat composes streams/traces like
    AddressTrace.concat, and of_kind/loads/stores filter per-kind with the
    same cycle totals as the dense filters."""
    from repro.core.trace import TraceStream as TS
    rng = np.random.default_rng(5)
    t1 = AddressTrace.from_stream(rng.integers(0, 256, 160), "load")
    t2 = AddressTrace.from_stream(rng.integers(0, 256, 96), "store")
    s = TS.concat(t1, TS((t2,)), t1)
    dense = AddressTrace.concat(t1, t2, t1)
    a16 = arch.get("16B")
    assert cost_many([a16], s)[0] == cost_many([a16], dense)[0]
    assert s.materialize().n_instructions == dense.n_instructions == 3
    assert cost_many([a16], s.loads())[0].load_cycles \
        == cost_many([a16], dense.loads())[0].load_cycles
    assert cost_many([a16], s.stores())[0].n_store_ops == dense.stores().n_ops


def test_arch_cost_auto_streams_above_threshold():
    """arch.cost with no block_ops streams large traces automatically
    (bit-equal to the explicit dense pass)."""
    from repro.core.cost_engine import STREAM_THRESHOLD
    rng = np.random.default_rng(9)
    n = STREAM_THRESHOLD + 17
    t = AddressTrace(rng.integers(0, 1 << 12, (n, LANES)),
                     rng.integers(0, 3, n).astype(np.int8),
                     np.sort(rng.integers(0, 50, n)).astype(np.int32))
    a16 = arch.get("16B")
    assert a16.cost(t) == cost_many([a16], t)[0]


# ------------------------------------------------ (c) property testing --

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 600), st.integers(0, 2 ** 20), st.integers(0, 1),
       st.sampled_from([1, 3, 16, 1000]))
def test_property_op_chunk_streams_equal_dense(n_req, seed, masked,
                                               block_ops):
    """Random one-instruction request streams (ragged tails, masks): the
    iter_op_chunks stream costs bit-equal to the dense from_ops trace at
    any block size — the construction-side streaming invariant."""
    from repro.core.trace import TraceStream, iter_op_chunks
    rng = np.random.default_rng(seed)
    req = rng.integers(0, 1 << 10, n_req)
    mask = (rng.random(n_req) > 0.3) if masked else None
    dense = AddressTrace.from_ops(req, "store", mask=mask)
    stream = TraceStream(
        lambda: iter_op_chunks(req, "store", mask=mask, block_ops=block_ops))
    archs = [arch.get(n) for n in ("16B", "4B-offset", "4R-2W", "4R-1W-VB")]
    assert cost_many(archs, stream) == cost_many(archs, dense)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(0, 2 ** 20), st.integers(0, 3),
       st.sampled_from([1, 7, 16, 1000]))
def test_property_random_traces_engine_equals_loop(n_ops, seed, mask_mode,
                                                   block_ops):
    """Random (addrs, kinds, masks, instruction grouping) traces: the fused
    engine, the chunked engine, and the legacy per-kind loop agree on a mix
    of banked / broadcast / multi-port points."""
    rng = np.random.default_rng(seed)
    mask = (None if mask_mode == 0
            else rng.random((n_ops, LANES)) > (0.15, 0.5, 0.9)[mask_mode - 1])
    t = AddressTrace(rng.integers(0, 1 << 14, (n_ops, LANES)),
                     rng.integers(0, 3, n_ops).astype(np.int8),
                     np.sort(rng.integers(0, 6, n_ops)).astype(np.int32),
                     mask)
    archs = [arch.get(n) for n in ("16B", "16B-bcast", "8B-offset",
                                   "4B-fold", "4R-2W", "4R-1W-VB")]
    batched = cost_many(archs, t)
    assert batched == cost_many(archs, t, block_ops=block_ops)
    for a, c in zip(archs, batched):
        assert c == a._cost_loop(t), a.name


# -------------------------------------------- rewired consumer parity --

def test_sweep_batched_records_match_per_cell_records():
    from repro.bench import run_cell, sweep
    w = transpose_workload(32)
    names = ("16B", "8B-offset", "4R-2W")
    batched = sweep(names, w)
    assert batched == [run_cell(n, w) for n in names]


def test_trace_workload_cache_keys_on_layout_not_name():
    """Satellite fix: two space points must share a lowering iff their
    lowering keys agree — never because they merely share a display name."""
    w = serving_workload(batch=2, prompt_len=8, decode_steps=4, page_len=4)
    a = arch.get("16B")
    b = arch.BankedMemory(16, "xor")           # different placement
    t_a, t_b = w.trace(a), w.trace(b)
    assert t_a is w.trace(a)                   # cached per layout
    assert t_b is not t_a
    # all layout-free memories share the canonical pool lowering
    assert w.trace(arch.get("4R-1W")) is w.trace(arch.get("4R-2W"))


def test_default_trace_workload_key_is_full_spec():
    """Satellite fix regression: the default cache key is the full MemSpec —
    a point with the *same display name* but a different spec re-lowers."""
    from repro.bench import TraceWorkload
    from repro.core.memsim import MemSpec
    calls = []

    def trace_fn(a):
        calls.append(a.name)
        return AddressTrace.from_stream(np.arange(16), "load")

    w = TraceWorkload(name="w", trace_fn=trace_fn)
    sixteen = arch.get("16B")
    clone = arch.BankedMemory(16, "lsb")               # equal spec: shares
    imposter = arch.from_spec(MemSpec(                 # same name "16B",
        kind="banked", name="16B", n_banks=16,         # different bank map:
        mapping="offset", map_shift=1))                # must NOT share
    w.trace(sixteen), w.trace(clone), w.trace(imposter)
    assert len(calls) == 2


def test_serving_cost_streams_through_engine():
    """ServeEngine.serving_cost == arch.cost(serving_trace()) — the live
    recorded traffic priced via the streaming path, single- and multi-arch."""
    import jax
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.models import init_tree, model_specs
    from repro.serving.engine import ServeEngine

    cfg = get_smoke_config("llama3.2-1b")
    rc = RunConfig(remat="none", attn_impl="dense")
    params = init_tree(model_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, rc, params, NO_AXES, max_batch=2, max_seq=32,
                      mem_arch="16B", page_len=8)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    eng.generate(prompts, max_new_tokens=4)
    want = eng.mem_arch.cost(eng.serving_trace())
    assert eng.serving_cost() == want
    assert eng.serving_cost(block_ops=3) == want
    many = eng.serving_cost(archs=PAPER_ARCHITECTURES)
    assert many[PAPER_ARCHITECTURES.index(eng.mem_arch)] == want
    # the live stream is the shared protocol and re-iterable (footgun fix)
    s = eng.serving_stream()
    total = sum(b.n_ops for b in s)
    assert total > 0 and sum(b.n_ops for b in s) == total


def test_physical_rows_table_is_cached():
    from repro.core.arch import BankedLayout
    lay = BankedLayout(8, "xor")
    assert lay.physical_rows(64) is BankedLayout(8, "xor").physical_rows(64)
    np.testing.assert_array_equal(
        np.sort(np.asarray(lay.physical_rows(64))), np.arange(64))


# ---------------------- (d) non-pow2 / two-level lattice (generic formula) --

#: the registered lattice extension: non-pow2 lsb/offset and two-level maps
EXTENDED_NAMES = ("12B", "6B-offset", "4x4B-g64", "2x8B-g32", "4x3B")


@pytest.mark.parametrize("name", EXTENDED_NAMES)
def test_extended_lattice_cost_many_equals_loop(name):
    """Every new registry arch prices identically through the fused engine
    and the legacy per-arch loop (the PR acceptance gate: >= 4 new points)."""
    rng = np.random.default_rng(11)
    a = arch.get(name)
    for n_ops in (1, 37, 96):
        t = _rand_trace(rng, n_ops=n_ops, n_words=1024)
        assert cost_many([a], t)[0] == a._cost_loop(t), (name, n_ops)


def test_extended_lattice_batched_with_paper_points():
    """Mixed batch: paper + extended points in ONE fused dispatch still
    match their individual loop costs (mod/two-level terms are no-ops for
    pow2 flat rows)."""
    rng = np.random.default_rng(12)
    t = _rand_trace(rng, n_ops=80)
    archs = [arch.get(n) for n in ("16B", "4B-offset", "4R-2W") +
             EXTENDED_NAMES]
    for a, c in zip(archs, cost_many(archs, t)):
        assert c == a._cost_loop(t), a.name


def test_two_level_default_granule_equals_flat():
    """4x4B with granule = inner capacity factors addresses exactly like a
    flat 16B lsb map (outer level = next 2 bits), so the conflict cycles
    are identical on any trace; only controller overheads could differ and
    both key on total banks = 16, so full TraceCost equality holds."""
    rng = np.random.default_rng(13)
    a_two = arch.get("4x4B")
    a_flat = arch.get("16B")
    assert a_two.spec.total_banks == 16
    for n_ops in (16, 64):
        t = _rand_trace(rng, n_ops=n_ops, n_words=4096)
        assert cost_many([a_two], t)[0] == cost_many([a_flat], t)[0]


def test_non_pow2_bank_formula_matches_modulo():
    """12B conflict cycles on a crafted trace equal a direct per-op
    max-per-bank count under addr % 12 (independent recomputation)."""
    a = arch.get("12B")
    addrs = np.arange(16 * 16).reshape(16, 16) * 3 + 5
    t = AddressTrace.from_ops(addrs.astype(np.int32), kind="load")
    got = cost_many([a], t)[0]
    want = 0
    for row in addrs:
        want += int(np.bincount(row % 12, minlength=12).max())
    from repro.core import controllers as ctl
    ovh = ctl.read_overhead(12)
    assert got.total_cycles == want + t.n_instructions * ovh


def test_extended_lattice_streams_and_chunks():
    """Chunked/streamed costing stays bit-equal on the new arch families."""
    rng = np.random.default_rng(14)
    parts = [_rand_trace(rng, n_ops=n) for n in (5, 1, 33)]
    dense = AddressTrace.concat(*parts)
    archs = [arch.get(n) for n in EXTENDED_NAMES]
    want = cost_many(archs, dense)
    assert cost_many(archs, dense, block_ops=7) == want
    stream = TraceStream(parts)
    assert cost_many(archs, stream, block_ops=7) == want


# ----------------------------------- (e) prefetch pipeline bit-equality --

def _thunk_stream(parts, lat_s=0.0):
    import time as _time

    def mk(p):
        def t():
            if lat_s:
                _time.sleep(lat_s)
            return p
        return t
    return TraceStream.from_thunks([mk(p) for p in parts])


@pytest.mark.parametrize("prefetch", (1, 2, 8))
def test_prefetch_thunk_stream_bit_equal(prefetch):
    """cost_many(..., prefetch=N) over a thunk-backed stream returns the
    exact serial result: worker construction order cannot reorder blocks
    (futures are consumed in thunk order) and pricing is per-block."""
    rng = np.random.default_rng(21)
    parts = [_rand_trace(rng, n_ops=n) for n in (9, 1, 64, 17)]
    a = [arch.get(n) for n in ("16B", "8B-offset", "12B")]
    want = cost_many(a, TraceStream(parts), block_ops=16)
    got = cost_many(a, _thunk_stream(parts), block_ops=16,
                    prefetch=prefetch)
    assert got == want


def test_prefetch_generator_stream_bit_equal():
    """Generator-backed streams prefetch through the producer thread —
    same result, and the pinned serving-trace cost from the serial path."""
    stream = simulate_serving_stream("16B", batch=2, prompt_len=9,
                                     decode_steps=4, page_len=8)
    a16 = arch.get("16B")
    want = cost_many([a16], stream)
    got = cost_many([a16], simulate_serving_stream(
        "16B", batch=2, prompt_len=9, decode_steps=4, page_len=8),
        prefetch=3)
    assert got == want


def test_prefetch_thunk_exception_propagates():
    def boom():
        raise RuntimeError("constructor died")
    s = TraceStream.from_thunks(
        [lambda: AddressTrace.from_stream(np.arange(16), "load"), boom])
    with pytest.raises(RuntimeError, match="constructor died"):
        cost_many([arch.get("16B")], s, prefetch=2)


def test_prefetch_generator_exception_propagates():
    def gen():
        yield AddressTrace.from_stream(np.arange(16), "load")
        raise RuntimeError("producer died")
    with pytest.raises(RuntimeError, match="producer died"):
        cost_many([arch.get("16B")], TraceStream(gen), prefetch=2)


def test_prefetch_validation():
    t = AddressTrace.from_stream(np.arange(16), "load")
    with pytest.raises(ValueError):
        cost_many([arch.get("16B")], TraceStream([t]), prefetch=0)


# ------------------------------------ (f) BlockCostCache bit-equality --

from repro.core.cost_engine import BlockCostCache  # noqa: E402


def test_cache_warm_reprice_bit_equal_and_hits():
    rng = np.random.default_rng(31)
    parts = [_rand_trace(rng, n_ops=24) for _ in range(6)]
    archs = [arch.get(n) for n in ("16B", "4B-offset", "12B", "4x4B-g64")]
    cache = BlockCostCache()
    cold = cost_many(archs, TraceStream(parts), cache=cache)
    assert cache.stats["misses"] == 6 and cache.stats["hits"] == 0
    warm = cost_many(archs, TraceStream(parts), cache=cache)
    assert warm == cold
    assert cache.stats["hits"] == 6
    # and both equal the no-cache reference
    assert cold == cost_many(archs, TraceStream(parts))


def test_cache_keys_on_arch_table_degraded_distinct():
    """A degraded variant lowers different remap rows -> different table
    digest -> no cross-contamination, while re-pricing the SAME degraded
    table hits."""
    rng = np.random.default_rng(32)
    t = _rand_trace(rng, n_ops=32, masked=False)
    healthy = arch.get("16B")
    degraded = healthy.degrade(dead_banks=(3,))
    cache = BlockCostCache()
    ch = cost_many([healthy], t, cache=cache)[0]
    cd = cost_many([degraded], t, cache=cache)[0]
    assert cache.stats["hits"] == 0 and cache.stats["misses"] == 2
    assert ch == healthy._cost_loop(t)
    assert cd == degraded._cost_loop(t)
    assert cost_many([degraded], t, cache=cache)[0] == cd
    assert cache.stats["hits"] == 1


def test_cache_lru_bounded():
    rng = np.random.default_rng(33)
    cache = BlockCostCache(max_entries=3)
    a16 = [arch.get("16B")]
    for i in range(5):
        cost_many(a16, _rand_trace(rng, n_ops=8), cache=cache)
    assert len(cache) == 3 and cache.stats["entries"] == 3


def test_cache_freezes_priced_blocks():
    """Payload arrays are frozen on first digest — mutating a priced
    block raises instead of silently re-pricing stale bytes."""
    t = AddressTrace.from_ops(np.arange(64, dtype=np.int32).reshape(4, 16),
                              kind="load")
    cost_many([arch.get("16B")], t, cache=BlockCostCache())
    with pytest.raises(ValueError):
        t.addrs[0, 0] = 99
    t.instr[0] = 0      # instruction ids are NOT frozen (not keyed)


@given(st.integers(0, 6), st.integers(1, 4), st.sampled_from([1, 7, 64, 0]))
@settings(max_examples=15, deadline=None)
def test_property_cached_prefix_plus_fresh_suffix(n_prefix, seed, block_ops):
    """The satellite property: price a PREFIX of a window through a cache,
    then the full window (cached prefix + fresh suffix) — bit-equal to a
    cold full pass, for block_ops in {1, 7, 64, n} and any split."""
    rng = np.random.default_rng(seed)
    parts = [_rand_trace(rng, n_ops=int(rng.integers(1, 40)))
             for _ in range(8)]
    bo = sum(p.n_ops for p in parts) if block_ops == 0 else block_ops
    archs = [arch.get(n) for n in ("16B", "8B-xor", "6B-offset", "2x8B-g32")]
    cache = BlockCostCache()
    if n_prefix:
        cost_many(archs, TraceStream(parts[:n_prefix]), block_ops=bo,
                  cache=cache)
    warm = cost_many(archs, TraceStream(parts), block_ops=bo, cache=cache)
    cold = cost_many(archs, TraceStream(parts), block_ops=bo)
    assert warm == cold
