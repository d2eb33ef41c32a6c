"""The unified MemoryArchitecture / kernel-registry / sweep API (redesign PR):
registry resolution, BankedLayout round-trips + agreement with the kernels'
internal physical-row math, legacy-shim equivalence, and the two predication
fixes (Memory.write scratch-word corruption, multiport masked costing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.core import arch
from repro.core.arch import (BankedLayout, BankedMemory, MemoryArchitecture,
                             MultiPortMemory)
from repro.core.memsim import (LANES, PAPER_MEMORIES, Memory, banked,
                               cost_trace, instruction_cycles, multiport,
                               op_conflict_cycles)

PAPER_NAMES = ("4R-1W", "4R-2W", "4R-1W-VB", "16B", "16B-offset",
               "8B", "8B-offset", "4B", "4B-offset")
#: the non-pow2 / two-level lattice extension (generic bank formula PR)
EXTENDED_NAMES = ("12B", "6B-offset", "4x4B-g64", "2x8B-g32", "4x3B")
#: the paper's seven kernel packages + the three model traffic lowerings
#: registered from repro.models.trace (attn/moe/ssm decode-step streams)
KERNEL_NAMES = ("banked_gather", "banked_scatter", "banked_transpose",
                "carry_arbiter", "conflict_popcount", "fft_stage",
                "moe_dispatch", "attn_decode", "moe_a2a", "ssm_scan")


# ------------------------------------------------------------ registry --

def test_registry_resolves_all_nine_paper_architectures():
    for name in PAPER_NAMES:
        a = arch.get(name)
        assert isinstance(a, MemoryArchitecture) and a.name == name
    assert set(arch.names()) == set(PAPER_NAMES) | set(EXTENDED_NAMES)
    assert len(arch.PAPER_ARCHITECTURES) == 9
    # PAPER_MEMORIES stays a thin spec view of the registered architectures
    assert tuple(a.spec for a in arch.PAPER_ARCHITECTURES) == PAPER_MEMORIES


def test_registry_parses_unregistered_names():
    a = arch.get("32B-xor")
    assert isinstance(a, BankedMemory)
    assert a.n_banks == 32 and a.mapping == "xor"
    b = arch.get("16B-offset-bcast")
    assert b.broadcast and b.mapping == "offset"
    m = arch.get("8R-2W")
    assert isinstance(m, MultiPortMemory) and m.read_ports == 8
    with pytest.raises(KeyError):
        arch.get("not-a-memory")


def test_register_new_architecture():
    custom = BankedMemory(64, "fold")
    arch.register(custom, name="test-custom-64")
    try:
        assert arch.get("test-custom-64") is custom
    finally:
        arch._REGISTRY.pop("test-custom-64")


def test_kernel_registry_resolves_all_builtins():
    assert set(kernels.names()) == set(KERNEL_NAMES)
    for name in KERNEL_NAMES:
        k = kernels.get(name)
        assert callable(k.pallas) and callable(k.ref)
    with pytest.raises(KeyError):
        kernels.get("nope")


def test_kernel_run_dispatches_under_arch():
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (256, 512))
    idx = jax.random.randint(key, (64,), 0, 256)
    k = kernels.get("banked_gather")
    for name in ("16B-offset", "4B", "4R-1W"):
        a = arch.get(name)
        np.testing.assert_array_equal(np.asarray(k.run(a, table, idx)),
                                      np.asarray(k.reference(a, table, idx)))
    # a conflicted index stream costs more cycles than a conflict-free one
    a16 = arch.get("16B")
    conflicted = jnp.zeros((64,), jnp.int32)          # all rows -> bank 0
    spread = jnp.arange(64, dtype=jnp.int32)          # unit stride
    assert (k.cost_cycles(a16, table, conflicted)
            > k.cost_cycles(a16, table, spread))


def test_kernel_dispatch_honors_nondefault_offset_shift():
    """The gather/scatter kernels must use the architecture's layout shift,
    not a hard-coded shift=1 (regression: silently wrong rows)."""
    key = jax.random.PRNGKey(1)
    table = jax.random.normal(key, (64, 512))
    idx = jnp.array([3, 60, 7, 7], jnp.int32)
    a = BankedMemory(16, "offset", shift=2)
    g = kernels.get("banked_gather")
    np.testing.assert_array_equal(np.asarray(g.run(a, table, idx)),
                                  np.asarray(g.reference(a, table, idx)))
    s = kernels.get("banked_scatter")
    upd = jax.random.normal(key, (4, 512))
    uidx = jnp.array([1, 5, 9, 33], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(s.run(a, table, uidx, upd)),
        np.asarray(s.reference(a, table, uidx, upd)))


def test_conflict_popcount_rejects_bankless_architectures():
    banks = jnp.zeros((4, 16), jnp.int32)
    k = kernels.get("conflict_popcount")
    with pytest.raises(NotImplementedError):
        k.run(arch.get("4R-2W"), banks)
    # VB variant arbitrates writes over 4 pseudo-banks
    counts, _ = k.run(arch.get("4R-1W-VB"), banks)
    assert counts.shape[-1] == 4
    # explicit override is allowed
    counts, _ = k.run(arch.get("4R-2W"), banks, n_banks=16)
    assert counts.shape[-1] == 16


# ------------------------------------------------------- banked layout --

@pytest.mark.parametrize("n_banks", [4, 8, 16])
@pytest.mark.parametrize("mapping", ["lsb", "offset", "xor", "fold"])
def test_banked_layout_roundtrip_property(n_banks, mapping):
    lay = BankedLayout(n_banks, mapping)
    for n_rows in (n_banks * 4, 128, 256):
        x = jnp.arange(n_rows, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
        np.testing.assert_array_equal(
            np.asarray(lay.from_banked(lay.to_banked(x))), np.asarray(x))
        phys = np.asarray(lay.physical_rows(n_rows))
        assert sorted(phys.tolist()) == list(range(n_rows))  # permutation


@pytest.mark.parametrize("n_banks", [4, 8, 16])
@pytest.mark.parametrize("mapping", ["lsb", "offset", "xor"])
def test_banked_layout_matches_kernel_physical_rows(n_banks, mapping):
    """The single source of truth agrees with the gather/scatter kernels'
    index-map math (``physical_row_of``, called inside their index maps)
    and with the legacy ops.py helpers."""
    from repro.core.arch import physical_row_of
    from repro.kernels.banked_gather.ops import physical_rows
    n_rows = 256
    lay = BankedLayout(n_banks, mapping)
    want = np.asarray(lay.physical_rows(n_rows))
    r = jnp.arange(n_rows, dtype=jnp.int32)
    got_kernel = np.asarray(physical_row_of(
        r, n_banks, n_rows // n_banks, mapping))
    np.testing.assert_array_equal(want, got_kernel)
    np.testing.assert_array_equal(
        want, np.asarray(physical_rows(n_rows, n_banks, mapping)))


def test_layout_bank_slot_is_bijective_and_bank_correct():
    from repro.core.bankmap import bank_of
    lay = BankedLayout(16, "offset")
    r = jnp.arange(512, dtype=jnp.int32)
    bank, slot = lay.bank_slot(r)
    np.testing.assert_array_equal(np.asarray(bank),
                                  np.asarray(bank_of(r, 16, "offset",
                                                     shift=1)))
    # (bank, slot) pairs are unique -> the mapping is invertible
    pairs = set(zip(np.asarray(bank).tolist(), np.asarray(slot).tolist()))
    assert len(pairs) == 512


# ------------------------------------------------------- legacy shims --

def test_legacy_shims_match_arch_methods():
    addrs = jnp.arange(64, dtype=jnp.int32).reshape(4, 16) * 3
    for spec in PAPER_MEMORIES:
        a = arch.from_spec(spec)
        np.testing.assert_array_equal(
            np.asarray(op_conflict_cycles(spec, addrs)),
            np.asarray(a.op_cycles(addrs)))
        for is_write in (False, True):
            assert (instruction_cycles(spec, addrs, is_write)
                    == a.instruction_cycles(addrs, is_write=is_write))
    c_old = cost_trace(banked(16), [addrs], [addrs], compute_cycles=7)
    c_new = arch.get("16B").cost_trace([addrs], [addrs], compute_cycles=7)
    assert c_old == c_new


def test_sweep_matches_direct_vm_costs():
    from repro.bench import sweep, transpose_workload
    from repro.isa.programs.transpose import transpose_program
    from repro.isa.vm import run_program
    w = transpose_workload(32)
    recs = sweep(["16B-offset", "4R-2W"], w)
    for rec in recs:
        spec = arch.get(rec["arch"]).spec
        c = run_program(transpose_program(32), spec,
                        np.zeros(2048, np.float32), execute=False).cost
        assert rec["total_cycles"] == c.total_cycles
        assert rec["time_us"] == pytest.approx(c.time_us(spec.fmax_mhz))


def test_sweep_verify_workload():
    from repro.bench import fft_workload, verify_workload
    err = verify_workload(fft_workload(1024, 4), "16B")
    assert err < 1e-5


# -------------------------------------------------- predication fixes --

def test_predicated_write_does_not_corrupt_last_word():
    """Masked-off lanes must not be routed anywhere real (the old scratch
    hack silently clobbered the last word)."""
    mem = Memory(jnp.arange(32, dtype=jnp.float32))
    addrs = jnp.arange(16, dtype=jnp.int32)
    vals = jnp.full((16,), 100.0)
    mask = jnp.array([1, 0] * 8)
    out = mem.write(addrs, vals, mask)
    got = np.asarray(out.words)
    assert got[31] == 31.0                       # last word untouched
    np.testing.assert_array_equal(got[0:16:2], 100.0)   # active lanes wrote
    np.testing.assert_array_equal(got[1:16:2],
                                  np.arange(1, 16, 2, dtype=np.float32))

    jit_write = jax.jit(
        lambda w, a, v, k: Memory(w).write(a, v, k).words)
    np.testing.assert_array_equal(
        np.asarray(jit_write(mem.words, addrs, vals, mask)), got)


def test_multiport_masked_ops_cost_only_active_lanes():
    m41 = multiport(4, 1)
    addrs = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    mask = jnp.concatenate([jnp.ones((1, 16), jnp.int32),
                            jnp.array([[1] * 4 + [0] * 12], jnp.int32)])
    np.testing.assert_array_equal(
        np.asarray(op_conflict_cycles(m41, addrs, mask)), [4, 1])
    np.testing.assert_array_equal(
        np.asarray(op_conflict_cycles(m41, addrs, mask, is_write=True)),
        [16, 4])
    # unmasked behaviour unchanged: ceil(LANES / ports)
    np.testing.assert_array_equal(
        np.asarray(op_conflict_cycles(m41, addrs)), [4, 4])
    # the VB write path already honored masks via bank arbitration
    vb = multiport(4, 1, vb=True)
    same = jnp.zeros((1, 16), jnp.int32)
    half = jnp.array([[1] * 8 + [0] * 8], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(op_conflict_cycles(vb, same, half, is_write=True)), [8])


def test_lanes_constant():
    assert LANES == 16
