"""Benchmark programs: functional correctness vs oracles + Table II/III
regression values (the faithful-reproduction gate)."""
import numpy as np
import pytest

from benchmarks.paper_data import TABLE2_EXACT
from repro.core.memsim import PAPER_MEMORIES, banked, multiport
from repro.isa.programs.fft import (digit_reverse_indices, fft_program,
                                    make_fft_memory, oracle_spectrum)
from repro.isa.programs.transpose import oracle as transpose_oracle
from repro.isa.programs.transpose import transpose_program
from repro.isa.vm import run_program


@pytest.mark.parametrize("n,radix", [(64, 4), (64, 8), (256, 16), (4096, 4),
                                     (4096, 8), (4096, 16)])
def test_fft_functional_vs_numpy(n, radix):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64)
    mem0, _ = make_fft_memory(n, x)
    res = run_program(fft_program(n, radix), banked(16), mem0)
    got = res.memory[0:2 * n:2] + 1j * res.memory[1:2 * n:2]
    want = oracle_spectrum(x, radix)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-3 * np.abs(want).max())


def test_digit_reverse_is_permutation():
    for radix in (4, 8, 16):
        rev = digit_reverse_indices(4096, radix)
        assert sorted(rev.tolist()) == list(range(4096))


@pytest.mark.parametrize("n", [32, 64, 128])
def test_transpose_functional(n):
    x = np.random.default_rng(1).standard_normal(n * n).astype(np.float32)
    mem0 = np.concatenate([x, np.zeros(n * n, np.float32)])
    res = run_program(transpose_program(n), banked(16, "offset"), mem0)
    np.testing.assert_allclose(res.memory, transpose_oracle(n, x))


# --- Table II regression (paper values; cycle-exact cells asserted hard) ----

TABLE2 = TABLE2_EXACT  # n -> mem -> (load, store)


@pytest.mark.parametrize("n", [32, 64, 128])
def test_table2_exact_cells(n):
    prog = transpose_program(n)
    mem0 = np.zeros(2 * n * n, np.float32)
    for name, (ld, st) in TABLE2[n].items():
        spec = banked(16) if name == "16B" else multiport(4, int(name[3]))
        c = run_program(prog, spec, mem0, execute=False).cost
        assert c.load_cycles == ld, (n, name)
        assert c.store_cycles == st, (n, name)


def test_table2_offset_within_2pct():
    paper = {32: 106, 64: 672, 128: 4672}
    for n, want in paper.items():
        c = run_program(transpose_program(n), banked(16, "offset"),
                        np.zeros(2 * n * n, np.float32), execute=False).cost
        assert abs(c.load_cycles - want) / want < 0.02, (n, c.load_cycles)


# --- Table III regression: every banked cell within 5 %, most exact --------

TABLE3_16B = {  # radix -> (D, TW, S) for 16 banks LSB / offset
    4: {"16B": (11200, 24152, 10960), "16B-offset": (7104, 21548, 6864)},
    8: {"16B": (12624, 16712, 12224), "16B-offset": (7425, 13844, 7104)},
    16: {"16B": (12160, 10888, 11680), "16B-offset": (11136, 9848, 10652)},
}


@pytest.mark.parametrize("radix", [4, 8, 16])
def test_table3_16bank_cells(radix):
    prog = fft_program(4096, radix)
    mem0 = np.zeros(16384, np.float32)
    for name, (d, tw, s) in TABLE3_16B[radix].items():
        spec = banked(16, "offset" if "offset" in name else "lsb")
        c = run_program(prog, spec, mem0, execute=False).cost
        for got, want in [(c.load_cycles, d), (c.tw_load_cycles, tw),
                          (c.store_cycles, s)]:
            assert abs(got - want) / want < 0.05, (radix, name, got, want)


def test_table3_multiport_exact():
    """Multi-port cycles are deterministic: 4 cyc/op reads, 16 writes."""
    prog = fft_program(4096, 16)
    mem0 = np.zeros(16384, np.float32)
    c = run_program(prog, multiport(4, 1), mem0, execute=False).cost
    assert c.load_cycles == 6144        # 1536 ops x 4
    assert c.tw_load_cycles == 3840     # 960 ops x 4
    assert c.store_cycles == 24576      # 1536 ops x 16


def test_fmax_time_model():
    """Time = cycles / fmax; 4R-2W runs at 600 MHz (Table II 32x32: 1.93 us)."""
    prog = transpose_program(32)
    mem0 = np.zeros(2048, np.float32)
    res = run_program(prog, multiport(4, 2), mem0, execute=False)
    assert res.cost.total_cycles == 1159
    assert res.time_us == pytest.approx(1.93, abs=0.01)
