"""Real-width compiles for a described TPU v5e chip (nothing runs).

The main path's kernels are lowered through their registry entry points
with Pallas in compiled mode and compiled by the TPU compiler for one chip
of a described ``v5e:2x2`` topology: the paged-KV gather and scatter at
minicpm-2b's bf16 page, as an 18432-wide line and as the serving pools'
(8, 2304) slab (16-bank offset and the non-power-of-two 12-bank maps), the
cost engine's fused block kernel at 4096 ops over the nine paper memories,
and the other five kernels.  Interpret mode cannot catch what this does — a
block shape Mosaic's tiling refuses, a primitive with no TPU lowering — and
it costs no chip time.

The topology is described only inside the module fixture below, never while
a module is imported, and the test skips where it cannot be described.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core import arch as A
from repro.core.cost_engine import _block_kind_cycles, lower_archs
from repro.core.memsim import LANES
from repro.kernels import registry
from repro.serving.kvcache import pool_pages

#: minicpm-2b's page: page_len 8 × 36 KV heads × head_dim 64, as one
#: 18432-wide line (a 2-D table row) and as the serving pools' (8, 2304) slab
PAGE_ROWS = {"page_line": (8 * 36 * 64,), "page_slab": (8, 36 * 64)}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with Pallas forced to compiled mode
    and the persistent compilation cache off (a compile for a described
    chip is written to it but cannot be read back without one).  Traces
    are cleared on both sides so no interpret-mode trace is reused here and
    no compiled-mode trace leaks to the CPU tests after this module."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    interpret_mode = registry.interpret_mode
    registry.interpret_mode = lambda: False
    jax.clear_caches()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        registry.interpret_mode = interpret_mode
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("row", sorted(PAGE_ROWS))
@pytest.mark.parametrize("mem_arch", ["16B-offset", "12B"])
@pytest.mark.parametrize("name", ["banked_gather", "banked_scatter"])
def test_banked_kernel_compiles_at_page_line(one_chip, name, mem_arch, row):
    """The serving hot path: one decode step's page gather (batch 8 ×
    max_seq 512 / page_len 8 pages) and current-page scatter, on a pool
    sized as ``ServeEngine`` sizes it."""
    arch = A.get(mem_arch)
    n_pages = pool_pages(arch.layout.n_banks, 8, 512, 8)
    page = PAGE_ROWS[row]
    pool = _spec((n_pages,) + page, jnp.bfloat16, one_chip)
    k = kernels.get(name)
    if name == "banked_gather":
        ids = _spec((8 * 64,), jnp.int32, one_chip)
        compiled = _compile(
            lambda p, i: k.run(arch, p, i, table_banked=True), pool, ids)
    else:
        ids = _spec((8,), jnp.int32, one_chip)
        rows = _spec((8,) + page, jnp.bfloat16, one_chip)
        compiled = _compile(
            lambda p, i, r: k.run(arch, p, i, r, table_banked=True),
            pool, ids, rows)
    assert "tpu_custom_call" in compiled.as_text()


def test_cost_engine_block_compiles(one_chip):
    """The fused pricing kernel at its default 4096-op block over the nine
    paper memories (plain XLA, no Pallas)."""
    table = lower_archs(A.PAPER_ARCHITECTURES)
    n_ops = 4096

    def spec(a):
        a = np.asarray(a)
        return _spec(a.shape, a.dtype, one_chip)

    compiled = _block_kind_cycles.lower(
        spec(table.params), spec(table.remaps),
        _spec((n_ops, LANES), jnp.int32, one_chip),
        _spec((n_ops, LANES), jnp.bool_, one_chip),
        _spec((n_ops,), jnp.int32, one_chip),
        need_uniq=table.need_uniq, need_remap=table.need_remap,
        need_mod=table.need_mod,
        need_two_level=table.need_two_level).compile()
    assert compiled.memory_analysis() is not None


def test_moe_dispatch_compiles(one_chip):
    """The running-count dispatch over several 512-request grid blocks
    (mixtral's 8 experts)."""
    k = kernels.get("moe_dispatch")
    experts = _spec((4096,), jnp.int32, one_chip)
    compiled = _compile(lambda e: k.run("16B", e, 8, 1024), experts)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,args", [
    ("banked_transpose", (((512, 512), jnp.float32),)),
    ("carry_arbiter", (((4096, 16), jnp.uint32),)),
    ("conflict_popcount", (((4096, LANES), jnp.int32),)),
    ("fft_stage", (((4, 4096), jnp.complex64),)),
])
def test_paper_kernel_compiles(one_chip, name, args):
    """The paper-workload kernels at their benchmark shapes."""
    k = kernels.get(name)
    specs = [_spec(shape, dtype, one_chip) for shape, dtype in args]
    compiled = _compile(lambda *a: k.run("16B", *a), *specs)
    assert "tpu_custom_call" in compiled.as_text()
