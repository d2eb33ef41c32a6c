from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.carry_arbiter.kernel import carry_arbiter_kernel


def _request_ops(req):
    """(ops, B) packed request words -> ((ops, LANES) bank ids, active
    mask): op o's lane l addresses the bank whose bit l is set."""
    import numpy as np

    from repro.core.memsim import LANES
    bits = (req[:, None, :] >> np.arange(LANES, dtype=np.uint32)[None, :,
                                         None]) & 1      # (ops, LANES, B)
    return bits.argmax(axis=-1), bits.any(axis=-1)


def carry_arbiter_trace(arch, requests, **_):
    """The lane→bank stream implied by packed request words: op o's lane l
    addresses the bank whose bit l is set in ``requests[o]`` (lanes with no
    request are masked off).  Costing this trace under a B-bank architecture
    reproduces the arbiter's own grant-cycle count."""
    import numpy as np

    from repro.core.trace import AddressTrace
    addrs, mask = _request_ops(np.asarray(requests, np.uint32))
    return AddressTrace.from_ops(addrs, kind="load", mask=mask)


def carry_arbiter_symbolic(arch, requests, **_):
    """The arbiter's lane→bank stream for the symbolic conflict prover:
    request words are inherently data-dependent, so the family is the exact
    unpacked (ops, LANES) matrix + active mask — proved through the
    independent bincount conflict algorithm."""
    import numpy as np

    from repro.analysis.symbolic import DataFamily, SymbolicTrace
    addrs, mask = _request_ops(np.asarray(requests, np.uint32))
    fam = DataFamily(name="arbiter requests", kind="load",
                     addrs=addrs, mask=mask)
    return SymbolicTrace(families=(fam,), meta={"kernel": "carry_arbiter"})


def carry_arbiter_trace_blocks(arch, requests, block_ops=None, **_):
    """Streaming counterpart of ``carry_arbiter_trace``: the request words
    are unpacked chunk-by-chunk (the (ops, LANES, B) bit tensor exists only
    per block), yielded as one carry-continued load instruction — bit-equal
    to the dense trace under every architecture."""
    import numpy as np

    from repro.core.trace import AddressTrace
    req = np.asarray(requests, np.uint32)
    if block_ops is not None and block_ops <= 0:
        raise ValueError(f"block_ops must be positive, got {block_ops}")
    step = max(1, req.shape[0]) if block_ops is None else block_ops
    for start in range(0, req.shape[0], step):
        addrs, mask = _request_ops(req[start:start + step])
        blk = AddressTrace.from_ops(addrs, kind="load", mask=mask)
        if start:
            blk.meta["instr_carry"] = True
        yield blk


@jax.jit
def carry_arbiter(requests: jnp.ndarray):
    """(ops, B) packed uint32 lane-request words -> (ops, 16, B) one-hot
    grant schedule (cycle-major), bit-exact vs the scan reference."""
    return carry_arbiter_kernel(requests)
