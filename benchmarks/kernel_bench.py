"""Pallas-kernel micro-bench harness: wall time per call + oracle agreement.
The kernels run compiled on a TPU and in the Pallas interpreter elsewhere
(structural only), so every row names the backend it ran on.
CSV: name,us_per_call,derived."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters: int = 3) -> float:
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def rows():
    from repro.kernels.banked_gather.ops import banked_gather, to_banked_layout
    from repro.kernels.banked_transpose.ops import banked_transpose
    from repro.kernels.carry_arbiter.ops import carry_arbiter
    from repro.kernels.conflict_popcount.ops import conflict_popcount
    from repro.kernels.fft_stage.ops import fft4096_radix4
    from repro.kernels.moe_dispatch.ops import moe_dispatch_positions

    key = jax.random.PRNGKey(0)
    out = []

    table = to_banked_layout(jax.random.normal(key, (1024, 512)), 16)
    idx = jax.random.randint(key, (256,), 0, 1024)
    out.append(("banked_gather_1024x512_r256",
                _time(lambda: banked_gather(table, idx, 16))))

    banks = jax.random.randint(key, (4096, 16), 0, 16)
    out.append(("conflict_popcount_4096ops",
                _time(lambda: conflict_popcount(banks, 16))))

    reqs = jax.random.randint(key, (1024, 16), 0, 2 ** 16).astype(jnp.uint32)
    out.append(("carry_arbiter_1024ops",
                _time(lambda: carry_arbiter(reqs))))

    experts = jax.random.randint(key, (8192,), 0, 16)
    out.append(("moe_dispatch_8192req_e16",
                _time(lambda: moe_dispatch_positions(experts, 16, 1024))))

    x = (jax.random.normal(key, (4, 4096))
         + 1j * jax.random.normal(key, (4, 4096))).astype(jnp.complex64)
    out.append(("fft4096_radix4_b4",
                _time(lambda: fft4096_radix4(x))))

    m = jax.random.normal(key, (512, 512))
    out.append(("banked_transpose_512",
                _time(lambda: banked_transpose(m))))

    from repro.kernels.registry import interpret_mode
    mode = "interpret-mode (structural)" if interpret_mode() else "compiled"
    note = f"{mode} {jax.default_backend()} wall time"
    return [{"name": n, "us_per_call": round(t, 1), "note": note}
            for n, t in out]


def main():
    for r in rows():
        print(f"{r['name']},{r['us_per_call']},{r['note']}")


if __name__ == "__main__":
    main()
