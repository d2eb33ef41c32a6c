"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and turns them, with ``--seed``, into work.

Every seed gets the same multiset of sizes, in another order: the sizes are
fixed quantiles of the stated distributions, and the seed only shuffles the
queue within small blocks and draws the prompt ids (or the routing seeds).
So two seeds differ in the order of nearby requests, not in how much work
the mix holds or how much of it a window reaches.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

#: the golden ratio's fractional part, which spreads quantile indices so
#: that every run of consecutive requests holds a representative mix
_PHI = (math.sqrt(5.0) - 1.0) / 2.0
#: a second irrational step, for the queue order (independent of _PHI's)
_SQRT2 = math.sqrt(2.0) - 1.0


def seed_stream(seed: int, *salt: int) -> np.random.Generator:
    """A generator for one purpose of one seed (seeds may exceed 32 bits)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def derived_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed for one purpose of one run seed."""
    ss = np.random.SeedSequence([int(seed), *salt])
    return int(ss.generate_state(1, np.uint32)[0]) & 0x7FFFFFFF


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at the mid-quantiles of a clipped log-normal
    (``median``, ``sigma``), rounded up to ``round_to``."""
    if dist.get("dist") != "lognormal":
        raise ValueError(f"unknown size distribution {dist.get('dist')!r}")
    nd = NormalDist()
    lo, hi = int(dist["min"]), int(dist["max"])
    step = int(dist.get("round_to", 1))
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        x = min(max(x, lo), hi)
        out.append(min(hi, -(-math.ceil(x) // step) * step))
    return np.asarray(out, np.int64)


def request_sizes(traffic: dict) -> list[tuple[int, int]]:
    """The mix's (prompt_len, output_len) pairs before any seed: prompt
    quantiles paired with output quantiles in a golden-ratio order, so the
    two lengths are decorrelated the same way for every seed."""
    n = int(traffic["n_requests"])
    prompts = quantile_sizes(traffic["prompt"], n)
    outputs = quantile_sizes(traffic["output"], n)
    order = np.argsort([(i * _PHI) % 1.0 for i in range(n)], kind="stable")
    cap = int(traffic["max_total"])
    pairs = []
    for p, o in zip(prompts, outputs[order]):
        pairs.append((int(p), int(max(1, min(o, cap - p)))))
    return pairs


def prompt_shapes(traffic: dict) -> list[int]:
    """Every distinct prompt length the mix sends (what set-up warms)."""
    return sorted({p for p, _ in request_sizes(traffic)})


def backlog_order(n: int, block: int, rng: np.random.Generator) -> list:
    """Queue order of ``n`` requests: a low-discrepancy sweep over the
    quantiles, shuffled by the seed only within consecutive blocks, so that
    every prefix of the backlog (what a window reaches) holds nearly the
    same sizes for every seed."""
    sweep = np.argsort([(i * _SQRT2) % 1.0 for i in range(n)],
                       kind="stable")
    out: list = []
    for start in range(0, n, block):
        chunk = sweep[start:start + block]
        out += [int(i) for i in chunk[rng.permutation(len(chunk))]]
    return out


def serving_requests(traffic: dict, seed: int, vocab_size: int):
    """The mix as (rid, prompt ids, output_len) triples, closed loop: all
    requests are queued at the start and a lane takes the next one as soon
    as it frees."""
    if traffic.get("loop") != "closed":
        raise ValueError(f"unsupported loop {traffic.get('loop')!r}")
    pairs = request_sizes(traffic)
    rng = seed_stream(seed, 1)
    order = backlog_order(len(pairs), int(traffic["shuffle_block"]), rng)
    out = []
    for rid, i in enumerate(order):
        plen, olen = pairs[i]
        ids = rng.integers(0, vocab_size, size=plen).astype(np.int32)
        out.append((rid, ids, olen))
    return out


def pricing_passes(traffic: dict, seed: int):
    """Routing seeds of the passes, in order (an endless stream: the window
    decides how many are priced).  A mix that prices one recorded step
    reuses the first seed for every pass."""
    fresh = bool(traffic["fresh_trace_per_pass"])
    i = 0
    while True:
        yield derived_seed(seed, 2, i if fresh else 0)
        i += 1
