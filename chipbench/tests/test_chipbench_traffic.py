"""The traffic generator is deterministic per seed, gives every seed the
same sizes in another order, and stays inside each mix's stated ranges."""
import json
from collections import Counter

import numpy as np
import pytest
from tiny_cells import BENCH

import traffic

SERVE_MIXES = ["chat-decode", "long-prompt"]
BIG_SEED = 2**31 + 987654321


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_serving_mix_is_deterministic_per_seed(name):
    t = mix(name)
    a = traffic.serving_requests(t, BIG_SEED, 1000)
    b = traffic.serving_requests(t, BIG_SEED, 1000)
    assert [(r, i.tolist(), o) for r, i, o in a] == \
        [(r, i.tolist(), o) for r, i, o in b]
    c = traffic.serving_requests(t, BIG_SEED + 1, 1000)
    assert [i.tolist() for _, i, _ in a] != [i.tolist() for _, i, _ in c]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    t = mix(name)
    sizes = [Counter((len(i), o) for _, i, o in
                     traffic.serving_requests(t, s, 1000))
             for s in (1, 2, BIG_SEED)]
    assert sizes[0] == sizes[1] == sizes[2]
    orders = [[(len(i), o) for _, i, o in traffic.serving_requests(t, s, 1000)]
              for s in (1, 2)]
    assert orders[0] != orders[1]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_seed_queues_the_same_sizes_block_by_block(name):
    # what a window reaches (a prefix of the backlog) is, to the block,
    # the same work for every seed
    t = mix(name)
    k = t["shuffle_block"]
    runs = [[(len(i), o) for _, i, o in traffic.serving_requests(t, s, 1000)]
            for s in (1, BIG_SEED)]
    for start in range(0, t["n_requests"], k):
        assert Counter(runs[0][start:start + k]) == \
            Counter(runs[1][start:start + k])


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_serving_sizes_stay_inside_the_stated_ranges(name):
    t = mix(name)
    reqs = traffic.serving_requests(t, 7, 500)
    assert len(reqs) == t["n_requests"]
    for _, ids, out in reqs:
        p = ids.shape[0]
        assert t["prompt"]["min"] <= p <= t["prompt"]["max"]
        assert p % t["prompt"]["round_to"] == 0
        assert 1 <= out <= t["output"]["max"]
        assert p + out <= t["max_total"]
        assert ids.min() >= 0 and ids.max() < 500
    shapes = traffic.prompt_shapes(t)
    lo, hi, step = (t["prompt"][k] for k in ("min", "max", "round_to"))
    assert shapes == list(range(lo, hi + 1, step))


def test_quantile_sizes_follow_the_distribution():
    d = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
         "max": 10_000}
    xs = traffic.quantile_sizes(d, 999)
    assert abs(np.median(xs) - 100) <= 1
    assert (np.diff(xs) >= 0).all()


@pytest.mark.parametrize("fresh", [True, False])
def test_pricing_passes_are_deterministic(fresh):
    t = {"fresh_trace_per_pass": fresh}
    it = traffic.pricing_passes(t, BIG_SEED)
    seeds = [next(it) for _ in range(5)]
    it2 = traffic.pricing_passes(t, BIG_SEED)
    assert seeds == [next(it2) for _ in range(5)]
    assert len(set(seeds)) == (5 if fresh else 1)
    assert all(0 <= s < 2**31 for s in seeds)
