"""The plain references agree with the program where they must, at sizes a
CPU holds: the published MiniCPM forward against the program's forward on
the same stored weights, and the pricing reference against ``cost_many``
on every kind of memory.  Their controls come out different."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_cells import TINY_LM, TINY_MOE, TINY_PRICE

import reference_lm
import reference_price
import weights


def test_the_published_forward_is_the_served_model_up_to_logit_scale():
    from drivers.serve import model_config
    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.models import transformer as T
    params = weights.init_params(TINY_LM, 5, "float32")
    tokens = np.random.default_rng(0).integers(0, 509, size=24)
    with jax.default_matmul_precision("highest"):
        rc = RunConfig(param_dtype="float32", compute_dtype="float32",
                       remat="none", attn_impl="dense")
        prog, _ = T.forward(model_config(TINY_LM), rc, params,
                            jnp.asarray(tokens)[None], NO_AXES)
        ref = reference_lm._forward(TINY_LM, params, jnp.asarray(tokens),
                                    fp8=False)
    fs = weights.fold_scales(TINY_LM)
    np.testing.assert_allclose(np.asarray(prog[0, :, :509]),
                               np.asarray(ref) * fs["embed"]
                               * fs["logit_div"], rtol=2e-4, atol=2e-4)


def test_reference_gaps_are_zero_on_its_own_greedy_tokens_and_not_on_fp8():
    params = weights.init_params(TINY_LM, 6, "float32")
    prompt = np.random.default_rng(1).integers(0, 509, size=16)
    toks = list(prompt)
    for _ in range(20):                      # greedy decode by the reference
        logits = reference_lm._forward(TINY_LM, params,
                                       jnp.asarray(np.asarray(toks)),
                                       fp8=False)
        toks.append(int(jnp.argmax(logits[-1])))
    served = np.asarray(toks[16:])
    [(gap, ctrl)] = reference_lm.gaps(TINY_LM, params, [(prompt, served)],
                                      64, control=True)
    assert gap == pytest.approx(0.0, abs=1e-6)
    assert ctrl >= 0.0
    wrong = served.copy()
    wrong[5] = (wrong[5] + 1) % 509
    [(gap2, _)] = reference_lm.gaps(TINY_LM, params, [(prompt, wrong)], 64)
    assert gap2 > 0.0


@pytest.mark.parametrize("routing_seed", [0, 1, 2**31 - 5])
def test_pricing_reference_equals_the_cost_engine(routing_seed):
    from drivers.price import model_config
    from repro.core import arch
    from repro.core.cost_engine import cost_many
    from repro.models.trace import model_step_trace
    t = TINY_PRICE
    trace = model_step_trace(model_config(TINY_MOE), t["page_map"],
                             batch=t["batch"], prompt_len=t["position"],
                             page_len=t["page_len"],
                             block_ops=t["block_ops"], seed=routing_seed)
    costs = cost_many([arch.get(m) for m in t["memories"]], trace,
                      block_ops=t["block_ops"])
    ref = reference_price.price(TINY_MOE, t, routing_seed, t["memories"])
    assert reference_price.mismatches(costs, ref) == 0
    assert costs[0].n_load_ops == ref[0]["n_load_ops"] > 0


@pytest.mark.parametrize("page_map", ["16B", "8B-xor", "4B-fold",
                                      "16B-offset"])
def test_reference_page_table_equals_the_serving_arbiter(page_map):
    from repro.core import arch
    from repro.models.trace import _decode_point
    m = reference_price.memory(page_map)
    *_, pt, _ = _decode_point(_tiny_moe_config(), arch.get(page_map), 6, 44, 8)
    mine = reference_price.page_table(6, 44, 8, m["banks"], m["map"])
    np.testing.assert_array_equal(np.asarray(pt), mine)


def _tiny_moe_config():
    from drivers.price import model_config
    return model_config(TINY_MOE)


def test_pricing_control_breaks_exactness():
    # batch 32 over 4 experts with top-2 at capacity 20: some experts
    # overflow, so predicated lanes exist and the control prices them
    t = dict(TINY_PRICE, batch=32)
    conf = copy.deepcopy(TINY_MOE)
    ref = reference_price.price(conf, t, 3, t["memories"])
    ctrl = reference_price.price(conf, t, 3, t["memories"],
                                 drop_masks=True)
    diff = sum(r[f] != c[f] for r, c in zip(ref, ctrl)
               for f in reference_price.FIELDS)
    assert diff > 0
