"""Driver of the serving cells: the paged ``ServeEngine`` decoding a closed
loop of requests through ``run_scheduler``, the path users run.

Set-up makes the weights on the device from the seed, builds the engine and
warms every shape the mix uses through the calls themselves (one request
per prompt length, which compiles each prefill, each admission scatter and
the decode step).  The window is one ``run_scheduler`` day over the mix's
backlog, driven by a ``Scheduler`` subclass of the benchmark's that notes
the wall time of every tick's tokens (they reach the host at the tick's
end) and, once ``--seconds`` have passed, cancels whatever is left with
``Scheduler.cancel``, so the day ends at the next tick and nothing in the
program changes.  Afterwards a seeded sample of the requests served to
their end is compared with the plain reference: the one with most served
tokens, then one request of every lane that finished one, then more until
the mix's token budget is in.
"""
from __future__ import annotations

import time

import numpy as np

import reference_lm
import traffic as traffic_mod
import weights as weights_mod
from harness import Check, RunResult, SetupError
from stats import percentile, rate, token_gaps


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    c = conf["config"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        rope_theta=float(c.get("rope_theta", 10000.0)),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]))


def check_layout(cfg, params) -> None:
    """The benchmark's weight layout must be the one the program loads."""
    import jax
    from repro.models import model_specs
    from repro.models.params import is_leaf
    want = jax.tree.map(lambda leaf: tuple(leaf.shape), model_specs(cfg),
                        is_leaf=is_leaf)
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if want != got:
        raise SetupError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")


def build(conf: dict, seed: int):
    """(engine, params) for a configuration, weights from the seed."""
    from repro.configs.base import RunConfig
    from repro.launch.sharding import NO_AXES
    from repro.serving.engine import ServeEngine
    dep = conf["deployment"]
    cfg = model_config(conf)
    params = weights_mod.init_params(conf, seed, dep["dtype"])
    check_layout(cfg, params)
    rc = RunConfig(param_dtype=dep["dtype"], compute_dtype=dep["dtype"],
                   remat="none", attn_impl=dep["attn_impl"])
    engine = ServeEngine(cfg, rc, params, NO_AXES,
                         max_batch=int(dep["lanes"]),
                         max_seq=int(dep["max_seq"]),
                         mem_arch=dep["mem_arch"], kv_mode="paged",
                         page_len=int(dep["page_len"]))
    return engine, params


def make_scheduler_class():
    from repro.serving.scheduler import Scheduler

    class WindowScheduler(Scheduler):
        """``Scheduler`` whose ``run`` notes when each tick's tokens reach
        the host and closes the window by cancelling what is left."""

        def __init__(self, *args, seconds: float, spans, **kw):
            super().__init__(*args, **kw)
            self.seconds = seconds
            self.spans = spans
            self.t_start = None
            self.t_close = None
            self.tokens: list = []        # (time, rid), in time order
            self.admitted = 0
            self.lane_of: dict = {}       # rid -> lane that served it

        def _close(self) -> None:
            for r in list(self.queue):
                self.cancel(r.rid)
            for rid in self.lane_rid:
                if rid >= 0 and int(rid) not in self._cancelled:
                    self.cancel(int(rid))

        def run(self, requests=None):
            if requests is not None:
                self.submit(requests)
            self.t_start = time.perf_counter()
            with self.spans("window"):
                while not self.done():
                    with self.spans("tick"):
                        ev = self.tick()
                    rids = self.lane_rid.copy()
                    with self.spans("engine"):
                        yield ev
                    t = time.perf_counter()
                    if self.t_close is not None:
                        continue
                    for adm in ev.admitted:
                        self.admitted += 1
                        self.lane_of[adm.request.rid] = int(adm.lane)
                        if adm.request.max_new_tokens >= 1:
                            self.tokens.append((t, adm.request.rid))
                    if ev.decoded:
                        for lane in np.flatnonzero(ev.active):
                            self.tokens.append((t, int(rids[lane])))
                    if t - self.t_start >= self.seconds:
                        self.t_close = t
                        self._close()

    return WindowScheduler


def requests_of(conf: dict, traffic: dict, seed: int):
    from repro.serving.scheduler import Request
    vocab = int(conf["config"]["vocab_size"])
    return [Request(rid=rid, arrival=0, prompt_len=int(ids.shape[0]),
                    max_new_tokens=int(olen), tokens=ids)
            for rid, ids, olen in traffic_mod.serving_requests(
                traffic, seed, vocab)]


def warm(engine, conf: dict, traffic: dict) -> None:
    """Compile every shape of the mix through the engine's own calls: one
    request per prompt length (each admission compiles that length's
    prefill and scatter), three tokens each (the decode step)."""
    from repro.serving.scheduler import Request
    shapes = traffic_mod.prompt_shapes(traffic)
    lanes = engine.max_batch
    for start in range(0, len(shapes), lanes):
        reqs = [Request(rid=i, arrival=0, prompt_len=p, max_new_tokens=3,
                        tokens=np.zeros(p, np.int32))
                for i, p in enumerate(shapes[start:start + lanes])]
        engine.run_scheduler(reqs)


def instrument(engine, spans, sync: bool) -> None:
    """Spans around the engine's admission and decode calls (instance
    attributes shadow the methods; the program is unchanged).  In a traced
    run an admission's span ends at a device sync, so it holds the
    admission's device work."""
    import jax
    ingest, decode = engine._ingest_request, engine._decode_sched

    def admit(*a, **k):
        with spans("admit"):
            out = ingest(*a, **k)
            if sync:
                jax.block_until_ready(out[0])
        return out

    def step(*a, **k):
        with spans("decode_call"):
            return decode(*a, **k)

    engine._ingest_request = admit
    engine._decode_sched = step


def serve_window(engine, reqs, seconds: float, spans):
    """One closed-loop day over ``reqs``; returns (scheduler, result)."""
    cls = make_scheduler_class()
    sched = cls(engine.kv_cfg, n_lanes=engine.max_batch,
                max_seq=engine.max_seq, policy="seq-skew",
                n_kv_layers=engine.n_kv_layers, seconds=seconds,
                spans=spans)
    res = engine.run_scheduler(reqs, scheduler=sched)
    return sched, res


def sample(reqs, res, traffic: dict, seed: int, lane_of: dict) -> list:
    """A seeded sample of the requests served to their end: the one with
    most served tokens, then one of every other lane that finished a
    request, then more until ``check.sample_tokens`` served tokens or
    ``check.max_requests`` requests are in.  A fault confined to some
    lanes is thus always in the sample."""
    served = [(r, res.outputs[r.rid]) for r in reqs
              if len(res.outputs.get(r.rid, ())) == r.max_new_tokens]
    if not served:
        return []
    served.sort(key=lambda x: (-len(x[1]), x[0].rid))
    first, rest = served[0], served[1:]
    rng = traffic_mod.seed_stream(seed, 3)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    picked, lanes = [first], {lane_of[first[0].rid]}
    for item in rest:
        if lane_of[item[0].rid] not in lanes:
            lanes.add(lane_of[item[0].rid])
            picked.append(item)
    want = int(traffic["check"]["sample_tokens"])
    cap = int(traffic["check"]["max_requests"])
    n = sum(len(out) for _, out in picked)
    for item in rest:
        if n >= want or len(picked) >= cap:
            break
        if all(item is not p for p in picked):
            picked.append(item)
            n += len(item[1])
    return picked


def model_flops(conf: dict, contexts) -> float:
    """FLOPs the model needs for tokens decoded at the given context
    lengths: two per weight of every matmul (the unembedding included)
    plus the attention scores and values over each token's context."""
    s = weights_mod.sizes(conf)
    d, kvd = s["d"], s["kv"] * s["hd"]
    per_layer = d * d * 2 + d * kvd * 2 + 3 * d * s["f"]
    dense = 2.0 * (s["layers"] * per_layer + d * s["vocab"])
    return float(sum(dense + 4.0 * s["layers"] * d * c for c in contexts))


def token_contexts(reqs, tokens) -> list:
    """Context length each served token was decoded at."""
    plen = {r.rid: r.prompt_len for r in reqs}
    seen: dict = {}
    out = []
    for _, rid in tokens:
        k = seen.get(rid, 0)
        seen[rid] = k + 1
        out.append(plen[rid] + k)
    return out


def run(cell, seed: int, seconds: float, trace: bool, env) -> RunResult:
    conf, traffic = cell.config, cell.traffic
    engine, params = build(conf, seed)
    reqs = requests_of(conf, traffic, seed)
    warm(engine, conf, traffic)
    instrument(engine, env.spans, sync=trace)
    if trace:
        env.start_profile()
    env.mark_setup_done()
    sched, res = serve_window(engine, reqs, seconds, env.spans)
    if trace:
        env.stop_profile()
    if sched.t_close is None:
        raise SetupError("the backlog ran out before the window closed; "
                         "the mix needs more requests")
    window = sched.t_close - sched.t_start
    gaps = token_gaps(sched.tokens)
    peak = env.read_memory_peak()
    stats = sched.stats()
    e2e = {"serve_tokens_per_s": rate(len(sched.tokens), window),
           "serve_itl_p90_ms": percentile(gaps, 90) * 1e3,
           "serve_peak_hbm_gb": peak / 1e9}
    values = {
        "lane_occupancy": float(stats["lane_occupancy"]),
        "model_flops": model_flops(conf, token_contexts(reqs, sched.tokens)),
    }
    picked = sample(reqs, res, traffic, seed, sched.lane_of)
    lanes = len({sched.lane_of[r.rid] for r, _ in picked})
    admitted = sched.admitted
    # the program's state is freed before the reference runs on the device
    del engine, sched, res
    seqs = [(r.tokens, out) for r, out in picked]
    limit = float(conf["correct"]["max_logit_gap"])
    results = reference_lm.gaps(conf, params, seqs,
                                int(conf["deployment"]["max_seq"]),
                                control=env.control)
    n_served = sum(len(out) for _, out in seqs)
    notes = {"sampled_requests": len(picked), "sampled_tokens": n_served,
             "sampled_lanes": lanes,
             "itl_ms": {f"p{q}": percentile(gaps, q) * 1e3
                        for q in (50, 95, 99)}}
    # the control puts the float8 reference's first choices in the served
    # tokens' place, and the same checks decide ``correct``
    pick = 1 if env.control else 0
    if env.control:
        notes["program_max_logit_gap"] = max(
            (g for g, _ in results), default=float("inf"))
    widest = max((r[pick] for r in results), default=float("inf"))
    checks = [Check("max_logit_gap", widest, limit),
              Check("sampled_tokens_short",
                    float(max(0, int(traffic["check"]["min_tokens"])
                              - n_served)), 0.0)]
    return RunResult(end_to_end=e2e, checks=checks, attempted=admitted,
                     failed=sum(r[pick] > limit for r in results),
                     readings=values, notes=notes)
