"""Serving on the banked memory model (docs/SERVING.md).

``ServeEngine`` runs batched prefill + decode with its KV cache living in a
banked paged pool: pages are allocated by the paper's carry-chain arbiter
(``kvcache.allocate_pages``), every decode-step KV read/write flows through
the ``banked_gather`` / ``banked_scatter`` registry kernels, and each step's
request stream is recorded as a first-class
``repro.core.trace.AddressTrace`` (``engine.step_trace()``), so
``arch.cost(trace)`` prices serving traffic exactly like the Table II/III
kernels.  ``bench.serving_workload`` wraps the same traffic as a sweep/tune
workload (``kvcache.simulate_serving_trace`` — no model required).

``repro.serving.scheduler`` adds the continuous-batching control plane:
multi-tenant request queues, mid-flight admission/eviction over a
free-bitmap ``PagePool`` with a sequence-skewed preferred-bank policy, and
whole serving *days* lowered to the streaming ``Trace`` protocol
(``simulate_scheduler_stream``); ``ServeEngine.run_scheduler`` drives the
same schedule lane-ragged against the real model.

Layout decisions (bank count, page→bank map, map shift) always come from a
``repro.core.arch`` architecture via ``PagedKVConfig.from_arch`` — serving
holds no private layout constants.
"""
from repro.serving.engine import (GenerationResult, SchedulerRunResult,
                                  ServeEngine)
from repro.serving.kvcache import (ALLOC_POLICIES, PagedKVConfig,
                                   PagedKVState, PageTableState,
                                   allocate_pages, allocate_prompt_pages,
                                   append_token,
                                   bank_load_stats, decode_step_trace,
                                   gather_kv, gather_pages, init_pages,
                                   init_state, pool_pages, prefill_trace,
                                   preferred_banks, resolve_policy,
                                   scatter_pages, simulate_serving_stream,
                                   simulate_serving_trace)
from repro.serving.scheduler import (PagePool, Request, Scheduler,
                                     scheduler_step_trace,
                                     simulate_scheduler_stream,
                                     synthesize_requests)

__all__ = [
    "ServeEngine", "GenerationResult", "SchedulerRunResult",
    "PagedKVConfig", "PagedKVState", "PageTableState",
    "pool_pages", "init_pages", "init_state", "allocate_pages",
    "allocate_prompt_pages", "append_token", "gather_kv", "bank_load_stats",
    "gather_pages", "scatter_pages",
    "decode_step_trace", "prefill_trace", "simulate_serving_trace",
    "simulate_serving_stream",
    "ALLOC_POLICIES", "preferred_banks", "resolve_policy",
    "Request", "Scheduler", "PagePool", "scheduler_step_trace",
    "simulate_scheduler_stream", "synthesize_requests",
]
