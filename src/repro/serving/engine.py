"""Serving engine over the banked paged-KV pools (paper mapping: KV pages =
banks; docs/SERVING.md).

There is one paged decode path, the lane-ragged step ``_scheduler_step``
(jit'd once as ``_decode_sched``): per attention layer it gathers each
lane's page list from the K and V pools (``kernels.get("banked_gather")``),
attends at the lane's own position, and writes the lane's *current* page
back (``kernels.get("banked_scatter")``, a read-modify-write append); a
Mamba layer advances its lane-indexed SSM state slot.  ``run_scheduler``
drives it for continuous batching; ``generate`` serves a fixed, padded
batch as its all-lanes-active case.  Either way a prompt's K/V is lowered
to page rows (``_lower_rows``) and scattered into the donated pools in one
call (``_land_rows``), after which no dense KV cache exists.

Every decode step records its exact ``repro.core.trace.AddressTrace``
(``step_trace()`` / ``serving_trace()`` / ``scheduler_stream()``), so
``arch.cost(trace)`` prices the serving traffic with the same model that
prices the Table II/III kernels.  ``kv_mode="dense"`` keeps the pre-banked
dense-cache decode as the reference the paged path is pinned against
(tests/test_serving_paged.py).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import arch as _arch
from repro.launch.sharding import Axes
from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime import telemetry
from repro.serving import kvcache as KV


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, new) generated ids
    prompt_len: int
    steps: int


@dataclass
class SchedulerRunResult:
    """One continuous-batching run: per-request generated ids (rid-keyed;
    a request's array has exactly ``max_new_tokens`` entries), the
    scheduler's run statistics (makespan, lane occupancy, bank-occupancy
    skew, fault counters), and the tick count.  ``preempted`` marks a run
    stopped mid-day by a preemption event (or ``PreemptionGuard``); its
    ``checkpoint`` path resumes via ``run_scheduler(resume_from=...)``
    with tokens identical to an uninterrupted run."""
    outputs: dict[int, np.ndarray]
    stats: dict
    ticks: int
    preempted: bool = False
    checkpoint: str | None = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, rc: RunConfig, params, ax: Axes,
                 max_batch: int = 8, max_seq: int = 256,
                 mem_arch="16B", kv_mode: str = "paged",
                 page_len: int = 8):
        self.cfg, self.rc, self.ax = cfg, rc, ax
        self.params = params
        self.max_batch, self.max_seq = max_batch, max_seq
        #: the shared-memory architecture serving-side layout decisions come
        #: from (KV page banking; see ``paged_kv_config``)
        self.mem_arch = _arch.resolve(mem_arch)
        if kv_mode not in ("paged", "dense"):
            raise ValueError(f"kv_mode must be 'paged' or 'dense', "
                             f"got {kv_mode!r}")
        if kv_mode == "paged" and self.mem_arch.layout is None:
            raise ValueError(
                f"{self.mem_arch.name} has no banked layout; pick a banked "
                f"mem_arch for paged-KV serving (or kv_mode='dense')")
        self.kv_mode = kv_mode
        self.page_len = page_len
        self.kv_cfg = (self.paged_kv_config(page_len)
                       if kv_mode == "paged" else None)
        self._prefill = jax.jit(
            lambda p, t: T.prefill(cfg, rc, p, t, ax))
        # an admission's three device calls: prefill with its first token,
        # the lowering of every attention layer's K/V to page rows (one
        # compile per prompt length), and the scatter of those rows into
        # the KV pools, which are donated: an undonated call over every
        # pool would hold a second copy of each
        self._prefill_first = jax.jit(self._prefill_first_token)
        self._lower_rows = jax.jit(self._cache_rows, static_argnums=1)
        self._land_rows = jax.jit(self._scatter_pool_rows, donate_argnums=0)
        self._decode = jax.jit(
            lambda p, tok, cache, pos: T.decode_step(cfg, rc, p, tok, cache,
                                                     pos, ax))
        self._decode_sched = jax.jit(self._scheduler_step)
        #: a fixed batch's page-table advance before each decode step
        self._grow_pages = jax.jit(self._next_pages)
        layers = [(f"b{j}s{sb}", f"b{j}", sb, kind == "attn")
                  for j, (kind, _) in enumerate(cfg.block_pattern())
                  for sb in range(cfg.n_superblocks)]
        #: pool key ``b{j}s{sb}`` -> (block ``b{j}``, superblock ``sb``)
        self._layer_of = {key: (blk, sb) for key, blk, sb, _ in layers}
        #: pool keys of the attention layers, each a K and a V page pool
        self._kv_keys = tuple(key for key, _, _, attn in layers if attn)
        #: pool keys of the Mamba layers: each holds one lane-indexed SSM
        #: state slot per lane (slot = lane) instead of a K/V page pool;
        #: empty for an attention-only model
        self._ssm_keys = tuple(key for key, _, _, attn in layers if not attn)
        # the old slots are dead once written: donated, so a write costs
        # one lane's rows and not a copy of every slot
        self._slot_write = jax.jit(self._write_slot_rows, donate_argnums=0)
        self._step_traces: list = []
        self._prefill_trace = None
        self._sched_traces: list = []
        self._sched_meta: dict = {}
        #: final PageTableState of the last paged generate (bank occupancy
        #: introspection: ``kvcache.bank_load_stats(engine.last_pages)``)
        self.last_pages: KV.PageTableState | None = None

    # -- configuration -----------------------------------------------------

    def paged_kv_config(self, page_len: int = 8) -> KV.PagedKVConfig:
        """Banked paged-KV pool layout for this engine's batch/seq budget,
        derived from ``mem_arch`` via ``repro.core.arch`` (bank count and
        page→bank map come from the architecture's ``BankedLayout``, not
        serving-local constants).  Pool is sized 2× the worst-case live
        pages, rounded up to a whole number of banks."""
        lay = self.mem_arch.layout
        if lay is None:
            raise ValueError(
                f"{self.mem_arch.name} has no banked layout; pick a banked "
                f"mem_arch for paged-KV serving")
        kv_heads = self.cfg.n_kv_heads or self.cfg.n_heads
        return KV.PagedKVConfig.from_arch(
            self.mem_arch,
            n_pages=KV.pool_pages(lay.n_banks, self.max_batch, self.max_seq,
                                  page_len),
            page_len=page_len, kv_heads=kv_heads, head_dim=self.cfg.hd)

    @property
    def n_kv_layers(self) -> int:
        """Attention layers, each with a K and a V page pool."""
        return len(self._kv_keys)

    @property
    def n_ssm_layers(self) -> int:
        """Mamba layers, each with a per-lane SSM state slot."""
        return len(self._ssm_keys)

    # -- paged decode path -------------------------------------------------

    def _paged_attention_decode_ragged(self, cfg, p, x, cache, pos, ax, *,
                                       window: int = 0, page_table=None,
                                       active=None, scratch=0):
        """``L.attention_decode`` against the banked page pools, with
        per-lane positions: gather each lane's pages (banked_gather),
        insert the new token at the lane's OWN position (``pos`` is (B,)),
        attend, and write the lane's current page back (banked_scatter).
        Numerics match the dense path — same einsums, masks and dtypes.
        Lanes with no resident sequence (``active`` False) insert nothing
        and scatter to the reserved ``scratch`` page — the Pallas scatter
        has no lane predication, so idle lanes need a harmless sink (the
        trace predicates them off; see ``kvcache.decode_step_trace``)."""
        kv = self.kv_cfg
        arch = self.mem_arch
        b = x.shape[0]
        plen = kv.page_len
        n_pt = page_table.shape[1]
        s_all = n_pt * plen
        kvh, hd = cfg.n_kv_heads, cfg.hd
        with jax.named_scope("qkv"):
            q, k_new, v_new = L._qkv(cfg, p, x, pos[:, None], ax)
        with jax.named_scope("page_gather"):
            ids = jnp.maximum(page_table, 0).reshape(-1)
            ck = KV.gather_pages(arch, kv, cache["k"], ids)
            cv = KV.gather_pages(arch, kv, cache["v"], ids)
            ck = ck.reshape(b, s_all, kvh, hd)
            cv = cv.reshape(b, s_all, kvh, hd)
        with jax.named_scope("attention"):
            idx = jnp.arange(s_all)
            hot = ((idx[None, :] == pos[:, None])
                   & active[:, None])[:, :, None, None]
            ck = jnp.where(hot, k_new.astype(ck.dtype), ck)
            cv = jnp.where(hot, v_new.astype(cv.dtype), cv)
            valid = ((idx[None, :] <= pos[:, None]) & active[:, None]
                     & jnp.repeat(page_table >= 0, plen, axis=1))
            if window:
                valid &= (pos[:, None] - idx[None, :]) < window
            s = jnp.einsum("bqkgh,btkh->bkgqt", q,
                           ck.astype(q.dtype)) / math.sqrt(hd)
            s = L.softcap(s, cfg.attn_softcap)
            s = jnp.where(valid[:, None, None, None, :], s, L.NEG_INF)
            pr = jax.nn.softmax(s.astype(jnp.float32),
                                axis=-1).astype(q.dtype)
            o = jnp.einsum("bkgqt,btkh->bqkgh", pr, cv.astype(q.dtype))
            o = o.reshape(b, 1, cfg.n_heads, hd)
            out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
        # per-lane read-modify-write append of each lane's current page
        with jax.named_scope("page_scatter"):
            pg = jnp.minimum(pos // plen, n_pt - 1)
            cur = jnp.where(active,
                            jnp.maximum(page_table[jnp.arange(b), pg], 0),
                            scratch)
            line = (pg * plen)[:, None] + jnp.arange(plen)[None, :]
            k_line = jnp.take_along_axis(ck, line[:, :, None, None], axis=1)
            v_line = jnp.take_along_axis(cv, line[:, :, None, None], axis=1)
            kp = KV.scatter_pages(arch, kv, cache["k"], cur,
                                  k_line.reshape((b,) + kv.page_shape))
            vp = KV.scatter_pages(arch, kv, cache["v"], cur,
                                  v_line.reshape((b,) + kv.page_shape))
        return out, {"k": kp, "v": vp}

    def _scheduler_step(self, params, tok, pools, page_table, pos, active,
                        scratch):
        """One lane-ragged full-model decode step (jit'd once; the page
        table, per-lane positions and active mask are traced values with
        static shapes, so admissions/completions never recompile).  The
        host side owns allocation: there is no in-graph ``allocate_pages``.
        A Mamba layer's ``pools`` entry is its lane-indexed SSM state
        (``{"h": (B, Di, N) f32, "conv": (B, K-1, Di)}``), advanced for
        the ``active`` lanes only."""
        cfg, rc, ax = self.cfg, self.rc, self.ax
        dtype = jnp.dtype(rc.compute_dtype)
        with jax.named_scope("embed"):
            x = params["embed"].astype(dtype)[tok]
        pattern = cfg.block_pattern()
        pools = dict(pools)
        attn_fn = functools.partial(
            self._paged_attention_decode_ragged, page_table=page_table,
            active=active, scratch=scratch)
        for sb in range(cfg.n_superblocks):
            for j, (kind, is_moe) in enumerate(pattern):
                p_sb = jax.tree.map(lambda a: a[sb],
                                    params["blocks"][f"b{j}"])
                key = f"b{j}s{sb}"
                with jax.named_scope(key):
                    x, new = T.apply_block_decode(
                        cfg, rc, p_sb, x, pools[key], pos, ax, kind, is_moe,
                        j, attn_fn=attn_fn)
                    if kind != "attn":
                        # an idle or still-prefilling lane keeps its SSM
                        # slot bit for bit
                        new = jax.tree.map(
                            lambda n, o: jnp.where(
                                active.reshape((-1,) + (1,) * (o.ndim - 1)),
                                n.astype(o.dtype), o), new, pools[key])
                    pools[key] = new
        with jax.named_scope("unembed"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = T._unembed(cfg, params, x)
        return logits, pools

    def _prefill_first_token(self, params, tokens):
        """``T.prefill`` of one request and its greedy first token."""
        logits, cache = T.prefill(self.cfg, self.rc, params, tokens, self.ax)
        return jnp.argmax(logits[0, -1, :self.cfg.vocab_size]), cache

    def _cache_rows(self, cache, plen: int):
        """Every attention block's stacked K/V prefill cache of ``B``
        prompts ``(n_superblocks, B, t, KV, HD)`` lowered to page rows
        ``(n_superblocks, B · n_pref) + page_shape`` (prompt ``b``'s page
        ``k`` at row ``b · n_pref + k``), keyed ``b{j}`` (jit'd once per
        batch and prompt length ``plen``).  ``t`` ≤ ``plen``: SWA keeps
        only the window, so the rows before it and past the prompt stay
        zero.  One superblock at a time (``lax.map``): lowering the whole
        stack at once makes the TPU compiler stage a relayout copy of it."""
        kv = self.kv_cfg
        n_pref = -(-plen // kv.page_len)

        def lower(c):                   # one superblock: (B, t, KV, HD)
            pad = [(0, 0)] * c.ndim
            pad[1] = (plen - c.shape[1], n_pref * kv.page_len - plen)
            return jnp.pad(c, pad).reshape((-1,) + kv.page_shape)

        return {f"b{j}": {h: jax.lax.map(lower, cache["blocks"][f"b{j}"][h])
                          for h in ("k", "v")}
                for j, (kind, _) in enumerate(self.cfg.block_pattern())
                if kind == "attn"}

    def _prefill_rows(self, prompt: np.ndarray):
        """Prefill ONE request and lower its K/V to page rows: returns the
        request's first generated token id, the page rows of every
        attention block (``_cache_rows``: page ``k`` of superblock ``sb``
        at ``[sb, k]``, ready to scatter at whatever tick the scheduler
        lands that page — whole-prompt admission scatters all rows at once;
        chunked prefill scatters slices as ``ev.prefill_chunks`` records
        arrive) and the prompt's final SSM state of every Mamba block
        (``{}`` for an attention-only model; ``_write_slots`` lands it).
        Two jitted calls, one compile each per distinct prompt length.  K/V
        slots past the prompt in its last page stay zero; every decode mask
        is ``idx <= pos``, so a stale slot is never read before the decode
        step that writes it."""
        telemetry.count("engine.admit_dispatches", 2)
        with telemetry.span("engine.prefill"):
            first, cache = self._prefill_first(self.params,
                                               jnp.asarray(prompt)[None])
            with telemetry.span("engine.rows"):
                rows = self._lower_rows(cache, int(prompt.shape[0]))
            state = {f"b{j}": cache["blocks"][f"b{j}"]   # (n_sb, 1, ...)
                     for j, (kind, _) in enumerate(self.cfg.block_pattern())
                     if kind != "attn"}
            first = int(first)
        return first, rows, state

    def _write_slot_rows(self, slots, state, lane):
        """Every Mamba layer's slot with lane ``lane``'s row replaced by
        one prefill's final state (jit'd once: ``lane`` is traced)."""
        out = {}
        for key in self._ssm_keys:
            blk, sb = self._layer_of[key]
            out[key] = {name: slot.at[lane].set(
                state[blk][name][sb, 0].astype(slot.dtype))
                for name, slot in slots[key].items()}
        return out

    def _write_slots(self, pools, state, lane: int):
        """Land an admitted request's prefill state in its lane's SSM slot
        of every Mamba layer: one jitted call, its old slots donated."""
        telemetry.count("engine.ssm_slot_writes", 2 * len(self._ssm_keys))
        telemetry.count("engine.admit_dispatches")
        with telemetry.span("engine.ssm_slot"):
            slots = {key: pools[key] for key in self._ssm_keys}
            return {**pools, **self._slot_write(slots, state, lane)}

    def _scatter_pool_rows(self, kv_pools, rows, ids, page_start):
        """Every KV pool with rows ``page_start .. page_start + len(ids)``
        of its held prefill rows scattered at ``ids`` (jit'd once per row
        and id count: ``page_start`` is traced; ``kv_pools`` is donated)."""
        n = ids.shape[0]
        out = {}
        for key in self._kv_keys:
            blk, sb = self._layer_of[key]
            out[key] = {h: KV.scatter_pages(
                self.mem_arch, self.kv_cfg, kv_pools[key][h], ids,
                jax.lax.dynamic_slice_in_dim(rows[blk][h][sb], page_start, n))
                for h in ("k", "v")}
        return out

    def _scatter_rows(self, pools, rows, page_ids, page_start: int = 0):
        """Scatter one contiguous slice of held prefill rows into every KV
        pool at the scheduler-allocated ids — the live half of a prefill
        chunk (or, with ``page_start=0`` and all ids, of a whole-prompt
        admission): one jitted call of one ``banked_scatter`` per pool and
        K/V, the KV pools donated; a hybrid's SSM slots pass through."""
        telemetry.count("engine.scatter_calls", 2 * self.n_kv_layers)
        telemetry.count("engine.admit_dispatches")
        with telemetry.span("engine.scatter"):
            kv_pools = {key: pools[key] for key in self._kv_keys}
            return {**pools, **self._land_rows(
                kv_pools, rows, np.asarray(page_ids, np.int32),
                page_start)}

    def _ingest_request(self, pools, prompt: np.ndarray, page_ids,
                        rid: int | None = None, lane: int | None = None):
        """Whole-prompt admission of request ``rid`` into ``lane``: prefill
        and scatter every prompt page at once (and, for a hybrid, write
        the lane's SSM slots).  Returns the updated pools and the first
        token id."""
        with telemetry.span("engine.admit", rid=rid):
            first, rows, state = self._prefill_rows(prompt)
            pools = self._scatter_rows(pools, rows, page_ids)
            if state:
                pools = self._write_slots(pools, state, lane)
            return pools, first

    def _init_pools(self, batch: int):
        """Zero pools for ``batch`` lanes: a K and a V page pool per
        attention layer, and per Mamba layer a lane-indexed SSM state slot
        (``{"h": (batch, Di, N) f32, "conv": (batch, K-1, Di)}``)."""
        cfg, dtype = self.cfg, jnp.dtype(self.rc.compute_dtype)

        def zeros(key):
            if key in self._ssm_keys:
                return {"h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state),
                                       jnp.float32),
                        "conv": jnp.zeros((batch, cfg.ssm_conv - 1,
                                           cfg.d_inner), dtype)}
            # K and V are distinct buffers: admission donates both
            return {h: jnp.zeros((self.kv_cfg.n_pages,)
                                 + self.kv_cfg.page_shape, dtype)
                    for h in ("k", "v")}

        return {key: zeros(key) for key in self._layer_of}

    def _migrate_pages(self, pools, old_ids, new_ids):
        """Evacuate a dying bank's live pages: gather each page's row from
        its old id and scatter it to the freshly allocated surviving-bank
        id, in every layer's K and V pool.  Data is preserved — the banked
        kernels themselves perform the migration, so the live traffic
        matches the ``fault_migrate`` trace block the scheduler emitted."""
        kv = self.kv_cfg
        old = jnp.asarray(np.asarray(old_ids, np.int32))
        new = jnp.asarray(np.asarray(new_ids, np.int32))
        pools = dict(pools)
        for key in self._kv_keys:           # SSM slots hold no pages
            out = {}
            for half, pool in pools[key].items():
                rows = KV.gather_pages(self.mem_arch, kv, pool, old)
                out[half] = KV.scatter_pages(self.mem_arch, kv, pool, new,
                                             rows)
            pools[key] = out
        return pools

    def _recover_page(self, pools, rec, toks, lane_tok, scratch):
        """Rebuild a corrupted page's data: zero its line in every pool
        (the data is LOST — this is the ECC-parity path, not migration),
        re-prefill the victim request's prompt pages, then replay its
        completed decode steps feeding the recorded tokens.  Every replayed
        token is pinned against the original — recovery that silently
        diverges is an error, not a degraded answer.  A hybrid lane's SSM
        slots are rebuilt on the way: the re-prefill rewrites them with the
        prompt's state and each replayed step (only the victim lane active)
        advances them by one served token."""
        r = rec["request"]
        rid, lane = rec["rid"], rec["lane"]
        pid = int(rec["pid"])
        pools = {key: (pair if key in self._ssm_keys else
                       {h: p.at[pid].set(0) for h, p in pair.items()})
                 for key, pair in pools.items()}
        pools, first = self._ingest_request(
            pools, np.asarray(r.tokens, np.int32), rec["prompt_ids"],
            rid=rid, lane=lane)
        seq = toks[rid]
        if seq and first != seq[0]:
            raise RuntimeError(
                f"recovery diverged for request {rid}: re-prefill produced "
                f"token {first}, original was {seq[0]}")
        plen = int(rec["plen"])
        act = np.zeros(self.max_batch, bool)
        act[lane] = True
        for j in range(int(rec["steps"])):
            pos = np.asarray(rec["pos"]).copy()
            pos[lane] = plen + j
            lt = lane_tok.at[lane, 0].set(int(seq[j]))
            logits, pools = self._decode_sched(
                self.params, lt, pools, jnp.asarray(rec["page_table"]),
                jnp.asarray(pos), jnp.asarray(act), scratch)
            got = int(jnp.argmax(logits[lane, -1, :self.cfg.vocab_size]))
            if got != int(seq[j + 1]):
                raise RuntimeError(
                    f"recovery diverged for request {rid} at replay step "
                    f"{j}: decoded {got}, original was {int(seq[j + 1])}")
        return pools

    def run_scheduler(self, requests, policy="seq-skew", scheduler=None,
                      fault_plan=None, guard=None, checkpoint_dir=None,
                      resume_from=None,
                      prefill_chunk_pages=None) -> SchedulerRunResult:
        """Continuous-batching generation: drive real lane-ragged decode
        steps from ``scheduler.Scheduler`` (greedy sampling).

        The same scheduler instance that picks lanes and allocates pages
        also emits the run's ``AddressTrace`` blocks, and this driver feeds
        the scheduler's OWN page-table/position/active snapshots to the
        jit'd step — so the recorded live trace (``scheduler_stream()``) is
        bit-equal to ``scheduler.simulate_scheduler_stream`` on the same
        traffic by construction (pinned in tests/test_scheduler.py).

        ``prefill_chunk_pages=N`` enables chunked prefill: the prompt's
        K/V rows are computed once at admission, HELD, and scattered chunk
        by chunk as the scheduler's ``ev.prefill_chunks`` records land the
        pages — other lanes keep decoding between chunks, and live == sim
        stays bit-equal across every chunk boundary.

        Requests need prompt ``tokens``; admission order, page placement
        and completion order are exactly the simulation's.

        Hybrids (Mamba + attention) are served too: each Mamba layer keeps
        a lane-indexed SSM state slot in ``pools`` beside the attention
        layers' page pools (slot = lane).  Admission writes the prompt's
        final state into the lane's slot (a chunked admission when its last
        chunk lands), the decode step advances active lanes only, and a
        checkpoint carries the slots with the pools.  The recorded trace
        lowers the attention layers' KV traffic only (``n_kv_layers``).
        An attention-only model allocates no slot.

        Fault tolerance (docs/ROBUSTNESS.md): ``fault_plan`` injects a
        seeded ``repro.runtime.FaultPlan`` timeline — bank losses migrate
        live pages through the banked kernels, corrupted pages re-prefill
        and replay with every token pinned, transient decode faults retry
        via ``runtime.retry_step``.  A preemption event (or a tripped
        ``PreemptionGuard``) checkpoints to ``checkpoint_dir`` after the
        tick's physics and returns ``preempted=True``; pass the directory
        back as ``resume_from`` (with ``requests=None`` and the SAME
        ``fault_plan``) to finish the day with identical tokens.
        """
        from repro.checkpoint import (latest_step, load_aux,
                                      restore_checkpoint, save_checkpoint)
        from repro.runtime import TransientFault, retry_step
        from repro.serving.scheduler import Scheduler
        if self.kv_mode != "paged":
            raise ValueError("run_scheduler requires kv_mode='paged'")
        if resume_from is not None and requests is not None:
            raise ValueError("pass requests=None when resuming: the "
                             "checkpointed scheduler still holds them")
        sched = scheduler or Scheduler(
            self.kv_cfg, n_lanes=self.max_batch, max_seq=self.max_seq,
            policy=policy, n_kv_layers=self.n_kv_layers,
            fault_plan=fault_plan, prefill_chunk_pages=prefill_chunk_pages,
            n_ssm_layers=self.n_ssm_layers)
        if sched.n_ssm_layers != self.n_ssm_layers:
            raise ValueError(
                f"the scheduler carries {sched.n_ssm_layers} SSM layers, "
                f"the model {self.n_ssm_layers}")
        pools = self._init_pools(self.max_batch)
        scratch = jnp.asarray(sched.scratch_page or 0, jnp.int32)
        lane_tok = jnp.zeros((self.max_batch, 1), jnp.int32)
        lane_rid = np.full(self.max_batch, -1, np.int64)
        toks: dict[int, list] = {}
        outputs: dict[int, np.ndarray] = {}
        #: held prefill rows of lanes mid-chunked-prefill
        #: (lane -> per-block row arrays; see ``_prefill_rows``)
        pending: dict[int, dict] = {}
        #: a hybrid's held prefill SSM state of those lanes, written into
        #: the lane's slots when the last chunk lands
        held_state: dict[int, dict] = {}
        if resume_from is not None:
            step = latest_step(resume_from)
            if step is None:
                raise ValueError(f"no checkpoint found in {resume_from}")
            restored = restore_checkpoint(
                resume_from, step, {"pools": pools, "lane_tok": lane_tok})
            pools, lane_tok = restored["pools"], restored["lane_tok"]
            aux = load_aux(resume_from, step)
            if aux is None:
                raise ValueError(
                    f"checkpoint step {step} in {resume_from} has no "
                    f"scheduler sidecar (aux.json); was it written by "
                    f"run_scheduler?")
            sched.load_state(aux["sched"])
            toks = {int(k): [int(t) for t in v]
                    for k, v in aux["toks"].items()}
            outputs = {int(k): np.asarray(v, np.int32)
                       for k, v in aux["outputs"].items()}
            lane_rid = np.asarray(aux["lane_rid"], np.int64)
            # lanes checkpointed mid-chunked-prefill: their landed chunks
            # are inside the restored pools; recompute the held rows from
            # the request tokens (prefill is deterministic, so the rows the
            # remaining chunks scatter are identical to an uninterrupted
            # run's)
            for lane in sched._prefill_next:
                r = sched._by_rid[int(sched.lane_rid[lane])]
                _, pending[lane], state = self._prefill_rows(
                    np.asarray(r.tokens, np.int32))
                if state:
                    held_state[lane] = state
        self._sched_traces = []
        preempted, ckpt_path = False, None
        for ev in sched.run(requests):
            for mig in ev.migrations:
                if mig["old_ids"]:
                    pools = self._migrate_pages(pools, mig["old_ids"],
                                                mig["new_ids"])
            for rec in ev.recoveries:
                if not rec["skipped"]:
                    pools = self._recover_page(pools, rec, toks, lane_tok,
                                               scratch)
            for c in ev.completed:
                outputs[c.request.rid] = np.asarray(
                    toks.pop(c.request.rid, []), np.int32)
                lane_rid[c.lane] = -1
                pending.pop(c.lane, None)    # cancelled mid-prefill
                held_state.pop(c.lane, None)
            for adm in ev.admitted:
                r = adm.request
                if r.tokens is None:
                    raise ValueError(
                        f"request {r.rid} has no prompt tokens; synthesize "
                        f"with vocab_size= or attach tokens for live runs")
                if sched.prefill_chunk_pages is None:
                    pools, first = self._ingest_request(
                        pools, np.asarray(r.tokens, np.int32), adm.page_ids,
                        rid=r.rid, lane=adm.lane)
                else:
                    # chunked admission: prefill now, HOLD the page rows
                    # (and SSM state); ev.prefill_chunks records (chunk 0
                    # included) scatter them tick by tick as the scheduler
                    # lands the pages
                    with telemetry.span("engine.admit", rid=r.rid):
                        first, pending[adm.lane], state = self._prefill_rows(
                            np.asarray(r.tokens, np.int32))
                    if state:
                        held_state[adm.lane] = state
                lane_rid[adm.lane] = r.rid
                toks[r.rid] = [first] if r.max_new_tokens >= 1 else []
                lane_tok = lane_tok.at[adm.lane, 0].set(first)
            for chunk in ev.prefill_chunks:
                pools = self._scatter_rows(pools, pending[chunk["lane"]],
                                           chunk["page_ids"],
                                           chunk["page_start"])
                if chunk["done"]:
                    del pending[chunk["lane"]]
                    if chunk["lane"] in held_state:
                        pools = self._write_slots(
                            pools, held_state.pop(chunk["lane"]),
                            chunk["lane"])
            if ev.decoded:
                args = (self.params, lane_tok, pools,
                        jnp.asarray(ev.page_table), jnp.asarray(ev.pos),
                        jnp.asarray(ev.active), scratch)
                with telemetry.span("engine.decode"):
                    if ev.transients:
                        # injected transient faults: the step raises
                        # ``failures`` times before succeeding, and the
                        # production retry path absorbs every one of them
                        budget = [ev.transients]

                        def flaky():
                            if budget[0] > 0:
                                budget[0] -= 1
                                raise TransientFault(
                                    f"injected decode fault at tick "
                                    f"{ev.tick}")
                            return self._decode_sched(*args)

                        logits, pools = retry_step(
                            flaky, retries=ev.transients, backoff=1e-6,
                            retry_on=(TransientFault,),
                            _sleep=lambda s: None)
                    else:
                        logits, pools = self._decode_sched(*args)
                nxt = jnp.argmax(logits[:, -1, :self.cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)[:, None]
                lane_tok = jnp.where(jnp.asarray(ev.active)[:, None],
                                     nxt, lane_tok)
                # the host waits here for the step's tokens
                with telemetry.span("engine.readback"):
                    nxt_np = np.asarray(nxt[:, 0])
                for lane in np.flatnonzero(ev.active):
                    toks[int(lane_rid[lane])].append(int(nxt_np[lane]))
            self._sched_traces.extend(ev.traces)
            if ev.preempt or (guard is not None and guard.should_stop):
                if checkpoint_dir is None:
                    raise ValueError(
                        "preemption fired but run_scheduler has no "
                        "checkpoint_dir to drain into")
                aux = {"sched": sched.state_dict(),
                       "toks": {str(k): [int(t) for t in v]
                                for k, v in toks.items()},
                       "outputs": {str(k): np.asarray(v).tolist()
                                   for k, v in outputs.items()},
                       "lane_rid": lane_rid.tolist()}
                ckpt_path = save_checkpoint(
                    checkpoint_dir, sched.now,
                    {"pools": pools, "lane_tok": lane_tok}, aux=aux)
                preempted = True
                break
        self._sched_meta = {"what": "scheduler-live",
                            "arch": self.mem_arch.name,
                            "policy": sched.policy_name,
                            "n_requests": len(outputs), "ticks": sched.now}
        return SchedulerRunResult(outputs=outputs, stats=sched.stats(),
                                  ticks=sched.now, preempted=preempted,
                                  checkpoint=ckpt_path)

    def scheduler_stream(self):
        """The last ``run_scheduler``'s KV traffic as a re-iterable
        ``TraceStream`` of the recorded per-tick blocks (same ``Trace``
        protocol as ``serving_stream``; bit-equal to the simulated
        lowering of the same traffic)."""
        from repro.core.trace import TraceStream
        if not self._sched_traces:
            raise RuntimeError("no scheduler traces; run run_scheduler()")
        return TraceStream(list(self._sched_traces),
                           meta=dict(self._sched_meta))

    def scheduler_cost(self, archs=None, block_ops: int | None = None):
        """Price the last ``run_scheduler`` traffic (one fused ``cost_many``
        pass; list ``archs`` for a comparison, default this engine's)."""
        from repro.core.cost_engine import cost_many
        stream = self.scheduler_stream()
        if archs is None:
            return cost_many([self.mem_arch], stream, block_ops=block_ops)[0]
        return cost_many(list(archs), stream, block_ops=block_ops)

    # -- dense reference path ----------------------------------------------

    def _pad_cache(self, cache, prompt_len: int):
        """Grow prefill caches (len = prompt) to the decode buffer (max_seq).

        SSM caches are length-free; attention caches pad the seq axis.  Ring
        (SWA) caches shorter than max_seq are kept at window size.
        """
        def grow(path, x):
            name = str(path[-1])
            if ("'k'" in name or "'v'" in name) and x.shape[2] == prompt_len:
                win = self.cfg.sliding_window
                if win and prompt_len == win:
                    return x                      # ring buffer stays at window
                pad = [(0, 0)] * x.ndim
                pad[2] = (0, self.max_seq - prompt_len)
                return jnp.pad(x, pad)
            return x
        return jax.tree_util.tree_map_with_path(grow, cache)

    # -- generation --------------------------------------------------------

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 seed: int = 0) -> GenerationResult:
        """prompts: (B, prompt_len) int32 (pre-padded request batch).

        Paged, a fixed batch is the all-lanes-active case of
        ``run_scheduler``'s step: its prompts land as an admission lands
        one (``_land_prompts``), and each decode step is ``_decode_sched``
        with every lane at the batch's one position, after ``_grow_pages``
        has given each lane at a page boundary its next page."""
        b, plen = prompts.shape
        assert b <= self.max_batch and plen + max_new_tokens <= self.max_seq
        logits, cache = self._prefill(self.params, jnp.asarray(prompts))
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits[:, -1], temperature, key)
        out.append(tok)
        paged = self.kv_mode == "paged"
        if paged:
            pools, pages = self._land_prompts(cache, plen, b)
            del cache                       # no dense KV survives prefill
            active = jnp.ones((b,), bool)
            self._step_traces = []
            self._prefill_trace = KV.prefill_trace(
                self.kv_cfg, np.asarray(pages.page_table), plen,
                self.n_kv_layers)
        else:
            cache = self._pad_cache(cache, plen)
        for i in range(1, max_new_tokens):
            if paged:
                pos = pages.seq_lens
                pages = self._grow_pages(pages)
                logits, pools = self._decode_sched(
                    self.params, tok, pools, pages.page_table, pos, active, 0)
                self._step_traces.append(KV.decode_step_trace(
                    self.kv_cfg, np.asarray(pages.page_table), plen + i - 1,
                    self.n_kv_layers))
            else:
                logits, cache = self._decode(
                    self.params, tok, cache,
                    jnp.asarray(plen + i - 1, jnp.int32))
            key, sub = jax.random.split(key)
            tok = self._sample(logits[:, -1], temperature, sub)
            out.append(tok)
        if paged:
            self.last_pages = pages
        tokens = np.concatenate([np.asarray(t) for t in out], axis=1)
        return GenerationResult(tokens=tokens, prompt_len=plen,
                                steps=max_new_tokens)

    def _land_prompts(self, cache, plen: int, batch: int):
        """Land a fixed batch's prefill ``cache`` in fresh pools: every
        prompt page allocated in one call (``KV.allocate_prompt_pages``),
        the K/V lowered to page rows (``_lower_rows``) and scattered into
        the donated KV pools in one call (``_land_rows``), and each Mamba
        layer's slots set to the prompts' final state.  Returns the pools
        and the page table."""
        kv = self.kv_cfg
        pages = KV.allocate_prompt_pages(kv, batch, self.max_seq, plen)
        n_pref = -(-plen // kv.page_len)
        ids = jnp.maximum(pages.page_table[:, :n_pref], 0).reshape(-1)
        pools = self._init_pools(batch)
        pools.update(self._land_rows(
            {key: pools[key] for key in self._kv_keys},
            self._lower_rows(cache, plen), ids, 0))
        for key in self._ssm_keys:
            blk, sb = self._layer_of[key]
            pools[key] = {name: cache["blocks"][blk][name][sb].astype(
                slot.dtype) for name, slot in pools[key].items()}
        return pools, pages

    def _next_pages(self, pages: KV.PageTableState) -> KV.PageTableState:
        """A fixed batch's page table for its next decode step: every lane
        at a page boundary gets its next page (``KV.allocate_pages``), and
        ``seq_lens`` moves past the step's token (jit'd as
        ``_grow_pages``)."""
        need = (pages.seq_lens % self.kv_cfg.page_len) == 0
        pages, _ = KV.allocate_pages(self.kv_cfg, pages, need)
        return pages._replace(seq_lens=pages.seq_lens + 1)

    def _sample(self, logits, temperature: float, key):
        logits = logits[..., :self.cfg.vocab_size]
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)[:, None]

    # -- serving-cost introspection ----------------------------------------

    def step_trace(self, step: int = -1):
        """The exact ``AddressTrace`` one decode step put on the KV pool
        (recorded by the last ``generate``); ``arch.cost(engine.step_trace())``
        prices a serving step like any Table II/III kernel."""
        if not self._step_traces:
            raise RuntimeError(
                "no decode traces recorded; run generate() with "
                "kv_mode='paged' and max_new_tokens >= 2 first "
                "(the first token comes from prefill, not a decode step)")
        return self._step_traces[step]

    def serving_trace(self, include_prefill: bool = True):
        """The last generation's full KV ``AddressTrace`` (prefill page
        writes + every decode step), one costed artifact."""
        from repro.core.trace import AddressTrace
        return AddressTrace.concat(*self._trace_chunks(include_prefill))

    def serving_stream(self, include_prefill: bool = True):
        """The last generation's KV traffic as a lazy
        ``repro.core.trace.TraceStream`` of per-step blocks — the shared
        ``Trace`` protocol the batched cost engine consumes in O(block)
        memory (long generations never concatenate into one dense matrix).
        The recorded step list is passed directly; the stream is re-iterable
        by construction."""
        from repro.core.trace import TraceStream
        return TraceStream(self._trace_chunks(include_prefill),
                           meta={"what": "serving-live",
                                 "arch": self.mem_arch.name,
                                 "steps": len(self._step_traces)})

    def serving_cost(self, archs=None, include_prefill: bool = True,
                     block_ops: int | None = None):
        """Price the last generation's serving traffic — through the
        streaming engine path, against one or many architectures at once.

        ``archs`` defaults to this engine's ``mem_arch`` (returns a single
        ``TraceCost``); a list prices the whole comparison in one fused
        ``cost_many`` pass and returns one ``TraceCost`` per entry."""
        from repro.core.cost_engine import cost_many
        stream = self.serving_stream(include_prefill)
        if archs is None:
            return cost_many([self.mem_arch], stream,
                             block_ops=block_ops)[0]
        return cost_many(list(archs), stream, block_ops=block_ops)

    def _trace_chunks(self, include_prefill: bool) -> list:
        chunks = list(self._step_traces)
        if include_prefill and self._prefill_trace is not None:
            chunks = [self._prefill_trace] + chunks
        if not chunks:
            raise RuntimeError(
                "no traces recorded; run generate() with kv_mode='paged'")
        return chunks
