"""Batched streaming cost engine — price a whole architecture list in one
fused pass, and million-op traces in O(block) memory.

The paper's deliverable is a *comparison* (9 memories × 51 benchmarks), and
``repro.tune`` generalizes it to searching an ``ArchSpace`` over arbitrary
traffic.  Pricing each (architecture, trace) cell through
``MemoryArchitecture.cost`` walks op kinds in Python with a host sync per
kind — ``len(archs) × 3`` device round-trips per sweep.  But every timing
model in the comparison is pure element-wise integer arithmetic over a small
parameter set:

  * banked:      bank = (((a >> sh) ^ (a >> xsh)) + (a >> ash)) mod B
                 [+ B · ((a // G) mod O) for two-level macro hierarchies];
                 cycles = max per-bank popcount (optionally over distinct
                 addresses — the broadcast variant)
  * multi-port:  cycles = ceil(active_lanes / ports); the -VB write path is
                 the banked formula over 4 pseudo-banks

so the whole lattice lowers to one ``(n_archs, 2 paths, 9)`` int32 parameter
table (``lower_archs``) and one jitted vmap prices every architecture
against a trace block simultaneously (``cost_many``) — one device sync
total.  Power-of-two-only lattices compile the historical ``& (B-1)``
mask form (bit-identical, no new cost); a non-pow2 bank count anywhere in
the list switches the whole dispatch to the ``% B`` form, and a two-level
arch adds the outer-granule term — both gated by STATIC flags so healthy
lattices pay nothing for the generality.

The engine consumes the one ``repro.core.trace.Trace`` protocol:
``as_trace(trace).blocks(block_ops)`` yields blocks with globally
consistent, non-decreasing instruction ids, so a dense ``AddressTrace``, a
chunked one, a lazy ``TraceStream`` of kernel/serving blocks, or any raw
block iterable all cost through the same loop in O(block) memory —
million-op traces never materialize their dense (ops × 16) matrix.

Two optional go-fast paths, each bit-equal to the plain serial pass:

  * ``cost_many(..., prefetch=N)`` — a bounded producer/consumer pipeline:
    upcoming source blocks are CONSTRUCTED on host while the device prices
    the current batch.  Thunk-backed streams
    (``TraceStream.from_thunks``) fan per-block construction over an
    N-worker pool (block construction is embarrassingly parallel);
    generator-backed streams run a single producer thread so construction
    overlaps dispatch.  Consumption stays in stream order, so the batch
    sequence — and therefore every cycle — is identical to the serial
    path.
  * ``cost_many(..., cache=BlockCostCache())`` — content-addressed
    memoization of per-block conflict-cycle partials keyed on (lowered
    arch-table digest, block content digest).  Re-pricing a traffic window
    that shares blocks with a previous window only dispatches the new
    blocks; hits replay the exact ``(n_archs, 3)`` integers the device
    returned the first time, so incremental re-pricing is bit-equal to a
    cold pass by construction.  Degraded ``!d`` variants key correctly:
    the table digest covers the remap rows.

Chunked, streamed, and dense costing are bit-equal (pinned in
tests/test_cost_engine.py): per-op cycles only depend on the op itself, and
per-instruction controller overheads are charged from the protocol's global
instruction ids by a streaming distinct-count (an instruction cut at a
block boundary keeps one id on both sides and is charged once).

``MemoryArchitecture.cost`` is a thin single-arch shim over this engine
(auto-chunking above ``STREAM_THRESHOLD`` ops); ``tune.search``,
``bench.sweep`` and the serving cost path batch through ``cost_many``
directly; ``tune.online`` wraps the cache in a rolling-window re-pricer.
"""
from __future__ import annotations

import functools
import hashlib
import queue
import threading
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import controllers as ctl
from repro.core.conflicts import first_occurrence
from repro.core.memsim import LANES, MemSpec, TraceCost
from repro.core.trace import (KIND_LOAD, KIND_STORE, KIND_TW, AddressTrace,
                              TraceStream, as_trace)
from repro.runtime import telemetry

__all__ = ["cost_many", "lower_archs", "ArchTable", "BlockCostCache",
           "DEFAULT_BLOCK_OPS", "STREAM_THRESHOLD"]

#: block size ``MemoryArchitecture.cost`` auto-chunks with when a dense
#: trace exceeds ``STREAM_THRESHOLD`` ops (bit-equal either way; chunking
#: merely bounds the device-buffer working set)
DEFAULT_BLOCK_OPS = 4096
STREAM_THRESHOLD = 1 << 15

#: shifting an int32 word address by 31 yields 0 (addresses are non-negative)
#: — the identity element for the generic bank formula's unused terms.
_NO_SHIFT = 31

#: parameter-table field indices (per architecture, per read/write path):
#: [use_banked, n_banks, sh, xsh, ash, use_uniq, ports, outer_banks,
#: outer_granule].  ``n_banks`` is the INNER bank count (1 for pure
#: multi-port paths, so the modulo form stays division-safe); two-level
#: rows carry outer_banks > 1 and the flat bank id the arbiter sees is
#: ``inner + n_banks · outer``.
(_F_BANKED, _F_NBANKS, _F_SH, _F_XSH, _F_ASH, _F_UNIQ, _F_PORTS,
 _F_OUTB, _F_OUTG) = range(9)
_N_FIELDS = 9

_KINDS = (KIND_LOAD, KIND_STORE, KIND_TW)


# --------------------------------------------------------------------------
# Architecture lowering
# --------------------------------------------------------------------------

def _map_shifts(mapping: str, n_banks: int, shift: int) -> tuple:
    """(sh, xsh, ash) such that
    bank = (((a >> sh) ^ (a >> xsh)) + (a >> ash)) mod B
    reproduces ``repro.core.bankmap.bank_of`` for every supported map.
    The bit-mixing maps (xor/fold) read log2(B) and stay power-of-two;
    the modulo maps (lsb/offset) use a single shift and take any B."""
    log2b = n_banks.bit_length() - 1
    if mapping == "lsb":
        return 0, _NO_SHIFT, _NO_SHIFT
    if mapping == "offset":
        return shift, _NO_SHIFT, _NO_SHIFT
    if mapping == "xor":
        return 0, log2b, _NO_SHIFT
    if mapping == "fold":
        return 0, _NO_SHIFT, log2b
    raise ValueError(f"unknown bank map {mapping!r}")


def _spec_paths(spec: MemSpec) -> tuple:
    """One spec -> ((read path), (write path), (read_ovh, write_ovh))."""
    if spec.is_banked:
        sh, xsh, ash = _map_shifts(spec.mapping, spec.n_banks, spec.map_shift)
        outb = spec.outer_banks if spec.is_two_level else 1
        outg = spec.outer_granule if spec.is_two_level else 1
        read = (1, spec.n_banks, sh, xsh, ash, int(spec.broadcast), 1,
                outb, outg)
        write = (1, spec.n_banks, sh, xsh, ash, 0, 1, outb, outg)
        return read, write, (ctl.read_overhead(spec.total_banks),
                             ctl.write_overhead(spec.total_banks))
    read = (0, 1, _NO_SHIFT, _NO_SHIFT, _NO_SHIFT, 0, spec.read_ports, 1, 1)
    if spec.vb_write_banks:
        write = (1, spec.vb_write_banks, 0, _NO_SHIFT, _NO_SHIFT, 0, 1, 1, 1)
        return read, write, (0, ctl.write_overhead(spec.vb_write_banks))
    write = (0, 1, _NO_SHIFT, _NO_SHIFT, _NO_SHIFT, 0, spec.write_ports, 1, 1)
    return read, write, (0, 0)


class ArchTable:
    """A lowered architecture list: the whole lattice as parameter arrays.

    ``params`` is (n_archs, 2, 9) int32 — per arch, a read-path and a
    write-path row of [use_banked, n_banks, sh, xsh, ash, use_uniq, ports,
    outer_banks, outer_granule]; ``overheads`` is (n_archs, 2)
    per-instruction controller overheads (read, write; twiddle loads are
    reads); ``need_uniq`` records whether any read path coalesces
    same-address requests.

    ``remaps`` is (n_archs, 2, W) int32 — the degraded-mode bank remap
    (``repro.core.arch.surviving_bank_remap``) applied to the generic
    formula's FLAT bank output (inner + n_banks·outer for two-level),
    identity-padded to the lattice's widest flat bank count; ``need_remap``
    is False for all-healthy lattices.  ``need_mod`` / ``need_two_level``
    are likewise static: a pow2-only single-level lattice compiles exactly
    the historical mask-form kernel and costs bit-identically to before the
    generalized formula existed.

    ``digest`` content-addresses the lowered table (params, remaps,
    overheads, static flags) — the arch half of every ``BlockCostCache``
    key, so degraded variants and any other parameter difference key
    distinct cache entries.
    """

    def __init__(self, specs: tuple):
        rows, ovhs = [], []
        for s in specs:
            read, write, ovh = _spec_paths(s)
            rows.append((read, write))
            ovhs.append(ovh)
        self.specs = specs
        self.params = np.asarray(rows, np.int32).reshape(
            len(specs), 2, _N_FIELDS)
        self.overheads = np.asarray(ovhs, np.int64).reshape(len(specs), 2)
        self.need_uniq = bool(self.params[:, 0, _F_UNIQ].any())
        banked = self.params[:, :, _F_BANKED].astype(bool)
        nb = self.params[:, :, _F_NBANKS]
        self.need_mod = bool((banked & (nb & (nb - 1) != 0)).any())
        self.need_two_level = bool(
            (self.params[:, :, _F_OUTB] > 1).any())
        flat = nb * self.params[:, :, _F_OUTB]
        width = max(1, int(flat.max()))
        self.remaps = np.tile(np.arange(width, dtype=np.int32),
                              (len(specs), 2, 1))
        self.need_remap = False
        for i, s in enumerate(specs):
            dead = getattr(s, "dead_banks", ())
            if not dead:
                continue
            from repro.core.arch import surviving_bank_remap
            remap = surviving_bank_remap(s.total_banks, dead)
            # both paths share the data banks (the -VB pseudo-bank write
            # path never coexists with a banked spec, so this is total)
            self.remaps[i, :, :s.total_banks] = np.asarray(remap, np.int32)
            self.need_remap = True
        self._digest: bytes | None = None

    @property
    def digest(self) -> bytes:
        """Content digest of the lowered table — the arch half of a
        ``BlockCostCache`` key."""
        if self._digest is None:
            h = hashlib.blake2b(digest_size=16)
            h.update(self.params.tobytes())
            h.update(self.remaps.tobytes())
            h.update(self.overheads.tobytes())
            h.update(bytes([self.need_uniq, self.need_remap,
                            self.need_mod, self.need_two_level]))
            self._digest = h.digest()
        return self._digest

    def __len__(self) -> int:
        return len(self.specs)


@functools.lru_cache(maxsize=None)
def _lowered(specs: tuple) -> ArchTable:
    return ArchTable(specs)


def lower_archs(archs) -> ArchTable:
    """Lower a list of architectures (names / specs / objects) to the
    parameter arrays one fused device pass consumes (cached per spec list)."""
    from repro.core import arch as _arch
    return _lowered(tuple(_arch.resolve(a).spec for a in archs))


# --------------------------------------------------------------------------
# The fused block kernel
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("need_uniq", "need_remap",
                                             "need_mod", "need_two_level"))
def _block_kind_cycles(params, remaps, addrs, mask, kinds, *,
                       need_uniq: bool, need_remap: bool,
                       need_mod: bool, need_two_level: bool):
    """One block, every architecture: (n_archs, 3) per-kind cycle sums.

    addrs (n_ops, LANES) int32, mask (n_ops, LANES) bool, kinds (n_ops,)
    int32; padded ops carry an all-False mask and cost 0 under every model.

    The banked max-conflict is computed from the lane-pair equality matrix
    rather than per-bank popcount bins: an active lane's count of same-bank
    active lanes IS its bank's popcount, so the max over active lanes
    equals the max over banks — with LANES² (256) int8 cells per op
    independent of bank count, which XLA:CPU vectorizes ~40× better than a
    (lanes × banks) one-hot reduction.

    The static flags route the generality: ``need_remap`` compiles the
    degraded-bank lookup, ``need_mod`` switches ``& (B-1)`` to ``% B``
    (numerically identical for pow2 B, required for non-pow2 lattice
    points), ``need_two_level`` adds the outer-granule macro term.
    All-healthy pow2 single-level lattices compile the historical kernel
    bit-for-bit.
    """
    is_write = kinds == KIND_STORE
    active = mask.sum(axis=-1, dtype=jnp.int32)                  # (n_ops,)
    uniq = (first_occurrence(addrs, mask).astype(bool)
            if need_uniq else mask)

    def one_arch(p, rm):                                 # p (2, 9), rm (2, W)
        pr = jnp.where(is_write[:, None], p[1], p[0])            # (n_ops, 9)
        nb = pr[:, _F_NBANKS, None]
        raw = (((addrs >> pr[:, _F_SH, None])
                ^ (addrs >> pr[:, _F_XSH, None]))
               + (addrs >> pr[:, _F_ASH, None]))                 # (n_ops, L)
        if need_mod:
            bank = raw % nb
            # int32 overflow of the xor+add form can make ``raw`` negative
            # (pow2 rows sharing a mixed lattice); C-style remainder keeps
            # the dividend's sign, so fold it back into [0, nb)
            bank = jnp.where(bank < 0, bank + nb, bank)
        else:
            bank = raw & (nb - 1)
        if need_two_level:
            bank = bank + nb * ((addrs // pr[:, _F_OUTG, None])
                                % pr[:, _F_OUTB, None])
        if need_remap:
            rm_rows = jnp.where(is_write[:, None], rm[1][None, :],
                                rm[0][None, :])                  # (n_ops, W)
            bank = jnp.take_along_axis(rm_rows, bank, axis=1)
        eff = mask & jnp.where(pr[:, _F_UNIQ, None].astype(bool), uniq, True)
        eq = (bank[:, :, None] == bank[:, None, :]) & eff[:, None, :]
        cnt = eq.sum(axis=-1, dtype=jnp.int8)                    # (n_ops, L)
        banked = jnp.where(eff, cnt, 0).max(axis=-1).astype(jnp.int32)
        ported = (active + pr[:, _F_PORTS] - 1) // pr[:, _F_PORTS]
        return jnp.where(pr[:, _F_BANKED].astype(bool), banked, ported)

    cyc = jax.vmap(one_arch)(params, remaps)                     # (A, n_ops)
    kind_onehot = (kinds[:, None]
                   == jnp.asarray(_KINDS, jnp.int32)).astype(jnp.int32)
    return cyc @ kind_onehot                                     # (A, 3)


def _pad_ops(addrs: np.ndarray, mask: np.ndarray,
             kinds: np.ndarray) -> tuple:
    """Pad an op batch to the next power-of-two op count (bounds the number
    of compiled shapes to log2 variants).  Padded ops are fully inactive."""
    n = addrs.shape[0]
    padded = 1 << max(0, n - 1).bit_length()
    a = np.zeros((padded, LANES), np.int32)
    a[:n] = addrs
    m = np.zeros((padded, LANES), bool)
    m[:n] = mask
    k = np.zeros((padded,), np.int32)
    k[:n] = kinds
    return a, m, k


# --------------------------------------------------------------------------
# BlockCostCache — content-addressed per-block conflict-cycle memo
# --------------------------------------------------------------------------

class BlockCostCache:
    """LRU memo of per-block (n_archs, 3) conflict-cycle partials.

    Keys are (``ArchTable.digest``, block content digest): the arch half
    covers the lowered parameter rows INCLUDING degraded-bank remaps, the
    block half covers addresses, mask, and op kinds — everything the fused
    kernel reads.  Instruction ids are deliberately NOT part of the key:
    per-op conflict cycles don't depend on them, and the per-instruction
    controller overhead is charged by ``cost_many``'s streaming counter on
    the host either way.  A hit replays the exact integers the device
    returned on the miss, so a warm re-price is bit-equal to a cold pass
    by construction (property-tested in tests/test_cost_engine.py).

    ``cost_many(..., cache=...)`` prices block-at-a-time when a cache is
    attached (cache granularity = protocol block), skipping device
    dispatch entirely on hits — the mechanism behind ``tune.online``'s
    rolling-window re-pricer, where consecutive windows share all but the
    newest blocks.

    A second, smaller memo (``digest_of``) short-circuits the content
    HASH itself: a rolling window re-observes the same payload arrays
    every tick (the renumbering wrapper shares them), so the digest is
    keyed on buffer identity — (base object, data pointer, shape,
    strides, dtype) per array, base pinned by a strong ref — and computed
    once.  Payload arrays are frozen (``writeable = False``) on first
    digest: a block's addrs/mask/kinds are treated as immutable once
    priced, and an in-place mutation afterwards raises instead of
    silently re-pricing stale bytes.  (Mutating through a pre-existing
    writable view of the same buffer is not detected — producers that
    recycle scratch buffers must copy before pricing through a cache.)
    """

    def __init__(self, max_entries: int = 4096,
                 max_digest_memo: int = 512):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = max_entries
        self.max_digest_memo = max_digest_memo
        self._store: OrderedDict = OrderedDict()
        self._digests: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def block_digest(addrs, mask, kinds) -> bytes:
        """Content digest of one block's kernel-visible payload.  A dense
        block and the same block with an explicit all-True mask digest
        identically (they price identically)."""
        h = hashlib.blake2b(digest_size=16)
        a = np.ascontiguousarray(addrs, dtype=np.int32)
        h.update(np.int64(a.shape[0]).tobytes())
        h.update(a.tobytes())
        if mask is None:
            h.update(b"\x01")
        else:
            m = np.ascontiguousarray(mask, dtype=bool)
            if m.all():
                h.update(b"\x01")
            else:
                h.update(b"\x00")
                h.update(m.tobytes())
        h.update(np.ascontiguousarray(kinds, dtype=np.int32).tobytes())
        return h.digest()

    def digest_of(self, addrs, mask, kinds) -> bytes:
        """``block_digest`` with a buffer-identity memo (see class
        docstring) — bit-equal to hashing, just skipped when the same
        frozen buffers come around again next window."""
        keys, pins = [], []
        for a in (addrs, mask, kinds):
            if isinstance(a, np.ndarray):
                base = a.base if a.base is not None else a
                keys.append((id(base), a.__array_interface__["data"][0],
                             a.shape, a.strides, a.dtype.str))
                pins.append((a, base))
            else:
                keys.append(None)
        key = tuple(keys)
        hit = self._digests.get(key)
        if hit is not None:
            self._digests.move_to_end(key)
            return hit[1]
        d = self.block_digest(addrs, mask, kinds)
        for a, base in pins:
            a.flags.writeable = False
            base.flags.writeable = False
        self._digests[key] = (pins, d)
        while len(self._digests) > self.max_digest_memo:
            self._digests.popitem(last=False)
        return d

    def get(self, key) -> np.ndarray | None:
        hit = self._store.get(key)
        if hit is None:
            self.misses += 1
            return None
        self._store.move_to_end(key)
        self.hits += 1
        return hit

    def put(self, key, partial: np.ndarray) -> None:
        self._store[key] = partial
        self._store.move_to_end(key)
        while len(self._store) > self.max_entries:
            self._store.popitem(last=False)

    def __len__(self) -> int:
        return len(self._store)

    @property
    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._store),
                "digest_memo": len(self._digests)}

    def clear(self) -> None:
        self._store.clear()
        self._digests.clear()


# --------------------------------------------------------------------------
# Prefetch pipeline — construct upcoming blocks while the device prices
# --------------------------------------------------------------------------

class _ProducerError:
    """Exception forwarded from the producer thread to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def _iter_thunk_result(result):
    """A thunk may return one source AddressTrace or an iterable of them."""
    if isinstance(result, AddressTrace):
        yield result
    else:
        yield from result


def _prefetched(src: TraceStream, prefetch: int) -> TraceStream:
    """A one-shot ``TraceStream`` delivering ``src``'s SOURCE blocks ahead
    of consumption, in order.

    Thunk-backed streams (``TraceStream.from_thunks``) construct up to
    ``prefetch`` blocks concurrently on a worker pool — per-block
    construction is independent by contract, and results are consumed in
    thunk order, so the downstream renumbering/costing sees the identical
    sequence.  Other streams run one producer thread over the source
    iterator with a bounded queue: construction (the generator's work)
    overlaps the consumer's padding + device dispatch.
    """
    thunks = src.thunks

    if thunks:
        def gen():
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(max_workers=prefetch) as pool:
                window: deque = deque()
                for t in thunks:
                    window.append(pool.submit(t))
                    if len(window) > prefetch:
                        yield from _iter_thunk_result(
                            window.popleft().result())
                while window:
                    yield from _iter_thunk_result(window.popleft().result())

        # in-flight construction futures cannot be rewound: single-pass by
        # design, consumed exactly once by cost_many
        return TraceStream(gen(), meta=dict(src.meta))  # lint: allow-one-shot-stream

    done = object()
    stop = threading.Event()
    q: queue.Queue = queue.Queue(maxsize=max(1, prefetch))

    def produce():
        try:
            for blk in src:
                while not stop.is_set():
                    try:
                        q.put(blk, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            item = done
        except BaseException as e:      # forwarded, re-raised by consumer
            item = _ProducerError(e)
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def gen():
        t = threading.Thread(target=produce, name="cost-prefetch",
                             daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    break
                if isinstance(item, _ProducerError):
                    raise item.exc
                yield item
        finally:
            stop.set()

    # the producer thread drains the source once: single-pass by design
    return TraceStream(gen(), meta=dict(src.meta))  # lint: allow-one-shot-stream


# --------------------------------------------------------------------------
# cost_many
# --------------------------------------------------------------------------

#: fold device partials into the int64 host accumulator every N blocks —
#: keeps the dispatch queue bounded without a per-block sync
_FOLD_EVERY = 256


def _fold(totals, partials: list, n_archs: int) -> np.ndarray:
    with telemetry.span("cost.fold"):
        if totals is None:
            totals = np.zeros((n_archs, 3), np.int64)
        for p in partials:
            totals += np.asarray(p, np.int64)
        partials.clear()
    return totals


def _timed_blocks(blocks):
    """``blocks`` with the time spent pulling each one from the stream
    (its construction, for a lazy stream) recorded as ``cost.blocks``."""
    it = iter(blocks)
    end = object()
    while True:
        with telemetry.span("cost.blocks"):
            blk = next(it, end)
        if blk is end:
            return
        yield blk


class _InstrCounter:
    """Streaming per-kind distinct-instruction counter over protocol blocks.

    Blocks arrive with globally consistent, NON-DECREASING instruction ids
    (the ``Trace.blocks`` contract), so distinct ids per kind can be counted
    one block at a time: a block's contribution is its per-kind unique-id
    count, minus one when its first id of that kind continues the previous
    block's last (the instruction the boundary cut).  This is what lets a
    single instruction span any number of stream chunks and still pay its
    controller overhead exactly once.
    """

    def __init__(self):
        self.n_instr = np.zeros(3, np.int64)
        self.n_ops = np.zeros(3, np.int64)
        self._last: dict = {}        # kind -> last global id seen

    def add(self, blk: AddressTrace) -> None:
        for i, kind in enumerate(_KINDS):
            sel = blk.kinds == kind
            n = int(sel.sum())
            if not n:
                continue
            self.n_ops[i] += n
            ids = np.unique(blk.instr[sel])
            add = ids.size
            if self._last.get(kind) == int(ids[0]):
                add -= 1
            self._last[kind] = int(ids[-1])
            self.n_instr[i] += add


def cost_many(archs, trace, block_ops: int | None = None,
              checked: bool | None = None, prefetch: int | None = None,
              cache: BlockCostCache | None = None) -> list[TraceCost]:
    """Price every architecture of ``archs`` against one trace in a single
    fused computation (one device sync total, not ``len(archs) × 3``).

    ``trace`` is anything ``repro.core.trace.as_trace`` accepts: a dense
    ``AddressTrace``, a lazy ``TraceStream`` (e.g. a kernel's
    ``trace_blocks`` stream or serving traffic), or a raw iterable /
    callable of ``AddressTrace`` blocks.  ``block_ops`` additionally chunks
    every block to at most that many ops, bounding peak memory; dense,
    chunked, and streamed costing are bit-equal.

    ``prefetch=N`` (N >= 1) overlaps host block CONSTRUCTION with device
    pricing: a bounded producer/consumer pipeline keeps up to N source
    blocks in flight — thunk-backed streams construct them on an N-worker
    pool, other streams on one producer thread.  Blocks are consumed in
    stream order, so results are bit-equal to the serial pass.

    ``cache`` attaches a ``BlockCostCache``: blocks found in the cache (by
    content digest, under this arch list's lowered-table digest) skip
    device dispatch and replay their memoized ``(n_archs, 3)`` partials —
    re-pricing a window that shares a prefix with an earlier call costs
    only the new blocks, bit-equal to a cold pass.  With a cache attached
    the engine dispatches block-at-a-time (cache granularity = protocol
    block) instead of coalescing small blocks.

    ``checked=True`` validates the Trace protocol contracts (globally
    non-decreasing instruction ids, legal ``instr_carry`` chains, shapes,
    non-negative addresses) on every block as it is priced — validation and
    costing share the stream's single pass, so even one-shot streams can be
    checked.  Raises ``repro.core.trace.TraceContractError`` on violation.
    The default (``None``) defers to the process-wide switch
    ``repro.analysis.contracts.checking()`` — off in production, on under
    the test suite's autouse fixture.

    Returns one ``TraceCost`` per architecture, in input order — exactly
    what ``arch.cost(trace)`` returns for each (``MemoryArchitecture.cost``
    is the single-arch shim over this function).
    """
    from repro.core import arch as _arch
    arch_objs = [_arch.resolve(a) for a in archs]
    if not arch_objs:
        return []
    table = _lowered(tuple(a.spec for a in arch_objs))
    with telemetry.span("cost.many"):
        totals, counter, compute_cycles, op_counts = _cost_stream(
            table, trace, block_ops, checked, prefetch, cache)
    n_instr, n_ops = counter.n_instr, counter.n_ops

    costs = []
    for i in range(len(table)):
        r_ovh, w_ovh = (int(table.overheads[i, 0]),
                        int(table.overheads[i, 1]))
        kind_cycles = {
            KIND_LOAD: int(totals[i, 0]) + int(n_instr[0]) * r_ovh,
            KIND_STORE: int(totals[i, 1]) + int(n_instr[1]) * w_ovh,
            KIND_TW: int(totals[i, 2]) + int(n_instr[2]) * r_ovh,
        }
        costs.append(TraceCost(
            load_cycles=kind_cycles[KIND_LOAD] if n_ops[0] else 0,
            store_cycles=kind_cycles[KIND_STORE] if n_ops[1] else 0,
            tw_load_cycles=kind_cycles[KIND_TW] if n_ops[2] else 0,
            compute_cycles=int(compute_cycles),
            n_load_ops=int(n_ops[0]), n_store_ops=int(n_ops[1]),
            n_tw_ops=int(n_ops[2]),
            fp_ops=int(op_counts.get("fp", 0)),
            int_ops=int(op_counts.get("int", 0)),
            imm_ops=int(op_counts.get("imm", 0)),
            other_ops=int(op_counts.get("other", 0))))
    return costs


def _cost_stream(table: ArchTable, trace, block_ops, checked, prefetch,
                 cache):
    """``cost_many``'s one pass over the trace: the int64 (n_archs, 3)
    conflict cycles, the instruction counter, and the compute cycles and
    op counts the blocks carry."""
    params = jnp.asarray(table.params)
    remaps = jnp.asarray(table.remaps)
    n_archs = len(table)

    def _dispatch(addrs, mask, kinds):
        telemetry.count("cost.ops", addrs.shape[0])
        with telemetry.span("cost.pad"):
            addrs, mask, kinds = _pad_ops(addrs, mask, kinds)
        telemetry.count("cost.padded_ops", addrs.shape[0])
        with telemetry.span("cost.transfer"):
            args = (jnp.asarray(addrs), jnp.asarray(mask),
                    jnp.asarray(kinds))
        with telemetry.span("cost.dispatch"):
            return _block_kind_cycles(
                params, remaps, *args, need_uniq=table.need_uniq,
                need_remap=table.need_remap, need_mod=table.need_mod,
                need_two_level=table.need_two_level)

    totals = None
    counter = _InstrCounter()
    compute_cycles = 0
    op_counts: dict = {}

    src = as_trace(trace)
    if prefetch is not None:
        if prefetch < 1:
            raise ValueError(f"prefetch must be >= 1, got {prefetch}")
        if isinstance(src, TraceStream):     # dense traces: nothing to
            src = _prefetched(src, prefetch)  # construct ahead of time
    blocks = src.blocks(block_ops)
    if checked is None or checked:
        # analysis imports core, never the reverse at module level — the
        # lazy import here is the one upward hook, and it only fires when
        # checking is requested (or to consult the process-wide switch).
        from repro.analysis import contracts as _contracts
        if checked or _contracts.is_checking():
            n_words = (src.meta.get("n_words")
                       if isinstance(getattr(src, "meta", None), dict)
                       else None)
            blocks = _contracts.checked_blocks(blocks, n_words=n_words,
                                               where="cost_many(checked)")
    blocks = _timed_blocks(blocks)

    if cache is not None:
        # block-at-a-time with content-addressed memoization: hits add
        # their stored int64 partial on the host; misses dispatch and are
        # stored at fold time (async until then — no per-miss sync)
        totals = np.zeros((n_archs, 3), np.int64)
        in_flight: list = []             # (key, device partial)

        def _fold_misses():
            nonlocal totals
            with telemetry.span("cost.fold"):
                for key, part in in_flight:
                    arr = np.asarray(part, np.int64)
                    cache.put(key, arr)
                    totals = totals + arr
                in_flight.clear()

        for blk in blocks:
            compute_cycles += blk.compute_cycles
            for k, v in blk.op_counts.items():
                op_counts[k] = op_counts.get(k, 0) + v
            if not blk.n_ops:
                continue
            with telemetry.span("cost.count"):
                counter.add(blk)
            key = (table.digest,
                   cache.digest_of(blk.addrs, blk.mask, blk.kinds))
            hit = cache.get(key)
            if hit is not None:
                totals = totals + hit
                continue
            mask = (np.ones_like(blk.addrs, bool) if blk.mask is None
                    else blk.mask)
            in_flight.append((key, _dispatch(blk.addrs, mask, blk.kinds)))
            if len(in_flight) >= _FOLD_EVERY:
                _fold_misses()
        _fold_misses()
    else:
        # Small protocol blocks (e.g. per-instruction kernel/VM chunks of
        # ~64 ops) are coalesced into one device dispatch of up to the
        # target op count — per-op cycles are independent of batch grouping
        # and the instruction counter works on the blocks themselves, so
        # coalescing cannot change a single cycle, only the dispatch count.
        target = block_ops if block_ops is not None else DEFAULT_BLOCK_OPS
        partials: list = []    # per-batch (A, 3) int32 device arrays;
        # summed in int64 on the host (folded every _FOLD_EVERY batches for
        # dispatch-queue backpressure), so totals cannot overflow int32
        # across batches (within one batch sums are bounded by the batch op
        # count × LANES)
        pending: list = []
        pending_ops = 0

        def _flush():
            nonlocal totals, pending_ops
            if not pending:
                return
            if len(pending) == 1:
                addrs, mask, kinds = pending[0]
            else:
                with telemetry.span("cost.coalesce"):
                    addrs = np.concatenate([p[0] for p in pending])
                    mask = np.concatenate([p[1] for p in pending])
                    kinds = np.concatenate([p[2] for p in pending])
            pending.clear()
            pending_ops = 0
            partials.append(_dispatch(addrs, mask, kinds))
            if len(partials) >= _FOLD_EVERY:
                totals = _fold(totals, partials, n_archs)

        for blk in blocks:
            compute_cycles += blk.compute_cycles
            for k, v in blk.op_counts.items():
                op_counts[k] = op_counts.get(k, 0) + v
            if not blk.n_ops:
                continue
            with telemetry.span("cost.count"):
                counter.add(blk)
            pending.append((blk.addrs,
                            np.ones_like(blk.addrs, bool) if blk.mask is None
                            else blk.mask,
                            blk.kinds))
            pending_ops += blk.n_ops
            if pending_ops >= target:
                _flush()
        _flush()
        totals = _fold(totals, partials, n_archs)

    return totals, counter, compute_cycles, op_counts
