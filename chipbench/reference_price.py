"""Plain reference of the pricing path: one whole-model decode step's
address traffic, built and priced in NumPy from the configuration, the mix
and the routing seed, with nothing of the program under test.

The traffic (``models/trace.model_step_trace``'s semantics, in the words
of the paper's memory model): per attention layer, unit-stride weight-row
loads of Wq, Wk and Wv, a RoPE row per (sequence, head) at the sequence's
position, the K and V page-list gathers of every sequence (unmapped pages
predicated off), the K and V appends of each sequence's current page, the
Wo rows and a store of one output row per sequence; per MoE layer the
router rows, the store of the priority-ordered expert ids (all first
choices before second), and the all-to-all send scatter and combine gather
at ``expert * capacity + grant position``, requests past the capacity
predicated off.  Pages come from the serving arbiter of the page-map
memory: each sequence's k-th page prefers the bank the map gives k, grants
go in lane order within the bank's free slots, and overflow spills to the
least-loaded banks.  Every stream is one memory instruction of 16-lane
operations; a ragged tail repeats the last address in idle lanes.

The cost of an operation (the paper's controller): on a banked memory the
largest number of active lanes that map to one bank (distinct addresses
only, on a broadcast memory's reads); on an nR-mW memory ceil(active /
ports), except that the -VB variant arbitrates writes over 4 lsb banks.
Each instruction adds its controller overhead once.

The control (``drop_masks=True``) prices predicated lanes as if they
issued their request, which breaks the guarantee that a predicated lane
costs nothing.
"""
from __future__ import annotations

import re

import numpy as np

LANES = 16
#: controller overhead per instruction by flat bank count (paper Tables
#: II/III calibration); other counts take the 16-bank values
READ_OVERHEAD = {16: 40, 8: 34, 4: 32}
WRITE_OVERHEAD = {16: 30, 8: 24, 4: 22}
FIELDS = ("load_cycles", "store_cycles", "tw_load_cycles", "compute_cycles",
          "n_load_ops", "n_store_ops", "n_tw_ops", "fp_ops", "int_ops",
          "imm_ops", "other_ops")

_BANKED = re.compile(r"^(\d+)B(?:-(lsb|offset|xor|fold))?(-bcast)?$")
_BCAST_ONLY = re.compile(r"^(\d+)B-bcast$")
_TWO = re.compile(r"^(\d+)x(\d+)B(?:-(lsb|offset|xor|fold))?(?:-g(\d+))?$")
_PORTS = re.compile(r"^(\d+)R-(\d+)W(-VB)?$")


def memory(name: str) -> dict:
    """A memory's parameters from its name (the paper's naming)."""
    m = _BCAST_ONLY.match(name)
    if m:
        return {"kind": "banked", "banks": int(m[1]), "map": "lsb",
                "bcast": True, "outer": 1, "granule": 1}
    m = _BANKED.match(name)
    if m:
        return {"kind": "banked", "banks": int(m[1]), "map": m[2] or "lsb",
                "bcast": bool(m[3]), "outer": 1, "granule": 1}
    m = _TWO.match(name)
    if m:
        inner = int(m[2])
        return {"kind": "banked", "banks": inner, "map": m[3] or "lsb",
                "bcast": False, "outer": int(m[1]),
                "granule": int(m[4]) if m[4] else inner}
    m = _PORTS.match(name)
    if m:
        return {"kind": "ports", "read": int(m[1]), "write": int(m[2]),
                "vb": bool(m[3])}
    raise ValueError(f"unknown memory {name!r}")


def bank_of(a, n: int, mapping: str, shift: int = 1):
    a = np.asarray(a, np.int64)
    if mapping == "lsb":
        return a % n
    if mapping == "offset":
        return (a >> shift) % n
    log2 = n.bit_length() - 1
    if 1 << log2 != n:
        raise ValueError(f"{mapping} map needs a power-of-two bank count")
    if mapping == "xor":
        return (a ^ (a >> log2)) & (n - 1)
    if mapping == "fold":
        return (a + (a >> log2)) & (n - 1)
    raise ValueError(f"unknown map {mapping!r}")


def row_at(bank, slot, n: int, mapping: str, shift: int = 1):
    """The page id stored at (bank, slot): the inverse of the map."""
    bank, slot = np.asarray(bank, np.int64), np.asarray(slot, np.int64)
    if mapping == "lsb":
        return slot * n + bank
    if mapping == "offset":
        low = slot & ((1 << shift) - 1)
        return (((slot >> shift) * n + bank) << shift) | low
    log2 = n.bit_length() - 1
    if mapping == "xor":
        return (slot << log2) | ((bank ^ slot) & (n - 1))
    return (slot << log2) | ((bank - slot) & (n - 1))


def page_table(batch: int, prompt_len: int, page_len: int, banks: int,
               mapping: str, shift: int = 1) -> np.ndarray:
    """Every prompt page plus the decode step's page of each sequence."""
    pages = -(-(prompt_len + 1) // page_len)
    n_pages = -(-2 * batch * pages // banks) * banks
    cap = n_pages // banks
    pt = np.full((batch, pages), -1, np.int64)
    used = np.zeros(banks, np.int64)
    lanes = np.arange(batch)

    def alloc(seq_len: int, need: np.ndarray):
        nonlocal used
        k = seq_len // page_len
        pref = np.full(batch, bank_of(k, banks, mapping, shift))
        pos1 = np.array([np.sum(need[:b] & (pref[:b] == pref[b]))
                         for b in lanes])
        slot1 = used[pref] + pos1
        ok1 = need & (slot1 < cap)
        used1 = used + np.bincount(pref[ok1], minlength=banks)
        over = need & ~ok1
        rank = np.cumsum(over) - over
        order = np.argsort(used1, kind="stable")
        free = (cap - used1)[order]
        cum = np.cumsum(free)
        sidx = np.clip(np.searchsorted(cum, rank, side="right"), 0,
                       banks - 1)
        bank2 = order[sidx]
        slot2 = used1[bank2] + rank - (cum[sidx] - free[sidx])
        ok2 = over & (rank < cum[-1]) & (slot2 < cap)
        bank = np.where(ok1, pref, bank2)
        slot = np.where(ok1, slot1, slot2)
        ok = ok1 | ok2
        used = used + np.bincount(bank[ok], minlength=banks)
        pt[ok, k] = row_at(bank[ok], slot[ok], banks, mapping, shift)

    for p in range(-(-prompt_len // page_len)):
        alloc(p * page_len, np.ones(batch, bool))
    if prompt_len % page_len == 0:
        alloc(prompt_len, np.ones(batch, bool))
    return pt


def _grant(ids: np.ndarray) -> np.ndarray:
    """Each request's rank among earlier requests for the same id."""
    out = np.zeros(ids.shape[0], np.int64)
    seen: dict = {}
    for i, x in enumerate(ids.tolist()):
        out[i] = seen.get(x, 0)
        seen[x] = out[i] + 1
    return out


def streams(conf: dict, traffic: dict, routing_seed: int) -> list:
    """The step's instructions in order: (kind, ids, mask or None)."""
    c = conf["config"]
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    experts = int(c.get("num_local_experts", 0))
    k = int(c.get("num_experts_per_tok", 0))
    layers = int(c["num_hidden_layers"])
    b, pos = int(traffic["batch"]), int(traffic["position"])
    plen = int(traffic["page_len"])
    pm = memory(traffic["page_map"])
    if pm["kind"] != "banked" or pm["outer"] != 1:
        raise ValueError("the reference allocates pages on flat banked "
                         "page maps only")
    pt = page_table(b, pos, plen, pm["banks"], pm["map"])
    read_ids, read_mask = np.maximum(pt, 0).reshape(-1), (pt >= 0).reshape(-1)
    cur = pt[np.arange(b), pos // plen]
    cur_ids, cur_mask = np.maximum(cur, 0), cur >= 0
    rows = np.arange(d)
    cap_f = float(conf["deployment"]["capacity_factor"])
    cap = max(4, -(-int(cap_f * k * b / max(experts, 1)) // 4) * 4)
    rng = np.random.default_rng(routing_seed)
    out = []
    for _ in range(layers):
        out += [("load", rows, None), ("load", rows, None),
                ("load", rows, None),
                ("load", np.repeat(np.full(b, pos), max(heads, 1)), None),
                ("load", read_ids, read_mask), ("load", read_ids, read_mask),
                ("store", cur_ids, cur_mask), ("store", cur_ids, cur_mask),
                ("load", rows, None), ("store", np.arange(b), None)]
        if experts:
            e = np.argsort(rng.random((b, experts)), axis=1)[:, :k]
            e = e.T.reshape(-1).astype(np.int64)
            g = _grant(e)
            kept = g < cap
            slot = np.where(kept, e * cap + g, 0)
            out += [("load", rows, None), ("store", e, None),
                    ("store", slot, kept), ("load", slot, kept)]
        else:
            out += [("load", np.arange(int(c["intermediate_size"])), None),
                    ("store", np.arange(b), None)]
    return out


def ops(ids: np.ndarray, mask) -> tuple:
    """(n_ops, 16) addresses and active lanes of one instruction."""
    ids = np.asarray(ids, np.int64)
    pad = (-ids.shape[0]) % LANES
    act = np.ones(ids.shape[0], bool) if mask is None \
        else np.asarray(mask, bool)
    if pad:
        ids = np.concatenate([ids, np.repeat(ids[-1], pad)])
        act = np.concatenate([act, np.full(pad, mask is None)])
    return ids.reshape(-1, LANES), act.reshape(-1, LANES)


def op_cycles(mem: dict, addrs, act, write: bool) -> np.ndarray:
    """Cycles of each operation under one memory."""
    if mem["kind"] == "ports":
        if write and mem["vb"]:
            mem = {"kind": "banked", "banks": 4, "map": "lsb",
                   "bcast": False, "outer": 1, "granule": 1}
        else:
            p = mem["write"] if write else mem["read"]
            return -(-act.sum(1) // p)
    bank = bank_of(addrs, mem["banks"], mem["map"])
    if mem["outer"] > 1:
        bank = bank + mem["banks"] * ((addrs // mem["granule"])
                                      % mem["outer"])
    live = act
    if mem["bcast"] and not write:
        same = addrs[:, :, None] == addrs[:, None, :]
        earlier = np.tril(np.ones((LANES, LANES), bool), -1)
        live = act & ~(same & act[:, None, :] & earlier).any(-1)
    total = mem["banks"] * mem["outer"]
    hits = (bank[:, :, None] == np.arange(total)) & live[:, :, None]
    return hits.sum(1).max(1)


def overheads(mem: dict) -> tuple:
    if mem["kind"] == "ports":
        return 0, (WRITE_OVERHEAD[4] if mem["vb"] else 0)
    total = mem["banks"] * mem["outer"]
    return READ_OVERHEAD.get(total, 40), WRITE_OVERHEAD.get(total, 30)


def price(conf: dict, traffic: dict, routing_seed: int, memories,
          drop_masks: bool = False) -> list:
    """Every memory's cost fields for one decode step."""
    insts = [(kind, *ops(ids, None if drop_masks else mask))
             for kind, ids, mask in streams(conf, traffic, routing_seed)]
    n_load = sum(a.shape[0] for k, a, _ in insts if k == "load")
    n_store = sum(a.shape[0] for k, a, _ in insts if k == "store")
    i_load = sum(k == "load" for k, _, _ in insts)
    i_store = sum(k == "store" for k, _, _ in insts)
    loads = (np.concatenate([a for k, a, _ in insts if k == "load"]),
             np.concatenate([m for k, _, m in insts if k == "load"]))
    stores = (np.concatenate([a for k, a, _ in insts if k == "store"]),
              np.concatenate([m for k, _, m in insts if k == "store"]))
    out = []
    for name in memories:
        mem = memory(name)
        r_ovh, w_ovh = overheads(mem)
        cost = dict.fromkeys(FIELDS, 0)
        cost["n_load_ops"], cost["n_store_ops"] = n_load, n_store
        if n_load:
            cost["load_cycles"] = int(op_cycles(mem, *loads, False).sum()
                                      + i_load * r_ovh)
        if n_store:
            cost["store_cycles"] = int(op_cycles(mem, *stores, True).sum()
                                       + i_store * w_ovh)
        out.append(cost)
    return out


def mismatches(costs, reference: list) -> int:
    """Fields in which a list of costs (the program's ``TraceCost``s, or
    the control's dicts) differs from the reference's."""
    def field(c, f):
        return int(c[f] if isinstance(c, dict) else getattr(c, f))
    return sum(field(c, f) != r[f]
               for c, r in zip(costs, reference, strict=True)
               for f in FIELDS)
