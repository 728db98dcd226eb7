"""Elle rw-register histories, vectorised, from a seed.

Shaped to Elle's `elle.txn/wr-txns` (what `elle.rw-register/gen` draws
and `jepsen.tests.cycle.wr` runs): txns of `min_txn_length`..
`max_txn_length` micro-ops (equal counts of each length), `:r` and `:w`
equally likely, a pool of `key_count` active keys drawn with exponential
skew (`key_dist_base`), each key retired after `max_writes_per_key`
writes and replaced by a fresh one, every write a fresh value of its key.

The draw is `elle_append.generate`'s, with each append read as a write:
the same keys, sizes, clients and timing from the same seed (a test pins
the two together).  It builds no read lists, only each read's value, so
a history of 2^19 txns costs about half the host time of a list-append
one.  Execution is serial in commit order, txn-major, so every read
returns its key's latest value (nil, shown as -1, before the first
write) and a txn sees its own writes; the list a list-append read
returns ends in exactly that value.  Sizes depend on (`n_txns`, shape)
alone, never on the seed.

`inject="read-skew"` is the probe: the latest pair a < b where a reads
k1 (u) before writing it (v) and writes k2 (w), and b reads v and w and
writes neither key, touching k1 once; b's read of k1 is set to u.  Then
a -> b by wr on k2 and b -> a by rw on k1 (u << v inside a): one
G-single, whose rw edge runs against commit order.  Snapshot isolation
proscribes it; read committed allows it.  Only a value changes.
"""

from __future__ import annotations

import numpy as np

from benchmark.gen import elle_append

READ, WRITE = elle_append.READ, elle_append.APPEND
sizes = elle_append.sizes


def generate(n_txns: int, shape: dict, timing: dict, seed,
             inject: str | None = None) -> dict:
    """One history as numpy columns (txn-major micro-ops):

    txn_process, txn_invoke_pos, txn_complete_pos, txn_ok  [T]
    mop_txn, mop_kind, mop_key                             [M]
    mop_val   [M] the value id written, or read (-1: nil)
    val_key, val_value                                     [V] per value id
    n_keys, n_events, injected (pair of txn ids or None)

    Value ids count writes in commit order; `val_value` is the value a
    client wrote (1, 2, ... within its key)."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    sz = sizes(n_txns, shape)
    T, M = n_txns, sz["n_mops"]
    P = int(shape["processes"])
    lead, lag = float(timing["invoke_lead"]), float(timing["complete_lag"])
    if not lead + lag < P:
        raise ValueError("invoke_lead + complete_lag must stay under "
                         "processes, or a client would overlap itself")

    # txn lengths, micro-op kinds and keys: elle_append's draw, call for
    # call, so that one seed gives both families the same history
    txn_len = rng.permutation(np.repeat(sz["lengths"], sz["per_length"]))
    mop_txn = np.repeat(np.arange(T, dtype=np.int32), txn_len)
    kind = np.full(M, WRITE, np.int8)
    kind[: sz["n_reads"]] = READ
    kind = rng.permutation(kind)
    kc = int(shape["key_count"])
    w = float(shape["key_dist_base"]) ** -np.arange(kc, dtype=np.float64)
    slot = rng.choice(kc, size=M, p=w / w.sum()).astype(np.int64)
    is_w = kind == WRITE
    mw = int(shape["max_writes_per_key"])
    before = np.empty(M, np.int64)  # writes to the slot before the mop
    for s in range(kc):
        idx = np.nonzero(slot == s)[0]
        a = is_w[idx].astype(np.int64)
        before[idx] = np.cumsum(a) - a
    gen = before // mw
    raw = gen * kc + slot
    uniq, first = np.unique(raw, return_index=True)
    order = np.argsort(first, kind="stable")
    dense = np.empty(len(uniq), np.int64)
    dense[order] = np.arange(len(uniq))
    mop_key = dense[np.searchsorted(uniq, raw)].astype(np.int32)
    if len(uniq) > sz["n_keys"]:
        raise AssertionError("key ids outran the fixed key space")
    pos_in_key = before - gen * mw  # writes to this key before the mop

    # values: one id per write, in commit order; a read returns the id of
    # its key's latest write, the (pos_in_key - 1)-th, or nil
    w_idx = np.nonzero(is_w)[0]
    val_key = mop_key[w_idx].astype(np.int32)
    val_value = (pos_in_key[w_idx] + 1).astype(np.int32)
    by_key = np.lexsort((val_value, val_key))
    key_first = np.searchsorted(val_key[by_key], np.arange(sz["n_keys"]))
    latest = by_key[np.maximum(key_first[mop_key] + pos_in_key - 1, 0)]
    mop_val = np.where(is_w, np.cumsum(is_w) - 1,
                       np.where(pos_in_key > 0, latest, -1)).astype(np.int32)

    # clients and realtime
    t = np.arange(T, dtype=np.float64)
    t_inv = t - rng.uniform(0.0, lead, T) - 1e-6
    t_cmp = t + rng.uniform(0.0, lag, T) + 1e-6
    ev = np.argsort(np.concatenate([t_inv, t_cmp]), kind="stable")
    pos = np.empty(2 * T, np.int64)
    pos[ev] = np.arange(2 * T)
    injected = None
    if inject == "read-skew":
        injected = _read_skew(mop_txn, kind, mop_key, mop_val)
    elif inject is not None:
        raise ValueError(f"unknown injection {inject!r}")
    return {
        "txn_process": (np.arange(T) % P).astype(np.int32),
        "txn_invoke_pos": pos[:T].astype(np.int32),
        "txn_complete_pos": pos[T:].astype(np.int32),
        "txn_ok": np.ones(T, bool),
        "mop_txn": mop_txn, "mop_kind": kind, "mop_key": mop_key,
        "mop_val": mop_val, "val_key": val_key, "val_value": val_value,
        "n_keys": sz["n_keys"], "n_events": 2 * T, "injected": injected,
    }


def _read_skew(mop_txn, kind, key, val):
    """Find the pair (module docstring) from the end, rewrite b's read of
    k1 in `val` in place and return (a, b)."""
    T = int(mop_txn[-1]) + 1
    first = np.searchsorted(mop_txn, np.arange(T + 1))
    writer = np.full(int(val.max()) + 1, -1, np.int64)
    w = kind == WRITE
    writer[val[w]] = mop_txn[w]

    def ops(t):
        s = slice(first[t], first[t + 1])
        return kind[s], key[s], val[s]

    def external_reads(k, ky, v):
        """key -> value of the txn's reads of keys it reads before any
        write of them"""
        out, seen = {}, set()
        for kd, kk, vv in zip(k.tolist(), ky.tolist(), v.tolist()):
            if kk not in seen and kd == READ:
                out[kk] = vv
            seen.add(kk)
        return out

    for b in range(T - 1, 0, -1):
        kb, keys_b, vb = ops(b)
        wrote_b = set(keys_b[kb == WRITE].tolist())
        reads_b = external_reads(kb, keys_b, vb)
        for k1, v in reads_b.items():
            if v < 0 or k1 in wrote_b or (keys_b == k1).sum() != 1:
                continue
            a = int(writer[v])
            ka, keys_a, va = ops(a)
            u = external_reads(ka, keys_a, va).get(k1)
            if u is None:
                continue
            finals = {kk: vv for kd, kk, vv in zip(
                ka.tolist(), keys_a.tolist(), va.tolist()) if kd == WRITE}
            if not any(k2 != k1 and reads_b.get(k2) == wv and
                       k2 not in wrote_b for k2, wv in finals.items()):
                continue
            at = first[b] + int(np.nonzero(keys_b == k1)[0][0])
            val[at] = u
            return a, b
    raise AssertionError("no txn pair fits a read-skew injection")
