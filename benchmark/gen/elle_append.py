"""Elle list-append histories, vectorised, from a seed.

A copy (not an import) of the idea behind the program's
`workloads/synth.packed_la_history`, reshaped to Elle's own generator
(`elle.list-append/gen`, used by `jepsen.tests.cycle.append`): txns of
`min_txn_length`..`max_txn_length` micro-ops, reads and appends equally
likely, a pool of `key_count` active keys drawn with exponential skew
(`key_dist_base`), each key retired after `max_writes_per_key` appends and
replaced by a fresh one, and `processes` clients whose txn intervals
overlap.

Execution is serial in commit order (txn index): every read returns the
list its key holds at that point, so the history is strict-serializable
by construction.  Txn t commits at time t.  Client `t % processes` runs
it, invoking up to `invoke_lead` commit slots before and completing up to
`complete_lag` slots after; `invoke_lead + complete_lag < processes`
keeps each client's txns in sequence.  With `complete_lag < 1`
completions follow commit order; a larger lag lets a later commit
complete first, as clients of a real database see.

Sizes are fixed by (`n_txns`, shape) alone, never by the seed: the
number of txns of each length, of appends and reads, and of key ids
(`n_keys`) are the same for every seed, so every seed's history pads to
the same shapes and compiles once.

`inject="stale-read"` makes one txn's read miss the append of the txn
committed just before it, which completed before the reader was invoked:
a G-single cycle through one realtime edge, invisible to a checker that
drops the realtime order (serializable instead of strict-serializable).
It takes the latest txn pair that fits, near the end of the history.
"""

from __future__ import annotations

import numpy as np

READ = 1
APPEND = 0


def _counts(total: int, n: int) -> np.ndarray:
    """`total` split into `n` near-equal whole parts (largest first)."""
    base = np.full(n, total // n, np.int64)
    base[: total % n] += 1
    return base


def sizes(n_txns: int, shape: dict) -> dict:
    """The seed-independent sizes of a history of this shape."""
    lengths = np.arange(shape["min_txn_length"], shape["max_txn_length"] + 1)
    per_len = _counts(n_txns, len(lengths))
    n_mops = int((per_len * lengths).sum())
    n_reads = int(round(n_mops * shape["read_share"]))
    n_app = n_mops - n_reads
    n_keys = n_app // shape["max_writes_per_key"] + shape["key_count"]
    return {"n_txns": n_txns, "n_mops": n_mops, "n_reads": n_reads,
            "n_appends": n_app, "n_keys": n_keys,
            "lengths": lengths, "per_length": per_len}


def generate(n_txns: int, shape: dict, timing: dict, seed,
             inject: str | None = None) -> dict:
    """One history as numpy columns (txn-major micro-ops):

    txn_process, txn_invoke_pos, txn_complete_pos  [T]
    mop_txn, mop_kind, mop_key, mop_val            [M]
    mop_rd_start, mop_rd_len                       [M] (reads only, else -1)
    rd_elems                                       [R] value ids
    val_key, val_value                             [V] per value id
    n_keys, n_events, injected (pair of txn ids or None)

    Value ids count appends in commit order; `val_value` is the element
    a client appended (1, 2, ... within its key).  `seed` is a
    non-negative whole number or a list of them."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    sz = sizes(n_txns, shape)
    T, M = n_txns, sz["n_mops"]
    P = int(shape["processes"])
    lead, lag = float(timing["invoke_lead"]), float(timing["complete_lag"])
    if not lead + lag < P:
        raise ValueError("invoke_lead + complete_lag must stay under "
                         "processes, or a client would overlap itself")

    # ---- txn lengths and micro-op kinds: fixed multisets, seeded order --
    txn_len = rng.permutation(np.repeat(sz["lengths"], sz["per_length"]))
    mop_txn = np.repeat(np.arange(T, dtype=np.int32), txn_len)
    kind = np.full(M, APPEND, np.int8)
    kind[: sz["n_reads"]] = READ
    kind = rng.permutation(kind)

    # ---- keys: slot of the active pool, exponential skew -----------------
    kc = int(shape["key_count"])
    w = float(shape["key_dist_base"]) ** -np.arange(kc, dtype=np.float64)
    slot = rng.choice(kc, size=M, p=w / w.sum()).astype(np.int64)
    is_app = kind == APPEND
    # appends to the slot before this mop; the slot's key retires after
    # max_writes appends, so its generation is that count // max_writes
    mw = int(shape["max_writes_per_key"])
    before = np.empty(M, np.int64)
    for s in range(kc):
        idx = np.nonzero(slot == s)[0]
        a = is_app[idx].astype(np.int64)
        before[idx] = np.cumsum(a) - a
    gen = before // mw
    # dense key ids in order of first use
    raw = gen * kc + slot
    uniq, first = np.unique(raw, return_index=True)
    order = np.argsort(first, kind="stable")
    dense = np.empty(len(uniq), np.int64)
    dense[order] = np.arange(len(uniq))
    mop_key = dense[np.searchsorted(uniq, raw)].astype(np.int32)
    if len(uniq) > sz["n_keys"]:
        raise AssertionError("key ids outran the fixed key space")
    pos_in_key = before - gen * mw  # appends to this key before the mop

    # ---- values: one id per append, in commit order ----------------------
    app_idx = np.nonzero(is_app)[0]
    mop_val = np.full(M, -1, np.int32)
    mop_val[app_idx] = np.arange(len(app_idx), dtype=np.int32)
    val_key = mop_key[app_idx].astype(np.int32)
    val_value = (pos_in_key[app_idx] + 1).astype(np.int32)
    # val id of the j-th append of each key: appends of a key are in
    # commit order, so sort them by (key, value)
    by_key = np.lexsort((val_value, val_key))
    key_first = np.searchsorted(val_key[by_key], np.arange(sz["n_keys"]))

    # ---- reads: the key's whole list at that point -----------------------
    rd_idx = np.nonzero(~is_app)[0]
    rd_len = pos_in_key[rd_idx]
    rd_start = np.concatenate([[0], np.cumsum(rd_len)[:-1]])
    R = int(rd_len.sum())
    reps = np.repeat(np.arange(len(rd_idx)), rd_len)
    offs = np.arange(R) - np.repeat(rd_start, rd_len)
    rd_elems = by_key[key_first[mop_key[rd_idx][reps]] + offs]
    mop_rd_start = np.full(M, -1, np.int32)
    mop_rd_len = np.full(M, -1, np.int32)
    mop_rd_start[rd_idx] = rd_start
    mop_rd_len[rd_idx] = rd_len

    # ---- clients and realtime --------------------------------------------
    t = np.arange(T, dtype=np.float64)
    t_inv = t - rng.uniform(0.0, lead, T) - 1e-6
    t_cmp = t + rng.uniform(0.0, lag, T) + 1e-6
    first = np.searchsorted(mop_txn, np.arange(T + 1))
    injected = None
    if inject == "stale-read":
        injected, rd_elems, mop_rd_start, mop_rd_len = _stale_read(
            first, kind, mop_key, mop_val, mop_rd_start, mop_rd_len,
            rd_elems, mop_txn)
        a, b = injected
        # a completes, then b is invoked: a realtime edge a -> b
        t_cmp[a] = t[a] + 1e-3
        t_inv[b] = t[b] - 1e-3
    elif inject is not None:
        raise ValueError(f"unknown injection {inject!r}")
    ev = np.argsort(np.concatenate([t_inv, t_cmp]), kind="stable")
    pos = np.empty(2 * T, np.int64)
    pos[ev] = np.arange(2 * T)
    return {
        "txn_process": (np.arange(T) % P).astype(np.int32),
        "txn_invoke_pos": pos[:T].astype(np.int32),
        "txn_complete_pos": pos[T:].astype(np.int32),
        "mop_txn": mop_txn, "mop_kind": kind, "mop_key": mop_key,
        "mop_val": mop_val, "mop_rd_start": mop_rd_start,
        "mop_rd_len": mop_rd_len, "rd_elems": rd_elems.astype(np.int32),
        "val_key": val_key, "val_value": val_value,
        "n_keys": sz["n_keys"], "n_events": 2 * T, "injected": injected,
    }


def _stale_read(first, kind, mop_key, mop_val, rd_start, rd_len, rd_elems,
                mop_txn):
    """Pick the latest txns a, b = a + 1 where b's read of key k ends in the one
    append a made to k, another read of k shows that append too (so the
    version order still holds it), a and b touch no other key in common
    and b touches k nowhere else; drop that element from b's read."""
    M = len(mop_txn)
    writer = np.full(int(mop_val.max()) + 2, -1, np.int64)
    app = kind == APPEND
    writer[mop_val[app]] = mop_txn[app]
    reads = np.nonzero((kind == READ) & (rd_len > 0))[0]
    last = rd_elems[rd_start[reads] + rd_len[reads] - 1]
    cand = reads[writer[last] == mop_txn[reads] - 1]
    for r in cand[::-1]:
        b = int(mop_txn[r])
        a, k = b - 1, int(mop_key[r])
        ka = mop_key[first[a]:first[a + 1]]
        kb = mop_key[first[b]:first[b + 1]]
        if (ka == k).sum() != 1 or (kb == k).sum() != 1:
            continue
        if np.intersect1d(ka[ka != k], kb).size:
            continue
        if ((mop_key[reads] == k) & (rd_len[reads] >= rd_len[r])).sum() < 2:
            continue
        cut = int(rd_start[r] + rd_len[r] - 1)
        rd_elems = np.delete(rd_elems, cut)
        rd_start = rd_start.copy()
        rd_len = rd_len.copy()
        rd_len[r] -= 1
        later = (np.arange(M) > r) & (rd_start >= 0)
        rd_start[later] -= 1
        return (a, b), rd_elems, rd_start, rd_len
    raise AssertionError("no txn pair fits a stale-read injection")
