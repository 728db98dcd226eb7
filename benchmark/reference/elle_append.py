"""Plain reference checker for Elle list-append histories (all txns ok).

Written from Elle's definitions (Kingsbury & Alvaro, "Elle: Inferring
Isolation Anomalies from Experimental Observations", VLDB 2020, and the
`elle.list-append` / `elle.txn` anomaly specs), and from nothing of the
program under test: it reads the generator's columns, not the program's
packing, and imports numpy and scipy only.

Version order of a key: its longest read, which every other read of the
key must be a prefix of (else `incompatible-order`).  Dependencies between
txns: ww (consecutive versions), wr (writer of a read's last element ->
reader), rw (reader -> writer of the version after its last element), the
per-client process order, and the realtime order (a completes before b is
invoked), encoded through one barrier node per completion so it stays
linear in size.  A cycle anomaly exists when its projection of those rels
holds a cycle of the named kind:

  any          -- G0 (ww), G1c (ww, wr)
  single       -- G-single: exactly one rw edge
  some         -- G2-item: one rw edge or more
  multi-nonadj -- G-nonadjacent: two rw edges or more, no two adjacent

each in a plain, a `-process` and a `-realtime` variant.  Every cycle lies
inside one strongly connected component of the union of all rels, so the
searches run on the nodes of the union's non-trivial components only.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order, connected_components

WW, WR, RW, PROCESS, REALTIME = range(5)
_BASE = {WW, WR, RW}
SPECS = {  # name -> (rels, kind)
    "G0": ({WW}, "any"),
    "G0-process": ({WW, PROCESS}, "any"),
    "G0-realtime": ({WW, REALTIME}, "any"),
    "G1c": ({WW, WR}, "any"),
    "G1c-process": ({WW, WR, PROCESS}, "any"),
    "G1c-realtime": ({WW, WR, REALTIME}, "any"),
    "G-single": (_BASE, "single"),
    "G-single-process": (_BASE | {PROCESS}, "single"),
    "G-single-realtime": (_BASE | {REALTIME}, "single"),
    "G-nonadjacent": (_BASE, "multi-nonadj"),
    "G-nonadjacent-process": (_BASE | {PROCESS}, "multi-nonadj"),
    "G-nonadjacent-realtime": (_BASE | {REALTIME}, "multi-nonadj"),
    "G2-item": (_BASE, "some"),
    "G2-item-process": (_BASE | {PROCESS}, "some"),
    "G2-item-realtime": (_BASE | {REALTIME}, "some"),
}
#: the rels each consistency model orders txns by; a model sees only
#: the anomalies whose rels it orders
MODEL_RELS = {
    "strict-serializable": {WW, WR, RW, PROCESS, REALTIME},
    "serializable": {WW, WR, RW},
}
#: simple cycles a G-nonadjacent search may walk before giving up
CYCLE_BUDGET = 200_000


class Undecided(RuntimeError):
    """The reference could not decide within its budget."""


def check(h: dict, model: str = "strict-serializable") -> dict:
    """{"valid?": bool, "anomaly-types": sorted names} for history `h`
    (the columns `gen.elle_append.generate` returns)."""
    rels_seen = MODEL_RELS[model]
    found = set(_item_anomalies(h))
    order_ok = "incompatible-order" not in found
    src, dst, rel, n_nodes = _edges(h, with_ww_rw=order_ok)
    keep = np.isin(rel, sorted(rels_seen))
    src, dst, rel = src[keep], dst[keep], rel[keep]
    for name in _cycle_anomalies(src, dst, rel, n_nodes, rels_seen):
        found.add(name)
    return {"valid?": not found, "anomaly-types": sorted(found)}


# ---------------------------------------------------------------------------
# anomalies of single reads and appends


def _reads(h):
    r = np.nonzero(h["mop_rd_len"] >= 0)[0]
    return r, h["mop_rd_start"][r].astype(np.int64), \
        h["mop_rd_len"][r].astype(np.int64)


def _ranges(start, ln):
    """Concatenated index ranges [start, start + ln)."""
    tot = int(ln.sum())
    return np.repeat(start, ln) + (
        np.arange(tot) - np.repeat(np.cumsum(ln) - ln, ln))


def _longest(keys, ln, n_keys):
    """Row of each key's longest read (-1 where the key is never read)."""
    ordr = np.lexsort((-ln, keys))
    first = np.ones(len(ordr), bool)
    first[1:] = keys[ordr][1:] != keys[ordr][:-1]
    out = np.full(n_keys, -1, np.int64)
    out[keys[ordr][first]] = ordr[first]
    return out


def _item_anomalies(h) -> list:
    out = []
    n_vals, n_keys = len(h["val_key"]), int(h["n_keys"])
    app = np.nonzero(h["mop_rd_len"] < 0)[0]
    pairs = h["mop_key"][app].astype(np.int64) * (1 << 32) + \
        h["val_value"][h["mop_val"][app]]
    if len(np.unique(pairs)) < len(pairs):
        out.append("duplicate-appends")
    r, start, ln = _reads(h)
    rows = np.repeat(np.arange(len(r)), ln)
    elems = h["rd_elems"][_ranges(start, ln)].astype(np.int64)
    keys = h["mop_key"][r].astype(np.int64)
    if len(elems) and ((elems < 0) | (elems >= n_vals)
                       | (h["val_key"][np.clip(elems, 0, n_vals - 1)]
                          != keys[rows])).any():
        raise ValueError("a read lists an element never appended to its key")
    if len(np.unique(rows * (n_vals + 1) + elems)) < len(elems):
        out.append("duplicate-elements")
    lk = _longest(keys, ln, n_keys)[keys[rows]]
    offs = np.arange(len(elems)) - np.repeat(np.cumsum(ln) - ln, ln)
    if (h["rd_elems"][start[lk] + offs] != elems).any():
        out.append("incompatible-order")
    if _g1b(h, r, start, ln):
        out.append("G1b")
    if _internal(h):
        out.append("internal")
    return out


def _g1b(h, r, start, ln) -> bool:
    """A read ends on an append that its writer followed with another
    append to the same key (and the reader is another txn)."""
    mt, mk = h["mop_txn"].astype(np.int64), h["mop_key"].astype(np.int64)
    ai = np.nonzero(h["mop_rd_len"] < 0)[0]
    tk = mt[ai] * (int(h["n_keys"]) + 1) + mk[ai]
    # final[v]: v is its writer's last append to its key
    final = np.zeros(len(h["val_key"]), bool)
    o = np.lexsort((ai, tk))
    last = np.ones(len(o), bool)
    last[:-1] = tk[o][1:] != tk[o][:-1]
    final[h["mop_val"][ai[o][last]]] = True
    writer = np.full(len(h["val_key"]), -1, np.int64)
    writer[h["mop_val"][ai]] = mt[ai]
    ne = ln > 0
    end = h["rd_elems"][start[ne] + ln[ne] - 1]
    return bool((~final[end] & (writer[end] != mt[r[ne]])).any())


def _internal(h) -> bool:
    """A read disagrees with what its own txn read or appended earlier on
    that key: after a read of L and appends A, the txn must read L + A;
    with no earlier read, a read must end with the txn's earlier appends
    to the key."""
    n = len(h["mop_txn"])
    group = h["mop_txn"].astype(np.int64) * (int(h["n_keys"]) + 1) + \
        h["mop_key"]
    o = np.lexsort((np.arange(n), group))  # (txn, key) runs, in mop order
    first = np.ones(n, bool)
    first[1:] = group[o][1:] != group[o][:-1]
    run = np.cumsum(first) - 1
    run_start = np.nonzero(first)[0]
    app = h["mop_rd_len"][o] < 0
    start = h["mop_rd_start"][o].astype(np.int64)
    ln = h["mop_rd_len"][o].astype(np.int64)
    app_vals = h["mop_val"][o][app]  # by run, then by order in the run
    apps_before = np.cumsum(app) - app  # over the whole sorted order
    in_run = apps_before - apps_before[run_start][run]
    base = apps_before[run_start][run]
    last_read = np.maximum.accumulate(np.where(~app, np.arange(n), -1))
    prev = np.concatenate([[-1], last_read[:-1]])
    prev = np.where(prev >= run_start[run], prev, -1)
    rd = np.nonzero(~app)[0]
    p = prev[rd]
    has = p >= 0
    lo = np.where(has, in_run[np.maximum(p, 0)], 0)
    n_app = in_run[rd] - lo  # appends since the previous read
    lp = np.where(has, ln[np.maximum(p, 0)], 0)
    lc = ln[rd]
    if (has & (lc != lp + n_app)).any() or (lc < n_app).any():
        return True
    e = h["rd_elems"]
    # what the previous read saw comes first ...
    q = rd[has]
    if (e[_ranges(start[q], lp[has])] != e[_ranges(start[p[has]],
                                                 lp[has])]).any():
        return True
    # ... and the appends since then close the read
    return bool((e[_ranges(start[rd] + lc - n_app, n_app)]
                 != app_vals[_ranges(base[rd] + lo, n_app)]).any())


# ---------------------------------------------------------------------------
# dependency graph


def _edges(h, with_ww_rw: bool = True):
    """(src, dst, rel, n_nodes): txn nodes 0..T-1, barrier nodes T..2T-1."""
    T = len(h["txn_process"])
    mt = h["mop_txn"].astype(np.int64)
    ai = np.nonzero(h["mop_rd_len"] < 0)[0]
    writer = np.full(len(h["val_key"]), -1, np.int64)
    writer[h["mop_val"][ai]] = mt[ai]
    r, start, ln = _reads(h)
    parts = []
    ne = ln > 0
    # wr: writer of the last element -> reader
    last = h["rd_elems"][start[ne] + ln[ne] - 1]
    parts.append((writer[last], mt[r[ne]], WR))
    if with_ww_rw:
        keys = h["mop_key"][r].astype(np.int64)
        n_keys = int(h["n_keys"])
        lrow = _longest(keys, ln, n_keys)
        lrow = lrow[lrow >= 0]  # the longest read of each read key
        lkey = keys[lrow]
        lstart, llen = start[lrow], ln[lrow]
        # ww: consecutive elements of each key's longest read
        el = h["rd_elems"][_ranges(lstart, llen)].astype(np.int64)
        seg = np.repeat(np.arange(len(lrow)), llen)
        same = seg[1:] == seg[:-1]
        parts.append((writer[el[:-1][same]], writer[el[1:][same]], WW))
        # rw: reader -> writer of the element after its read, when known
        full = np.zeros(n_keys, np.int64)
        full[lkey] = llen
        kstart = np.zeros(n_keys, np.int64)
        kstart[lkey] = lstart
        has_next = ln < full[keys]
        nxt = h["rd_elems"][kstart[keys[has_next]] + ln[has_next]]
        parts.append((mt[r[has_next]], writer[nxt], RW))
    # process order: a client's txns in invoke order
    inv = h["txn_invoke_pos"].astype(np.int64)
    o = np.lexsort((inv, h["txn_process"]))
    same = h["txn_process"][o][1:] == h["txn_process"][o][:-1]
    parts.append((o[:-1][same], o[1:][same], PROCESS))
    # realtime: txn -> its completion barrier -> later barriers; the last
    # barrier completed before a txn's invocation -> that txn
    cmp_ = h["txn_complete_pos"].astype(np.int64)
    bo = np.argsort(cmp_, kind="stable")  # barrier rank -> txn
    parts.append((np.arange(T), T + np.argsort(bo), REALTIME))
    parts.append((T + np.arange(T - 1), T + np.arange(1, T), REALTIME))
    k = np.searchsorted(cmp_[bo], inv) - 1  # barriers completed before
    has = k >= 0
    parts.append((T + k[has], np.arange(T)[has], REALTIME))
    src = np.concatenate([p[0] for p in parts])
    dst = np.concatenate([p[1] for p in parts])
    rel = np.concatenate([np.full(len(p[0]), p[2], np.int8) for p in parts])
    keep = (src >= 0) & (dst >= 0) & (src != dst)
    return src[keep], dst[keep], rel[keep], 2 * T


def _scc(n, src, dst):
    g = csr_matrix((np.ones(len(src), np.int8), (src, dst)), shape=(n, n))
    return connected_components(g, directed=True, connection="strong")[1]


def _cycle_anomalies(src, dst, rel, n, rels_seen) -> list:
    lab = _scc(n, src, dst)
    sizes = np.bincount(lab, minlength=n)
    inner = (sizes[lab[src]] > 1) & (lab[src] == lab[dst])
    if not inner.any():
        return []
    # compact the non-trivial components
    s, d, rl = src[inner], dst[inner], rel[inner]
    nodes, inv = np.unique(np.concatenate([s, d]), return_inverse=True)
    s, d = inv[: len(s)], inv[len(s):]
    m = len(nodes)
    found = []
    for name, (rels, kind) in SPECS.items():
        if not rels <= rels_seen:
            continue
        sel = np.isin(rl, sorted(rels))
        if _has_cycle(m, s[sel], d[sel], rl[sel], kind):
            found.append(name)
    return found


def _reach(m, s, d, root):
    g = csr_matrix((np.ones(len(s), np.int8), (s, d)), shape=(m, m))
    seen = np.zeros(m, bool)
    seen[breadth_first_order(g, root, directed=True,
                             return_predecessors=False)] = True
    return seen


def _has_cycle(m, s, d, rl, kind) -> bool:
    if not len(s):
        return False
    lab = _scc(m, s, d)
    same = lab[s] == lab[d]
    if kind == "any":
        return bool(same.any())
    rw = np.nonzero((rl == RW) & same)[0]
    if kind == "some":
        return len(rw) > 0
    if kind == "single":
        nrw = rl != RW
        for e in rw.tolist():
            if _reach(m, s[nrw], d[nrw], int(d[e]))[int(s[e])]:
                return True
        return False
    return _nonadjacent(m, s[same], d[same], rl[same])


def _nonadjacent(m, s, d, rl) -> bool:
    """A simple cycle with >= 2 rw edges, no two of them adjacent
    (cyclically): exhaustive search from each rw edge, budgeted."""
    out = [[] for _ in range(m)]
    for a, b, r in zip(s.tolist(), d.tolist(), rl.tolist()):
        out[a].append((b, r == RW))
    budget = [CYCLE_BUDGET]
    for e in np.nonzero(rl == RW)[0].tolist():
        a, b = int(s[e]), int(d[e])
        # walk from b back to a; the first edge (a -> b) is rw
        stack = [(b, True, 1, iter(out[b]))]
        on = {a, b}
        while stack:
            node, prev_rw, n_rw, it = stack[-1]
            step = next(it, None)
            if step is None:
                stack.pop()
                on.discard(node)
                continue
            budget[0] -= 1
            if budget[0] < 0:
                raise Undecided("G-nonadjacent search over budget")
            nxt, is_rw = step
            if is_rw and prev_rw:
                continue
            if nxt == a:
                # the closing edge meets the rw edge out of a
                if not is_rw and n_rw >= 2:
                    return True
                continue
            if nxt in on:
                continue
            on.add(nxt)
            stack.append((nxt, is_rw, n_rw + is_rw, iter(out[nxt])))
    return False
