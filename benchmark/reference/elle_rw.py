"""Plain reference checker for Elle rw-register histories.

Written from Elle's definitions (Kingsbury & Alvaro, "Elle: Inferring
Isolation Anomalies from Experimental Observations", VLDB 2020, and
`elle.rw-register`), and from nothing of the program under test: it
reads the generator's columns (`gen/elle_rw.py`; `txn_ok` marks
committed txns, all of them when absent), imports numpy and scipy, and
shares the cycle search of the list-append reference (`SPECS`,
`_cycle_anomalies`), which imports nothing of the program either.

Writes are unique by contract; a value written twice is
`duplicate-writes`.  Anomalies of single txns, on committed readers:
`internal` (a read that disagrees with the txn's own earlier write or
read of the key), `G1a` (an external read of a value written by an
aborted txn), `G1b` (an external read of a value its writer overwrote
in the same txn), `lost-update` (two txns or more that externally read
one version of a key and then write that key).

Version order of a key, over committed writes: nil precedes every
written version; inside a txn, a read of u or a write of u followed by
the txn's next write of the key, v, gives u << v.  A cycle among
versions is `cyclic-versions`.  Txn dependencies among committed txns:
wr (writer of v -> each external reader of v), ww (writer of u ->
writer of v for each version edge with a real u), rw (each external
reader of u -> writer of v for each version edge u -> v, nil included).
Neither model of the configuration orders txns by process or realtime,
so those orders are left out.  The cycle anomalies searched are those
the list-append reference knows over ww, wr and rw; a model sees the
ones it proscribes.

Departures from Elle:

- Version order comes from the initial state and each txn's own order
  on a key alone; Elle can add further sources behind options
  (sequential or linearizable keys), none of which this configuration
  asks for.
- Intermediate writes are versions too: they follow nil and the txn's
  previous read or write of the key, so a G1b reader of one also gains
  an rw edge to the txn that overwrote it.
- Version cycles are searched across all keys at once: a strongly
  connected component of more than one version is `cyclic-versions`.
- A txn's dependencies on itself are dropped.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.elle_append import (
    RW,
    WR,
    WW,
    _cycle_anomalies,
    _scc,
)

READ, WRITE = 1, 0
#: the anomalies each model proscribes, from Elle's consistency-model
#: lattice (`elle.consistency-model`), closed over the models it implies
PROSCRIBED = {
    "snapshot-isolation": {
        "G0", "G1a", "G1b", "G1c", "G-single", "G-SI", "G-SIa", "G-SIb",
        "G-MSR", "G-monotonic", "aborted-read", "cyclic-versions",
        "dirty-update", "duplicate-elements", "duplicate-writes",
        "fractured-read", "incompatible-order", "intermediate-read",
        "internal", "lost-update", "monotonic-atomic-view-violation"},
    "read-committed": {
        "G0", "G1a", "G1b", "G1c", "aborted-read", "cyclic-versions",
        "dirty-update", "duplicate-elements", "duplicate-writes",
        "incompatible-order", "intermediate-read"},
}
#: reported whatever the model: the history breaks the generator's
#: contract or cannot be ordered
ALWAYS = {"duplicate-writes", "cyclic-versions"}


def check(h: dict, model: str = "snapshot-isolation") -> dict:
    """{"valid?": bool, "anomaly-types": sorted names} for history `h`."""
    if model not in PROSCRIBED:
        raise ValueError(f"no proscribed set for model {model!r}")
    found = set()
    T, V = len(h["txn_process"]), len(h["val_key"])
    ok = np.asarray(h.get("txn_ok", np.ones(T, bool)), bool)
    mt = h["mop_txn"].astype(np.int64)
    key = h["mop_key"].astype(np.int64)
    val = h["mop_val"].astype(np.int64)
    w = h["mop_kind"] == WRITE
    okm = ok[mt]
    # a read's value, nil of key k as V + k; a write's own value
    enc = np.where(val >= 0, val, V + key)

    # ---- writers: the committed one first where a value has two ----------
    wi = np.nonzero(w)[0]
    if len(np.unique(val[wi])) < len(wi):
        found.add("duplicate-writes")
    o = np.lexsort((wi, ~okm[wi], val[wi]))
    wv = val[wi][o]
    first = np.r_[True, wv[1:] != wv[:-1]][:len(wv)]
    writer = np.full(V, -1, np.int64)
    writer[wv[first]] = mt[wi][o][first]

    # ---- (txn, key) runs in micro-op order --------------------------------
    grp = mt * (int(h["n_keys"]) + 1) + key
    ro = np.argsort(grp, kind="stable")
    starts = np.r_[True, grp[ro][1:] != grp[ro][:-1]]
    run = np.empty(len(mt), np.int64)
    run[ro] = np.cumsum(starts) - 1
    prev = np.full(len(mt), -1, np.int64)  # the run's previous micro-op
    prev[ro[1:][~starts[1:]]] = ro[:-1][~starts[1:]]
    rd = ~w & okm
    ext = rd & (prev < 0)
    inner = np.nonzero(rd & (prev >= 0))[0]
    if (enc[inner] != enc[prev[inner]]).any():
        found.add("internal")

    # ---- G1a, G1b ----------------------------------------------------------
    er = np.nonzero(ext & (val >= 0))[0]
    wr_src = writer[val[er]]
    has = wr_src >= 0
    if (has & ~ok[np.maximum(wr_src, 0)]).any():
        found.add("G1a")
    # final[v]: a write of v is the last write of its run
    wo = ro[w[ro]]
    last = np.r_[run[wo][1:] != run[wo][:-1], True][:len(wo)]
    final = np.zeros(V, bool)
    final[val[wo[last]]] = True
    if (has & ~final[val[er]] & (wr_src != mt[er])).any():
        found.add("G1b")

    # ---- lost update ---------------------------------------------------------
    run_writes = np.bincount(run[w], minlength=int(run.max(initial=-1)) + 1)
    up = np.nonzero(ext & (run_writes[run] > 0))[0]
    u = np.unique(enc[up] * T + mt[up]) // T  # one row per (version, txn)
    if (u[1:] == u[:-1]).any():
        found.add("lost-update")

    # ---- version order -------------------------------------------------------
    cw = np.nonzero(w & okm)[0]
    p = prev[cw]
    vs = np.concatenate([V + key[cw], enc[p[p >= 0]]])
    vd = np.concatenate([val[cw], val[cw[p >= 0]]])
    n_ver = V + int(h["n_keys"])
    ve = np.unique(vs * n_ver + vd)
    vs, vd = ve // n_ver, ve % n_ver
    if len(vs):
        lab = _scc(n_ver, vs, vd)
        if ((lab[vs] == lab[vd]) & (vs != vd)).any():
            found.add("cyclic-versions")

    # ---- dependencies among committed txns ------------------------------------
    parts = [(wr_src, mt[er], WR)]
    real = vs < V
    parts.append((writer[vs[real]], writer[vd[real]], WW))
    xr = np.nonzero(ext)[0]
    ro2 = np.argsort(enc[xr], kind="stable")
    r_enc, r_txn = enc[xr][ro2], mt[xr][ro2]
    lo = np.searchsorted(r_enc, vs, side="left")
    cnt = np.searchsorted(r_enc, vs, side="right") - lo
    e = np.repeat(np.arange(len(vs)), cnt)
    off = np.arange(len(e)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    parts.append((r_txn[lo[e] + off], writer[vd[e]], RW))
    src = np.concatenate([s for s, _, _ in parts])
    dst = np.concatenate([d for _, d, _ in parts])
    rel = np.concatenate([np.full(len(s), r, np.int8) for s, _, r in parts])
    keep = (src >= 0) & (dst >= 0) & (src != dst)
    keep &= ok[np.maximum(src, 0)] & ok[np.maximum(dst, 0)]
    found.update(_cycle_anomalies(src[keep], dst[keep], rel[keep], T,
                                  {WW, WR, RW}))

    found &= PROSCRIBED[model] | ALWAYS
    return {"valid?": not found, "anomaly-types": sorted(found)}
