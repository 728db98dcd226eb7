"""Device-side rw-register inference + fused core check.

The TPU half of the `elle/rw_register.clj` equivalent (SURVEY.md §2.3,
§7 stage 5): version-graph inference, non-cycle anomaly scans, txn
dependency edges and the 5-projection cycle sweep, all under one
`jax.jit` over the padded SoA arrays — the rw-register analogue of
`device_core.core_check` (round-2 VERDICT item 3: inference was
host-numpy only; BASELINE config 3 is a 1M-op rw-register history).

The inference is an exact jnp port of the host checker's vectorized
numpy (`rw_register.py` — which remains the semantic oracle, and whose
verdicts the fused check is differentially tested against):

- writers: committed-priority scatter-min (ok > info > fail) so an
  aborted duplicate cannot fabricate a G1a;
- per-(txn, key) runs via one lexsort; txn-local state (cur-before),
  final writes and last-write positions from segmented scans;
- version edges u -> v (or init(k) -> v for blind writes); cyclic
  versions detected by a rank sweep over the version graph (value-id
  ranks: inference contradictions are the backward edges);
- txn edges: wr (reader of v <- writer(v)), ww (writer(u) -> writer(v)),
  rw (external readers of u -> writer(v)) — the reader x version-edge
  join is shape-static: prefix-sum offsets + expansion into a fixed
  `rw_cap` slot budget, both lookups read from counting tables (a
  histogram's prefix sum over the bounded integer keys), with exact
  overflow reporting (the device never silently truncates; callers
  regrow or fall back to the host).

Bit layout of the result: [duplicate-writes, internal, G1a, G1b,
lost-update, cyclic-versions, cycle-proj0..4, converged].
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.checkers.elle.device_core import (
    PROJECTIONS,
    chain_include_stack,
    proj_include_stack,
)
from jepsen_tpu.checkers.elle.device_infer import (
    PaddedLA,
    family_graph,
    pad_packed,
)
from jepsen_tpu.history.soa import (
    MOP_APPEND,
    MOP_READ,
    TXN_FAIL,
    TXN_INFO,
    TXN_OK,
    PackedTxns,
)
from jepsen_tpu.ops.cycle_sweep import (
    MAX_K_CAP,
    MAX_ROUNDS_CAP,
    FamilyGraph,
    projection_scan,
)
from jepsen_tpu.ops.segments import segmented_cummax, segmented_cumsum

BIG = jnp.int32(2 ** 30)
NO_PREV = jnp.int32(-3)

COUNT_NAMES_RW = ("duplicate-writes", "internal", "G1a", "G1b",
                  "lost-update", "cyclic-versions")


_BLOCK = 1024


def _cumsum(x):
    """`jnp.cumsum` of a 1-D int32 array, summed in rows of `_BLOCK`: the
    same values, but XLA:TPU emits the one-dimensional scan as code that
    grows with the length (for a v5e, 2.4 MB at 2^21 rows against about
    0.55 MB in rows), and a loaded program's code sits in device
    memory."""
    n = x.shape[0]
    rows = jnp.cumsum(jnp.pad(x, (0, -n % _BLOCK)).reshape(-1, _BLOCK),
                      axis=1)
    before = jnp.cumsum(rows[:, -1]) - rows[:, -1]
    return (rows + before[:, None]).reshape(-1)[:n]


def rw_join(r_val, r_txn, e_u, e_usable, e_dst, n_bins: int, cap: int):
    """The rw join, shape-static: every usable version edge i (from value
    `e_u[i]` to a write by txn `e_dst[i]`) paired with every reader of
    `e_u[i]` (`r_val` the value each of the M mops reads, `r_txn` its
    txn), expanded into `cap` slots, edge by edge in edge order.

    Readers are sorted by value and each edge's slots start at the prefix
    sum of the counts before it.  Both lookups are counting tables, not
    binary searches: every key is an integer in `[0, n_bins)` (unused
    readers and edges carry keys of their own above every value), so a
    search is a read of a histogram or its prefix sum.  Returns the
    join's tables (`lo`, `cnt`, `offsets`, `e_j`), the slots (`src`,
    `dst`, `valid`) and `overflow`, the pairs beyond `cap` that were not
    emitted (exact; 0 when all fit)."""
    M = e_u.shape[0]
    r_ord = jnp.argsort(r_val, stable=True)
    rv_sorted = r_val[r_ord]
    rt_sorted = r_txn[r_ord]
    # readers of each value; below[v] = #readers with value < v, which is
    # searchsorted(rv_sorted, v, "left") (tables padded to whole rows)
    n_rd = jnp.zeros(n_bins + -n_bins % _BLOCK, jnp.int32).at[
        rv_sorted].add(1, indices_are_sorted=True)
    below = _cumsum(n_rd) - n_rd
    lo = below[e_u]
    cnt = jnp.where(e_usable, n_rd[e_u], 0)
    offsets = _cumsum(cnt)
    total = offsets[-1]
    j = jnp.arange(cap, dtype=jnp.int32)
    # e_j = #offsets <= j = searchsorted(offsets, j, "right") for j < cap;
    # offsets past the table cannot count for any j < cap
    n_off = jnp.zeros(cap + -cap % _BLOCK, jnp.int32).at[offsets].add(
        1, indices_are_sorted=True, mode="drop")
    e_j = _cumsum(n_off)[:cap]
    e_jc = jnp.clip(e_j, 0, M - 1)
    prev_off = jnp.where(e_j > 0, offsets[jnp.clip(e_j - 1, 0, M - 1)], 0)
    off = j - prev_off
    valid = (j < total) & (e_j < M)
    reader_j = rt_sorted[jnp.clip(lo[e_jc] + off, 0, M - 1)]
    return {
        "lo": lo, "cnt": cnt, "offsets": offsets, "e_j": e_j,
        "src": jnp.where(valid, reader_j, -1),
        "dst": jnp.where(valid, e_dst[e_jc], -1),
        "valid": valid,
        "overflow": jnp.maximum(total - cap, 0),
    }


@partial(jax.jit, static_argnames=("n_keys", "rw_cap"))
def infer_rw(h: PaddedLA, n_keys: int, rw_cap: int = 0):
    """Inference over a padded rw-register history.  Returns a dict of
    counts, edges, chains, ranks (same shape contract as
    `device_infer.infer`) plus version-graph arrays and the rw-join
    overflow (edges beyond rw_cap that could NOT be emitted)."""
    T = h.txn_type.shape[0]
    M = h.mop_txn.shape[0]
    V = h.rd_elems.shape[0]  # value-id capacity (same convention as la)
    nk = max(n_keys, 1)
    VN = V + nk              # version nodes: values + one init per key
    CAP = rw_cap or M

    ttype = h.txn_type
    ok = ttype == TXN_OK
    graph_txn = ok | (ttype == TXN_INFO)

    kind = jnp.where(h.mop_mask, h.mop_kind, -1)
    mtxn = jnp.clip(h.mop_txn, 0, T - 1)
    is_w = h.mop_mask & (kind == MOP_APPEND) & (h.mop_val >= 0)
    is_r = h.mop_mask & (kind == MOP_READ)
    known = jnp.where(is_r, h.mop_rd_len >= 0, h.mop_mask)
    mop_pos = jnp.arange(M, dtype=jnp.int32)

    # ---- writers: committed-priority (ok=0 < info=1 < fail=2, then pos)
    wt = ttype[mtxn]
    prio = jnp.where(ok[mtxn], 0, jnp.where(wt == TXN_INFO, 1, 2))
    enc = prio.astype(jnp.int32) * M + mop_pos
    val_slot = jnp.where(is_w, h.mop_val, V)
    enc_min = jnp.full(V + 1, 3 * M + M, jnp.int32).at[val_slot].min(
        jnp.where(is_w, enc, 3 * M + M))[:V]
    have_writer = enc_min < 3 * M + M
    writer = jnp.where(have_writer, mtxn[jnp.clip(enc_min % M, 0, M - 1)],
                       -1)
    writer_type = jnp.where(writer >= 0,
                            ttype[jnp.clip(writer, 0, T - 1)], 0)
    w_count = jnp.zeros(V + 1, jnp.int32).at[val_slot].add(
        is_w.astype(jnp.int32))[:V]
    duplicate_writes = jnp.sum((w_count > 1).astype(jnp.int32))

    # ---- (txn, key) runs --------------------------------------------------
    run_sort = jnp.lexsort((mop_pos,
                            jnp.where(h.mop_mask, h.mop_key, nk),
                            jnp.where(h.mop_mask, h.mop_txn, T)))
    rt = mtxn[run_sort]
    rk = jnp.where(h.mop_mask, h.mop_key, nk)[run_sort]
    rkind = kind[run_sort]
    rval = h.mop_val[run_sort]
    rknown = known[run_sort]
    rmask = h.mop_mask[run_sort]
    t2 = jnp.where(rmask, rt, T)
    run_start = jnp.concatenate([jnp.ones(1, bool),
                                 (t2[1:] != t2[:-1]) | (rk[1:] != rk[:-1])])
    run_end = jnp.concatenate([run_start[1:], jnp.ones(1, bool)])
    q = jnp.arange(M, dtype=jnp.int32)

    # last write position within the run (suffix max = reversed cummax)
    wpos = jnp.where(rmask & (rkind == MOP_APPEND), q, -1)
    last_w = segmented_cummax(wpos[::-1], run_end[::-1])[::-1]

    # final write per value: the run's last write mop
    r_final = rmask & (rkind == MOP_APPEND) & (q == last_w)
    is_final = jnp.zeros(V + 1, bool).at[
        jnp.where(r_final, rval, V)].max(r_final)[:V]

    # txn-local state before each mop (cur-before): previous defining mop
    defines = rmask & ((rkind == MOP_APPEND) |
                       ((rkind == MOP_READ) & rknown))
    def_val = jnp.where(rkind == MOP_APPEND, rval,
                        jnp.where(rval >= 0, rval, V + rk)).astype(jnp.int32)
    def_pos = jnp.where(defines, q, -1)
    prev_def = segmented_cummax(def_pos, run_start, exclusive=True,
                                neutral=-1)
    cur_before = jnp.where(prev_def >= 0,
                           def_val[jnp.clip(prev_def, 0, M - 1)], NO_PREV)

    r_is_read = rmask & (rkind == MOP_READ) & rknown & ok[rt]
    external_read = r_is_read & (cur_before == NO_PREV)

    # ---- internal ---------------------------------------------------------
    internal_bad = r_is_read & (cur_before != NO_PREV) & \
        (def_val != cur_before)
    internal = jnp.sum(internal_bad.astype(jnp.int32))

    # ---- G1a / G1b on external reads of real values -----------------------
    ev = jnp.clip(def_val, 0, V - 1)
    ext_real = external_read & (def_val < V)
    has_w = ext_real & (writer[ev] >= 0)
    g1a = has_w & (writer_type[ev] == TXN_FAIL)
    g1a_count = jnp.sum(g1a.astype(jnp.int32))
    g1b = has_w & (~is_final[ev]) & (writer[ev] != rt)
    g1b_count = jnp.sum(g1b.astype(jnp.int32))

    # ---- version edges ----------------------------------------------------
    ve_ok = rmask & (rkind == MOP_APPEND) & (rval >= 0) & graph_txn[rt]
    ve_u = jnp.where(cur_before >= 0, cur_before, V + rk).astype(jnp.int32)
    ve_v = jnp.clip(rval, 0, V - 1).astype(jnp.int32)
    # version-node ranks: init(k) -> k (first), value v -> nk + v; edges
    # against value-id order are the backward edges of the version sweep
    rank_v = jnp.concatenate([
        nk + jnp.arange(V, dtype=jnp.int32),
        jnp.arange(nk, dtype=jnp.int32)])  # node V+k = init(k)

    # ---- lost update ------------------------------------------------------
    # external reads of u whose txn later writes the key; >= 2 distinct
    # txns per u is a lost update
    upd = external_read & (last_w > q)
    u_key = jnp.where(upd, def_val, VN + 1)
    u_txn = jnp.where(upd, rt, T)
    lo_ord = jnp.lexsort((u_txn, u_key))
    su = u_key[lo_ord]
    st = u_txn[lo_ord]
    s_valid = su < VN + 1
    uniq_pair = s_valid & jnp.concatenate(
        [jnp.ones(1, bool), (su[1:] != su[:-1]) | (st[1:] != st[:-1])])
    grp_start = jnp.concatenate([jnp.ones(1, bool), su[1:] != su[:-1]])
    grp_end = jnp.concatenate([grp_start[1:], jnp.ones(1, bool)])
    grp_cnt = segmented_cumsum(uniq_pair.astype(jnp.int32), grp_start)
    lost_update = jnp.sum((grp_end & s_valid &
                           (grp_cnt >= 2)).astype(jnp.int32))

    # ---- txn dependency edges --------------------------------------------
    def edge_mask(src, dst, base):
        return base & (src >= 0) & (dst >= 0) & (src != dst) & \
            graph_txn[jnp.clip(src, 0, T - 1)] & \
            graph_txn[jnp.clip(dst, 0, T - 1)]

    # wr: writer(v) -> external reader of v
    wr_src = jnp.where(ext_real, writer[ev], -1)
    wr_dst = rt
    wr_ok = edge_mask(wr_src, wr_dst, ext_real)

    # ww: writer(u) -> writer(v) over version edges with real u
    ww_u_real = ve_ok & (ve_u < V)
    ww_src = jnp.where(ww_u_real, writer[jnp.clip(ve_u, 0, V - 1)], -1)
    ww_dst = jnp.where(ve_ok, writer[ve_v], -1)
    ww_ok = edge_mask(ww_src, ww_dst, ww_u_real)

    # rw: external readers of u -> writer(v) per version edge (u, v)
    S_NOREAD = jnp.int32(VN + 2)
    S_NOEDGE = jnp.int32(VN + 3)
    rdv = jnp.where(external_read, def_val, S_NOREAD)
    e_wdst = jnp.where(ve_ok, writer[ve_v], -1)
    e_usable = ve_ok & (e_wdst >= 0) & graph_txn[jnp.clip(e_wdst, 0, T - 1)]
    e_u = jnp.where(e_usable, ve_u, S_NOEDGE)
    join = rw_join(rdv, rt, e_u, e_usable, e_wdst, VN + 4, CAP)
    rw_src, rw_dst = join["src"], join["dst"]
    rw_ok = edge_mask(rw_src, rw_dst, join["valid"])
    rw_overflow = join["overflow"]

    # ---- process chains + realtime barriers (same as la infer) ------------
    tidx = jnp.arange(T, dtype=jnp.int32)
    rank_txn = jnp.where(h.txn_mask, 2 * h.txn_complete_pos, BIG + tidx)
    pslot = jnp.where(h.txn_mask & graph_txn, h.txn_process, BIG)
    porder = jnp.lexsort((h.txn_invoke_pos, pslot))
    p_nodes = porder.astype(jnp.int32)
    p_sorted = pslot[porder]
    p_mask = p_sorted < BIG
    p_starts = jnp.concatenate([jnp.ones(1, bool),
                                p_sorted[1:] != p_sorted[:-1]])
    bslot = jnp.where(h.txn_mask & ok, h.txn_complete_pos, BIG)
    border = jnp.argsort(bslot)
    b_txn = border.astype(jnp.int32)
    b_mask = bslot[border] < BIG
    barrier_node = (T + tidx).astype(jnp.int32)
    rank_barrier = jnp.where(b_mask, 2 * bslot[border] + 1, BIG + T + tidx)
    b_starts = jnp.concatenate([jnp.ones(1, bool), jnp.zeros(T - 1, bool)])
    tb_src = b_txn
    tb_dst = barrier_node
    tb_ok = b_mask
    comp_sorted = jnp.where(b_mask, bslot[border], BIG)
    bi = jnp.searchsorted(comp_sorted, h.txn_invoke_pos, side="left") - 1
    bt_ok = h.txn_mask & graph_txn & (bi >= 0)
    bt_src = (T + jnp.clip(bi, 0, T - 1)).astype(jnp.int32)
    bt_dst = tidx

    return {
        "counts": {
            "duplicate-writes": duplicate_writes,
            "internal": internal,
            "G1a": g1a_count,
            "G1b": g1b_count,
            "lost-update": lost_update,
        },
        "edges": {
            "ww": (ww_src, ww_dst, ww_ok),
            "wr": (wr_src, wr_dst, wr_ok),
            "rw": (rw_src, rw_dst, rw_ok),
            "tb": (tb_src, tb_dst, tb_ok),
            "bt": (bt_src, bt_dst, bt_ok),
        },
        "chains": {
            "process": (p_nodes, p_starts, p_mask),
            "barrier": (barrier_node, b_starts, b_mask),
        },
        "ranks": {
            "txn": rank_txn.astype(jnp.int32),
            "barrier": rank_barrier.astype(jnp.int32),
        },
        "versions": {
            # node count is static (V + nk) — recomputed by callers, NOT
            # returned here (a jit output would turn it into a tracer)
            "src": jnp.where(ve_ok, ve_u, 0),
            "dst": jnp.where(ve_ok, ve_v, 0),
            "mask": ve_ok,
            "rank": rank_v,
        },
        "rw_overflow": rw_overflow,
    }


def _cc_call(h, n_keys, max_k, max_rounds, rw_cap):
    """The guarded dispatch body: rw_core_check through the AOT compile
    cache (memory table -> persisted executable -> compile+persist,
    plain jit on any failure — see jepsen_tpu.compilecache)."""
    from jepsen_tpu import compilecache

    return compilecache.call("elle.rw-core-check", rw_core_check, h,
                             n_keys=n_keys, max_k=max_k,
                             max_rounds=max_rounds, rw_cap=rw_cap)


@partial(jax.jit, static_argnames=("n_keys", "max_k", "max_rounds",
                                   "rw_cap"))
def rw_core_check(h: PaddedLA, n_keys: int, max_k: int = 128,
                  max_rounds: int = 64, rw_cap: int = 0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused device verdict for an rw-register history.

    Returns (bits, overflowed, rw_overflow):
    bits: (12,) int32 — [6 counts per COUNT_NAMES_RW, 5 projection cycle
    flags, converged]; overflowed: backward edges beyond max_k across all
    sweeps (grow and retry); rw_overflow: rw-join edges beyond rw_cap
    (grow rw_cap or fall back to the host checker)."""
    out = infer_rw(h, n_keys, rw_cap=rw_cap)
    conv_all, overflow, cyc_bits = projection_scan(
        family_graph(out), max_k, max_rounds,
        proj_include_stack(PROJECTIONS), chain_include_stack(PROJECTIONS))

    # cyclic versions: the version graph's one projection, all of it
    ver = out["versions"]
    vn_nodes = h.rd_elems.shape[0] + max(n_keys, 1)  # static: V + nk
    whole = jnp.ones((1, 1), jnp.int32)
    v_conv, v_over, v_bits = projection_scan(
        FamilyGraph.plain(vn_nodes, ver["rank"], ver["src"], ver["dst"],
                          ver["mask"]),
        max_k, max_rounds, whole, whole)
    conv_all = conv_all & v_conv
    overflow = jnp.maximum(overflow, v_over)

    counts = jnp.stack(
        [out["counts"][n].astype(jnp.int32) for n in COUNT_NAMES_RW[:-1]]
        + [v_bits[0]])
    bits = jnp.concatenate(
        [counts, cyc_bits, conv_all.astype(jnp.int32)[None]])
    return bits, overflow, out["rw_overflow"]


RW_CAP_LIMIT = 1 << 24


def check(p: PackedTxns | PaddedLA, n_keys: int = None, max_k: int = 128,
          max_rounds: int = 64, deadline=None, policy=None,
          plan=None) -> dict:
    """Fused device check of an rw-register history; summary dict in the
    `check_sharded` row format.  Grows the backward-edge and rw-join
    budgets on overflow (exactness first); returns "unknown" only when
    every budget is exhausted — callers then use the host checker.

    Resilience: the fused jit seam runs under the device guard
    (transient retries per `policy`, synthetic faults per `plan`);
    `deadline` is polled before each grow-retry and raises
    `DeadlineExceeded` on expiry — `rw_register.check` and `check_safe`
    map that to an unknown/degraded verdict."""
    from jepsen_tpu import resilience, telemetry

    # one phase span over the whole device check, the host pad and every
    # grow-retry included (inference and sweeps are fused in one jit
    # program, so inside it only the pad and each program run are spans)
    ph = telemetry.phases()
    ph.start("elle.rw-core-check", device=True)
    with telemetry.span("elle.pad") as sp:
        h = p if isinstance(p, PaddedLA) else pad_packed(p)
        sp.set_attr(T=h.txn_type.shape[0], M=h.mop_txn.shape[0],
                    V=h.rd_elems.shape[0])
    n_keys = h.n_keys if n_keys is None else n_keys
    rw_cap = h.mop_txn.shape[0]

    while True:
        if deadline is not None:
            deadline.check("elle.rw-core-check")
        # one span per program run, ending at the reads the loop makes
        with telemetry.span("rw.core-call", rw_cap=rw_cap, max_k=max_k,
                            max_rounds=max_rounds) as sp:
            bits, over, rw_over = resilience.device_call(
                "elle.rw-core-check",
                lambda: _cc_call(h, n_keys, max_k, max_rounds, rw_cap),
                policy=policy, deadline=deadline, plan=plan)
            over_i = int(np.asarray(over))
            rw_over_i = int(np.asarray(rw_over))
            conv = int(np.asarray(bits)[-1]) == 1
            retry = None
            if rw_over_i > 0 and rw_cap < RW_CAP_LIMIT:
                retry = "rw-cap"
                need = min(rw_cap + rw_over_i, RW_CAP_LIMIT)
                while rw_cap < need:
                    rw_cap *= 2
                rw_cap = min(rw_cap, RW_CAP_LIMIT)
            elif over_i > 0 and max_k < MAX_K_CAP:
                retry = "max-k"
                need = max_k + over_i
                while max_k < need:
                    max_k *= 2
                max_k = min(max_k, MAX_K_CAP)
            elif not conv and over_i == 0 and max_rounds < MAX_ROUNDS_CAP:
                retry = "rounds"
                max_rounds = min(max_rounds * 2, MAX_ROUNDS_CAP)
            # the budget this run's overflow regrows for the next run
            sp.set_attr(retry=retry)
        if retry is None:
            break

    ph.end()
    row = np.asarray(bits)
    nc = len(COUNT_NAMES_RW)
    counts = {n: int(row[i]) for i, n in enumerate(COUNT_NAMES_RW)}
    cycles = [bool(x) for x in row[nc:-1]]
    exact = bool(row[-1]) and over_i == 0 and rw_over_i == 0
    invalid = any(v > 0 for v in counts.values()) or any(cycles)
    return {
        "valid?": (not invalid) if exact else "unknown",
        "counts": counts,
        "cycles": {
            "G0": cycles[0], "G1c": cycles[1], "G2-family": cycles[2],
            "G2-family-process": cycles[3],
            "G2-family-realtime": cycles[4],
        },
        "exact": exact,
    }
