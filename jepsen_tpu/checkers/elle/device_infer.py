"""Device-side edge inference + anomaly scans for list-append histories.

The TPU half of the `elle/list_append.clj` equivalent: everything here runs
under one `jax.jit` over the SoA history arrays (SURVEY.md §7 stage 2a/2b).

Design notes (TPU-first, not a translation):
- The reference builds per-key version orders with per-key Clojure maps and
  unions bifurcan graphs.  Here every per-key computation is a flat
  *segment op* over arrays sorted by key — the vmap-over-keys equivalent
  that stays dense under Zipfian key skew (no ragged padding).  All scans
  are parallel (cumsum / cummax / associative_scan); nothing sequential.
- Version order per key = the longest ok-read of that key (reads must be
  prefix-compatible; violations are flagged, as in the reference).
- Dependency edges come out as fixed-capacity masked COO arrays, ready for
  the cycle sweep kernel:
    ww  — consecutive version writers  (capacity: read-element slots)
    wr  — final-version writer -> reader (capacity: mop slots)
    rw  — reader -> next-version writer  (capacity: mop slots)
  plus chain inputs: per-process order and the realtime barrier chain (the
  exact O(n)-edge transitive encoding of the realtime relation).
- Non-cycle anomaly scans (duplicate-elements/appends, incompatible-order,
  G1a, G1b, internal, dirty-update) are elementwise flags with counts and
  argmax witnesses.  `internal` is exact whenever reads are
  prefix-compatible; under incompatible-order the history is already
  invalid and both checkers report it.

All shapes static; padding is masked.  Pure function of its inputs — safe
to vmap / shard_map over a batch of histories.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.checkers.elle.graph import (
    REL_PROCESS,
    REL_REALTIME,
    REL_RW,
    REL_WR,
    REL_WW,
)
from jepsen_tpu.history.soa import (
    MOP_APPEND,
    MOP_READ,
    TXN_FAIL,
    TXN_INFO,
    TXN_OK,
    PackedTxns,
)
from jepsen_tpu.ops import pallas_fill
from jepsen_tpu.ops.cycle_sweep import FamilyGraph
from jepsen_tpu.ops.segments import (
    segment_ids_from_starts,
    segmented_cummax,
    segmented_cumsum,
)

BIG = jnp.int32(2 ** 30)
BIG_I = 2 ** 30  # host-side twin (the IR column derivation)

#: the inferred edge families, in the order `family_graph` concatenates
#: them, and the rel of each (tb/bt: the realtime edges into and out of
#: the barrier nodes)
FAMILIES = ("ww", "wr", "rw", "tb", "bt")
FAMILY_RELS = (REL_WW, REL_WR, REL_RW, REL_REALTIME, REL_REALTIME)
#: the chain groups, in `family_graph`'s order, and the rel of each
CHAINS = ("process", "barrier")
CHAIN_RELS = (REL_PROCESS, REL_REALTIME)


@dataclasses.dataclass
class PaddedLA:
    """Padded device inputs for a list-append history.

    T/M/R are padded capacities; *_mask mark real rows.  val ids < R.
    """

    txn_type: jnp.ndarray          # (T,) i8 (0 = padding)
    txn_process: jnp.ndarray       # (T,) i32
    txn_invoke_pos: jnp.ndarray    # (T,) i32
    txn_complete_pos: jnp.ndarray  # (T,) i32
    txn_mask: jnp.ndarray          # (T,) bool
    mop_txn: jnp.ndarray           # (M,) i32
    mop_kind: jnp.ndarray          # (M,) i8
    mop_key: jnp.ndarray           # (M,) i32
    mop_val: jnp.ndarray           # (M,) i32 (append value id or -1)
    mop_rd_start: jnp.ndarray      # (M,) i32
    mop_rd_len: jnp.ndarray        # (M,) i32 (-1 unknown)
    mop_mask: jnp.ndarray          # (M,) bool
    rd_elems: jnp.ndarray          # (R,) i32
    rd_elem_mask: jnp.ndarray      # (R,) bool
    n_keys: int                    # static
    n_vals: int                    # static
    # Static layout facts, host-verified at padding time (False/0 = unknown,
    # infer falls back to device sorts).  They hold by construction for
    # TxnPacker output; pad_packed re-checks so hand-built PackedTxns with
    # exotic layouts stay correct through the fallback.
    txn_major: bool = False        # static: mop_txn nondecreasing, valid
    #                                mops contiguous before the padding tail
    run_cap: int = 0               # static: pow2 bucket >= max mops/txn
    #                                (0 = unknown or > _RUN_CAP_MAX)
    complete_monotone: bool = False  # static: txn_complete_pos strictly
    #                                  increasing over valid txns
    # IR v2 capacity/layout facts (history/ir.py).  0/False = unknown:
    # infer falls back to the legacy R-sized tables / unsorted scatters.
    v_cap: int = 0                 # static: pow2 > max value id — the
    #                                value-table capacity (legacy: R)
    o_cap: int = 0                 # static: pow2 >= total version-order
    #                                slots (sum of per-key longest-read
    #                                lengths; legacy: R)
    app_val_mono: bool = False     # static: append mop val ids
    #                                nondecreasing in mop order
    rd_start_mono: bool = False    # static: rd_start strictly increasing
    #                                and in-bounds over has-elems reads
    proc_seq: bool = False         # static: within each process,
    #                                invoke_pos increases with txn row
    spmd: bool = False             # static: arrays placed over a multi-
    #                                device mesh (GSPMD); Mosaic kernels
    #                                cannot be auto-partitioned, so infer
    #                                takes the lax fills there
    # IR derived-order columns (history/ir.py, docs/IR.md): computed
    # ONCE host-side at pad time and reused by every check over the same
    # history — the in-program sorts/scatters they replace are the top
    # steady-state inference costs on scatter-hostile backends.  None =
    # derive in-program (legacy; exact either way, pinned by the IR
    # round-trip differentials).
    run_sort: Optional[jnp.ndarray] = None      # (M,) i32 (txn,key,pos) order
    inv_run: Optional[jnp.ndarray] = None       # (M,) i32 its inverse
    key_ord_len: Optional[jnp.ndarray] = None   # (K,) i32 longest known read
    key_ord_read: Optional[jnp.ndarray] = None  # (K,) i32 its mop (-1 none)
    proc_order: Optional[jnp.ndarray] = None    # (T,) i32 (process, invoke)
    barrier_order: Optional[jnp.ndarray] = None  # (T,) i32 ok-completion
    barrier_bi: Optional[jnp.ndarray] = None    # (T,) i32 barrier index
    #                                             before each invoke (-1)


jax.tree_util.register_dataclass(
    PaddedLA,
    data_fields=["txn_type", "txn_process", "txn_invoke_pos",
                 "txn_complete_pos", "txn_mask", "mop_txn", "mop_kind",
                 "mop_key", "mop_val", "mop_rd_start", "mop_rd_len",
                 "mop_mask", "rd_elems", "rd_elem_mask", "run_sort",
                 "inv_run", "key_ord_len", "key_ord_read", "proc_order",
                 "barrier_order", "barrier_bi"],
    meta_fields=["n_keys", "n_vals", "txn_major", "run_cap",
                 "complete_monotone", "v_cap", "o_cap", "app_val_mono",
                 "rd_start_mono", "proc_seq", "spmd"],
)

# Above this many mops in one txn the shifted-compare ranking (2*(cap-1)
# M-sized passes) stops beating the O(M log^2 M) bitonic sort it replaces.
_RUN_CAP_MAX = 32


def pow2_at_least(n: int, floor: int = 8) -> int:
    x = floor
    while x < n:
        x *= 2
    return x


def run_cap_of(longest: int) -> int:
    """Pow2 bucket for the longest per-txn mop run; 0 = too long, use the
    device-sort fallback.  Single definition so the pad_packed and
    streamed-staging paths can't drift apart on compile-cache keys."""
    return pow2_at_least(max(longest, 1), floor=1) \
        if longest <= _RUN_CAP_MAX else 0


def _layout_facts(p: PackedTxns) -> tuple[bool, int, bool]:
    """Host-verify the packing-layout invariants that let `infer` skip
    device sorts (cheap numpy scans; ~ms at 1M txns)."""
    txn_major = bool(
        p.n_mops == 0
        or (np.all(np.diff(p.mop_txn) >= 0)
            and p.mop_txn[0] >= 0 and p.mop_txn[-1] < p.n_txns))
    run_cap = 0
    if txn_major:
        longest = int(np.bincount(
            p.mop_txn, minlength=max(p.n_txns, 1)).max()) if p.n_mops \
            else 1
        run_cap = run_cap_of(longest)
    complete_monotone = bool(np.all(np.diff(p.txn_complete_pos) > 0)) \
        if p.n_txns > 1 else True
    return txn_major, run_cap, complete_monotone


def _ir_facts(p: PackedTxns) -> dict:
    """Host-verify the IR v2 capacity/layout facts (cheap numpy; ~50 ms
    at 1M txns).  Every fact degrades to the legacy path when False/0,
    so exotic hand-built histories stay exact.

    The capacities are the big lever on this class of backend: the
    legacy layout sized the value table and the version-order table at R
    (the read-element capacity, 2^24 at 1M txns) when the data needs
    2^22 — and XLA:CPU scatters cost per *update*, so the order-table
    passes were 4x oversized (ISSUE 12).

    NOT memoized on the instance: hand-built tests (and shrink probes)
    mutate PackedTxns arrays in place and re-pad — a cache would serve
    stale facts for a different history.  Batch paths avoid the double
    computation by passing `batch_caps`'s facts into `pad_packed`
    explicitly (`ir_facts=`)."""
    nk = max(p.n_keys, 1)
    kind = p.mop_kind
    # ---- v_cap: one past the max value id anywhere ----------------------
    mx = p.n_vals - 1
    if p.n_mops:
        mx = max(mx, int(p.mop_val.max()))
    if len(p.rd_elems):
        mx = max(mx, int(p.rd_elems.max()))
    v_cap = pow2_at_least(mx + 1, floor=8)
    # ---- o_cap: sum of per-key longest known-read lengths ---------------
    # only when every real mop key is in range: the program's scatter
    # semantics for out-of-range keys (wrap/drop) are not worth
    # emulating host-side — fall back to the legacy R-sized table
    o_cap = 0
    keys_ok = p.n_mops == 0 or (
        int(p.mop_key.min()) >= 0 and int(p.mop_key.max()) < nk)
    if keys_ok:
        rd = (kind == MOP_READ) & (p.mop_rd_len >= 0)
        total = 0
        if rd.any():
            mk = np.zeros(nk, np.int64)
            np.maximum.at(mk, p.mop_key[rd], p.mop_rd_len[rd])
            total = int(mk.sum())
        o_cap = pow2_at_least(max(total, 1), floor=8)
    # ---- append-val monotonicity ----------------------------------------
    app = (kind == MOP_APPEND) & (p.mop_val >= 0)
    app_val_mono = bool(np.all(np.diff(p.mop_val[app]) >= 0)) \
        if app.any() else True
    # ---- read-element allocation monotonicity ---------------------------
    he = (kind == MOP_READ) & (p.mop_rd_len > 0)
    if he.any():
        hs = p.mop_rd_start[he]
        rd_start_mono = bool(
            hs[0] >= 0 and np.all(np.diff(hs) > 0)
            and int(hs[-1] + p.mop_rd_len[he][-1]) <= len(p.rd_elems))
    else:
        rd_start_mono = True
    # ---- per-process invoke order == row order --------------------------
    if p.n_txns > 1:
        order = np.argsort(p.txn_process, kind="stable")
        inv_s = p.txn_invoke_pos[order]
        same = p.txn_process[order][1:] == p.txn_process[order][:-1]
        proc_seq = bool(np.all(inv_s[1:][same] > inv_s[:-1][same]))
    else:
        proc_seq = True
    return {"v_cap": v_cap, "o_cap": o_cap, "app_val_mono": app_val_mono,
            "rd_start_mono": rd_start_mono, "proc_seq": proc_seq}


def _ir_columns(p: PackedTxns, T: int, M: int, txn_major: bool,
                run_cap: int) -> Optional[dict]:
    """Host-derive the IR order columns over the PADDED index spaces,
    bit-for-bit replicating the orders `infer` would compute in-program
    (same sentinel placement, same stable tie-breaks).  Returns None
    when the packing is too exotic to replicate safely (ids out of
    range) — infer then derives everything in-program, exactly as
    before."""
    n, m = p.n_txns, p.n_mops
    nk = max(p.n_keys, 1)
    if m and (int(p.mop_txn.min()) < 0 or int(p.mop_txn.max()) >= max(n, 1)
              or int(p.mop_key.min()) < 0 or int(p.mop_key.max()) >= nk):
        return None

    # ---- (txn, key, pos) run permutation --------------------------------
    # padded tail carries the same (T, nk) sentinels the device sort
    # keys use, so it lands after every valid row in position order
    if txn_major and run_cap:
        # within-txn counting by shifted compares (the device fast
        # path's exact host twin) — ~10x cheaper than a full lexsort
        te = p.mop_txn.astype(np.int64)
        ke = p.mop_key.astype(np.int64)
        rank = np.zeros(m, np.int64)
        for d in range(1, run_cap):
            same = te[d:] == te[:-d]
            rank[d:] += same & (ke[:-d] <= ke[d:])
            rank[:-d] += same & (ke[d:] < ke[:-d])
        first_mop = np.searchsorted(te, np.arange(n, dtype=np.int64))
        inv_v = first_mop[te] + rank
    else:
        inv_v = np.empty(m, np.int64)
        inv_v[np.lexsort((np.arange(m), p.mop_key.astype(np.int64),
                          p.mop_txn.astype(np.int64)))] = np.arange(m)
    inv_run = np.concatenate([inv_v, np.arange(m, M)]).astype(np.int32)
    run_sort = np.zeros(M, np.int32)
    run_sort[inv_run] = np.arange(M, dtype=np.int32)

    # ---- per-key longest known read -------------------------------------
    ok = p.txn_type == TXN_OK
    K = pow2_at_least(nk, floor=8)
    kl = np.zeros(K, np.int64)
    kr_read = np.full(K, M, np.int64)
    if m:
        kr = (p.mop_kind == MOP_READ) & (p.mop_rd_len >= 0) & ok[p.mop_txn]
        np.maximum.at(kl, p.mop_key[kr], p.mop_rd_len[kr])
        longest = kr & (p.mop_rd_len == kl[p.mop_key])
        np.minimum.at(kr_read, p.mop_key[longest],
                      np.nonzero(longest)[0])
    key_ord_read = np.where(kr_read < M, kr_read, -1).astype(np.int32)

    # ---- process / realtime orders --------------------------------------
    graph = ok | (p.txn_type == TXN_INFO)
    pslot = np.full(T, BIG_I, np.int64)
    pslot[:n] = np.where(graph, p.txn_process, BIG_I)
    inv_pad = np.zeros(T, np.int64)
    inv_pad[:n] = p.txn_invoke_pos
    proc_order = np.lexsort((np.arange(T), inv_pad, pslot)).astype(np.int32)
    bslot = np.full(T, BIG_I, np.int64)
    bslot[:n] = np.where(ok, p.txn_complete_pos, BIG_I)
    border = np.argsort(bslot, kind="stable").astype(np.int32)
    comp_sorted = np.where(bslot[border] < BIG_I, bslot[border], BIG_I)
    bi = (np.searchsorted(comp_sorted, inv_pad, side="left") - 1) \
        .astype(np.int32)
    return {
        "run_sort": run_sort, "inv_run": inv_run,
        "key_ord_len": kl.astype(np.int32), "key_ord_read": key_ord_read,
        "proc_order": proc_order, "barrier_order": border,
        "barrier_bi": bi,
    }


def pad_packed(p: PackedTxns, t_pad: int = 0, m_pad: int = 0,
               r_pad: int = 0, v_pad: int = 0, o_pad: int = 0,
               ir_facts: Optional[dict] = None) -> PaddedLA:
    """Pad a PackedTxns to pow2 capacities (host-side, cheap numpy).

    `v_pad`/`o_pad` pin the value-table / order-table capacities (batch
    paths share one executable across groups); 0 = derive from the data
    (`_ir_facts`).  `ir_facts` (a dict `_ir_facts(p)` produced for THIS
    packing) skips re-deriving the facts — batch paths computed them in
    `batch_caps` already."""
    T = t_pad or pow2_at_least(p.n_txns)
    M = m_pad or pow2_at_least(p.n_mops)
    R = r_pad or pow2_at_least(max(len(p.rd_elems), p.n_vals, p.n_keys + 1))
    txn_major, run_cap, complete_monotone = _layout_facts(p)
    ir = dict(ir_facts) if ir_facts is not None else _ir_facts(p)
    if v_pad:
        ir["v_cap"] = v_pad
    if o_pad:
        ir["o_cap"] = o_pad
    # capacities never exceed R (the legacy sizing): a degenerate history
    # whose id space outruns its element table keeps the old layout
    ir["v_cap"] = min(ir["v_cap"], R) if ir["v_cap"] else 0
    ir["o_cap"] = min(ir["o_cap"], R) if ir["o_cap"] else 0
    cols = _ir_columns(p, T, M, txn_major, run_cap)
    if cols is not None:
        ir.update({k: jnp.asarray(v) for k, v in cols.items()})

    def pad(a, n, fill=0):
        out = np.full(n, fill, dtype=a.dtype)
        out[: len(a)] = a
        return jnp.asarray(out)

    return PaddedLA(
        txn_type=pad(p.txn_type, T),
        txn_process=pad(p.txn_process, T),
        txn_invoke_pos=pad(p.txn_invoke_pos, T),
        txn_complete_pos=pad(p.txn_complete_pos, T),
        txn_mask=jnp.asarray(np.arange(T) < p.n_txns),
        mop_txn=pad(p.mop_txn, M),
        mop_kind=pad(p.mop_kind, M, fill=-1),
        mop_key=pad(p.mop_key, M),
        mop_val=pad(p.mop_val, M, fill=-1),
        mop_rd_start=pad(p.mop_rd_start, M, fill=-1),
        mop_rd_len=pad(p.mop_rd_len, M, fill=-1),
        mop_mask=jnp.asarray(np.arange(M) < p.n_mops),
        rd_elems=pad(p.rd_elems, R, fill=-1),
        rd_elem_mask=jnp.asarray(np.arange(R) < len(p.rd_elems)),
        n_keys=p.n_keys,
        n_vals=p.n_vals,
        txn_major=txn_major,
        run_cap=run_cap,
        complete_monotone=complete_monotone,
        **ir,
    )


@partial(jax.jit, static_argnames=("n_keys",))
def infer(h: PaddedLA, n_keys: int) -> Dict[str, dict]:
    """Full inference: anomaly flags + dependency edges + chains + ranks."""
    use_fill = pallas_fill.fill_enabled() and not h.spmd
    T = h.txn_type.shape[0]
    M = h.mop_txn.shape[0]
    R = h.rd_elems.shape[0]
    # value-id / version-order-table capacities: the host-verified IR
    # facts size these at pow2(actual need) — 4x under R at 1M bench
    # shapes, and XLA:CPU scatters cost per update (0 = legacy layout)
    V = h.v_cap or R
    O = h.o_cap or R
    nk = max(n_keys, 1)

    ok = h.txn_type == TXN_OK
    graph_txn = ok | (h.txn_type == TXN_INFO)  # fail txns carry no edges

    is_append = h.mop_mask & (h.mop_kind == MOP_APPEND) & (h.mop_val >= 0)
    is_read = h.mop_mask & (h.mop_kind == MOP_READ)
    mop_txn_c = jnp.clip(h.mop_txn, 0, T - 1)
    reader_ok = ok[mop_txn_c]
    known_read = is_read & (h.mop_rd_len >= 0) & reader_ok
    mop_pos = jnp.arange(M, dtype=jnp.int32)

    # ---- writers ---------------------------------------------------------
    if h.app_val_mono:
        # append val ids are nondecreasing in mop order (host-verified):
        # forward-fill gives a globally nondecreasing index vector whose
        # non-append rows carry a no-op payload, unlocking XLA's
        # sorted-scatter path (~3.5x the unsorted one on this CPU)
        w_idx = jnp.clip(
            jax.lax.cummax(jnp.where(is_append, h.mop_val, -1)), 0, V)
        writer = jnp.full(V + 1, -1, jnp.int32).at[w_idx].max(
            jnp.where(is_append, h.mop_txn, -1),
            indices_are_sorted=True)[:V]
        app_count = jnp.zeros(V + 1, jnp.int32).at[w_idx].add(
            is_append.astype(jnp.int32), indices_are_sorted=True)[:V]
    else:
        val_slot = jnp.where(is_append, h.mop_val, V)
        writer = jnp.full(V + 1, -1, jnp.int32).at[val_slot].max(
            jnp.where(is_append, h.mop_txn, -1))[:V]
        app_count = jnp.zeros(V + 1, jnp.int32).at[val_slot].add(
            is_append.astype(jnp.int32))[:V]
    writer_type = jnp.where(
        writer >= 0, h.txn_type[jnp.clip(writer, 0, T - 1)], 0)
    duplicate_appends = jnp.sum((app_count > 1).astype(jnp.int32))

    # ---- (txn, key, pos) run order ---------------------------------------
    # shared by final-append detection and the internal-consistency pass
    # (historically two separate M-sized lexsorts; M-sorts are a top
    # cost).  Two sort keys, not three: a STABLE sort breaks (txn, key)
    # ties in operand order, which is already mop position — and the
    # sorted iota payload IS the permutation.
    txn_eff = jnp.where(h.mop_mask, h.mop_txn, T)
    key_eff = jnp.where(h.mop_mask, h.mop_key, nk)
    if h.run_sort is not None:
        # IR columns (pad-time host derivation, docs/IR.md): the
        # permutation arrives as input — no in-program ranking or
        # inverse-permutation scatter at all
        run_sort = h.run_sort
        inv_run = h.inv_run
        t2 = txn_eff[run_sort]
        k2 = key_eff[run_sort]
    elif h.txn_major and h.run_cap:
        # Sort-free: mops are packed txn-major (host-verified static
        # flag), so the global (txn, key, pos) order decomposes into a
        # within-txn ranking by (key, pos) over runs of <= run_cap mops.
        # rank(i) = |{j in txn(i): (key_j, j) < (key_i, i)}| via
        # 2*(run_cap-1) shifted compares — O(M * run_cap) elementwise
        # work instead of an O(M log^2 M) device bitonic sort (the top
        # TPU inference cost at 1M shapes, PROFILE.md §2d).  Exactness:
        # stability matches lax.sort (earlier pos wins key ties: the
        # backward compare uses <=, the forward one <), and the padding
        # tail maps to itself, exactly where the masked sort keys
        # (T, nk) would stably place it.
        rank = jnp.zeros(M, jnp.int32)
        for d in range(1, h.run_cap):
            same_p = txn_eff[d:] == txn_eff[:-d]
            zpad = jnp.zeros(d, bool)
            le_p = key_eff[:-d] <= key_eff[d:]
            lt_n = key_eff[d:] < key_eff[:-d]
            rank += jnp.concatenate([zpad, same_p & le_p]).astype(jnp.int32) \
                + jnp.concatenate([same_p & lt_n, zpad]).astype(jnp.int32)
        # txn_major: mop_txn is nondecreasing with the padding tail at T,
        # so the scatter indices are sorted — tell XLA
        first_mop = jnp.full(T + 1, M, jnp.int32).at[
            jnp.where(h.mop_mask, mop_txn_c, T)].min(
            jnp.where(h.mop_mask, mop_pos, M), indices_are_sorted=True)[:T]
        inv_run = jnp.where(h.mop_mask, first_mop[mop_txn_c] + rank,
                            mop_pos)
        run_sort = jnp.zeros(M, jnp.int32).at[inv_run].set(mop_pos)
        t2 = txn_eff[run_sort]
        k2 = key_eff[run_sort]
    else:
        t2, k2, run_sort = jax.lax.sort(
            (txn_eff, key_eff, mop_pos), num_keys=2, is_stable=True)
        inv_run = jnp.zeros(M, jnp.int32).at[run_sort].set(mop_pos)
    app2 = is_append[run_sort]
    known2 = known_read[run_sort]
    len2 = h.mop_rd_len[run_sort]
    val2 = h.mop_val[run_sort]
    run_start = jnp.concatenate([jnp.ones(1, bool),
                                 (t2[1:] != t2[:-1]) | (k2[1:] != k2[:-1])])
    run_end = jnp.concatenate([run_start[1:], jnp.ones(1, bool)])
    q = jnp.arange(M, dtype=jnp.int32)

    # final vs intermediate appends: an append is final iff it is the last
    # append of its (txn, key) run — i.e. its run's exclusive suffix holds
    # no append.  Reverse segmented cummax of append positions (scan the
    # reversed axis; segment starts there are the reversed run ends).
    suf_app_q = segmented_cummax(
        jnp.where(app2, q, -1)[::-1], run_end[::-1],
        exclusive=True, neutral=-1)[::-1]
    run_final = app2 & (suf_app_q < 0)
    if h.app_val_mono:
        # scatter in mop order through the same sorted index vector the
        # writer table uses (run_final gathered back via inv_run)
        is_final = jnp.zeros(V + 1, bool).at[w_idx].max(
            is_append & run_final[inv_run], indices_are_sorted=True)[:V]
    else:
        is_final = jnp.zeros(V + 1, bool).at[
            jnp.where(app2, val2, V)].max(run_final)[:V]

    # ---- version orders (longest known read per key) ---------------------
    if h.key_ord_len is not None and h.key_ord_len.shape[0] >= nk:
        # IR columns: per-key longest-read table precomputed at pad time
        ord_len = h.key_ord_len[:nk]
        ord_read = h.key_ord_read[:nk]
    else:
        key_slot = jnp.where(known_read, h.mop_key, nk)
        ord_len = jnp.zeros(nk + 1, jnp.int32).at[key_slot].max(
            jnp.where(known_read, h.mop_rd_len, 0))[:nk]
        # pick one longest read per key (two-pass scatter; no 64-bit
        # packing); ties take the earliest read, matching the host oracle
        is_longest = known_read & (h.mop_rd_len == ord_len[
            jnp.clip(h.mop_key, 0, nk - 1)])
        ord_read_raw = jnp.full(nk + 1, M, jnp.int32).at[
            jnp.where(is_longest, h.mop_key, nk)].min(
            jnp.where(is_longest, mop_pos, M))[:nk]
        ord_read = jnp.where(ord_read_raw < M, ord_read_raw, -1)
    ord_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(ord_len)[:-1].astype(jnp.int32)])
    total_ord = jnp.sum(ord_len)

    # materialize ord_elems: slot j belongs to key k(j) at offset o(j).
    # slot_key = max key whose segment start <= slot (starts are monotone;
    # zero-length keys share a start and the scatter-max picks the last,
    # which is the containing one) — a scatter + cummax forward fill, an
    # O(O) replacement for the former O(O log nk) searchsorted.  The
    # whole table lives in the O-capacity space (sum of per-key longest
    # reads), not R: at 1M bench shapes that is 2^22 vs 2^24.
    slot = jnp.arange(O, dtype=jnp.int32)
    slot_valid = slot < total_ord
    if nk == 1:
        # single key: every slot is key 0.  Also dodges a real compile
        # cost: with nk == 1 the scatter seed below is compile-time
        # constant (ord_start == [0], key_ids == [0]) and XLA:CPU
        # constant-folds the cummax's R-sized reduce-window tree
        # interpretively — measured 1-18 s of compile per shape.
        slot_key = jnp.zeros(O, jnp.int32)
        slot_off = slot
        src_read0 = ord_read[0]
        src_start = jnp.where(
            src_read0 >= 0,
            h.mop_rd_start[jnp.clip(src_read0, 0, M - 1)], 0)
    elif use_fill:
        # TPU: the three slot_key-indexed expansions (slot_key itself,
        # ord_start[slot_key], rd_start[ord_read[slot_key]]) are
        # monotone/segment-constant fills — seed per-key values at the
        # segment starts and forward-fill with the single-pass Pallas
        # LOCF kernel instead of R-sized gathers (measured ~0.45 s per
        # gather at R = 2^24 on chip).  slot_key's seed is scatter-MAX
        # over possibly-shared starts (zero-length keys) exactly as the
        # lax path, and its seeded values are non-decreasing, so LOCF
        # is bitwise cummax.  The value channels seed only n_elems > 0
        # keys (unique starts): every valid slot's containing key has
        # elements, and invalid slots are masked by slot_valid.
        key_ids = jnp.arange(nk, dtype=jnp.int32)
        sk_seed = jnp.full(O + 1, -1, jnp.int32).at[
            jnp.clip(ord_start, 0, O)].max(
            key_ids, indices_are_sorted=True)[:O]
        slot_key = jnp.clip(pallas_fill.locf_flat(sk_seed), 0, nk - 1)
        nonempty = ord_len > 0
        pos_ne = jnp.where(nonempty, ord_start, O)
        osv_seed = jnp.full(O + 1, -1, jnp.int32).at[
            jnp.clip(pos_ne, 0, O)].max(
            jnp.where(nonempty, ord_start, -1))[:O]
        # per-key rd_start of the chosen longest read (ord_len > 0
        # implies ord_read >= 0)
        srcst_k = h.mop_rd_start[jnp.clip(ord_read, 0, M - 1)]
        srcst_seed = jnp.full(O + 1, -1, jnp.int32).at[
            jnp.clip(pos_ne, 0, O)].max(
            jnp.where(nonempty, srcst_k, -1))[:O]
        ord_start_f = pallas_fill.locf_flat(osv_seed)
        src_start = pallas_fill.locf_flat(srcst_seed)
        slot_off = slot - jnp.where(ord_start_f >= 0, ord_start_f, 0)
        src_start = jnp.where(src_start >= 0, src_start, 0)
    else:
        key_ids = jnp.arange(nk, dtype=jnp.int32)
        # ord_start is a cumsum, so the seed indices are sorted by
        # construction — no layout fact needed
        sk_seed = jnp.full(O + 1, -1, jnp.int32).at[
            jnp.clip(ord_start, 0, O)].max(
            key_ids, indices_are_sorted=True)[:O]
        slot_key = jnp.clip(jax.lax.cummax(sk_seed), 0, nk - 1)
        slot_off = slot - ord_start[slot_key]
        src_read = ord_read[slot_key]
        src_start = jnp.where(src_read >= 0,
                              h.mop_rd_start[jnp.clip(src_read, 0, M - 1)], 0)
    ord_elems = jnp.where(
        slot_valid, h.rd_elems[jnp.clip(src_start + slot_off, 0, R - 1)], -1)
    cv = jnp.clip(ord_elems, 0, V - 1)

    # ---- read-element table ----------------------------------------------
    # elem -> owning read mop: scatter read ids at their start slots, then
    # forward-fill with a parallel cummax (read extents are contiguous and
    # allocated in mop order, so ids are increasing)
    has_elems = known_read & (h.mop_rd_len > 0)
    if h.rd_start_mono:
        # rd_start strictly increases over has-elems reads (host-verified
        # allocation-order fact): forward-fill the masked rows onto the
        # previous read's start (whose payload then loses the max) so
        # the scatter indices are sorted
        seed = jnp.full(R + 1, -1, jnp.int32).at[
            jnp.clip(jax.lax.cummax(
                jnp.where(has_elems, h.mop_rd_start, -1)), 0, R)].max(
            jnp.where(has_elems, mop_pos, -1),
            indices_are_sorted=True)[:R]
    else:
        seed = jnp.full(R + 1, -1, jnp.int32).at[
            jnp.where(has_elems, h.mop_rd_start, R)].max(
            jnp.where(has_elems, mop_pos, -1))[:R]

    def _aseed(vals):
        # value channel seeded at the same (unique) read-start slots
        return jnp.full(R + 1, -1, jnp.int32).at[
            jnp.where(has_elems, h.mop_rd_start, R)].max(
            jnp.where(has_elems, vals.astype(jnp.int32), -1))[:R]

    if use_fill:
        # TPU: forward-fill the owning-read id AND the four per-read
        # table values in one Pallas pass each, replacing lax.cummax
        # plus four R-sized `table[er]` gathers (~0.45 s each at
        # R = 2^24 on chip, PROFILE.md round-5 trace).  elem_read is
        # bitwise cummax (monotone seeds); the value channels replicate
        # the legacy `table[clip(er, 0, M-1)]` exactly, including
        # table[0] on the leading er == -1 prefix.
        elem_read = pallas_fill.locf_flat(seed)
        hole = elem_read < 0
        erd_start = jnp.where(hole, h.mop_rd_start[0],
                              pallas_fill.locf_flat(_aseed(h.mop_rd_start)))
        erd_len = jnp.where(hole, h.mop_rd_len[0],
                            pallas_fill.locf_flat(_aseed(h.mop_rd_len)))
        elem_key = jnp.where(hole, h.mop_key[0],
                             pallas_fill.locf_flat(_aseed(h.mop_key)))
        elem_txn = jnp.where(hole, h.mop_txn[0],
                             pallas_fill.locf_flat(_aseed(h.mop_txn)))
        er = jnp.clip(elem_read, 0, M - 1)
    else:
        elem_read = jax.lax.cummax(seed)
        er = jnp.clip(elem_read, 0, M - 1)
        erd_start = h.mop_rd_start[er]
        erd_len = h.mop_rd_len[er]
        elem_key = h.mop_key[er]
        elem_txn = h.mop_txn[er]
    elem_off = jnp.arange(R, dtype=jnp.int32) - erd_start
    elem_in_read = h.rd_elem_mask & (elem_read >= 0) & (elem_off >= 0) & \
        (elem_off < erd_len)
    ev = jnp.clip(h.rd_elems, 0, V - 1)

    # incompatible-order: element disagrees with its key's version order
    expect = ord_elems[jnp.clip(
        ord_start[jnp.clip(elem_key, 0, nk - 1)] + elem_off, 0, O - 1)]
    incompat = elem_in_read & (h.rd_elems != expect)
    incompatible_order = jnp.sum(incompat.astype(jnp.int32))
    incompat_witness = jnp.argmax(incompat)

    # G1a: reading a failed txn's append
    g1a = elem_in_read & (writer_type[ev] == TXN_FAIL)
    g1a_count = jnp.sum(g1a.astype(jnp.int32))
    g1a_witness = jnp.argmax(g1a)

    # duplicate elements inside one read.  Fast path: value ids are
    # key-scoped (interned per (key, content)), so duplicates in the
    # version ORDERS are one scatter-add over the order table; and when
    # every read element agrees with its key's order
    # (incompatible_order == 0), a read holds a duplicate iff its key's
    # order does (reads are elementwise prefixes of the orders).  Only a
    # disagreeing — already-invalid — history can hide a read-dup from
    # the orders, and only then does the exact per-read R-sized sort run
    # (that sort is ~70% of inference runtime at 1M: PROFILE.md §2d).
    # Caveats: (a) under vmap (the batched checking paths) lax.cond
    # lowers to select_n and BOTH branches run — batched checks keep
    # paying the sort, as before this change, plus the cheap scatter;
    # (b) the reported COUNT is per-order multiplicity on the fast path
    # and per-read adjacent pairs on the slow one — presence (> 0) is
    # the exactness contract, matched against the oracle either way.
    ord_cnt = jnp.zeros(V + 1, jnp.int32).at[
        jnp.where(slot_valid, cv, V)].add(1)[:V]
    dup_fast = jnp.sum(jnp.maximum(ord_cnt - 1, 0))

    def dup_slow(_):
        # adjacent equal (read, value) pairs after one stable single-key
        # sort by value — exact because elem_read is monotone over
        # slots, so within an equal-value block one read's slots stay
        # contiguous
        d_val, d_read = jax.lax.sort(
            (jnp.where(elem_in_read, ev, V),
             jnp.where(elem_in_read, elem_read, M)),
            num_keys=1, is_stable=True)
        dups = (d_read[1:] == d_read[:-1]) & (d_val[1:] == d_val[:-1]) & \
            (d_read[1:] < M)
        return jnp.sum(dups.astype(jnp.int32))

    # presence flag only (0/1): the two branches count different things
    # (per-order multiplicity vs per-read adjacent pairs), so surfacing
    # the raw number would make the same history report path-dependent
    # counts on batched vs single paths — presence is the contract
    duplicate_elements = jnp.minimum(jax.lax.cond(
        incompatible_order > 0, dup_slow, lambda _: dup_fast,
        operand=None), 1)

    # G1b: last element of a read is an intermediate append of another txn
    is_last_elem = elem_in_read & (elem_off == erd_len - 1)
    g1b = is_last_elem & (writer[ev] >= 0) & (~is_final[ev]) & \
        (writer[ev] != elem_txn)
    g1b_count = jnp.sum(g1b.astype(jnp.int32))
    g1b_witness = jnp.argmax(g1b)

    # dirty-update: aborted write immediately followed by a committed one
    nxt_slot_same_key = slot_valid & (slot + 1 < total_ord) & \
        (slot_key == slot_key[jnp.clip(slot + 1, 0, O - 1)])
    nv = jnp.clip(ord_elems[jnp.clip(slot + 1, 0, O - 1)], 0, V - 1)
    dirty = nxt_slot_same_key & (writer_type[cv] == TXN_FAIL) & \
        (writer_type[nv] == TXN_OK)
    dirty_update = jnp.sum(dirty.astype(jnp.int32))

    # ---- internal consistency --------------------------------------------
    # mops sorted by (txn, key, pos) form per-(txn,key) runs.  Within a run:
    #   n_app_before[q]  — appends since the last known read (exclusive)
    #   prev_q[q]        — run position of the last known read before q
    # Then a read of length L with previous read of length P must satisfy
    # L == P + n_app_before, and its elements at offsets [base, base+n)
    # (base = P, or L - n when no previous read) must equal the appended
    # values at run positions q-n .. q-1, in order.  Exact given
    # prefix-compatible reads (see module docstring).
    # (run_sort order and its per-run arrays are computed above, beside
    # the final-append detection that shares them)
    cum_app_excl = segmented_cumsum(app2.astype(jnp.int32), run_start,
                                    exclusive=True)
    prev_q = segmented_cummax(jnp.where(known2, q, -1), run_start,
                              exclusive=True, neutral=-1)
    have_prev = prev_q >= 0
    prev_app_base = jnp.where(
        have_prev,
        (cum_app_excl + app2.astype(jnp.int32))[jnp.clip(prev_q, 0, M - 1)],
        0)
    n_app_before = cum_app_excl - prev_app_base
    prev_len = jnp.where(have_prev, len2[jnp.clip(prev_q, 0, M - 1)], 0)

    bad_len = known2 & have_prev & (len2 != prev_len + n_app_before)
    bad_suffix = known2 & ~have_prev & (len2 < n_app_before)
    internal_len_bad = jnp.sum((bad_len | bad_suffix).astype(jnp.int32))

    # element-side content check: element at offset o of read m belongs to
    # the appends-since-last-read window iff o >= base; it must then equal
    # the append at run position q(m) - n + (o - base)
    if use_fill:
        # same Pallas LOCF expansion as the read-element table above:
        # all four are per-read constants, so compose them per-mop
        # (M-sized gathers, ~4x cheaper than R-sized on chip), seed at
        # the read starts, and fill — replacing four more R-sized
        # gathers.  The leading er == -1 prefix replicates the legacy
        # clip-to-mop-0 values.
        erc = jnp.clip(inv_run, 0, M - 1)
        comp_n = n_app_before[erc]
        comp_have = have_prev[erc].astype(jnp.int32)
        comp_prev_len = prev_len[erc]

        def _rfill(valsM):
            f = pallas_fill.locf_flat(_aseed(valsM))
            return jnp.where(hole, valsM[0].astype(jnp.int32), f)

        er_run = _rfill(inv_run)
        er_n = _rfill(comp_n)
        er_have = _rfill(comp_have) != 0
        er_prev_len = _rfill(comp_prev_len)
    else:
        er_run = inv_run[er]                      # run position of the read
        er_n = n_app_before[jnp.clip(er_run, 0, M - 1)]
        er_have = have_prev[jnp.clip(er_run, 0, M - 1)]
        er_prev_len = prev_len[jnp.clip(er_run, 0, M - 1)]
    base = jnp.where(er_have, er_prev_len, erd_len - er_n)
    j = elem_off - base
    in_window = elem_in_read & (j >= 0) & (j < er_n)
    exp_val = val2[jnp.clip(er_run - er_n + j, 0, M - 1)]
    internal_content = in_window & (h.rd_elems != exp_val)
    internal = internal_len_bad + jnp.sum(internal_content.astype(jnp.int32))

    # ---- dependency edges -------------------------------------------------
    ww_src = jnp.where(slot_valid, writer[cv], -1)
    ww_dst = jnp.where(nxt_slot_same_key, writer[nv], -1)
    ww_ok = nxt_slot_same_key & (ww_src >= 0) & (ww_dst >= 0) & \
        (ww_src != ww_dst) & \
        graph_txn[jnp.clip(ww_src, 0, T - 1)] & \
        graph_txn[jnp.clip(ww_dst, 0, T - 1)]

    last_val = jnp.where(
        has_elems,
        h.rd_elems[jnp.clip(h.mop_rd_start + h.mop_rd_len - 1, 0, R - 1)], -1)
    wr_src = jnp.where(last_val >= 0, writer[jnp.clip(last_val, 0, V - 1)], -1)
    wr_dst = h.mop_txn
    wr_ok = has_elems & (wr_src >= 0) & (wr_src != wr_dst) & \
        graph_txn[jnp.clip(wr_src, 0, T - 1)]

    key_c = jnp.clip(h.mop_key, 0, nk - 1)
    has_next = known_read & (h.mop_rd_len < ord_len[key_c])
    nxt_val = jnp.where(
        has_next,
        ord_elems[jnp.clip(ord_start[key_c] + h.mop_rd_len, 0, O - 1)], -1)
    rw_dst = jnp.where(nxt_val >= 0, writer[jnp.clip(nxt_val, 0, V - 1)], -1)
    rw_src = h.mop_txn
    rw_ok = has_next & (rw_dst >= 0) & (rw_dst != rw_src) & \
        graph_txn[jnp.clip(rw_dst, 0, T - 1)]

    # ---- node ranks -------------------------------------------------------
    # txn = 2*complete_pos (even), barrier = 2*complete_pos + 1 (odd);
    # padding gets unique high ranks with no edges attached
    tidx = jnp.arange(T, dtype=jnp.int32)
    rank_txn = jnp.where(h.txn_mask, 2 * h.txn_complete_pos, BIG + tidx)

    # ---- chains -----------------------------------------------------------
    # process chains: ok/info txns by (process, invoke_pos); complete_pos is
    # monotone along a process chain, so ranks increase as required
    pslot = jnp.where(h.txn_mask & graph_txn, h.txn_process, BIG)
    if h.proc_order is not None:
        # IR column: the (process, invoke) order precomputed at pad time
        porder = h.proc_order
        p_sorted = pslot[porder]
    elif h.proc_seq:
        # within each process, invoke order == txn row order
        # (host-verified: a jepsen process is sequential), so a stable
        # 1-key sort by process reproduces the (process, invoke) order
        # for every chain row; the BIG-keyed masked rows may permute
        # among themselves but never enter the chain (p_mask)
        p_sorted, porder = jax.lax.sort((pslot, tidx), num_keys=1,
                                        is_stable=True)
    else:
        p_sorted, _, porder = jax.lax.sort(
            (pslot, h.txn_invoke_pos, tidx), num_keys=2, is_stable=True)
    p_nodes = porder.astype(jnp.int32)
    p_mask = p_sorted < BIG
    p_starts = jnp.concatenate([jnp.ones(1, bool),
                                p_sorted[1:] != p_sorted[:-1]])

    # realtime barriers: one per ok txn, ordered by completion
    bslot = jnp.where(h.txn_mask & ok, h.txn_complete_pos, BIG)
    if h.barrier_order is not None:
        # IR column: ok-completion order precomputed at pad time
        border = h.barrier_order
    elif h.complete_monotone:
        # complete_pos is strictly increasing over valid txns
        # (host-verified static flag: TxnPacker emits txns in completion
        # order), so argsort(bslot) is a stable partition — ok txns keep
        # index order, everything else follows — an O(T) cumsum+scatter
        # instead of a T-sized device sort
        okm = bslot < BIG
        n_ok_incl = jnp.cumsum(okm.astype(jnp.int32))
        dest_b = jnp.where(
            okm, n_ok_incl - 1,
            n_ok_incl[-1] + jnp.cumsum((~okm).astype(jnp.int32)) - 1)
        border = jnp.zeros(T, jnp.int32).at[dest_b].set(tidx)
    else:
        border = jnp.argsort(bslot)
    b_txn = border.astype(jnp.int32)
    b_mask = bslot[border] < BIG
    barrier_node = (T + tidx).astype(jnp.int32)
    rank_barrier = jnp.where(b_mask, 2 * bslot[border] + 1, BIG + T + tidx)
    b_starts = jnp.concatenate([jnp.ones(1, bool), jnp.zeros(T - 1, bool)])
    tb_src = b_txn
    tb_dst = barrier_node
    tb_ok = b_mask
    if h.barrier_bi is not None:
        bi = h.barrier_bi
    else:
        comp_sorted = jnp.where(b_mask, bslot[border], BIG)
        bi = jnp.searchsorted(comp_sorted, h.txn_invoke_pos,
                              side="left") - 1
    bt_ok = h.txn_mask & graph_txn & (bi >= 0)
    bt_src = (T + jnp.clip(bi, 0, T - 1)).astype(jnp.int32)
    bt_dst = tidx

    return {
        "counts": {
            "duplicate-appends": duplicate_appends,
            "duplicate-elements": duplicate_elements,
            "incompatible-order": incompatible_order,
            "G1a": g1a_count,
            "G1b": g1b_count,
            "dirty-update": dirty_update,
            "internal": internal,
        },
        "witness": {
            "incompatible-order": incompat_witness,
            "G1a": g1a_witness,
            "G1b": g1b_witness,
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
        "order": {
            "elems": ord_elems, "start": ord_start, "len": ord_len,
            "writer": writer,
        },
    }


def family_graph(out) -> FamilyGraph:
    """The cycle sweep's graph of an inference's `edges`, `chains` and
    `ranks` (`infer`, `device_rw.infer_rw`), not yet enumerated: txn
    nodes 0..T-1, barrier nodes T..2T-1."""
    edges, chains = out["edges"], out["chains"]
    return FamilyGraph(
        n_nodes=2 * out["ranks"]["txn"].shape[0],
        rank=jnp.concatenate([out["ranks"]["txn"], out["ranks"]["barrier"]]),
        nc_src=jnp.concatenate([edges[k][0] for k in FAMILIES]),
        nc_dst=jnp.concatenate([edges[k][1] for k in FAMILIES]),
        base_mask=jnp.concatenate([edges[k][2] for k in FAMILIES]),
        fam_lens=tuple(edges[k][0].shape[0] for k in FAMILIES),
        chain_nodes=jnp.concatenate([chains[c][0] for c in CHAINS]),
        chain_starts=jnp.concatenate([chains[c][1] for c in CHAINS]),
        chain_masks=tuple(chains[c][2] for c in CHAINS))


def includes(rels):
    """(family flags, chain-group flags), 1 or 0 each: what the
    projection of a `family_graph` onto the rel codes `rels` keeps."""
    return ([int(r in rels) for r in FAMILY_RELS],
            [int(r in rels) for r in CHAIN_RELS])
