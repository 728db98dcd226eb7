"""Device cycle detection over dependency graphs: the parallel-SCC engine.

This replaces the reference's sequential Java Tarjan
(`io.lacuna.bifurcan.Graphs/stronglyConnectedComponents`, SURVEY.md §2.5 #1)
with a TPU-shaped decomposition.  Tarjan is inherently sequential; instead:

1. **Rank decomposition.**  Nodes carry a static rank (completion order of
   txns, with realtime-barrier nodes interleaved).  Edges split into
   *forward* (rank(src) < rank(dst)) and *backward* (the rest).  Forward
   edges alone form a DAG, so **every cycle contains >= 1 backward edge**.
   In valid histories backward edges are rare (version order mostly agrees
   with commit order), giving a device-only fast path: K == 0 -> acyclic.

2. **Forward reachability from backward-edge heads.**  label[v] = the set
   of backward edges e with dst(e) ->* v through forward edges, as (N, K)
   0/1 int8 planes (OR == max, so relaxation is scatter-max — native on
   TPU).  Long chains (realtime barrier chain, per-process order, per-key
   ww version order) would make naive relaxation O(diameter); they are
   instead resolved each round by **segmented prefix-OR scans**
   (associative_scan, O(log N) depth), so rounds are bounded by the number
   of *non-chain* hops (wr/rw/barrier-entry/exit edges) on the longest
   shortest-path — small in practice.  Fixpoint via `lax.while_loop`.

3. **Meta-closure.**  Cycle exists iff the K-node meta-graph — meta-edge
   e -> e' iff dst(e) ->*_forward src(e') — has a cycle (self-loops
   included).  K x K boolean closure by repeated squaring (MXU-friendly).

Backward edges on meta-cycles are returned as *witnesses*; exact anomaly
classification/explanation happens host-side on the (small) offending
subgraph, mirroring the reference's SCC -> in-SCC search split.

If the fixpoint loop hits `max_rounds` without converging the result is
flagged `converged=False`; callers MUST fall back to the host checker
(checkers are oracles — a truncated propagation could miss cycles, and we
never trade exactness for speed).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.ops.segments import (
    gather_rows,
    scatter_or,
    segmented_prefix_or,
)


@dataclasses.dataclass
class SweepGraph:
    """Static, padded graph layout for the sweep kernel (device arrays).

    Non-chain edges are COO (src, dst, mask).  Chain edges are given as
    concatenated node sequences: chain_nodes with chain_starts flags; the
    implied edges are chain_nodes[i] -> chain_nodes[i+1] within a segment.
    chain_mask disables whole entries (padding / rel not in projection).
    All ranks must be unique per node; forward = rank increases.
    """

    n_nodes: int
    rank: jnp.ndarray          # (N,) int32, unique
    nc_src: jnp.ndarray        # (E,) int32 non-chain edges
    nc_dst: jnp.ndarray        # (E,) int32
    nc_mask: jnp.ndarray       # (E,) bool
    chain_nodes: jnp.ndarray   # (C,) int32
    chain_starts: jnp.ndarray  # (C,) bool
    chain_mask: jnp.ndarray    # (C,) bool


def backward_test(rank, nc_src, nc_dst, n_nodes: int):
    """The projection-independent backward-edge test (edge goes backward
    iff rank does not increase).  Single source of truth for callers that
    hoist it out of a projection scan AND for `_sweep_window`'s internal
    fallback — the two must stay bit-identical."""
    return rank[jnp.clip(nc_src, 0, n_nodes - 1)] >= \
        rank[jnp.clip(nc_dst, 0, n_nodes - 1)]


def _sweep_window(n_nodes: int, k_total: int, k_local: int, max_rounds: int,
                  rank, nc_src, nc_dst, nc_mask,
                  chain_nodes, chain_starts, chain_mask,
                  k_offset, axis_name=None, back_pre=None, back_tables=None):
    """Sweep kernel over a window of the backward-edge axis.

    Each caller owns backward edges with global ids in
    [k_offset, k_offset + k_local) and propagates only their (N, k_local)
    label planes — backward-edge columns are fully independent until the
    tiny meta-graph closure, which is the ONLY cross-window coupling.  With
    `axis_name` set (inside shard_map over a mesh axis of
    k_total // k_local devices) the local meta rows are combined with an
    ICI all_gather and convergence with a psum; every device then holds the
    full (k_total, k_total) meta graph and computes the closure redundantly
    (it is k_total^2 bytes — trivial next to the label planes).

    Returns (has_cycle, witness_bits (k_total,), n_backward, converged) —
    replicated across the axis when axis_name is set.
    """
    # ---- split edges: backward iff rank[src] >= rank[dst] -----------------
    # (chain edges are forward by construction: caller guarantees ranks
    # increase along chains)
    if back_pre is not None:
        # caller hoisted the backward enumeration: (is_back, n_back), and
        # the (k_total,) endpoint tables `back_tables` that come with it.
        # `project_families` reads both off ONE enumeration of the family
        # union, where each projection ran a rank test, an E-sized cumsum
        # and the two E-sized scatter-max reductions below (on TPU the
        # scatters measured 2.4 s/run at 1M shapes: 0.24 s x 2 x 5
        # projections, ~24% of the whole check).  Must be bit-identical
        # to the block below.
        is_back, n_back = back_pre
        bsrc_full, bdst_full = back_tables
        bdst_local = jax.lax.dynamic_slice(
            bdst_full, (k_offset,), (k_local,))
    else:
        is_back = nc_mask & backward_test(rank, nc_src, nc_dst, n_nodes)
        n_back = jnp.sum(is_back.astype(jnp.int32))

        # stable enumeration of backward edges: order by edge position
        back_order = jnp.cumsum(is_back.astype(jnp.int32)) - 1
        back_id = jnp.where(is_back, back_order, -1)

        # full-width source table (identical on every window — needed
        # for the meta-graph columns)
        in_full = is_back & (back_id < k_total)
        scat_full = jnp.where(in_full, back_id, k_total).astype(jnp.int32)
        bsrc_full = jnp.zeros((k_total + 1,), jnp.int32).at[scat_full].max(
            jnp.where(in_full, nc_src, 0))[:k_total]

        # local window endpoints
        in_local = is_back & (back_id >= k_offset) \
            & (back_id < k_offset + k_local)
        scat_local = jnp.where(in_local, back_id - k_offset,
                               k_local).astype(jnp.int32)
        bdst_local = jnp.zeros((k_local + 1,), jnp.int32).at[scat_local].max(
            jnp.where(in_local, nc_dst, 0))[:k_local]

    bvalid_full = (jnp.arange(k_total) < n_back)
    bvalid_local = (jnp.arange(k_local) + k_offset) < n_back
    fwd_mask = nc_mask & ~is_back  # forward non-chain edges only

    def propagate(_):
        # labels: (N, k_local) int8; seed label[bdst[e], e] = 1
        labels0 = jnp.zeros((n_nodes, k_local), jnp.int8)
        labels0 = labels0.at[jnp.where(bvalid_local, bdst_local, 0),
                             jnp.arange(k_local)].max(
            bvalid_local.astype(jnp.int8))

        def chain_pass(labels):
            vals = gather_rows(labels, chain_nodes, chain_mask)
            pref = segmented_prefix_or(vals, chain_starts, exclusive=True)
            return scatter_or(labels, chain_nodes, pref, chain_mask)

        def relax_pass(labels):
            vals = gather_rows(labels, nc_src, fwd_mask)
            return scatter_or(labels, nc_dst, vals, fwd_mask)

        def body(state):
            labels, _, i = state
            new = chain_pass(labels)
            new = relax_pass(new)
            new = chain_pass(new)
            changed = jnp.any(new != labels)
            return new, changed, i + 1

        def cond(state):
            _, changed, i = state
            return changed & (i < max_rounds)

        # carry components derive from sharded inputs so their varying-axis
        # type matches the body's outputs under shard_map
        changed0 = n_back >= 0                 # always True, varying-typed
        rounds0 = jnp.where(n_back < 0, 1, 0)  # always 0, varying-typed
        if axis_name is not None:
            # the label plane is varying over the mesh axis (its window
            # depends on axis_index), so the whole carry must be too
            changed0 = jax.lax.pcast(changed0, axis_name, to="varying")
            rounds0 = jax.lax.pcast(rounds0, axis_name, to="varying")
        labels, changed, rounds = jax.lax.while_loop(
            cond, body, (chain_pass(labels0), changed0, rounds0))
        converged = ~(changed & (rounds >= max_rounds))

        # meta-graph rows for the local window: meta[e, e2] = dst(e) ->*
        # src(e2), read from labels[src(e2), e]
        meta_local = gather_rows(labels, bsrc_full, bvalid_full).T
        if axis_name is not None:
            meta = jax.lax.all_gather(meta_local, axis_name, axis=0,
                                      tiled=True)
            # psum/pmax outputs are replicated over the axis — required for
            # the P() out_specs of the enclosing shard_map
            n_bad = jax.lax.psum((~converged).astype(jnp.int32), axis_name)
            converged = n_bad == 0
            meta = jax.lax.pmax(meta, axis_name)
        else:
            meta = meta_local
        meta = meta & bvalid_full[:, None].astype(jnp.int8) \
                    & bvalid_full[None, :].astype(jnp.int8)

        def close_body(_, r):
            ri = r.astype(jnp.int32)
            r2 = ((ri @ ri) > 0).astype(jnp.int8)
            return r | r2

        n_sq = max(1, int(np.ceil(np.log2(max(2, k_total)))))
        closure = jax.lax.fori_loop(0, n_sq, close_body, meta)
        # backward edge e is on a cycle iff closure[e][e] (dst ->* src,
        # then the edge src -> dst itself closes it)
        witness = jnp.diagonal(closure) & bvalid_full.astype(jnp.int8)
        return jnp.any(witness == 1), witness, converged

    def acyclic(_):
        # no backward edges: forward edges strictly increase rank, so the
        # projection is a DAG — nothing to propagate (the common case for
        # valid histories; this skip is the fast path)
        # zeros derived from n_back so the varying-axis type matches the
        # propagate branch under shard_map
        zeros = jnp.zeros((k_total,), jnp.int8) + (n_back * 0).astype(jnp.int8)
        return (n_back < 0, zeros, n_back >= 0)

    has_cycle, witness, converged = jax.lax.cond(
        n_back > 0, propagate, acyclic, operand=None)
    return has_cycle, witness, n_back, converged


def _sweep_arrays(n_nodes: int, max_k: int, max_rounds: int,
                  rank, nc_src, nc_dst, nc_mask,
                  chain_nodes, chain_starts, chain_mask, back_pre=None,
                  back_tables=None):
    """Core kernel (single window).  Returns (has_cycle, witness_bits,
    n_backward, converged).

    witness_bits: (max_k,) int8 — 1 for backward edges on some cycle.
    n_backward: actual number of backward edges found (may exceed max_k —
    caller must re-batch; we still compute exactly for the first max_k and
    report overflow via n_backward).
    """
    return _sweep_window(n_nodes, max_k, max_k, max_rounds,
                         rank, nc_src, nc_dst, nc_mask,
                         chain_nodes, chain_starts, chain_mask,
                         k_offset=jnp.int32(0), axis_name=None,
                         back_pre=back_pre, back_tables=back_tables)


_sweep = jax.jit(_sweep_arrays,
                 static_argnames=("n_nodes", "max_k", "max_rounds"))


# arrays-first twins of _sweep/_sweep_sharded for the AOT compile-cache
# seam (compilecache.call dispatches a cached Compiled with the dynamic
# args alone, so statics must bind by keyword behind the arrays)
@partial(jax.jit, static_argnames=("n_nodes", "max_k", "max_rounds"))
def _sweep_kw(rank, nc_src, nc_dst, nc_mask, chain_nodes, chain_starts,
              chain_mask, *, n_nodes, max_k, max_rounds):
    return _sweep_arrays(n_nodes, max_k, max_rounds, rank, nc_src,
                         nc_dst, nc_mask, chain_nodes, chain_starts,
                         chain_mask)


@partial(jax.jit, static_argnames=("n_nodes", "max_k", "max_rounds",
                                   "mesh", "axis"))
def _sweep_sharded(n_nodes: int, max_k: int, max_rounds: int, mesh, axis,
                   rank, nc_src, nc_dst, nc_mask,
                   chain_nodes, chain_starts, chain_mask):
    """`_sweep_arrays` with the backward-edge axis sharded over `mesh`
    (the per-projection form of `parallel/op_shard.py`'s K-window
    pattern): each device owns max_k / n_shards backward-edge columns
    and propagates only its label-plane window; the (K, K) meta graph
    merges with one all_gather.  Same result contract as `_sweep`."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    assert max_k % n_shards == 0, (max_k, n_shards)
    k_local = max_k // n_shards
    rep = P()

    @partial(jax.shard_map, mesh=mesh, in_specs=(rep,) * 7,
             out_specs=(rep, rep, rep, rep))
    def run(rank_, s_, d_, m_, cn_, cs_, cm_):
        off = jax.lax.axis_index(axis) * k_local
        return _sweep_window(n_nodes, max_k, k_local, max_rounds,
                             rank_, s_, d_, m_, cn_, cs_, cm_,
                             k_offset=off, axis_name=axis)

    return run(rank, nc_src, nc_dst, nc_mask, chain_nodes, chain_starts,
               chain_mask)


@partial(jax.jit, static_argnames=("n_nodes", "max_k", "max_rounds",
                                   "mesh", "axis"))
def _sweep_sharded_kw(rank, nc_src, nc_dst, nc_mask, chain_nodes,
                      chain_starts, chain_mask, *, n_nodes, max_k,
                      max_rounds, mesh, axis):
    return _sweep_sharded(n_nodes, max_k, max_rounds, mesh, axis, rank,
                          nc_src, nc_dst, nc_mask, chain_nodes,
                          chain_starts, chain_mask)


def _per_edge(vals, fam_lens):
    """Per-family values broadcast over their concatenated edge blocks."""
    return jnp.concatenate([jnp.broadcast_to(vals[f], (L,))
                            for f, L in enumerate(fam_lens)])


def enumerate_families(n_nodes: int, k_tab: int, fam_lens,
                       rank, e_src, e_dst, union_mask):
    """The backward-edge enumeration of a union of edge families: ONE
    rank test and ONE E-sized cumsum for every projection that keeps
    whole families (the single source `projection_scan` and the
    list-append sweep both call; `project_families` reads one
    projection off it).

    Families are concatenated blocks of `fam_lens` edges.  `cum` steps
    by exactly 1 at each union-masked backward edge, so family f's j-th
    backward edge (in edge order) is the first position of f's block
    where `cum` reaches cum_start[f] + j + 1: k_tab binary searches per
    family build the endpoint tables, where a scatter-max over every
    edge did before (0.24 s each per projection at 1M-txn TPU shapes).

    Returns (back_all (E,) bool, count_f (F,) int32, fam_src, fam_dst
    (F, k_tab) int32), the tables 0 past count_f[f].
    """
    bounds = np.cumsum([0] + list(fam_lens))
    back_all = union_mask & backward_test(rank, e_src, e_dst, n_nodes)
    cum = jnp.cumsum(back_all.astype(jnp.int32))
    cum_start = [cum[int(b) - 1] if b > 0 else jnp.int32(0)
                 for b in bounds[:-1]]
    count_f = jnp.stack([
        (cum[int(e) - 1] if e > 0 else jnp.int32(0)) - s
        for s, e in zip(cum_start, bounds[1:])])
    j = jnp.arange(k_tab, dtype=jnp.int32)
    srcs, dsts = [], []
    for f, L in enumerate(fam_lens):
        if L == 0:
            srcs.append(jnp.zeros((k_tab,), jnp.int32))
            dsts.append(jnp.zeros((k_tab,), jnp.int32))
            continue
        lo, hi = int(bounds[f]), int(bounds[f + 1])
        pos = lo + jnp.searchsorted(cum[lo:hi], cum_start[f] + j + 1,
                                    side="left").astype(jnp.int32)
        pos = jnp.clip(pos, 0, cum.shape[0] - 1)
        ok = j < count_f[f]
        srcs.append(jnp.where(ok, e_src[pos], 0))
        dsts.append(jnp.where(ok, e_dst[pos], 0))
    return back_all, count_f, jnp.stack(srcs), jnp.stack(dsts)


def project_families(fam_lens, max_k: int, union_mask, chain_masks,
                     enumeration, inc, cinc):
    """One projection of a family union, read off the union's
    enumeration (`enumerate_families`) with no E-sized gather, scan or
    scatter: the masks and the hoisted backward enumeration
    `_sweep_window` takes.

    The projection keeps edge family f whole iff inc[f] > 0 and chain
    group g iff cinc[g] > 0.  Its family-f backward set IS the union's
    (family masks don't vary per projection, only inclusion), so its
    position-stable enumeration is each kept family's, shifted by the
    counts of the kept families before it: bit-identical to a cumsum
    and scatter-max over the projection's own mask (ids >= n_back stay
    0).  Returns (nc_mask (E,), chain_mask (C,), back_pre (is_back,
    n_back), back_tables (bsrc, bdst) (max_k,)).
    """
    back_all, count_f, fam_src, fam_dst = enumeration
    k_tab = fam_src.shape[1]
    if k_tab < max_k:
        raise ValueError(f"endpoint tables of {k_tab} < max_k {max_k}")
    keep = _per_edge(inc > 0, fam_lens)
    chain_mask = jnp.concatenate([m & (cinc[g] > 0)
                                  for g, m in enumerate(chain_masks)])
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(count_f * inc)[:-1]])
    tgt = jnp.arange(max_k, dtype=jnp.int32)
    bsrc = jnp.zeros((max_k,), jnp.int32)
    bdst = jnp.zeros((max_k,), jnp.int32)
    for f, L in enumerate(fam_lens):
        if L == 0:
            continue
        j = tgt - offs[f]
        sel = (inc[f] > 0) & (j >= 0) & (j < count_f[f])
        jc = jnp.clip(j, 0, k_tab - 1)
        bsrc = jnp.where(sel, fam_src[f, jc], bsrc)
        bdst = jnp.where(sel, fam_dst[f, jc], bdst)
    return (union_mask & keep, chain_mask,
            (back_all & keep, jnp.sum(count_f * inc)), (bsrc, bdst))


def projection_scan(n_nodes: int, max_k: int, max_rounds: int,
                    rank, e_src, e_dst, fam_masks, inc_stack,
                    chain_nodes, chain_starts, chain_masks, cinc_stack,
                    sweep=None):
    """Scan `_sweep_arrays` over projections given per-family masks and
    per-projection family-include flags — the single-sourced hoisted
    form shared by device_core.core_check and device_rw.rw_core_check.

    `sweep` (optional) replaces the single-window `_sweep_arrays` call
    with a caller-supplied kernel of signature (rank, e_src, e_dst,
    mask, chain_nodes, chain_starts, chain_mask, back_pre,
    back_tables) -> (has, witness, n_back, converged), where back_pre
    is (is_back, n_back) and back_tables the (max_k,) (bsrc, bdst)
    endpoint pair of `project_families` — how the K-windowed sharded
    paths (`parallel/op_shard.py`, `parallel/hybrid.py`) reuse this
    scan with `_sweep_window` inside shard_map while keeping the
    hoisted enumeration (VERDICT r04 item 2: the sharded sweep
    previously re-materialized (5, E) mask stacks and ran 5 E-sized
    cumsums).

    Instead of materialized (P, E)/(P, C) mask stacks and an E-sized
    cumsum per projection, the scan consumes tiny include matrices:
    per-projection masks are `family_mask & include`, and the
    backward-edge enumeration is `enumerate_families`' ONE, read per
    projection by `project_families`.  Measured effect at 1M txns on
    CPU: fused check 7.98 s -> 5.18 s and compile 28.8 s -> 7.9 s
    (PROFILE.md §0b).

    fam_masks: per-family (E_f,) bool masks, concat order == e_src.
    inc_stack: (P, F) int32 — family f included in projection p.
    chain_masks: per-chain-group (C_g,) bool, concat order ==
    chain_nodes.  cinc_stack: (P, G) int32.
    Returns (conv_all, overflow, cyc_bits (P,) int32).
    """
    fam_lens = [int(m.shape[0]) for m in fam_masks]
    union_mask = jnp.concatenate(list(fam_masks))
    enum = enumerate_families(n_nodes, max_k, fam_lens, rank, e_src, e_dst,
                              union_mask)
    count_f = enum[1]

    def proj_body(carry, mc):
        conv_all, overflow = carry
        inc, cinc = mc
        m, cm, back_pre, tables = project_families(
            fam_lens, max_k, union_mask, chain_masks, enum, inc, cinc)
        if sweep is None:
            has, _, n_back_out, conv = _sweep_arrays(
                n_nodes, max_k, max_rounds, rank, e_src, e_dst, m,
                chain_nodes, chain_starts, cm, back_pre=back_pre,
                back_tables=tables)
        else:
            has, _, n_back_out, conv = sweep(
                rank, e_src, e_dst, m, chain_nodes, chain_starts, cm,
                back_pre, tables)
        carry = (conv_all & conv,
                 jnp.maximum(overflow,
                             jnp.maximum(n_back_out - max_k, 0)))
        return carry, has.astype(jnp.int32)

    # carry init derives from traced inputs so its varying-axis type
    # matches the body outputs under shard_map/vmap
    zero0 = e_src[0] * 0
    n_proj = int(inc_stack.shape[0])

    def run_scan(_):
        (conv_all, overflow), cyc_bits = jax.lax.scan(
            proj_body, (zero0 == 0, zero0), (inc_stack, cinc_stack))
        return conv_all, overflow, cyc_bits

    def no_backward(_):
        # zero backward edges across the FULL family union: every
        # projection's backward set is a subset, so all P projections
        # are DAGs — converged, no overflow, no cycles.  The common
        # case for valid histories; skipping the scan saves P rounds of
        # E-sized masking/enumeration.  (Under vmap this cond lowers to
        # select and both branches still run — batched paths keep their
        # old cost, never a new one.)
        return zero0 == 0, zero0, jnp.zeros((n_proj,), jnp.int32) + zero0

    return jax.lax.cond(jnp.sum(count_f) > 0, run_scan, no_backward,
                        operand=None)

#: budget ceilings shared by every sweep driver (detect_cycles here,
#: grow_until_exact in device_core): past these, callers fall back to
#: the host oracle rather than approximate
MAX_K_CAP = 8192
MAX_ROUNDS_CAP = 1024


@dataclasses.dataclass
class FamilyGraph:
    """A sweep graph whose projections each keep whole edge families and
    chain groups (device arrays): the list-append checker's, whose
    projections are sets of dependency rels.

    Non-chain edges are families concatenated in blocks of `fam_lens`
    edges under one `base_mask`; chains are groups concatenated in
    `chain_nodes` order, one mask each.  `enumerate_backward` makes the
    union's backward-edge enumeration once (`enumeration`); each
    `project(...)` is then swept by `detect_cycles` with no E-sized
    rank test, cumsum or scatter of its own.
    """

    n_nodes: int
    rank: jnp.ndarray                   # (N,) int32, unique
    nc_src: jnp.ndarray                 # (E,) int32, families concatenated
    nc_dst: jnp.ndarray                 # (E,) int32
    base_mask: jnp.ndarray              # (E,) bool
    fam_lens: Tuple[int, ...]
    chain_nodes: jnp.ndarray            # (C,) int32
    chain_starts: jnp.ndarray           # (C,) bool
    chain_masks: Tuple[jnp.ndarray, ...]  # per group (C_g,) bool
    #: (back_all, count_f, fam_src, fam_dst) of `enumerate_families`
    enumeration: Optional[tuple] = None
    #: host copy of the union's backward-edge positions, made on the
    #: first witness map that needs it (`dataclasses.replace` starts a
    #: new graph without it)
    _host_back: Optional[np.ndarray] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def k_tab(self) -> int:
        """Edges a family in the endpoint tables (0: not enumerated)."""
        return self.enumeration[2].shape[1] if self.enumeration else 0

    def host_backward(self) -> np.ndarray:
        """Positions of the union's backward edges, in edge order: one
        device-to-host copy of the enumeration's `back_all` per graph."""
        if self._host_back is None:
            self._host_back = np.nonzero(np.asarray(self.enumeration[0]))[0]
        return self._host_back

    def project(self, inc: Sequence[int],
                cinc: Sequence[int]) -> "FamilyProjection":
        """The projection keeping family f iff inc[f] and chain group g
        iff cinc[g]."""
        return FamilyProjection(self, tuple(inc), tuple(cinc))


@dataclasses.dataclass
class FamilyProjection:
    """One projection of an enumerated `FamilyGraph`: the edge families
    (`inc`) and chain groups (`cinc`) it keeps, 1 or 0 each."""

    graph: FamilyGraph
    inc: Tuple[int, ...]
    cinc: Tuple[int, ...]

    def host_backward(self) -> np.ndarray:
        """The projection's backward-edge positions on the host, in edge
        order: the union's (`FamilyGraph.host_backward`) in the blocks
        of the families it keeps, since a kept family's backward set is
        the union's there."""
        pos = self.graph.host_backward()
        bounds = np.cumsum((0,) + tuple(self.graph.fam_lens))
        cut = np.searchsorted(pos, bounds)
        return np.concatenate([pos[:0]] + [
            pos[lo:hi] for lo, hi, keep in zip(cut[:-1], cut[1:], self.inc)
            if keep])


@partial(jax.jit, static_argnames=("n_nodes", "k_tab", "fam_lens", "mesh"))
def _enumerate_kw(rank, nc_src, nc_dst, base_mask, *, n_nodes, k_tab,
                  fam_lens, mesh=None):
    def run(*a):
        return enumerate_families(n_nodes, k_tab, fam_lens, *a)

    if mesh is not None:
        # every chip enumerates the whole union, so what each projection's
        # shard_map sweep reads goes in replicated
        from jax.sharding import PartitionSpec as P

        run = jax.shard_map(run, mesh=mesh, in_specs=(P(),) * 4,
                            out_specs=(P(),) * 4)
    return run(rank, nc_src, nc_dst, base_mask)


def enumerate_backward(g: FamilyGraph, k_tab: int = 128, mesh=None,
                       axis: str = "batch") -> FamilyGraph:
    """`g` with its union's backward-edge enumeration made, endpoint
    tables of `k_tab` edges a family (rounded up to the mesh, as
    `detect_cycles` rounds max_k): one program (span `sweep.enumerate`)
    for all of its projections' sweeps.  `detect_cycles` enumerates
    again, as wide as its `max_k`, only for a retry that outgrows the
    tables: MAX_K_CAP-wide tables up front would make every check pay
    for binary searches that only a retry reads (about 6 ms of a 52 ms
    enumeration on a v5e at 2^18 txns)."""
    from jepsen_tpu import compilecache, telemetry

    if mesh is not None and mesh.devices.size <= 1:
        mesh = None
    if mesh is not None:
        k_tab = -(-k_tab // mesh.shape[axis]) * mesh.shape[axis]
    with telemetry.span("sweep.enumerate") as sp:
        enum = compilecache.call(
            "cycle-sweep.enumerate", _enumerate_kw, g.rank, g.nc_src,
            g.nc_dst, g.base_mask, n_nodes=g.n_nodes, k_tab=k_tab,
            fam_lens=tuple(g.fam_lens), mesh=mesh)
        if telemetry.enabled():
            # the read ends the span at the program's end
            sp.set_attr(n_backward_union=int(np.asarray(enum[1]).sum()),
                        edges=int(g.nc_src.shape[0]), k_tab=k_tab,
                        sharded=mesh is not None)
    return dataclasses.replace(g, enumeration=tuple(enum))


def _sweep_families(n_nodes, max_k, k_local, max_rounds, fam_lens, rank,
                    nc_src, nc_dst, base_mask, enumeration, inc, chain_nodes,
                    chain_starts, chain_masks, cinc, k_offset,
                    axis_name=None):
    """`_sweep_window` over one projection of a `FamilyGraph`, read off
    the union's enumeration by `project_families`: only O(max_k) and
    elementwise work before the `n_back > 0` cond."""
    nc_mask, chain_mask, back_pre, tables = project_families(
        fam_lens, max_k, base_mask, chain_masks, enumeration, inc, cinc)
    return _sweep_window(n_nodes, max_k, k_local, max_rounds, rank, nc_src,
                         nc_dst, nc_mask, chain_nodes, chain_starts,
                         chain_mask, k_offset, axis_name=axis_name,
                         back_pre=back_pre, back_tables=tables)


@partial(jax.jit, static_argnames=("n_nodes", "max_k", "max_rounds",
                                   "fam_lens", "mesh", "axis"))
def _sweep_families_kw(rank, nc_src, nc_dst, base_mask, enumeration, inc,
                       chain_nodes, chain_starts, chain_masks, cinc, *,
                       n_nodes, max_k, max_rounds, fam_lens, mesh=None,
                       axis=None):
    """One projection's sweep: single-window, or with `mesh` the
    backward-edge axis sharded over it as `_sweep_sharded` does, every
    input replicated."""
    args = (rank, nc_src, nc_dst, base_mask, enumeration, inc, chain_nodes,
            chain_starts, chain_masks, cinc)
    if mesh is None:
        return _sweep_families(n_nodes, max_k, max_k, max_rounds, fam_lens,
                               *args, k_offset=jnp.int32(0))
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    assert max_k % n_shards == 0, (max_k, n_shards)
    k_local = max_k // n_shards

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(),) * len(args),
             out_specs=(P(),) * 4)
    def run(*a):
        off = jax.lax.axis_index(axis) * k_local
        return _sweep_families(n_nodes, max_k, k_local, max_rounds,
                               fam_lens, *a, k_offset=off, axis_name=axis)

    return run(*args)


def _run_sweep(g, max_k: int, max_rounds: int, mesh, axis: str):
    """Dispatch one sweep program through the AOT compile cache: verifier
    sweep chunks and checker projections pad to pow2 (N, E) classes, so
    maintenance rounds and probes share persisted executables."""
    from jepsen_tpu import compilecache

    if isinstance(g, FamilyProjection):
        u = g.graph
        return compilecache.call(
            "cycle-sweep.families", _sweep_families_kw, u.rank, u.nc_src,
            u.nc_dst, u.base_mask, u.enumeration,
            jnp.asarray(g.inc, jnp.int32), u.chain_nodes, u.chain_starts,
            tuple(u.chain_masks), jnp.asarray(g.cinc, jnp.int32),
            n_nodes=u.n_nodes, max_k=max_k, max_rounds=max_rounds,
            fam_lens=tuple(u.fam_lens), mesh=mesh,
            axis=axis if mesh is not None else None)
    if mesh is not None:
        return compilecache.call(
            "cycle-sweep.sharded", _sweep_sharded_kw, g.rank, g.nc_src,
            g.nc_dst, g.nc_mask, g.chain_nodes, g.chain_starts,
            g.chain_mask, n_nodes=g.n_nodes, max_k=max_k,
            max_rounds=max_rounds, mesh=mesh, axis=axis)
    return compilecache.call(
        "cycle-sweep", _sweep_kw, g.rank, g.nc_src, g.nc_dst, g.nc_mask,
        g.chain_nodes, g.chain_starts, g.chain_mask, n_nodes=g.n_nodes,
        max_k=max_k, max_rounds=max_rounds)


@dataclasses.dataclass
class SweepResult:
    has_cycle: bool
    witness_edge_ids: np.ndarray  # indices into the non-chain edge arrays
    n_backward: int
    converged: bool


def detect_cycles(g: SweepGraph | FamilyProjection, max_k: int = 128,
                  max_rounds: int = 64, deadline=None, mesh=None,
                  axis: str = "batch") -> SweepResult:
    """Run the sweep; rebatch automatically if backward edges exceed max_k.

    Exact: cycle reported iff one exists in the (masked) graph, provided
    converged=True.  Witnesses identify backward edges on cycles (for the
    first max_k; enough to hand the host a subgraph to classify).

    `g` is a plain `SweepGraph`, swept by a program that enumerates its
    backward edges itself, or a `FamilyProjection` of a `FamilyGraph`
    enumerated on the same `mesh` (`enumerate_backward`), whose program
    reads them off the union's enumeration.  Both give the same result.

    `deadline` (a `resilience.Deadline`) is polled before each grow-
    retry — the budget-doubling fixpoint is this driver's unbounded
    loop, and a pathological graph must not hold the checker past its
    time budget (expiry raises `DeadlineExceeded`).

    `mesh` (a 1-D jax Mesh, ISSUE 12 sharded-by-default) shards the
    backward-edge axis over its devices — verdict-identical to the
    single-device sweep, differential-pinned in tests/test_parallel.py.
    """
    if deadline is not None:
        deadline.check("cycle-sweep")
    from jepsen_tpu import telemetry

    if mesh is not None and mesh.devices.size > 1:
        n_shards = mesh.shape[axis]
        if max_k % n_shards:
            max_k = ((max_k // n_shards) + 1) * n_shards
    else:
        mesh = None
    if isinstance(g, FamilyProjection) and g.graph.k_tab < max_k:
        # a retry outgrew the union's endpoint tables: enumerate again
        g = dataclasses.replace(
            g, graph=enumerate_backward(g.graph, max_k, mesh, axis))
    # one span per program run, ending at the n_back read that syncs it
    # (and the converged read the result needs anyway)
    with telemetry.span("sweep.call") as sp:
        has, wit, n_back, conv = _run_sweep(g, max_k, max_rounds, mesh, axis)
        n_back = int(n_back)
        has = bool(has)
        fits = n_back <= max_k
        if fits:
            conv = bool(conv)
        if telemetry.enabled():
            sp.set_attr(max_k=max_k, max_rounds=max_rounds,
                        n_backward=n_back, sharded=mesh is not None)
            if fits:
                sp.set_attr(converged=conv)
    if not fits:
        if n_back > MAX_K_CAP or max_k >= MAX_K_CAP:
            # bit budget unreachable or exhausted (an (n_nodes, max_k)
            # label plane past the cap would chew through memory; and
            # n_back is a property of the graph, so a capped retry that
            # still cannot fit it would be a guaranteed-wasted sweep):
            # report inexact — the caller falls back to the host oracle,
            # same contract as grow_until_exact
            return SweepResult(has_cycle=has,
                               witness_edge_ids=np.zeros(0, np.int64),
                               n_backward=n_back, converged=False)
        # too many backward edges for the bit budget: double and retry
        return detect_cycles(g,
                             max_k=min(max(max_k * 2, _pow2(n_back)),
                                       MAX_K_CAP),
                             max_rounds=max_rounds, deadline=deadline,
                             mesh=mesh, axis=axis)
    if not conv and max_rounds < MAX_ROUNDS_CAP:
        # fixpoint truncated: grow rounds like grow_until_exact does for
        # the fused path (histories dense with injected cycles can need
        # hundreds of rounds) before surrendering to the host fallback
        return detect_cycles(g, max_k=max_k,
                             max_rounds=min(max_rounds * 2,
                                            MAX_ROUNDS_CAP),
                             deadline=deadline, mesh=mesh, axis=axis)
    with telemetry.span("sweep.witness-map") as sp:
        # the witness bits are diagonal(closure) & bvalid and has_cycle is
        # their any(): without a cycle there is no bit to map, so nothing
        # is copied from the device
        base = g.graph if isinstance(g, FamilyProjection) else g
        wit_ids = np.zeros(0, np.int64)
        host_copy = False
        if has:
            # map witness backward-edge ids back to edge-array positions
            if isinstance(g, FamilyProjection):
                host_copy = base._host_back is None
                back_pos = g.host_backward()
            else:
                rank = np.asarray(g.rank)
                src = np.clip(np.asarray(g.nc_src), 0, g.n_nodes - 1)
                dst = np.clip(np.asarray(g.nc_dst), 0, g.n_nodes - 1)
                back_pos = np.nonzero(np.asarray(g.nc_mask)
                                      & (rank[src] >= rank[dst]))[0]
            wit = np.asarray(wit)
            wit_ids = back_pos[np.nonzero(wit[:len(back_pos)])[0]]
        if telemetry.enabled():
            sp.set_attr(edges=int(base.nc_src.shape[0]),
                        witnesses=len(wit_ids), mapped=has,
                        host_copy=host_copy)
    return SweepResult(has_cycle=has, witness_edge_ids=wit_ids,
                       n_backward=n_back, converged=conv)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
