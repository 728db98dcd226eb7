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


def backward_test(rank, nc_src, nc_dst, n_nodes: int):
    """`enumerate_families`' rank test: an edge goes backward iff rank
    does not increase along it."""
    return rank[jnp.clip(nc_src, 0, n_nodes - 1)] >= \
        rank[jnp.clip(nc_dst, 0, n_nodes - 1)]


def _sweep_window(n_nodes: int, k_total: int, k_local: int, max_rounds: int,
                  rank, nc_src, nc_dst, nc_mask,
                  chain_nodes, chain_starts, chain_mask,
                  k_offset, back_pre, back_tables, axis_name=None):
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

    `back_pre` (is_back, n_back) and `back_tables` (the (k_total,)
    bsrc, bdst endpoint tables) are one projection's backward-edge
    enumeration, read by `project_families` off its union's
    (`enumerate_families`); chain edges are forward by construction
    (callers guarantee ranks increase along chains).

    Returns (has_cycle, witness_bits (k_total,), n_backward, converged) —
    replicated across the axis when axis_name is set.
    """
    is_back, n_back = back_pre
    bsrc_full, bdst_full = back_tables
    bdst_local = jax.lax.dynamic_slice(bdst_full, (k_offset,), (k_local,))

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


def _per_edge(vals, fam_lens):
    """Per-family values broadcast over their concatenated edge blocks."""
    return jnp.concatenate([jnp.broadcast_to(vals[f], (L,))
                            for f, L in enumerate(fam_lens)])


def enumerate_families(n_nodes: int, k_tab: int, fam_lens,
                       rank, e_src, e_dst, union_mask):
    """The backward-edge enumeration of a union of edge families: ONE
    rank test and ONE E-sized cumsum for every projection that keeps
    whole families — every sweep's (`projection_scan` in the fused
    programs, `enumerate_backward` for `detect_cycles`;
    `project_families` reads one projection off it).

    Families are concatenated blocks of `fam_lens` edges.  `cum` steps
    by exactly 1 at each union-masked backward edge, so family f's j-th
    backward edge (in edge order) is the first position of f's block
    where `cum` reaches cum_start[f] + j + 1: k_tab binary searches per
    family build the endpoint tables, with no E-sized scatter (a
    scatter-max over every edge took 0.24 s at 1M-txn TPU shapes).

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
    counts of the kept families before it: the enumeration of the
    projection's own mask in edge order (ids >= n_back stay 0).
    Returns (nc_mask (E,), chain_mask (C,), back_pre (is_back, n_back),
    back_tables (bsrc, bdst) (max_k,)).
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


#: budget ceilings shared by every sweep driver (detect_cycles here,
#: grow_until_exact in device_core): past these, callers fall back to
#: the host oracle rather than approximate
MAX_K_CAP = 8192
MAX_ROUNDS_CAP = 1024


@dataclasses.dataclass
class FamilyGraph:
    """A sweep graph whose projections each keep whole edge families and
    chain groups (device arrays): the list-append checker's, whose
    projections are sets of dependency rels, or a plain graph of one
    family (`plain`).

    Non-chain edges are families concatenated in blocks of `fam_lens`
    edges under one `base_mask`; chains are groups concatenated in
    `chain_nodes` order, one mask each: chain_nodes[i] -> chain_nodes[i
    + 1] within a segment (`chain_starts` flags segment heads).  Ranks
    are unique per node and increase along chains; forward = rank
    increases.  `enumerate_backward` makes the union's backward-edge
    enumeration once (`enumeration`); each `project(...)` is then swept
    by `detect_cycles` with no E-sized rank test, cumsum or scatter of
    its own.
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

    @classmethod
    def plain(cls, n_nodes: int, rank, src, dst, mask) -> "FamilyGraph":
        """One family of edges (src -> dst where `mask`) and one empty
        chain group."""
        return cls(n_nodes=n_nodes, rank=jnp.asarray(rank),
                   nc_src=jnp.asarray(src), nc_dst=jnp.asarray(dst),
                   base_mask=jnp.asarray(mask),
                   fam_lens=(int(src.shape[0]),),
                   chain_nodes=jnp.zeros(0, jnp.int32),
                   chain_starts=jnp.zeros(0, bool),
                   chain_masks=(jnp.zeros(0, bool),))

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


def replicated(fn, mesh):
    """`fn` under a shard_map over `mesh` with every input and output
    replicated: each device runs all of it, and a sweep inside sweeps
    that device's window of the backward-edge axis."""
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P())


def _sweep_families(g: FamilyGraph, max_k: int, max_rounds: int, inc, cinc,
                    axis: Optional[str] = None, n_shards: int = 1):
    """`_sweep_window` over one projection of an enumerated graph, read
    off the union's enumeration by `project_families`: only O(max_k)
    and elementwise work before the `n_back > 0` cond.  The one entry
    to the K-window: with `axis` (inside a shard_map over a mesh axis
    of `n_shards` devices) each device sweeps max_k // n_shards
    backward-edge columns at axis_index * k_local."""
    if axis is None:
        k_local, k_offset = max_k, jnp.int32(0)
    else:
        assert max_k % n_shards == 0, (max_k, n_shards)
        k_local = max_k // n_shards
        k_offset = jax.lax.axis_index(axis) * k_local
    nc_mask, chain_mask, back_pre, tables = project_families(
        g.fam_lens, max_k, g.base_mask, g.chain_masks, g.enumeration, inc,
        cinc)
    return _sweep_window(g.n_nodes, max_k, k_local, max_rounds, g.rank,
                         g.nc_src, g.nc_dst, nc_mask, g.chain_nodes,
                         g.chain_starts, chain_mask, k_offset, back_pre,
                         tables, axis_name=axis)


def projection_scan(g: FamilyGraph, max_k: int, max_rounds: int,
                    inc_stack, cinc_stack, axis: Optional[str] = None,
                    n_shards: int = 1):
    """Sweep projections of `g` inside a traced program: one
    `enumerate_families` of the union, then one sweep instantiation
    scanned over the projections — the form of the fused checks
    (device_core, device_rw).  A Python loop would inline a while_loop
    kernel per projection (125.8 s of XLA compile at 100k-txn shapes);
    the scan keeps one (N, max_k) label plane live.

    inc_stack: (P, F) int32 — family f kept in projection p;
    cinc_stack: (P, G) int32 — chain group g kept.  `axis` and
    `n_shards` as in `_sweep_families`.  Returns (conv_all, overflow,
    cyc_bits (P,) int32).
    """
    g = dataclasses.replace(g, enumeration=enumerate_families(
        g.n_nodes, max_k, g.fam_lens, g.rank, g.nc_src, g.nc_dst,
        g.base_mask))
    count_f = g.enumeration[1]

    def proj_body(carry, mc):
        conv_all, overflow = carry
        inc, cinc = mc
        has, _, n_back_out, conv = _sweep_families(
            g, max_k, max_rounds, inc, cinc, axis=axis, n_shards=n_shards)
        carry = (conv_all & conv,
                 jnp.maximum(overflow,
                             jnp.maximum(n_back_out - max_k, 0)))
        return carry, has.astype(jnp.int32)

    # carry init derives from traced inputs so its varying-axis type
    # matches the body outputs under shard_map/vmap
    zero0 = g.nc_src[0] * 0
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
        # E-sized masking.  (Under vmap this cond lowers to select and
        # both branches still run.)
        return zero0 == 0, zero0, jnp.zeros((n_proj,), jnp.int32) + zero0

    return jax.lax.cond(jnp.sum(count_f) > 0, run_scan, no_backward,
                        operand=None)


@partial(jax.jit, static_argnames=("n_nodes", "k_tab", "fam_lens", "mesh"))
def _enumerate_kw(rank, nc_src, nc_dst, base_mask, *, n_nodes, k_tab,
                  fam_lens, mesh=None):
    def run(*a):
        return enumerate_families(n_nodes, k_tab, fam_lens, *a)

    if mesh is not None:
        # every chip enumerates the whole union, so what each projection's
        # shard_map sweep reads goes in replicated
        run = replicated(run, mesh)
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


# arrays first and statics by keyword: the AOT compile-cache seam
# (compilecache.call dispatches a cached Compiled with the dynamic args
# alone)
@partial(jax.jit, static_argnames=("n_nodes", "max_k", "max_rounds",
                                   "fam_lens", "mesh", "axis"))
def _sweep_families_kw(rank, nc_src, nc_dst, base_mask, enumeration, inc,
                       chain_nodes, chain_starts, chain_masks, cinc, *,
                       n_nodes, max_k, max_rounds, fam_lens, mesh=None,
                       axis=None):
    """One projection's sweep: single-window, or with `mesh` the
    backward-edge axis sharded over it, every input replicated."""
    n_shards = mesh.shape[axis] if mesh is not None else 1

    def run(rank, nc_src, nc_dst, base_mask, enumeration, inc, chain_nodes,
            chain_starts, chain_masks, cinc):
        g = FamilyGraph(n_nodes, rank, nc_src, nc_dst, base_mask, fam_lens,
                        chain_nodes, chain_starts, chain_masks, enumeration)
        return _sweep_families(g, max_k, max_rounds, inc, cinc, axis=axis,
                               n_shards=n_shards)

    if mesh is not None:
        run = replicated(run, mesh)
    return run(rank, nc_src, nc_dst, base_mask, enumeration, inc,
               chain_nodes, chain_starts, chain_masks, cinc)


def _run_sweep(g: FamilyProjection, max_k: int, max_rounds: int, mesh,
               axis: str):
    """Dispatch one sweep program through the AOT compile cache: verifier
    sweep chunks and checker projections pad to pow2 (N, E) classes, so
    maintenance rounds and probes share persisted executables."""
    from jepsen_tpu import compilecache

    u = g.graph
    return compilecache.call(
        "cycle-sweep.families", _sweep_families_kw, u.rank, u.nc_src,
        u.nc_dst, u.base_mask, u.enumeration,
        jnp.asarray(g.inc, jnp.int32), u.chain_nodes, u.chain_starts,
        tuple(u.chain_masks), jnp.asarray(g.cinc, jnp.int32),
        n_nodes=u.n_nodes, max_k=max_k, max_rounds=max_rounds,
        fam_lens=tuple(u.fam_lens), mesh=mesh,
        axis=axis if mesh is not None else None)


@dataclasses.dataclass
class SweepResult:
    has_cycle: bool
    witness_edge_ids: np.ndarray  # indices into the non-chain edge arrays
    n_backward: int
    converged: bool


def detect_cycles(g: FamilyGraph | FamilyProjection, max_k: int = 128,
                  max_rounds: int = 64, deadline=None, mesh=None,
                  axis: str = "batch") -> SweepResult:
    """Run the sweep; rebatch automatically if backward edges exceed max_k.

    Exact: cycle reported iff one exists in the (masked) graph, provided
    converged=True.  Witnesses identify backward edges on cycles (for the
    first max_k; enough to hand the host a subgraph to classify).

    `g` is a projection of a `FamilyGraph`, or a `FamilyGraph` meaning
    all of it.  A graph not enumerated on the same `mesh`
    (`enumerate_backward`), or whose tables are narrower than max_k, is
    enumerated here first.

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

    if isinstance(g, FamilyGraph):
        g = g.project((1,) * len(g.fam_lens), (1,) * len(g.chain_masks))
    if mesh is not None and mesh.devices.size > 1:
        n_shards = mesh.shape[axis]
        if max_k % n_shards:
            max_k = ((max_k // n_shards) + 1) * n_shards
    else:
        mesh = None
    if g.graph.k_tab < max_k:
        # not enumerated yet, or a retry outgrew the endpoint tables
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
        wit_ids = np.zeros(0, np.int64)
        host_copy = False
        if has:
            # map witness backward-edge ids back to edge-array positions
            host_copy = g.graph._host_back is None
            back_pos = g.host_backward()
            wit = np.asarray(wit)
            wit_ids = back_pos[np.nonzero(wit[:len(back_pos)])[0]]
        if telemetry.enabled():
            sp.set_attr(edges=int(g.graph.nc_src.shape[0]),
                        witnesses=len(wit_ids), mapped=has,
                        host_copy=host_copy)
    return SweepResult(has_cycle=has, witness_edge_ids=wit_ids,
                       n_backward=n_back, converged=conv)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
