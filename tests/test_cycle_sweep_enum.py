"""The cycle sweep over one backward-edge enumeration per graph
(`cycle_sweep.FamilyGraph`, `enumerate_backward`) against a host
reference that shares no sweep code (a numpy rank test and host
reachability over each projection): the same verdict, backward count,
convergence and witness edge ids on every graph, in the list-append
checker's five-family form and in the one-family form of `txn_cycles`
and the verifier, one chip and sharded over a 4-device mesh.

Also: `projection_scan`'s outputs on one fixed graph, as they were
before it shared `enumerate_families`; the union tables against a numpy
enumeration; no edge-sized scatter or rank gather in the per-projection
program outside its `n_back > 0` cond, and no edge scatter in the rw
fused program's version sweep; one `sweep.enumerate` span per
list-append check.  The witness map against a numpy map of the sweep
program's own witness bits, and its spans: a map (and one host copy of
the union's enumeration per check) only after a sweep that found a
cycle.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from jepsen_tpu.ops import cycle_sweep as cs

#: five edge families (ww, wr, rw, tb, bt in the checker) and two chain
#: groups (process, barrier)
FAM_LENS = (128, 96, 160, 48, 48)
CHAIN_LENS = (96, 64)
N_NODES = 256
#: (family include flags, chain-group include flags) per projection: the
#: checker's rel sets, and a few it never asks for
PROJECTIONS = (
    ((1, 0, 0, 0, 0), (0, 0)),
    ((1, 1, 0, 0, 0), (0, 0)),
    ((1, 1, 1, 0, 0), (0, 0)),
    ((1, 1, 1, 0, 0), (1, 0)),
    ((1, 1, 1, 1, 1), (0, 1)),
    ((1, 1, 1, 1, 1), (1, 1)),
    ((0, 0, 1, 0, 0), (0, 0)),
    ((0, 1, 1, 1, 1), (1, 1)),
    ((0, 0, 0, 0, 0), (1, 1)),
)
#: graph cases: (seed, backward edges, the planted cycles or not)
CASES = {
    "no-backward": (1, 0, False),
    "backward-acyclic": (2, 6, False),
    "backward-cycle": (3, 6, True),
    "past-max-k": (4, 300, True),
}


def _graph(seed, n_back, cycle, back_fam=None, n_nodes=N_NODES,
           fam_lens=FAM_LENS, chain_lens=CHAIN_LENS):
    """numpy arrays of a graph with `n_back` masked-in backward edges
    (and 8 masked out), all out of source nodes that no other edge or
    chain enters.  With `cycle`, two planted cycles, the only ones: a ->
    b -> c forward in family 0 and c -> a backward in family 2; and q ->
    p backward in family 1 with p -> q the first segment of chain group
    0, where no other edge or chain enters q."""
    rng = np.random.default_rng(seed)
    rank = rng.permutation(n_nodes).astype(np.int32)
    by_rank = np.argsort(rank)
    sources = rng.choice(by_rank[n_nodes // 2:], 16, replace=False)
    a, b, c, p, q = by_rank[[n_nodes * i // 16 for i in (2, 4, 6, 1, 7)]]
    inner = np.setdiff1d(np.arange(n_nodes), np.append(sources, q))
    E = sum(fam_lens)
    starts = np.cumsum((0,) + tuple(fam_lens))
    fam_of = np.repeat(np.arange(len(fam_lens)), fam_lens)

    # forward edges: into inner nodes only, from a lower rank
    x = rng.choice(n_nodes, 8 * E)
    y = rng.choice(inner, 8 * E)
    ok = rank[x] < rank[y]
    src, dst = x[ok][:E].copy(), y[ok][:E].copy()
    mask = rng.random(E) < 0.9

    free = np.ones(E, bool)
    for k, on in ((n_back, True), (8, False)):
        pool = np.nonzero(free & ((fam_of == back_fam)
                                  if back_fam is not None else True))[0]
        pos = rng.choice(pool, k, replace=False)
        src[pos] = rng.choice(sources, k)
        dst[pos] = [rng.choice(inner[rank[inner] < rank[s]])
                    for s in src[pos]]
        mask[pos], free[pos] = on, False
    if cycle:
        for f, (s, d) in ((0, (a, b)), (0, (b, c)), (2, (c, a)),
                          (1, (q, p))):
            pos = rng.choice(np.nonzero(free & (fam_of == f))[0])
            src[pos], dst[pos], mask[pos], free[pos] = s, d, True, False

    def chain(n, head=()):
        # `head`, then 6 segments rising in rank in random order, then
        # 4 pads
        m = n - 4 - len(head)
        nodes = rng.choice(np.setdiff1d(inner, head), m, replace=False)
        nodes = nodes[np.argsort(rank[nodes])]
        cut = np.sort(rng.choice(np.arange(1, m), 5, replace=False))
        segs = np.split(np.arange(m), cut)
        order = np.concatenate([segs[i] for i in rng.permutation(6)])
        heads = np.isin(np.arange(m), [0, *cut])
        return (np.concatenate([head, nodes[order], np.zeros(4, np.int64)]),
                np.concatenate([np.arange(len(head)) == 0, heads[order],
                                np.ones(4, bool)]),
                np.concatenate([np.ones(n - 4, bool), np.zeros(4, bool)]))

    chains = [chain(chain_lens[0], (p, q) if cycle else ()),
              chain(chain_lens[1])]
    return dict(rank=rank, src=src.astype(np.int32),
                dst=dst.astype(np.int32), mask=mask,
                fam_lens=tuple(fam_lens), chains=chains, starts=starts)


def _family_graph(gr):
    (pn, ps, pm), (bn, bs, bm) = gr["chains"]
    return cs.FamilyGraph(
        n_nodes=len(gr["rank"]), rank=jnp.asarray(gr["rank"]),
        nc_src=jnp.asarray(gr["src"]), nc_dst=jnp.asarray(gr["dst"]),
        base_mask=jnp.asarray(gr["mask"]), fam_lens=gr["fam_lens"],
        chain_nodes=jnp.asarray(np.concatenate([pn, bn]).astype(np.int32)),
        chain_starts=jnp.asarray(np.concatenate([ps, bs])),
        chain_masks=(jnp.asarray(pm), jnp.asarray(bm)))


def _projection_edges(gr, inc, cinc):
    """(src, dst, mask) of the projection: every family edge, masked in
    where its family is kept, then the edges the kept chain groups imply
    (each masked-in chain node to the next in its segment)."""
    keep = np.repeat(np.asarray(inc) > 0, gr["fam_lens"])
    src, dst, mask = [gr["src"]], [gr["dst"]], [gr["mask"] & keep]
    for on, (nodes, starts, m) in zip(cinc, gr["chains"]):
        if on:
            idx = np.nonzero(m)[0]
            seg = np.cumsum(starts)[idx]
            same = seg[1:] == seg[:-1]
            src.append(nodes[idx[:-1][same]])
            dst.append(nodes[idx[1:][same]])
            mask.append(np.ones(int(same.sum()), bool))
    return (np.concatenate(src).astype(np.int32),
            np.concatenate(dst).astype(np.int32), np.concatenate(mask))


def _host_sweep(rank, src, dst, mask):
    """The reference: backward edges by a numpy rank test, and a
    backward edge u -> w a witness iff host reachability over the masked
    edges leads from w to u.  Past MAX_K_CAP backward edges the sweep
    hands the check to the host: converged False, no witnesses."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import breadth_first_order

    back = np.nonzero(mask & (rank[src] >= rank[dst]))[0]
    if len(back) > cs.MAX_K_CAP:
        return types.SimpleNamespace(
            has_cycle=None, witness_edge_ids=np.zeros(0, np.int64),
            n_backward=len(back), converged=False)
    n = len(rank)
    adj = csr_matrix((np.ones(int(mask.sum())), (src[mask], dst[mask])),
                     shape=(n, n))
    wit = np.asarray([e for e in back if src[e] in breadth_first_order(
        adj, dst[e], return_predecessors=False)], np.int64)
    return types.SimpleNamespace(has_cycle=len(wit) > 0,
                                 witness_edge_ids=wit,
                                 n_backward=len(back), converged=True)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("batch",)) if n > 1 else None


def _same(got, want):
    """`got` as the host reference `want` has it (a sweep handed to the
    host has no verdict to compare)."""
    assert (got.n_backward, got.converged) == \
        (want.n_backward, want.converged)
    if want.converged:
        assert got.has_cycle is want.has_cycle
    assert np.array_equal(got.witness_edge_ids, want.witness_edge_ids)


def _numpy_back(gr, inc=(1, 1, 1, 1, 1)):
    src, dst, rank = gr["src"], gr["dst"], gr["rank"]
    keep = np.repeat(np.asarray(inc) > 0, gr["fam_lens"])
    return gr["mask"] & keep & (rank[src] >= rank[dst])


@pytest.mark.parametrize("form", ["families", "one-family"])
@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_sweep_matches_the_host_reference(case, chips, form):
    """Each projection swept as a projection of the five-family graph,
    or as its own one-family graph (`FamilyGraph.plain`) whose edges are
    the projection's, chain edges included."""
    seed, n_back, cycle = CASES[case]
    gr = _graph(seed, n_back, cycle)
    assert int(_numpy_back(gr).sum()) == n_back + 2 * cycle
    mesh = _mesh(chips)
    fam = cs.enumerate_backward(_family_graph(gr), mesh=mesh)
    for inc, cinc in PROJECTIONS:
        src, dst, mask = _projection_edges(gr, inc, cinc)
        if form == "families":
            g = fam.project(inc, cinc)
        else:
            g = cs.FamilyGraph.plain(N_NODES, gr["rank"], src, dst, mask)
        got = cs.detect_cycles(g, mesh=mesh)
        _same(got, _host_sweep(gr["rank"], src, dst, mask))
        assert got.converged
        assert got.n_backward == int(_numpy_back(gr, inc).sum())
        # the planted cycles: where families 0 and 2 are kept, and where
        # family 1 and chain group 0 are
        on = [bool(cycle and inc[0] and inc[2]),
              bool(cycle and inc[1] and cinc[0])]
        assert got.has_cycle is any(on)
        assert len(got.witness_edge_ids) == sum(on)


@pytest.mark.parametrize("chips", [1, 4])
def test_a_retry_past_the_tables_enumerates_again(chips):
    from jepsen_tpu import telemetry

    gr = _graph(4, 300, True)
    mesh = _mesh(chips)
    fam = cs.enumerate_backward(_family_graph(gr), mesh=mesh)
    assert fam.k_tab == 128
    inc, cinc = (1, 1, 1, 1, 1), (1, 1)
    c = telemetry.activate()
    try:
        got = cs.detect_cycles(fam.project(inc, cinc), mesh=mesh)
    finally:
        telemetry.deactivate(c)
    names = [sp.name for sp in c.roots]
    # 128, then one enumeration and one sweep at the grown max_k
    assert names == ["sweep.call", "sweep.enumerate", "sweep.call",
                     "sweep.witness-map"]
    assert c.roots[1].attrs["k_tab"] == 512 == c.roots[2].attrs["max_k"]
    _same(got, _host_sweep(gr["rank"], *_projection_edges(gr, inc, cinc)))


@pytest.mark.parametrize("chips", [1, 4])
def test_past_the_cap_both_hand_to_the_host(chips):
    """More backward edges than MAX_K_CAP: no retry, converged False and
    no witnesses, as the host reference reads it."""
    gr = _graph(5, cs.MAX_K_CAP + 200, False, back_fam=2, n_nodes=1024,
                fam_lens=(64, 64, cs.MAX_K_CAP + 256, 32, 32))
    mesh = _mesh(chips)
    fam = cs.enumerate_backward(_family_graph(gr), mesh=mesh)
    for inc, cinc in ((1, 1, 1, 0, 0), (0, 0)), ((1, 1, 0, 0, 0), (1, 1)):
        got = cs.detect_cycles(fam.project(inc, cinc), mesh=mesh)
        _same(got, _host_sweep(gr["rank"], *_projection_edges(gr, inc,
                                                              cinc)))
    assert got.converged is True and got.n_backward == 0
    got = cs.detect_cycles(fam.project((0, 0, 1, 0, 0), (0, 0)), mesh=mesh)
    assert got.n_backward == cs.MAX_K_CAP + 200 and not got.converged
    assert len(got.witness_edge_ids) == 0


def test_union_tables_are_the_numpy_enumeration():
    gr = _graph(4, 300, True)
    back_all, count_f, fsrc, fdst = jax.jit(
        cs.enumerate_families, static_argnums=(0, 1, 2))(
        N_NODES, 512, FAM_LENS, jnp.asarray(gr["rank"]),
        jnp.asarray(gr["src"]), jnp.asarray(gr["dst"]),
        jnp.asarray(gr["mask"]))
    want = _numpy_back(gr)
    assert np.array_equal(np.asarray(back_all), want)
    for f, (lo, hi) in enumerate(zip(gr["starts"][:-1], gr["starts"][1:])):
        pos = lo + np.nonzero(want[lo:hi])[0]
        assert int(count_f[f]) == len(pos)
        row_s, row_d = np.asarray(fsrc[f]), np.asarray(fdst[f])
        assert np.array_equal(row_s[:len(pos)], gr["src"][pos])
        assert np.array_equal(row_d[:len(pos)], gr["dst"][pos])
        assert not row_s[len(pos):].any() and not row_d[len(pos):].any()


#: `projection_scan` on `_graph(3, 6, True)` and on `_graph(4, 300,
#: True)` at max_k 128 and 512, over the fused checkers' five projections:
#: (converged, overflow, cycle bit per projection), as computed before
#: the scan read its enumeration from `enumerate_families`
SCAN_GOLDEN = {
    (3, 6, 128): (True, 0, [0, 0, 1, 1, 1]),
    (4, 300, 128): (True, 174, [0, 0, 0, 1, 0]),
    (4, 300, 512): (True, 0, [0, 0, 1, 1, 1]),
}


@pytest.mark.parametrize("seed,n_back,max_k", sorted(SCAN_GOLDEN))
def test_projection_scan_outputs_unchanged(seed, n_back, max_k):
    from jepsen_tpu.checkers.elle.device_core import (
        PROJECTIONS as FUSED,
        chain_include_stack,
        proj_include_stack,
    )

    gr = _graph(seed, n_back, True)
    g = _family_graph(gr)

    @jax.jit
    def scan(rank, src, dst, mask, cn, cst, cms):
        return cs.projection_scan(
            cs.FamilyGraph(N_NODES, rank, src, dst, mask, g.fam_lens, cn,
                           cst, cms),
            max_k, 64, proj_include_stack(FUSED), chain_include_stack(FUSED))

    conv, over, bits = scan(g.rank, g.nc_src, g.nc_dst, g.base_mask,
                            g.chain_nodes, g.chain_starts, g.chain_masks)
    assert (bool(conv), int(over), np.asarray(bits).tolist()) == \
        SCAN_GOLDEN[(seed, n_back, max_k)]


def _top_level_edge_ops(jaxpr, n_edges):
    """Scatters and gathers over `n_edges` indices in `jaxpr`, outside any
    cond branch (inner jits are looked into), each with the shape of the
    array it reads or writes."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            continue
        if name.startswith("scatter") or name == "gather":
            idx = eqn.invars[1].aval
            if idx.ndim and idx.shape[0] == n_edges:
                found.append((name, tuple(eqn.invars[0].aval.shape)))
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                found += _top_level_edge_ops(inner, n_edges)
    return found


def test_no_edge_scatter_or_rank_gather_before_the_cond():
    gr = _graph(3, 6, True)
    fam = cs.enumerate_backward(_family_graph(gr))
    g = fam.project((1, 1, 1, 0, 0), (1, 0))
    E = sum(FAM_LENS)
    new = jax.make_jaxpr(lambda *a: cs._sweep_families_kw(
        *a, n_nodes=N_NODES, max_k=128, max_rounds=64,
        fam_lens=FAM_LENS))(
        fam.rank, fam.nc_src, fam.nc_dst, fam.base_mask, fam.enumeration,
        jnp.asarray(g.inc, jnp.int32), fam.chain_nodes, fam.chain_starts,
        fam.chain_masks, jnp.asarray(g.cinc, jnp.int32))
    assert _top_level_edge_ops(new.jaxpr, E) == []

    # the rw fused program's version sweep: before its cond, only its
    # enumeration's rank test (two gathers of the version ranks) and no
    # scatter of its edges into a (max_k,)-sized endpoint table
    from jepsen_tpu.checkers.elle import device_rw
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.history.soa import pack_txns
    from jepsen_tpu.workloads import synth

    p = pack_txns(synth.rw_history(n_txns=200, n_keys=6, seed=3),
                  "rw-register")
    h = pad_packed(p)
    max_k = 40
    rw = jax.make_jaxpr(lambda h: device_rw.rw_core_check(
        h, p.n_keys, max_k=max_k))(h)
    ops = _top_level_edge_ops(rw.jaxpr, h.mop_txn.shape[0])
    n_versions = h.rd_elems.shape[0] + p.n_keys
    assert [op for op, shape in ops if shape == (n_versions,)] == \
        ["gather", "gather"]
    assert not [op for op, shape in ops if op.startswith("scatter")
                and shape in ((max_k,), (max_k + 1,))]


@pytest.mark.parametrize("cycle", [False, True])
def test_one_enumerate_span_per_check(cycle):
    from jepsen_tpu import telemetry
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.workloads import synth

    h = synth.la_history(n_txns=120, n_keys=5, concurrency=4, seed=11)
    if cycle:
        assert synth.inject_wr_cycle(h)
    c = telemetry.activate()
    try:
        with telemetry.span("check"):
            res = list_append.check(h, ["strict-serializable"])
    finally:
        telemetry.deactivate(c)
    assert res["valid?"] is (not cycle)
    (root,) = c.roots
    sweep = next(x for x in root.children if x.name == "elle.cycle-sweep")
    names = [x.name for x in sweep.children]
    assert names.count("sweep.enumerate") == 1
    assert names[0] == "sweep.enumerate" and "sweep.call" in names
    n_union = sweep.children[0].attrs["n_backward_union"]
    calls = [x for x in sweep.children if x.name == "sweep.call"]
    # every projection's backward set lies in the union's
    assert all(x.attrs["n_backward"] <= n_union for x in calls)
    assert n_union >= cycle


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("case", ["backward-acyclic", "backward-cycle",
                                  "past-max-k"])
def test_witness_ids_are_the_numpy_map_of_the_sweep_bits(case, chips):
    """`detect_cycles`' witness ids on every projection are the edge
    positions of `mask & rank[src] >= rank[dst]` at the witness bits the
    sweep program itself returns (mapped only after a cycle, from the
    union's host copy)."""
    seed, n_back, cycle = CASES[case]
    gr = _graph(seed, n_back, cycle)
    mesh = _mesh(chips)
    fam = cs.enumerate_backward(_family_graph(gr), mesh=mesh)
    wide = cs.enumerate_backward(_family_graph(gr), k_tab=512, mesh=mesh)
    n_cyclic = 0
    for inc, cinc in PROJECTIONS:
        got = cs.detect_cycles(fam.project(inc, cinc), mesh=mesh)
        back = _numpy_back(gr, inc)
        # the budget `detect_cycles` grows to: 128, else the next pow2
        max_k = 128 if back.sum() <= 128 else cs._pow2(int(back.sum()))
        has, wit, nb, conv = cs._run_sweep(wide.project(inc, cinc), max_k,
                                           64, mesh, "batch")
        assert (int(nb), bool(conv)) == (int(back.sum()), True)
        pos = np.nonzero(back)[0]
        want = pos[np.nonzero(np.asarray(wit)[:len(pos)])[0]]
        assert np.array_equal(got.witness_edge_ids, want)
        assert got.has_cycle is bool(has) is (len(want) > 0)
        n_cyclic += got.has_cycle
    assert (n_cyclic > 0) is cycle


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("cycle", [False, True])
def test_witness_map_only_after_a_cycle(cycle, chips, monkeypatch):
    from jepsen_tpu import telemetry
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.workloads import synth

    monkeypatch.setenv("JEPSEN_SHARDS", str(chips))
    h = synth.la_history(n_txns=120, n_keys=5, concurrency=4, seed=11)
    if cycle:
        assert synth.inject_wr_cycle(h)
    c = telemetry.activate()
    try:
        for _ in range(2):
            with telemetry.span("check"):
                res = list_append.check(h, ["strict-serializable"])
            assert res["valid?"] is (not cycle)
    finally:
        telemetry.deactivate(c)
    assert len(c.roots) == 2
    for root in c.roots:
        sweep = next(x for x in root.children
                     if x.name == "elle.cycle-sweep")
        calls = [x for x in sweep.children if x.name == "sweep.call"]
        maps = [x for x in sweep.children if x.name == "sweep.witness-map"]
        # still one map span per sweep
        assert len(maps) == len(calls) == sweep.attrs["projections"]
        assert all(x.attrs["sharded"] is (chips > 1) for x in calls)
        mapped = [m for m in maps if m.attrs["mapped"]]
        copies = [m for m in maps if m.attrs["host_copy"]]
        assert all(m.attrs["witnesses"] == 0 for m in maps
                   if not m.attrs["mapped"])
        if cycle:
            assert mapped and all(m.attrs["witnesses"] >= 1
                                  for m in mapped)
            # the first map builds the host copy; the rest reuse it
            assert copies == mapped[:1]
        else:
            assert mapped == [] and copies == []
