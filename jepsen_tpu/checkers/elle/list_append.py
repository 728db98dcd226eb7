"""TPU list-append checker — the flagship device pipeline.

`check()` here is API-compatible with `jepsen_tpu.checkers.elle.oracle.check`
(the exact host reference) and with the capability surface of the
reference's `elle.list-append/check` (SURVEY.md §2.3): same anomaly
classification, same consistency-model verdicts.

Split of labor (mirrors the reference's SCC-on-graph / search-in-SCC split,
relocated to TPU):
  device — SoA packing -> `device_infer.infer` (version orders, non-cycle
           anomaly scans, ww/wr/rw/process/realtime edges) -> per-projection
           cycle detection via the rank-sweep kernel (`ops.cycle_sweep`).
  host   — only when a projection reports a cycle: extract the small
           offending region around witness backward edges (numpy frontier
           BFS) and classify/render the exact cycle per anomaly spec with
           the shared rel-constrained search (`graph.find_cycle`).

Fast path: a valid history never leaves the device except for O(1) flags.

If the sweep fails to converge (adversarial alternation depth; see
ops/cycle_sweep.py) the checker falls back to the host oracle — verdicts
are never approximated.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from jepsen_tpu import resilience, telemetry
from jepsen_tpu.checkers.elle import consistency, coverage, oracle
from jepsen_tpu.checkers.elle.device_infer import (
    CHAIN_RELS,
    CHAINS,
    FAMILY_RELS,
    family_graph,
    includes,
    infer,
    pad_packed,
)
from jepsen_tpu.checkers.elle.graph import (
    CycleSpec,
    EdgeList,
    find_cycle,
)
from jepsen_tpu.checkers.elle.specs import CYCLE_ANOMALY_SPECS, SPEC_ORDER
from jepsen_tpu.history.ir import HistoryIR
from jepsen_tpu.history.soa import TXN_OK, PackedTxns, pack_txns
from jepsen_tpu.ops.cycle_sweep import (
    MAX_K_CAP,
    detect_cycles,
    enumerate_backward,
)


def check(history, consistency_models: Sequence[str] = ("serializable",),
          anomalies: Sequence[str] = (), max_reported: int = 8,
          _force_no_fallback: bool = False, deadline=None, policy=None,
          plan=None) -> Dict[str, Any]:
    """Check a list-append history on device.  Accepts History / op list /
    PackedTxns.

    Resilience (ISSUE 2): `deadline` (a `resilience.Deadline`) is polled
    between device stages and per sweep projection — expiry returns
    ``{"valid?": "unknown", "error": "deadline-exceeded"}`` with
    whatever anomaly counts inference already produced.  The device
    entry points (infer, cycle sweep) run under the resilience guard:
    transient XLA failures retry per `policy`; a persistent device
    failure degrades to the host oracle with ``"degraded":
    "host-fallback"`` stamped into the result.  `plan` pins a fault
    plan (tests/chaos); default is the process-active one."""
    try:
        return _check_device(history, consistency_models, anomalies,
                             max_reported, _force_no_fallback, deadline,
                             policy, plan)
    except resilience.DeadlineExceeded:
        # expiry before/inside a device stage: the canonical unknown —
        # the sweep loop returns richer partial stats on its own
        return resilience.deadline_result(checker="list-append")
    except Exception as e:  # noqa: BLE001 — persistent device failure
        if _force_no_fallback:
            raise
        try:
            # shared degradation tail: counter + span attr + deadline
            # poll + "degraded"/"device-error" stamps (guard.py) — an
            # expired budget is never converted into a host run
            return resilience.degrade_to_host(
                "elle.list-append",
                lambda: oracle.check(history, consistency_models,
                                     anomalies,
                                     max_reported=max_reported,
                                     deadline=deadline),
                e, deadline=deadline)
        except resilience.DeadlineExceeded:
            return resilience.deadline_result(checker="list-append")


def _check_device(history, consistency_models, anomalies, max_reported,
                  _force_no_fallback, deadline, policy, plan
                  ) -> Dict[str, Any]:
    def poll(site: str) -> None:
        if deadline is not None:
            deadline.check(site)

    def dev(site: str, fn, *args):
        # guarded seam: synthetic faults fire here, transients retry;
        # a persistent failure raises out to check()'s oracle fallback
        return resilience.device_call(site, fn, *args, policy=policy,
                                      deadline=deadline, plan=plan)

    # phase spans matching the host oracle's stage names (device=True
    # distinguishes them in one trace); the spans inside a phase each end
    # at a sync the check makes anyway, so they add no device read
    ph = telemetry.phases()
    ir = history if isinstance(history, HistoryIR) else None
    if isinstance(history, PackedTxns):
        p = history
    else:
        ph.start("elle.pack", device=True)
        p = (ir.packed("list-append") if ir is not None
             else pack_txns(history, "list-append"))
    if ir is not None and ir.packed_only:
        # packed-only IR: downstream consumers (oracle fallback, session
        # coverage) must see the bare PackedTxns degradation semantics
        history = p
    if p.n_txns == 0 or not (p.txn_type == TXN_OK).any():
        ph.end()
        return {"valid?": "unknown", "anomaly-types": [], "anomalies": {},
                "not": [], "also-not": []}

    poll("elle.infer")
    ph.start("elle.infer", device=True, txns=p.n_txns)
    with telemetry.span("elle.pad") as sp:
        # the IR caches the padded layout (capacity facts + derived-order
        # columns): repeat checks over one history skip the pad entirely
        h = ir.padded("list-append") if ir is not None else pad_packed(p)
        if telemetry.enabled():
            from jepsen_tpu.parallel.batch import _stage_bytes

            sp.set_attr(T=h.txn_type.shape[0], M=h.mop_txn.shape[0],
                        R=h.rd_elems.shape[0])
            # `.nbytes` of the padded columns: no device read
            _stage_bytes(sp, h)
    # sharded-by-default (ISSUE 12): with >1 visible device and a large
    # enough history, op arrays go up with NamedSharding(P("batch")) so
    # GSPMD partitions inference, and each projection sweep runs the
    # K-axis shard_map kernel
    from jepsen_tpu.parallel import slots as _slots

    mesh = _slots.default_mesh(h.txn_type.shape[0])
    if mesh is not None:
        from jepsen_tpu.parallel.op_shard import shard_padded

        with telemetry.span("elle.stage") as sp:
            if telemetry.enabled():
                sp.set_attr(devices=mesh.devices.size)
            h, _ = shard_padded(h, mesh, "batch")
    # infer rides the AOT compile cache: shrink probes and campaign
    # cells over same-bucket histories (pad_packed pads to pow2
    # classes) share one executable instead of compiling per shape
    from jepsen_tpu import compilecache

    with telemetry.span("elle.infer.run"):
        out = dev("elle.infer",
                  lambda: compilecache.call("elle.infer", infer, h,
                                            n_keys=h.n_keys))
        counts = {k: int(v) for k, v in out["counts"].items()}

    found: Dict[str, List[Any]] = {}
    for name, cnt in counts.items():
        if cnt > 0:
            found[name] = [{"count": cnt}]

    # which anomalies to search/report
    want = set(consistency.anomalies_for_models(
        [consistency.canonical(m) for m in consistency_models]))
    want |= set(anomalies)
    want |= {"duplicate-appends", "duplicate-elements", "incompatible-order"}


    # ---- cycle anomalies: group specs by rel projection -------------------
    ph.start("elle.graph-build", device=True)
    specs = [(name, CYCLE_ANOMALY_SPECS[name]) for name in SPEC_ORDER
             if name in want]
    projections: Dict[frozenset, List[Tuple[str, CycleSpec]]] = {}
    for name, spec in specs:
        projections.setdefault(spec.rels, []).append((name, spec))

    T = h.txn_type.shape[0]
    # each projection keeps whole edge families (by rel) and chain groups
    fam = family_graph(out)

    host_edges: EdgeList = None  # lazily materialized for classification
    explainer = None             # lazily built per-edge Explainer
    needs_fallback = False
    ph.start("elle.cycle-sweep", device=True,
             projections=len(projections))
    # one backward-edge enumeration of the family union for every
    # projection's sweep
    fam = dev("elle.cycle-sweep",
              lambda: enumerate_backward(fam, mesh=mesh))
    for rels, group in projections.items():
        # deadline poll per projection: the sweep fixpoint retries
        # (grow max_k/max_rounds) can stretch a pathological history —
        # expiry returns unknown + the counts inference already found
        # (via check(), not bare expired(), so the telemetry counter
        # records the expiry site)
        if deadline is not None:
            try:
                deadline.check("elle.cycle-sweep")
            except resilience.DeadlineExceeded:
                ph.end()
                return resilience.deadline_result(
                    **{"anomaly-types": sorted(found),
                       "anomalies": found, "not": [], "also-not": [],
                       "partial": "cycle-sweep interrupted"})
        g = fam.project(*includes(rels))
        res = dev("elle.cycle-sweep",
                  lambda g=g: detect_cycles(g, deadline=deadline,
                                            mesh=mesh))
        if not res.converged:
            needs_fallback = True
            break
        if not res.has_cycle:
            continue
        # ---- host classification over witness regions --------------------
        with telemetry.span("elle.classify") as sp:
            if host_edges is None:
                host_edges = _materialize_host_edges(fam, out["chains"])
            proj = host_edges.project(rels)
            regions = _witness_regions(
                proj, np.asarray(fam.nc_src), np.asarray(fam.nc_dst),
                res.witness_edge_ids, 2 * T, limit=16)
            n_found = 0
            for name, spec in group:
                hit = None
                for region in regions:
                    hit = find_cycle(region, proj, spec)
                    if hit is not None:
                        break
                if hit is not None:
                    if explainer is None:
                        from jepsen_tpu.checkers.elle.explain import \
                            la_explainer

                        explainer = la_explainer(
                            p, {k: np.asarray(v)
                                for k, v in out["order"].items()})
                    found.setdefault(name, []).append(
                        {"cycle": _render(hit, p, T, explainer),
                         "witnesses": int(len(res.witness_edge_ids))})
                    n_found += 1
            if telemetry.enabled():
                sp.set_attr(regions=len(regions), found=n_found)

    if needs_fallback:
        ph.end()
        if _force_no_fallback:
            raise RuntimeError("cycle sweep did not converge")
        poll("elle.host-fallback")
        # pass the ORIGINAL input: an op-level history keeps its session
        # checkability through the fallback (packing drops it); the
        # budget follows — the oracle polls it itself now
        with telemetry.span("elle.host-fallback") as sp:
            if telemetry.enabled():
                sp.set_attr(reason=("max-k-cap" if res.n_backward > MAX_K_CAP
                                    else "not-converged"),
                            n_backward=res.n_backward)
            return oracle.check(history, consistency_models, anomalies,
                                max_reported=max_reported,
                                deadline=deadline)

    # session-guarantee tokens run the dedicated per-process checker —
    # after the fallback decision, so a non-converged sweep doesn't do
    # the (host-side) session walk twice (see coverage.py for the
    # PackedTxns degradation rule)
    poll("elle.sessions")
    ph.start("elle.sessions", device=False)
    sess_found, sess_checked = coverage.run_la_sessions(
        history, want, isinstance(history, PackedTxns),
        max_reported=max_reported)
    for k, v in sess_found.items():
        found.setdefault(k, []).extend(v)
    ph.end()

    # shared verdict tail (oracle.boundary_verdict): the device pipeline
    # reached this point only with committed txns (the no-ok case early-
    # returned unknown above), so has_ok is True by construction
    with telemetry.span("elle.verdict") as sp:
        verdict = oracle.boundary_verdict(found, consistency_models, want,
                                          has_ok=True,
                                          sess_checked=sess_checked)
        if telemetry.enabled():
            sp.set_attr(valid=verdict["valid?"])
    return verdict


def _materialize_host_edges(fam, chains) -> EdgeList:
    """Pull a `family_graph`'s edges + chain-implied edges into a host
    EdgeList."""
    src = np.asarray(fam.nc_src)
    dst = np.asarray(fam.nc_dst)
    m = np.asarray(fam.base_mask)
    rel_of = np.repeat(np.asarray(FAMILY_RELS, np.int8), fam.fam_lens)
    parts_s = [src[m]]
    parts_d = [dst[m]]
    parts_r = [rel_of[m]]
    for cname, rel in zip(CHAINS, CHAIN_RELS):
        nodes, starts, cm = (np.asarray(x) for x in chains[cname])
        ok = cm[:-1] & cm[1:] & ~starts[1:]
        parts_s.append(nodes[:-1][ok])
        parts_d.append(nodes[1:][ok])
        parts_r.append(np.full(int(ok.sum()), rel, np.int8))
    e = EdgeList()
    e.src = np.concatenate(parts_s).astype(np.int32)
    e.dst = np.concatenate(parts_d).astype(np.int32)
    e.rel = np.concatenate(parts_r).astype(np.int8)
    return e


def _csr(n: int, src: np.ndarray, dst: np.ndarray):
    order = np.argsort(src, kind="stable")
    ss, dd = src[order], dst[order]
    starts = np.searchsorted(ss, np.arange(n + 1))
    return dd, starts


def _bfs_reach(n: int, src, dst, roots: np.ndarray) -> np.ndarray:
    """Boolean reachability from roots via numpy frontier expansion."""
    dd, starts = _csr(n, src, dst)
    seen = np.zeros(n, bool)
    seen[roots] = True
    frontier = np.unique(roots)
    while len(frontier):
        outs = np.concatenate([dd[starts[v]:starts[v + 1]] for v in frontier]) \
            if len(frontier) < 1024 else _expand_all(dd, starts, frontier)
        outs = outs[~seen[outs]]
        if not len(outs):
            break
        seen[outs] = True
        frontier = np.unique(outs)
    return seen


def _expand_all(dd, starts, frontier):
    counts = starts[frontier + 1] - starts[frontier]
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, dd.dtype)
    idx = np.repeat(starts[frontier], counts) + \
        (np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts))
    return dd[idx]


def _witness_regions(proj: EdgeList, e_src, e_dst, witness_ids, n_nodes,
                     limit: int = 16) -> List[np.ndarray]:
    """Nodes on cycles through each witness backward edge (u -> w):
    forward-reach(w) ∩ reverse-reach(u) in the projection."""
    regions = []
    for wid in witness_ids[:limit]:
        u, w = int(e_src[wid]), int(e_dst[wid])
        fwd = _bfs_reach(n_nodes, proj.src, proj.dst, np.array([w]))
        bwd = _bfs_reach(n_nodes, proj.dst, proj.src, np.array([u]))
        nodes = np.nonzero(fwd & bwd)[0]
        if len(nodes):
            regions.append(nodes.astype(np.int64))
    return regions


def _render(cyc, p: PackedTxns, T: int, explainer=None):
    """Collapse barrier hops and emit reported edges, each carrying the
    Explainer's per-edge justification (key, values, why) — the
    reference's `elle/core.clj` Explainer output shape.  Single shared
    implementation in `txn_cycles._render_cycle`."""
    from jepsen_tpu.checkers.elle.txn_cycles import _render_cycle

    return _render_cycle(cyc, explainer, T, np.asarray(p.txn_orig_index))
