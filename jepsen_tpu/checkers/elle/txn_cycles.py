"""Shared cycle-anomaly detection over host-built txn dependency edges.

Used by checkers whose edge inference runs host-side (rw-register) but
whose cycle *detection* still rides the device rank-sweep kernel — the
same split `list_append` uses with device-built edges.  Falls back to host
Tarjan + spec search when the device is unavailable or the sweep doesn't
converge (exactness first).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu.checkers.elle.graph import (
    REL_NAMES,
    CycleSpec,
    EdgeList,
    find_cycle,
    nontrivial_sccs,
)
from jepsen_tpu.checkers.elle.specs import CYCLE_ANOMALY_SPECS, SPEC_ORDER


def cycle_anomalies(edges: EdgeList, n_nodes: int, rank: np.ndarray,
                    want: set, use_device: bool = True,
                    max_reported: int = 4, explainer=None,
                    n_txns: int = None,
                    orig_index: np.ndarray = None) -> Dict[str, List[dict]]:
    """Find cycle anomalies among `want` specs over the given edges.

    rank: per-node order where most edges go forward (completion order);
    used by the device sweep.  Returns {anomaly: [witness dicts]}.

    `explainer(src, rel_name, dst) -> dict` (see `explain.py`) adds
    per-edge justification fields to each reported cycle edge — the
    reference's Explainer protocol.  When `n_txns` is given, nodes >=
    n_txns (realtime barrier nodes) are collapsed out of reported
    cycles; `orig_index` maps internal txn ids to history indices.
    """
    specs = [(name, CYCLE_ANOMALY_SPECS[name]) for name in SPEC_ORDER
             if name in want]
    projections: Dict[frozenset, List[Tuple[str, CycleSpec]]] = {}
    for name, spec in specs:
        projections.setdefault(spec.rels, []).append((name, spec))

    found: Dict[str, List[dict]] = {}
    for rels, group in projections.items():
        proj = edges.project(rels)
        if not len(proj):
            continue
        regions = _cycle_regions(proj, n_nodes, rank, use_device)
        if regions is None:
            continue
        for name, spec in group:
            for region in regions[:max_reported * 4]:
                hit = find_cycle(region, proj, spec)
                if hit is not None:
                    found.setdefault(name, []).append(
                        {"cycle": _render_cycle(hit, explainer, n_txns,
                                                orig_index)})
                    break
    return found


def _render_cycle(hit, explainer, n_txns, orig_index) -> List[dict]:
    """Emit reported edges: collapse barrier hops (nodes >= n_txns) into
    single realtime edges, map ids to history indices, and attach the
    Explainer's justification per edge."""
    if n_txns is None:
        return [{"src": int(s), "rel": REL_NAMES[r], "dst": int(d)}
                for (s, r, d) in hit]
    out = []
    pend_src = None
    k = next((i for i, (s, _, _) in enumerate(hit) if s < n_txns), 0)
    hit = hit[k:] + hit[:k]
    for (s, r, d) in hit:
        if d >= n_txns:
            if s < n_txns:
                pend_src = s
            continue
        src = s if s < n_txns else pend_src
        rel_name = REL_NAMES[r]
        edge = {"src": int(orig_index[src]) if orig_index is not None and
                src is not None and src < len(orig_index) else src,
                "rel": rel_name,
                "dst": int(orig_index[d]) if orig_index is not None and
                d < len(orig_index) else int(d)}
        if explainer is not None and src is not None:
            edge.update(explainer(int(src), rel_name, int(d)))
        out.append(edge)
    return out


def _padded_edges(src: np.ndarray, dst: np.ndarray):
    """(src, dst, mask) padded to the next power of two with masked-off
    edges at the end, so that histories of one size share their sweep
    programs and witness ids still index the real edges."""
    n = len(src)
    cap = 1 << max(n - 1, 0).bit_length()
    out_src = np.zeros(cap, np.int32)
    out_dst = np.zeros(cap, np.int32)
    out_src[:n], out_dst[:n] = src, dst
    return out_src, out_dst, np.arange(cap) < n


def _cycle_regions(proj: EdgeList, n_nodes: int, rank: np.ndarray,
                   use_device: bool):
    """Node regions containing cycles, or None if the projection is
    acyclic.  Device path: rank sweep -> witness backward edges -> local
    BFS regions.  Host path: Tarjan SCCs, exact, also where the device
    path raises, does not converge or maps its witnesses to no region
    (an `elle.host-fallback` span with the reason)."""
    reason = None
    if use_device:
        try:
            from jepsen_tpu.ops.cycle_sweep import FamilyGraph, detect_cycles

            res = detect_cycles(FamilyGraph.plain(
                n_nodes, rank, *_padded_edges(proj.src, proj.dst)))
            if not res.converged:
                reason = "not-converged"
            elif not res.has_cycle:
                return None
            else:
                from jepsen_tpu.checkers.elle.list_append import (
                    _witness_regions,
                )
                regions = _witness_regions(
                    proj, proj.src, proj.dst, res.witness_edge_ids, n_nodes)
                if regions:
                    return regions
                reason = "no-regions"
        except Exception as e:  # noqa: BLE001 -- the host path is exact
            reason = f"device-error:{type(e).__name__}"
    if reason is None:
        sccs = nontrivial_sccs(n_nodes, proj.src, proj.dst)
    else:
        with telemetry.span("elle.host-fallback", reason=reason):
            sccs = nontrivial_sccs(n_nodes, proj.src, proj.dst)
    return sccs if sccs else None
