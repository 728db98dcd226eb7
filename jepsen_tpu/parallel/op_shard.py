"""Single-history checking sharded across a device mesh (config 4).

The reference's scaling wall is ONE giant history on ONE JVM (SURVEY.md
§2.7 "SCC / cycle search": bifurcan's Tarjan is single-threaded; upstream
`elle/txn.clj cycles!` runs it on the whole graph).  This module is the
TPU answer for that axis — BASELINE.json config 4, a 10M-op list-append
history on a v5e-8 — decomposed TPU-first rather than by translating
Tarjan:

1. **Edge inference** runs under one jit whose *inputs are sharded along
   the op/mop axes* (GSPMD): XLA partitions the elementwise scans and
   segment ops and inserts the collectives the data flow needs.  The
   packing order guarantees mops of one txn are contiguous, so sorted-run
   computations parallelize along the mop axis naturally.

2. **Cycle sweep** is sharded over the *backward-edge axis* K with
   shard_map: each device owns K/n_dev backward edges and propagates only
   their (N, K/n_dev) reachability label planes — columns are fully
   independent (the expensive part: at 10M ops the full label planes are
   (20M x 128) int8 = 2.5 GB *per projection*; sharding K divides both
   that memory and the propagation FLOPs by the mesh size).  The only
   cross-device coupling is the (K, K) meta-graph — assembled with one ICI
   `all_gather` of the local meta rows, after which every device computes
   the trivial closure redundantly.  Convergence flags combine with a
   `psum`.

Verdicts are bitwise-identical to the single-device `core_check` — tested
differentially (tests/test_parallel.py) per the determinism-as-oracle
rule (SURVEY.md §5).

Since ISSUE 12 this module is the ENGINE under the sharded-by-default
path: `device_core.core_check_auto` / `core_check_exact` /
`list_append.check` resolve a mesh via `parallel.slots.default_mesh`
and dispatch through `_core_check_sharded` + `shard_padded` directly.
`check_sharded` remains as the explicit opt-in wrapper (superseded as
an entry point — docs/IR.md).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu.checkers.elle.device_core import (
    COUNT_NAMES,
    PROJECTIONS,
    grow_until_exact,
)
from jepsen_tpu.checkers.elle.device_infer import PaddedLA, infer, pad_packed
from jepsen_tpu.history.soa import PackedTxns
from jepsen_tpu.ops.cycle_sweep import _sweep_window


def projection_sweep_bits(out, max_k: int, sweep):
    """The 5-projection scan over an inferred edge set, with `sweep` a
    callable (rank, e_src, e_dst, mask, chain_nodes, chain_starts,
    chain_mask, back_pre, back_tables) -> (has_cycle, witness, n_back,
    converged); back_pre is the hoisted backward enumeration (is_back,
    n_back) and back_tables the searchsorted-built (max_k,) (bsrc,
    bdst) endpoint pair that `_sweep_window` consumes directly.

    One sweep instantiation scanned over the 5 projections — same
    compile-time + label-plane-memory rationale as device_core.core_check
    (5 inlined while_loop kernels measured 125.8 s of XLA compile at
    100k-txn shapes in round 2).  Shared by the K-axis sharded path and
    the 2D hybrid (dcn x k) path (`parallel/hybrid.py`).  Since round 5
    this delegates to `cycle_sweep.projection_scan` — family-include
    flags plus ONE shared E-sized backward cumsum — instead of
    materializing (5, E)/(5, C) mask stacks and re-running 5 cumsums
    (VERDICT r04 item 2; the single-device paths migrated in round 4,
    PROFILE.md §0b).
    """
    edges = out["edges"]
    chains = out["chains"]
    rank = jnp.concatenate([out["ranks"]["txn"], out["ranks"]["barrier"]])
    e_src = jnp.concatenate([edges[k][0] for k in ("ww", "wr", "rw", "tb",
                                                   "bt")])
    e_dst = jnp.concatenate([edges[k][1] for k in ("ww", "wr", "rw", "tb",
                                                   "bt")])

    pc_nodes, pc_starts, pc_mask = chains["process"]
    bc_nodes, bc_starts, bc_mask = chains["barrier"]
    chain_nodes = jnp.concatenate([pc_nodes, bc_nodes])
    chain_starts = jnp.concatenate([pc_starts, bc_starts])

    from jepsen_tpu.checkers.elle.device_core import (
        chain_include_stack,
        proj_include_stack,
    )
    from jepsen_tpu.ops.cycle_sweep import projection_scan

    # max_rounds is owned by the sweep closure (unused when sweep is set)
    conv_all, overflow, cyc_bits = projection_scan(
        rank.shape[0], max_k, 0, rank, e_src, e_dst,
        [edges[k][2] for k in ("ww", "wr", "rw", "tb", "bt")],
        proj_include_stack(PROJECTIONS),
        chain_nodes, chain_starts, [pc_mask, bc_mask],
        chain_include_stack(PROJECTIONS), sweep=sweep)

    counts = jnp.stack([out["counts"][n].astype(jnp.int32)
                        for n in COUNT_NAMES])
    bits = jnp.concatenate(
        [counts, cyc_bits, conv_all.astype(jnp.int32)[None]])
    return bits, overflow


@partial(jax.jit,
         static_argnames=("n_keys", "mesh", "axis", "max_k", "max_rounds"))
def _core_check_sharded(h: PaddedLA, n_keys: int, mesh: Mesh, axis: str,
                        max_k: int = 128, max_rounds: int = 64):
    """core_check with the sweep's backward-edge axis sharded over the
    mesh.  Same bit layout as device_core.core_check."""
    n_shards = mesh.shape[axis]
    assert max_k % n_shards == 0, (max_k, n_shards)
    k_local = max_k // n_shards

    out = infer(h, n_keys)
    T = h.txn_type.shape[0]
    rep = P()

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(rep,) * 11, out_specs=(rep, rep, rep, rep))
    def sharded_sweep(rank_, e_src_, e_dst_, m_, cn_, cs_, cm_,
                      ib_, nb_, bsrc_, bdst_):
        off = jax.lax.axis_index(axis) * k_local
        return _sweep_window(2 * T, max_k, k_local, max_rounds,
                             rank_, e_src_, e_dst_, m_, cn_, cs_, cm_,
                             k_offset=off, axis_name=axis,
                             back_pre=(ib_, nb_),
                             back_tables=(bsrc_, bdst_))

    return projection_sweep_bits(
        out, max_k,
        lambda r, s, d, m, cn, cs, cm, bp, bt: sharded_sweep(
            r, s, d, m, cn, cs, cm, *bp, *bt))


def shard_padded(h: PaddedLA, mesh: Mesh, axis: str = "dp"
                 ) -> tuple[PaddedLA, bool]:
    """device_put a padded history with its op/mop/element axes sharded
    along the mesh axis (GSPMD input shardings for edge inference).

    Arrays whose leading dim doesn't divide the mesh (padded capacities
    are powers of two, so e.g. a 6-device mesh never divides) are
    replicated instead — inference then runs unsharded but the K-axis
    sweep sharding (the dominant cost at scale) still applies.  Returns
    (placed history, inference_sharded) — False means every array was
    replicated, a fact callers must surface (a user on a 6-device mesh
    should be able to see that input sharding didn't happen)."""
    n = mesh.shape[axis]
    sharded = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    any_sharded = False

    def put(x):
        nonlocal any_sharded
        divisible = x.ndim > 0 and x.shape[0] % n == 0
        any_sharded = any_sharded or divisible
        return jax.device_put(x, sharded if divisible else replicated)

    placed = dataclasses.replace(jax.tree_util.tree_map(put, h),
                                 spmd=n > 1)
    return placed, any_sharded


def check_sharded(p: PackedTxns | PaddedLA, mesh: Optional[Mesh] = None,
                  axis: str = "dp", max_k: int = 128,
                  max_rounds: int = 64, deadline=None, plan=None,
                  policy=None) -> dict:
    """Check ONE history sharded across the mesh; summary dict like a
    `check_batch` row.  Falls back to growing budgets (like
    `core_check_exact`) when the sweep overflows.  `deadline` bounds
    the grow loop (resilience contract; expiry raises
    `DeadlineExceeded`); the sharded dispatch itself is a guarded
    fault-plan site (``parallel.op-shard``), so JEPSEN_FAULTS chaos
    reaches the K-axis sharded sweep too."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.parallel.batch import _stage_bytes

    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (axis,))
    h = p if isinstance(p, PaddedLA) else pad_packed(p)
    n_keys = h.n_keys
    n_shards = mesh.shape[axis]
    with telemetry.span("parallel.op-shard", shards=n_shards,
                        max_k=max_k) as sp:
        h, infer_sharded = shard_padded(h, mesh, axis)
        _stage_bytes(sp, h)
        sp.set_attr(inference_sharded=infer_sharded)
        if max_k % n_shards:
            # non-power-of-two meshes: round the budget up to a mesh
            # multiple
            max_k = ((max_k // n_shards) + 1) * n_shards

        bits, over = grow_until_exact(
            lambda k, r: _core_check_sharded(h, n_keys, mesh, axis,
                                             max_k=k, max_rounds=r),
            max_k, max_rounds, round_to=n_shards, deadline=deadline,
            site="parallel.op-shard", plan=plan, policy=policy)
        over_i = int(np.asarray(over))

    row = np.asarray(bits)
    counts = {n: int(row[j]) for j, n in enumerate(COUNT_NAMES)}
    cycles = [bool(x) for x in row[len(COUNT_NAMES):-1]]
    converged = bool(row[-1]) and over_i == 0
    invalid = any(v > 0 for v in counts.values()) or any(cycles)
    return {
        "valid?": (not invalid) if converged else "unknown",
        "counts": counts,
        "cycles": {
            "G0": cycles[0], "G1c": cycles[1], "G2-family": cycles[2],
            "G2-family-process": cycles[3],
            "G2-family-realtime": cycles[4],
        },
        "exact": converged,
        # False = input arrays were replicated (leading dims don't divide
        # the mesh); the K-axis sweep sharding still applied
        "inference-sharded": infer_sharded,
    }
