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
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu.checkers.elle.device_core import (
    COUNT_NAMES,
    _verdict,
    grow_until_exact,
)
from jepsen_tpu.checkers.elle.device_infer import PaddedLA, infer, pad_packed
from jepsen_tpu.history.soa import PackedTxns
from jepsen_tpu.ops.cycle_sweep import replicated


@partial(jax.jit,
         static_argnames=("n_keys", "mesh", "axis", "max_k", "max_rounds"))
def _core_check_sharded(h: PaddedLA, n_keys: int, mesh: Mesh, axis: str,
                        max_k: int = 128, max_rounds: int = 64):
    """core_check with inference under GSPMD and the verdict in one
    shard_map, its inputs replicated: every device enumerates the whole
    union and sweeps its window of the backward-edge axis.  Same bit
    layout as device_core.core_check."""
    out = infer(h, n_keys)
    out = {k: out[k] for k in ("counts", "edges", "chains", "ranks")}
    return replicated(
        partial(_verdict, max_k=max_k, max_rounds=max_rounds, axis=axis,
                n_shards=mesh.shape[axis]), mesh)(out)


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
