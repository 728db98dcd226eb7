"""Single-jit core verdict function for list-append histories.

`core_check` = device_infer + cycle sweeps over a fixed projection set,
fused into one jittable, vmap-able, shard_map-able function of the padded
SoA arrays.  Returns a compact anomaly bitmap — the form used by the
benchmark, the graft entry point, and the batched/sharded checking path
(BASELINE.json config 5).  Host-side cycle classification (naming the
exact cycle) lives in `list_append.check`; this core answers the
valid/invalid question entirely on device.

Projection set (covers strict-serializable checking, the strongest graded
config):
  0: ww                       (G0)
  1: ww+wr                    (G1c)
  2: ww+wr+rw                 (G-single / G2-item family)
  3: ww+wr+rw+process         (strong-session variants)
  4: ww+wr+rw+realtime        (strict/strong variants)

Bit layout of the result:  [duplicate-appends, duplicate-elements,
incompatible-order, G1a, G1b, dirty-update, internal,
cycle-proj0..cycle-proj4, converged]
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from jepsen_tpu.checkers.elle.device_infer import (
    PaddedLA,
    family_graph,
    includes,
    infer,
)
from jepsen_tpu.checkers.elle.graph import REL_CODES
from jepsen_tpu.ops.cycle_sweep import (
    MAX_K_CAP,
    MAX_ROUNDS_CAP,
    projection_scan,
)

N_COUNT_BITS = 7
PROJECTIONS = (
    ("ww",),
    ("ww", "wr"),
    ("ww", "wr", "rw"),
    ("ww", "wr", "rw", "process"),
    ("ww", "wr", "rw", "realtime"),
)
COUNT_NAMES = ("duplicate-appends", "duplicate-elements",
               "incompatible-order", "G1a", "G1b", "dirty-update",
               "internal")


def _cc(site, jitfn, *args, **static):
    """Route one device dispatch through the AOT compile cache: memory
    table -> persisted executable -> compile+persist, falling through
    to the plain jit call on any failure (see jepsen_tpu.compilecache).
    Statics go by keyword so the cached Compiled can be dispatched with
    the dynamic args alone."""
    from jepsen_tpu import compilecache

    return compilecache.call(site, jitfn, *args, **static)


def proj_include_stack(projections=PROJECTIONS) -> jnp.ndarray:
    """(P, F) family-include flags of `device_infer.family_graph`."""
    return jnp.asarray([includes({REL_CODES[n] for n in p})[0]
                        for p in projections], jnp.int32)


def chain_include_stack(projections=PROJECTIONS) -> jnp.ndarray:
    """(P, G) chain-group include flags of `device_infer.family_graph`."""
    return jnp.asarray([includes({REL_CODES[n] for n in p})[1]
                        for p in projections], jnp.int32)


def _verdict(out, max_k: int, max_rounds: int, axis=None, n_shards: int = 1):
    """Sweep half of the core check: infer output -> (bits, overflowed).
    Plain function — jitted fused with infer by `core_check`, or as its
    own (much smaller) XLA program by `core_check_staged`; with `axis`
    (inside a shard_map over a mesh axis of `n_shards` devices) each
    device sweeps its window of the backward-edge axis."""
    conv_all, overflow, cyc_bits = projection_scan(
        family_graph(out), max_k, max_rounds,
        proj_include_stack(PROJECTIONS), chain_include_stack(PROJECTIONS),
        axis=axis, n_shards=n_shards)

    counts = jnp.stack([out["counts"][n].astype(jnp.int32)
                        for n in COUNT_NAMES])
    bits = jnp.concatenate(
        [counts, cyc_bits, conv_all.astype(jnp.int32)[None]])
    return bits, overflow


@partial(jax.jit, static_argnames=("n_keys", "max_k", "max_rounds"))
def core_check(h: PaddedLA, n_keys: int, max_k: int = 128,
               max_rounds: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (bits, overflowed):
    bits: (13,) int32 — counts/flags per the module docstring, last slot is
    converged (1 = trustworthy).
    overflowed: int32 — max backward edges seen beyond max_k (0 = exact).
    """
    return _verdict(infer(h, n_keys), max_k, max_rounds)


@partial(jax.jit, static_argnames=("n_keys",))
def _infer_stage(h: PaddedLA, n_keys: int):
    # only the keys _verdict consumes: materializing the full infer dict
    # would keep the R-sized order table (+ witnesses) live in HBM at
    # exactly the 10M shapes this path exists for — the fused program
    # dead-code-eliminates them, so the staged one must drop them too
    out = infer(h, n_keys)
    return {k: out[k] for k in ("counts", "edges", "chains", "ranks")}


@partial(jax.jit, static_argnames=("max_k", "max_rounds"))
def _sweep_stage(out, max_k: int, max_rounds: int):
    return _verdict(out, max_k, max_rounds)


def core_check_staged(h: PaddedLA, n_keys: int, max_k: int = 128,
                      max_rounds: int = 64,
                      verbose: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """core_check as TWO separately-compiled XLA programs (infer, then
    sweep) with the intermediate edge/chain arrays materialized on
    device.

    Bitwise-equal to `core_check` (same `_verdict` body; the only
    difference is the stage boundary).  Halves per-program compile
    complexity at 2^24-txn shapes, where the one fused program has
    never been seen to compile.  Whether the chip's compiler needs the
    split is open until a chip compile of the 2^24 bucket (ROADMAP
    3.2).  The lost infer→sweep fusion only re-reads the materialized
    COO edges (~3 GB at 10M shapes)."""
    import time as _time

    t0 = _time.perf_counter()
    out = _cc("elle.core-check.infer", _infer_stage, h, n_keys=n_keys)
    jax.block_until_ready(out)
    if verbose:
        print(f"  staged: infer {_time.perf_counter() - t0:.1f}s",
              flush=True)
    t0 = _time.perf_counter()
    res = _cc("elle.core-check.sweep", _sweep_stage, out, max_k=max_k,
              max_rounds=max_rounds)
    jax.block_until_ready(res)
    if verbose:
        print(f"  staged: sweep {_time.perf_counter() - t0:.1f}s",
              flush=True)
    return res




# Padded txn capacity from which TPU callers dispatch to the staged split
# (bitwise-equal; see core_check_staged for why it exists and what would
# retire it).  Other backends always run the fused program.
STAGED_T_THRESHOLD = 1 << 24


def _use_staged(h: PaddedLA) -> bool:
    """One definition of the fused-vs-staged boundary, shared by
    core_check_auto and core_check_exact so they can't drift."""
    return h.txn_type.shape[0] >= STAGED_T_THRESHOLD and \
        jax.default_backend() == "tpu"


def _sharded_dispatch(h: PaddedLA, n_keys: int, max_k: int,
                      max_rounds: int, mesh):
    """The sharded-by-default core (ISSUE 12): op arrays placed with
    NamedSharding(P("batch")) for GSPMD inference, K-axis sweep under
    shard_map — verdicts bitwise-identical to `core_check`."""
    from jepsen_tpu.parallel.op_shard import _core_check_sharded, \
        shard_padded

    n = mesh.shape["batch"]
    if max_k % n:
        max_k = ((max_k // n) + 1) * n
    h, _ = shard_padded(h, mesh, "batch")
    return _cc("parallel.op-shard", _core_check_sharded, h,
               n_keys=n_keys, mesh=mesh, axis="batch", max_k=max_k,
               max_rounds=max_rounds)


def core_check_auto(h: PaddedLA, n_keys: int, max_k: int = 128,
                    max_rounds: int = 64):
    """Shape-aware dispatch between the mesh-sharded default (>1 visible
    device and a large enough history — `parallel.slots.default_mesh`),
    `core_check` (fused) and `core_check_staged` — the single boundary
    every large-shape caller (bench, stream.py, core_check_exact)
    shares."""
    from jepsen_tpu.parallel import slots

    mesh = slots.default_mesh(h.txn_type.shape[0])
    if mesh is not None:
        return _sharded_dispatch(h, n_keys, max_k, max_rounds, mesh)
    if _use_staged(h):
        return core_check_staged(h, n_keys, max_k=max_k,
                                 max_rounds=max_rounds)
    return _cc("elle.core-check", core_check, h, n_keys=n_keys,
               max_k=max_k, max_rounds=max_rounds)


def grow_until_exact(run, max_k: int = 128, max_rounds: int = 64,
                     round_to: int = 1, deadline=None,
                     site: str = "elle.core-check", plan=None,
                     policy=None):
    """Host-side rebatch policy, shared by every fused-check caller.

    `run(max_k, max_rounds)` -> (bits, overflowed).  If the sweep
    overflows its backward-edge budget, retry with the budget grown past
    the observed count (rounded up to a multiple of `round_to` — mesh
    size for sharded sweeps); if the fixpoint hits max_rounds, retry with
    doubled rounds.  Gives up (returning the last, inexact result) only
    at the caps — callers then fall back to the host oracle.

    `deadline` (a `resilience.Deadline`) is polled before each fixpoint
    retry: the grow loop is the unbounded part of the fused check, and
    a checker time budget must bound it (expiry raises
    `DeadlineExceeded`, which `check_safe` maps to an unknown verdict).
    Each `run` dispatch goes through the resilience guard — transient
    device failures retry, injected faults land here in chaos mode.
    `site`/`plan`/`policy` let callers label and pin that ONE guard
    (e.g. the sharded sweeps use site "parallel.op-shard") — callers
    must NOT wrap `run` in a second device_call: nested guards multiply
    retries (attempts²) and double-advance the fault plan's call
    counter, breaking the deterministic replay contract.
    """
    import numpy as np

    from jepsen_tpu import resilience

    while True:
        if deadline is not None:
            deadline.check("elle.grow-until-exact")
        bits, over = resilience.device_call(
            site, run, max_k, max_rounds, deadline=deadline, plan=plan,
            policy=policy)
        over_i = int(np.asarray(over))
        conv = int(np.asarray(bits)[-1]) == 1
        if over_i > 0 and max_k < MAX_K_CAP:
            need = max_k + over_i
            while max_k < need:
                max_k *= 2
            max_k = min(max_k, MAX_K_CAP)
            if max_k % round_to:
                max_k = ((max_k // round_to) + 1) * round_to
            continue
        if not conv and over_i == 0 and max_rounds < MAX_ROUNDS_CAP:
            max_rounds = min(max_rounds * 2, MAX_ROUNDS_CAP)
            continue
        return bits, over


def core_check_exact(h: PaddedLA, n_keys: int, max_k: int = 128,
                     max_rounds: int = 64, deadline=None):
    """core_check with host-side rebatching until exact.  Returns
    (bits, overflowed) like core_check; exact iff bits[-1] == 1 and
    overflowed == 0.  `deadline` bounds the grow loop (see
    grow_until_exact).  Takes the mesh-sharded default path when
    `parallel.slots.default_mesh` resolves one."""
    from jepsen_tpu.parallel import slots

    mesh = slots.default_mesh(h.txn_type.shape[0])
    if mesh is not None:
        from jepsen_tpu.parallel.op_shard import _core_check_sharded, \
            shard_padded

        n = mesh.shape["batch"]
        h2, _ = shard_padded(h, mesh, "batch")
        if max_k % n:
            max_k = ((max_k // n) + 1) * n
        return grow_until_exact(
            lambda k, r: _cc("parallel.op-shard", _core_check_sharded,
                             h2, n_keys=n_keys, mesh=mesh, axis="batch",
                             max_k=k, max_rounds=r),
            max_k, max_rounds, round_to=n, deadline=deadline)
    if _use_staged(h):
        # staged split: infer is independent of max_k/max_rounds, so a
        # budget retry re-runs only the (cheap-on-acyclic) sweep stage —
        # the fused program had to redo inference every retry
        out = _cc("elle.core-check.infer", _infer_stage, h,
                  n_keys=n_keys)
        jax.block_until_ready(out)
        return grow_until_exact(
            lambda k, r: _cc("elle.core-check.sweep", _sweep_stage, out,
                             max_k=k, max_rounds=r),
            max_k, max_rounds, deadline=deadline)
    return grow_until_exact(
        lambda k, r: _cc("elle.core-check", core_check, h,
                         n_keys=n_keys, max_k=k, max_rounds=r),
        max_k, max_rounds, deadline=deadline)
