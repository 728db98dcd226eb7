"""Hybrid 2D checking: batch data-parallelism × K-axis sweep sharding.

The multi-host shape (SURVEY.md §5 "Distributed communication backend":
ICI collectives within a host/pod slice, DCN across hosts; §2.7 "Batched
multi-history DP").  The mesh has two axes:

  dcn — one batch shard per row (across hosts on a real pod: the only
        cross-row traffic is the final per-history bit vectors, so this
        axis can ride the slow DCN links)
  k   — the backward-edge windows of `parallel/op_shard.py` within a
        row (the per-round meta-graph all_gather + convergence psum stay
        on ICI)

Each (dcn-row, history) pair runs the full fused inference locally
(replicated along `k`, like `op_shard.shard_padded`'s fallback) and
sweeps only its (N, max_k/n_k) label-plane window — so a 100 × 1M-op
batch (BASELINE config 5) divides both ways: histories across rows,
label-plane memory across `k`.

On a real multi-host pod build the mesh with
`jax.experimental.mesh_utils.create_hybrid_device_mesh((n_k,), (n_dcn,))`
so `dcn` crosses hosts; on one host `make_hybrid_mesh` reshapes the
local devices.  Verdicts are bitwise-identical to unsharded
`check_batch` (differential-tested on the virtual mesh,
tests/test_parallel.py).
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu import resilience
from jepsen_tpu.checkers.elle.device_core import _verdict
from jepsen_tpu.checkers.elle.device_infer import infer
from jepsen_tpu.history.soa import PackedTxns
from jepsen_tpu.parallel.batch import (
    batch_caps,
    pad_batch,
    summarize_batch_bits,
)


def make_hybrid_mesh(n_dcn: int, n_k: int, devices=None) -> Mesh:
    devs = np.asarray(devices if devices is not None else jax.devices())
    assert devs.size >= n_dcn * n_k, (devs.size, n_dcn, n_k)
    return Mesh(devs[:n_dcn * n_k].reshape(n_dcn, n_k), ("dcn", "k"))


@partial(jax.jit, static_argnames=("n_keys", "mesh", "max_k", "max_rounds"))
def _hybrid_core(batch, n_keys: int, mesh: Mesh, max_k: int = 128,
                 max_rounds: int = 64):
    bspec = P("dcn")

    @partial(jax.shard_map, mesh=mesh, in_specs=(bspec,),
             out_specs=(bspec, bspec))
    def rows(b):
        def one(h):
            return _verdict(infer(h, n_keys), max_k, max_rounds, axis="k",
                            n_shards=mesh.shape["k"])

        return jax.vmap(one)(b)

    return rows(batch)


def check_batch_hybrid(ps: Sequence[PackedTxns], mesh: Mesh,
                       max_k: int = 128, max_rounds: int = 64,
                       deadline=None, plan=None, policy=None
                       ) -> List[dict]:
    """Check a batch of histories over a 2D ("dcn", "k") mesh; one
    summary dict per history (the `check_batch` row shape).

    The batch is padded to a multiple of the dcn axis with copies of the
    first history (dropped from the results).  Inexact verdicts
    (overflow / non-convergence) are re-run alone through the exact
    single-device path rather than approximated.  The 2D dispatch is a
    guarded fault-plan site (``parallel.hybrid``) like the other
    sharded seams.
    """
    from jepsen_tpu import telemetry
    from jepsen_tpu.parallel.batch import _stage_bytes

    n_dcn = mesh.shape["dcn"]
    n_k = mesh.shape["k"]
    if max_k % n_k:
        max_k = ((max_k // n_k) + 1) * n_k

    caps = batch_caps(ps)
    n_real = len(ps)
    fill = (-n_real) % n_dcn
    with telemetry.span("parallel.hybrid", histories=n_real,
                        dcn=n_dcn, k=n_k, max_k=max_k) as sp:
        batch = pad_batch(list(ps) + [ps[0]] * fill, caps)
        batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("dcn"))),
            batch)
        _stage_bytes(sp, batch)

        from jepsen_tpu import compilecache

        bits, over = resilience.device_call(
            "parallel.hybrid",
            lambda: compilecache.call(
                "parallel.hybrid", _hybrid_core, batch,
                n_keys=batch.n_keys, mesh=mesh, max_k=max_k,
                max_rounds=max_rounds),
            deadline=deadline, plan=plan, policy=policy)
        return summarize_batch_bits(bits, over, batch, batch.n_keys,
                                    n_real, k_floor=max_k)
