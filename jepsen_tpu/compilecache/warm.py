"""Bucket-ladder pre-warm: populate the AOT store at service start.

:func:`warm_ladder` walks the default bucket ladder
(:data:`bucket.LADDER`, overridable via ``--sizes``, capped/extended to
``--max-txns``'s bucket) and ensures every rung's checker
executables exist in the persistent store — so the first shrink probe,
campaign cell, or fleet claim of a known shape class pays dispatch,
not compile.

Per rung and family it warms the same programs the live dispatchers
route (the warmed class label must equal the live one, or the warm is
useless — pinned by tests/test_compilecache.py):

- ``la``: `elle.infer` (the classification pipeline's program) and the
  fused `elle.core-check` — or, when `parallel.slots.default_mesh`
  resolves a mesh for the rung, the sharded `parallel.op-shard`
  program the auto path would dispatch;
- ``rw``: the fused `elle.rw-core-check`.

Fused/infer programs are lowered at abstract ``ShapeDtypeStruct``
shapes (no multi-GB arrays held through the compile); the sharded
program is lowered from concretely placed shards, since its executable
bakes the input shardings.

Every rung is individually guarded: a failed warm records the error
and moves on (``compilecache.warm`` is a chaos seam —
``fuzz_faults.py --compilecache`` pins that injected warm faults never
wedge the ladder or corrupt the store).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional

from jepsen_tpu import compilecache
from jepsen_tpu.compilecache import bucket

__all__ = ["warm_ladder", "warm_one"]

#: generator defaults shared with `utils.prestage` — warming any other
#: shape would populate classes no default cell ever dispatches
_LA_KW = dict(concurrency=10, mops_per_txn=4, read_frac=0.25, seed=7)
_RW_KW = dict(concurrency=10, mops_per_txn=3, read_frac=0.5, seed=11)


def _keys_for(n_txns: int) -> int:
    return max(64, n_txns // 8)


def _sds(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def warm_one(family: str, n_txns: int, max_k: int = 128,
             max_rounds: int = 64) -> List[Dict[str, Any]]:
    """Warm one rung of one family; one record per program ensured."""
    from jepsen_tpu.workloads import synth

    compilecache._fire(compilecache.SITE_WARM)
    recs: List[Dict[str, Any]] = []
    nk = _keys_for(n_txns)
    if family == "la":
        from jepsen_tpu.checkers.elle.device_core import core_check
        from jepsen_tpu.checkers.elle.device_infer import infer, \
            pad_packed
        from jepsen_tpu.parallel import slots

        p = synth.packed_la_history(n_txns=n_txns, n_keys=nk, **_LA_KW)
        h = pad_packed(p)
        mesh = slots.default_mesh(h.txn_type.shape[0])
        hs = _sds(h)
        recs.append(_ensure("elle.infer", infer, (hs,),
                            {"n_keys": p.n_keys}))
        if mesh is not None:
            from jepsen_tpu.parallel.op_shard import \
                _core_check_sharded, shard_padded

            n = mesh.shape["batch"]
            mk = max_k if max_k % n == 0 else ((max_k // n) + 1) * n
            h2, _ = shard_padded(h, mesh, "batch")
            recs.append(_ensure(
                "parallel.op-shard", _core_check_sharded, (h2,),
                {"n_keys": p.n_keys, "mesh": mesh, "axis": "batch",
                 "max_k": mk, "max_rounds": max_rounds}))
        else:
            recs.append(_ensure(
                "elle.core-check", core_check, (hs,),
                {"n_keys": p.n_keys, "max_k": max_k,
                 "max_rounds": max_rounds}))
        del h, hs
    elif family == "rw":
        from jepsen_tpu.checkers.elle.device_rw import pad_packed, \
            rw_core_check

        p = synth.packed_rw_history(n_txns=n_txns, n_keys=nk, **_RW_KW)
        h = pad_packed(p)
        recs.append(_ensure(
            "elle.rw-core-check", rw_core_check, (_sds(h),),
            {"n_keys": h.n_keys, "max_k": max_k,
             "max_rounds": max_rounds, "rw_cap": h.mop_txn.shape[0]}))
        del h
    else:
        raise ValueError(f"unknown warm family {family!r}")
    return recs


def _ensure(site: str, jitfn, args: tuple,
            static: dict) -> Dict[str, Any]:
    t0 = time.perf_counter()
    rec = {"site": site,
           "class": bucket.class_label(site, args, static)}
    try:
        rec["how"] = compilecache.ensure(site, jitfn, *args, **static)
    except Exception as e:  # noqa: BLE001 — a rung must not stop the
        # ladder (the chaos contract); the error is the record
        rec["how"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["seconds"] = round(time.perf_counter() - t0, 3)
    return rec


def warm_ladder(sizes: Optional[Iterable[int]] = None,
                max_txns: Optional[int] = None,
                families: Iterable[str] = ("la", "rw"),
                max_k: int = 128, max_rounds: int = 64,
                verbose: bool = False) -> List[Dict[str, Any]]:
    """Warm every (rung, family) cell of the ladder; returns one record
    per rung with its program records + wall seconds."""
    out: List[Dict[str, Any]] = []
    for n in bucket.ladder(max_txns=max_txns, sizes=sizes):
        for fam in families:
            t0 = time.perf_counter()
            try:
                programs = warm_one(fam, n, max_k=max_k,
                                    max_rounds=max_rounds)
                rec = {"rung": n, "family": fam, "ok": all(
                    p.get("how") != "error" for p in programs),
                    "programs": programs}
            except Exception as e:  # noqa: BLE001 — see warm_one
                rec = {"rung": n, "family": fam, "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
            rec["seconds"] = round(time.perf_counter() - t0, 3)
            out.append(rec)
            if verbose:
                print(f"cache warm: {fam}@{n} "
                      f"{'ok' if rec['ok'] else 'FAILED'} "
                      f"({rec['seconds']:.1f}s)", flush=True)
    return out
