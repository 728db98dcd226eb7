"""The user's entry for list-append histories:
`jepsen_tpu.checkers.elle.list_append.check(packed, models)`.

The only module of the benchmark that imports the program.  It turns the
generator's columns into the program's `PackedTxns` (a fresh object per
history, so no padded layout is cached between checks) and reads the
answer a user reads: the verdict and the anomaly types.

A check the program answers from its host oracle did not run on the
device.  Its resilience guard stamps that answer `degraded`; a sweep
that does not converge falls back to `oracle.check` with no stamp, so
`check` watches that function for the length of each check and marks
any answer it gave (`HOST_ORACLE`).
"""

from __future__ import annotations

import numpy as np

#: stamps the entry sets when a check did not run on the device
FALLBACK_STAMPS = ("degraded", "device-error")
#: the mark `check` puts on an answer the host oracle gave
HOST_ORACLE = "benchmark-host-oracle"
#: the program's telemetry spans around each phase of one check
SPANS = ("elle.pack", "elle.infer", "elle.graph-build", "elle.cycle-sweep",
         "elle.sessions")


class _ValNames:
    """val id -> (key id, element): what `PackedTxns.val_names` holds,
    without a million Python tuples."""

    def __init__(self, val_key, val_value):
        self._k, self._v = val_key, val_value

    def __len__(self):
        return len(self._k)

    def __getitem__(self, v):
        return (int(self._k[v]), int(self._v[v]))


def prepare(h: dict):
    from jepsen_tpu.history.soa import TXN_OK, PackedTxns

    T = len(h["txn_process"])
    return PackedTxns(
        txn_type=np.full(T, TXN_OK, np.int8),
        txn_process=h["txn_process"],
        txn_invoke_pos=h["txn_invoke_pos"],
        txn_complete_pos=h["txn_complete_pos"],
        txn_orig_index=h["txn_complete_pos"].copy(),
        mop_txn=h["mop_txn"], mop_kind=h["mop_kind"], mop_key=h["mop_key"],
        mop_val=h["mop_val"], mop_rd_start=h["mop_rd_start"],
        mop_rd_len=h["mop_rd_len"], rd_elems=h["rd_elems"],
        key_names=list(range(h["n_keys"])),
        val_names=_ValNames(h["val_key"], h["val_value"]),
        n_events=h["n_events"])


def check(packed, models):
    from jepsen_tpu.checkers.elle import list_append, oracle

    calls = []
    real = oracle.check

    def watched(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    oracle.check = watched
    try:
        res = list_append.check(packed, list(models))
    finally:
        oracle.check = real
    return {**res, HOST_ORACLE: len(calls)} if calls else res


def answer(result: dict) -> dict:
    return {"valid?": result.get("valid?"),
            "anomaly-types": sorted(result.get("anomaly-types", []))}


def fell_back(result: dict) -> bool:
    return any(s in result for s in FALLBACK_STAMPS + (HOST_ORACLE,))

