"""The user's entry for rw-register histories:
`jepsen_tpu.checkers.elle.rw_register.check(packed, models)`.

The only module of the benchmark for this family that imports the
program.  It turns the generator's columns into the program's
`PackedTxns` (a fresh object per history): a read has `mop_rd_len` 0 and
the value id it read, or -1 for nil, in `mop_val`; no read lists.  It
reads the answer a user reads: the verdict and the anomaly types.

A check of a large history goes first to the fused device program
(`device_rw.check`); a verdict of valid from it is the answer, anything
else goes on to the host report, whose cycle search runs the device
sweep.  An answer not decided on the device is marked: one stamped
`degraded` or `device-error`; one of a check in which `device_rw.check`
did not run or did not come back exact; one that the host report found
valid after the fused program, exact, had found it invalid (the fused
verdict flags any G2-family cycle, process and realtime included, so a
write skew, which snapshot isolation allows, is answered by the report's
numpy inference on the host); one in which the report's sweep fell back
to host Tarjan (the `nontrivial_sccs` that `txn_cycles` runs under its
`elle.host-fallback` span).  `check` watches both functions for the
length of each check (`HOST_ANSWER`).
"""

from __future__ import annotations

import numpy as np

from benchmark.entries.elle_list_append import _ValNames

#: stamps the program sets when a check did not run on the device
FALLBACK_STAMPS = ("degraded", "device-error")
#: the mark `check` puts on an answer not decided on the device
HOST_ANSWER = "benchmark-host-answer"
#: the program's telemetry spans inside one check
SPANS = ("elle.rw-core-check", "elle.pad", "rw.core-call",
         "elle.host-fallback")


def prepare(h: dict):
    from jepsen_tpu.history.soa import TXN_FAIL, TXN_OK, PackedTxns

    M = len(h["mop_txn"])
    rd = h["mop_kind"] == 1
    return PackedTxns(
        txn_type=np.where(h["txn_ok"], TXN_OK, TXN_FAIL).astype(np.int8),
        txn_process=h["txn_process"],
        txn_invoke_pos=h["txn_invoke_pos"],
        txn_complete_pos=h["txn_complete_pos"],
        txn_orig_index=h["txn_complete_pos"].copy(),
        mop_txn=h["mop_txn"], mop_kind=h["mop_kind"], mop_key=h["mop_key"],
        mop_val=h["mop_val"],
        mop_rd_start=np.full(M, -1, np.int32),
        mop_rd_len=np.where(rd, 0, -1).astype(np.int32),
        rd_elems=np.zeros(0, np.int32),
        key_names=list(range(h["n_keys"])),
        val_names=_ValNames(h["val_key"], h["val_value"]),
        n_events=int(h["n_events"]))


def check(packed, models):
    from jepsen_tpu.checkers.elle import device_rw, rw_register, txn_cycles

    fused, host = [], []
    real_fused, real_sccs = device_rw.check, txn_cycles.nontrivial_sccs

    def watched_fused(*a, **kw):
        res = real_fused(*a, **kw)
        fused.append((bool(res.get("exact")), res.get("valid?")))
        return res

    def watched_sccs(*a, **kw):
        host.append(1)
        return real_sccs(*a, **kw)

    device_rw.check, txn_cycles.nontrivial_sccs = watched_fused, watched_sccs
    try:
        res = rw_register.check(packed, list(models))
    finally:
        device_rw.check, txn_cycles.nontrivial_sccs = real_fused, real_sccs
    # the fused verdict is the answer only where it stands: exact, and
    # not overturned to valid by the host report
    on_device = (len(fused) == 1 and fused[0][0] and not host and
                 not (fused[0][1] is False and res.get("valid?") is True))
    return res if on_device else {**res, HOST_ANSWER: True}


def answer(result: dict) -> dict:
    return {"valid?": result.get("valid?"),
            "anomaly-types": sorted(result.get("anomaly-types", []))}


def fell_back(result: dict) -> bool:
    return any(s in result for s in FALLBACK_STAMPS + (HOST_ANSWER,))
