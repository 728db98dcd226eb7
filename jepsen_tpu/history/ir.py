"""One canonical packed-history IR for every checker family (ISSUE 12).

Before this module each checker family packed its own arrays — elle
list-append/rw packing (`history/soa.py`), the invariants matrices
(`checkers/invariants/packed.py`), knossos's entry table
(`checkers/knossos/prep.py`) — and a composed check over one history
re-derived each of them from the op list.  :class:`HistoryIR` is the
single carrier: built once per history, it memoizes

- the SoA transactional packing per workload kind (``PackedTxns``:
  txn/mop/read-element columns),
- the padded device layout (``PaddedLA``) including the static
  capacity/layout facts and the pad-time derived-order columns
  (run permutation, per-key longest-read table, process/realtime
  orders) that `device_infer.infer` consumes instead of re-sorting
  in-program — see docs/IR.md for the exact column set,
- the rw dependency inference (``RwInference``: writer maps, version
  edges, per-key chain ranks, ww/wr/rw + process/realtime orders)
  shared by the predicate and session invariants checkers,
- the bank balance matrix (``PackedBank``), and
- the knossos linearizability entry table (``LinOp`` rows).

``HistoryIR`` subclasses :class:`~jepsen_tpu.history.ops.History` and
*shares* the source history's op list and pair index, so every
non-IR-aware consumer (stats folds, timeline, perf, the host oracles)
keeps working unchanged — the IR is a History that also remembers its
packings.  ``checkers.api.Compose`` wraps each checked history once, so
a composed run derives each section exactly once.

Versioning: ``IR_VERSION`` stamps the layout contract (bump when a
column's meaning changes); the padded layout's static facts
(`PaddedLA.v_cap/o_cap/...`) are part of v2.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from jepsen_tpu.history.ops import History
from jepsen_tpu.history.soa import PackedTxns, pack_txns

__all__ = ["IR_VERSION", "HistoryIR"]


def _booked(build):
    """Run one cache-miss section build, booking its wall as
    ``host_pack_s`` phase self-time on the enclosing telemetry span
    (ISSUE 16 phase classification) — memoized hits pay nothing."""
    from jepsen_tpu.telemetry import spans as _spans

    t0 = time.perf_counter()
    out = build()
    _spans.add_phase("host_pack_s", time.perf_counter() - t0)
    return out

#: layout contract version: v1 = the implicit per-family packings,
#: v2 = this module (capacity facts + pad-time derived-order columns)
IR_VERSION = 2


class HistoryIR(History):
    """A History that memoizes every checker family's packed view."""

    def __init__(self, source):
        self._packed: Dict[str, PackedTxns] = {}
        self._padded: Dict[str, Any] = {}
        self._rw_inf = None
        self._bank: Dict[Any, Any] = {}
        self._queue: Dict[str, Any] = {}
        self._lin_ops: Optional[List[Any]] = None
        self._packed_source: Optional[PackedTxns] = None
        if isinstance(source, PackedTxns):
            # packed-only IR: no op-level view (checkers that need ops
            # degrade exactly as they do for a bare PackedTxns today)
            self.ops = []
            self._pair = np.zeros(0, np.int64)
            self._packed_source = source
        elif isinstance(source, History):
            # share, don't rebuild: the op list and pair index are the
            # source's own objects
            self.ops = source.ops
            self._pair = source._pair
        else:
            ops = list(source)
            super().__init__(
                ops, reindex=any(op.index < 0 for op in ops))

    @property
    def packed_only(self) -> bool:
        """True when built from a bare PackedTxns — no op-level view;
        checkers needing ops must degrade exactly as for PackedTxns."""
        return self._packed_source is not None

    @classmethod
    def of(cls, history) -> "HistoryIR":
        """Idempotent constructor: an IR passes through unchanged."""
        if isinstance(history, HistoryIR):
            return history
        return cls(history)

    # -- memoized sections --------------------------------------------------

    def packed(self, workload: str = "list-append") -> PackedTxns:
        """The SoA transactional packing for `workload`
        ("list-append" / "rw-register")."""
        if self._packed_source is not None:
            return self._packed_source
        p = self._packed.get(workload)
        if p is None:
            p = self._packed[workload] = _booked(
                lambda: pack_txns(self, workload))
        return p

    def padded(self, workload: str = "list-append"):
        """The padded device layout (PaddedLA) with IR capacity facts
        and derived-order columns — pad cost paid once per history."""
        h = self._padded.get(workload)
        if h is None:
            from jepsen_tpu.checkers.elle.device_infer import pad_packed

            packed = self.packed(workload)
            h = self._padded[workload] = _booked(
                lambda: pad_packed(packed))
        return h

    def rw_inference(self):
        """The shared rw dependency inference (RwInference) the
        predicate and session invariants checkers both consume."""
        if self._rw_inf is None:
            from jepsen_tpu.checkers.invariants import packed as inv_packed

            packed = self.packed("rw-register")
            self._rw_inf = _booked(
                lambda: inv_packed.infer_rw(packed))
        return self._rw_inf

    def bank(self, accounts=None):
        """The bank balance-matrix packing (PackedBank)."""
        key = tuple(sorted(map(repr, accounts))) if accounts else None
        pb = self._bank.get(key)
        if pb is None:
            from jepsen_tpu.checkers.invariants.packed import pack_bank

            pb = self._bank[key] = _booked(
                lambda: pack_bank(self, accounts))
        return pb

    def queue(self, kind: str = "kafka"):
        """The queue-family packing: ``"kafka"`` -> PackedKafka
        (send/poll/epoch columns + derived orders), ``"fifo"`` ->
        PackedFifo (enqueue/dequeue counting columns + the
        per-consumer dequeue order)."""
        pq = self._queue.get(kind)
        if pq is None:
            from jepsen_tpu.checkers.queue import packed as q_packed

            build = (q_packed.pack_kafka if kind == "kafka"
                     else q_packed.pack_fifo)
            pq = self._queue[kind] = _booked(lambda: build(self))
        return pq

    def lin_ops(self) -> List[Any]:
        """The knossos linearizability entry table (LinOp rows)."""
        if self._lin_ops is None:
            from jepsen_tpu.checkers.knossos.prep import prepare

            self._lin_ops = _booked(lambda: prepare(self))
        return self._lin_ops

    def bucket_class(self, workload: str = "list-append",
                     site: str = "elle.infer") -> str:
        """The compile-cache shape-class label of this history's padded
        device view (``compilecache.bucket.class_label``): which AOT
        executable a check over it shares.  The padded layout already
        pads to pow2 capacities, so nearby history sizes report the
        SAME class — the property the bucket ladder pre-warms against."""
        from jepsen_tpu.compilecache import bucket

        h = self.padded(workload)
        return bucket.class_label(site, (h,), {"n_keys": h.n_keys})

    def layout(self) -> Dict[str, Any]:
        """The versioned layout summary of the padded list-append view
        (docs/IR.md): capacities + which facts/columns are active."""
        h = self.padded("list-append")
        return {
            "version": IR_VERSION,
            "T": int(h.txn_type.shape[0]),
            "M": int(h.mop_txn.shape[0]),
            "R": int(h.rd_elems.shape[0]),
            "v_cap": h.v_cap, "o_cap": h.o_cap,
            "txn_major": h.txn_major, "run_cap": h.run_cap,
            "complete_monotone": h.complete_monotone,
            "app_val_mono": h.app_val_mono,
            "rd_start_mono": h.rd_start_mono,
            "proc_seq": h.proc_seq,
            "derived_columns": h.run_sort is not None,
        }
