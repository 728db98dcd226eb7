"""Packed queue/kafka checker family (ROADMAP item 4).

The last scenario frontier rebuilt the ISSUE-9/-11 way: queue and
kafka semantics — previously host-only scans (`workloads/kafka.py`'s
`KafkaChecker`, `checker_api.TotalQueueChecker`) — as whole-history
vectorized reductions over SoA columns on the HistoryIR, with a device
path behind ``resilience.with_fallback(site="queue.check")``,
compile-cache routing, and the original scans pinned as differential
twins (verdict-for-verdict on seeded corpora; tests/
test_queue_checkers.py).

- :mod:`.packed` — pack send/poll/assign/offset-commit histories into
  per-key offset ladders, per-consumer observation rows, and pack-time
  derived orders (``HistoryIR.queue(kind)`` memoizes both views);
- :mod:`.kafka` — the kafka anomaly classification (lost-write, duplicate,
  inconsistent-offsets, poll/send order, precommitted-read,
  stale-consumer-group) as one fused mask kernel;
- :mod:`.fifo` — the total-queue counting model + the opt-in
  per-consumer FIFO pass.

Registry: :data:`MODELS` follows `checkers.invariants.MODELS` — model
name -> flywheel metadata (workload, device classification, anomaly
vocabulary) so campaign specs, shrink probe twins, and witness
renderers agree on one table.
"""

from __future__ import annotations

from jepsen_tpu.checkers.queue import fifo, kafka, packed

__all__ = ["packed", "kafka", "fifo", "MODELS"]

#: model name -> flywheel metadata (same shape as invariants.MODELS)
MODELS = {
    "kafka": {
        "workload": "kafka",
        "device": True,
        "anomalies": kafka.ANOMALIES,
    },
    "total-queue": {
        "workload": "queue",
        "device": True,
        "anomalies": (fifo.LOST, fifo.PHANTOM, fifo.FIFO),
    },
}
