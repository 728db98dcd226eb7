"""Default-mesh resolution + campaign device-slot mesh slices.

The sharded-by-default decision point (ISSUE 12): every single-history
device check resolves its mesh here —

- the visible device set is this thread's **slot slice** when a
  campaign/fleet scheduler assigned one (`set_active_slot`), so one
  host process drives N sub-meshes concurrently;
- ``JEPSEN_SHARDS`` forces a shard count (``1`` disables sharding);
- otherwise a history is checked sharded over ALL visible devices as
  a 1-D ``Mesh(("batch",))`` once it is big enough to amortize the
  partitioning overhead (``JEPSEN_SHARD_MIN_TXNS``, default 65536 —
  below that the single-device program wins on every backend we
  measured).

Keeping this module import-light matters: it is consulted from the
checker hot path and from the campaign scheduler threads.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Tuple

__all__ = ["set_active_slot", "active_slot", "slot_devices",
           "default_mesh", "place_sharded", "SHARD_MIN_TXNS"]

#: below this many (padded) txns the sharded program's partitioning
#: overhead exceeds its win — the single-device path is the default
SHARD_MIN_TXNS = 65536

_local = threading.local()
_mesh_cache: dict = {}


def set_active_slot(slot: Optional[int], n_slots: int = 1) -> None:
    """Pin this THREAD's device slice to campaign slot `slot` of
    `n_slots` (None clears).  The campaign scheduler calls this around
    each device run of its thread executor."""
    _local.slot = None if slot is None else (int(slot), max(1, int(n_slots)))


def set_forced_shards(n: Optional[int]) -> None:
    """Pin this THREAD's shard count (None clears) — the thread-safe
    form of JEPSEN_SHARDS, used by fleet workers running cells with a
    pinned ``opts["mesh"]`` (several workers may share one process)."""
    _local.shards = None if n is None else int(n)


def _forced_shards() -> Optional[int]:
    n = getattr(_local, "shards", None)
    if n is not None:
        return n
    env = os.environ.get("JEPSEN_SHARDS")
    if env is None:
        return None
    try:
        return int(env)
    except ValueError:
        return None


def active_slot() -> Optional[Tuple[int, int]]:
    """(slot, n_slots) for this thread, or None."""
    return getattr(_local, "slot", None)


def slot_devices(slot: int, n_slots: int, devices=None) -> List:
    """Contiguous device slice for `slot` of `n_slots` sub-meshes.
    With fewer devices than slots, slots round-robin single devices
    (a 1-device slice = the plain single-device path)."""
    import jax

    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if n == 0:
        return devs
    if n < n_slots:
        return [devs[slot % n]]
    per = n // n_slots
    lo = (slot % n_slots) * per
    return devs[lo:lo + per]


def _visible_devices() -> List:
    import jax

    devs = jax.devices()
    sl = active_slot()
    if sl is not None:
        devs = slot_devices(sl[0], sl[1], devs)
    return devs


def default_mesh(n_txns: Optional[int] = None):
    """The 1-D ("batch",) mesh this check should shard over, or None
    for the single-device path.  `n_txns` (padded txn capacity) gates
    the size threshold; None skips the gate (caller forces).

    On the CPU backend, "multiple devices" are virtual host devices on
    the same cores, so unforced sharding can only lose (and XLA:CPU's
    GSPMD compile of the big checker programs is pathologically slow at
    >= 2^16-txn shapes — measured >20 min on the 1-core dev box, for
    the opt-in `parallel/` paths too, a pre-existing property).  There
    the sharded default activates only when explicitly forced
    (``JEPSEN_SHARDS``) or slot-assigned (a campaign/fleet mesh slice);
    real accelerator backends shard by default."""
    forced = _forced_shards()
    devs = _visible_devices()
    if forced is not None:
        if forced <= 1:
            return None
        devs = devs[:forced]
    else:
        try:
            min_txns = int(os.environ.get("JEPSEN_SHARD_MIN_TXNS",
                                          SHARD_MIN_TXNS))
        except ValueError:
            min_txns = SHARD_MIN_TXNS
        if n_txns is not None and n_txns < min_txns:
            return None
        import jax

        if jax.default_backend() == "cpu" and active_slot() is None:
            return None
    if len(devs) < 2:
        return None
    key = tuple(id(d) for d in devs)
    mesh = _mesh_cache.get(key)
    if mesh is None:
        import jax
        import numpy as np

        mesh = _mesh_cache[key] = jax.sharding.Mesh(
            np.array(devs), ("batch",))
    return mesh


def place_sharded(x, mesh=None):
    """device_put `x` with NamedSharding(P("batch")) on its leading
    axis when a default mesh is active and the axis divides; replicate
    otherwise.  The cheap GSPMD on-ramp for the embarrassingly
    shardable invariants reductions (bank row sums, session cummax
    inputs)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        # the leading dim stands in for n_txns so the SHARD_MIN size
        # gate applies to these small reductions too
        mesh = default_mesh(x.shape[0] if getattr(x, "ndim", 0) >= 1
                            else 0)
    if mesh is None:
        return jax.numpy.asarray(x)
    n = mesh.devices.size
    divisible = getattr(x, "ndim", 0) >= 1 and x.shape[0] % n == 0
    return jax.device_put(
        x, NamedSharding(mesh, P("batch") if divisible else P()))
