"""Kafka anomaly classification as whole-history vectorized reductions.

Every pass `workloads.kafka.KafkaChecker` runs as a python scan over
(send, poll) tuples becomes an array reduction over the
:class:`~jepsen_tpu.checkers.queue.packed.PackedKafka` columns —
adjacency compares over pack-time sorted orders, searchsorted
membership against the per-key offset ladder, and one segment
reduction (the stale-group run lengths):

- **lost-write** — send rows below their key's max polled offset whose
  ``key*off_base+off`` code is absent from the unique polled table;
- **duplicate** — adjacent same-``(key, value)`` rows in the unique
  polled ``(key, value, offset)`` table (two offsets for one value);
- **inconsistent-offsets** — adjacent same-``(key, offset)`` rows in
  the unique observed ``(key, offset, value)`` table;
- **nonmonotonic-poll / poll-skip** — adjacent batch rows in
  ``(process, key, seq)`` order, gated on equal assignment epochs (the
  pack-time ``(reassign-bisect, rebalance-generation)`` code), with
  the skip's "an offset in between was actually polled" test a
  searchsorted interval count;
- **int-nonmonotonic-poll / int-poll-skip** — the same on adjacent
  message rows within one batch;
- **nonmonotonic-send / int-send-skip** — adjacent send rows in
  ``(process, key, seq)`` / ``(op, key, seq)`` order;
- **precommitted-read** — message rows observed at an op index before
  their value's send was invoked;
- **stale-consumer-group** — ≥3 subscribe-mode batches of one
  ``(key, generation)`` re-reading the same start offset while the
  key's log extends past them: the group's committed offset stopped
  advancing (run detection over the ``(key, gen, start)`` sort, run
  lengths via one bincount);
- **unseen** — informational, as in the host scan.

The device path runs the fused mask kernel behind
``resilience.with_fallback(site="queue.check")`` with compile-cache
routing (`compilecache.call`, pow2-padded columns, validity sentinels
``key == -1`` instead of static lengths so nearby history sizes share
one executable); the host path is the SAME arithmetic in numpy
(:func:`host_verdict` — the oracle twin the device path is
differentially pinned against, while `KafkaChecker` itself stays the
independent scan twin).  Verdict-for-verdict parity with the scan is
pinned by tests/test_queue_checkers.py.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu.checkers import api as checker_api
from jepsen_tpu.checkers.queue import packed as packed_mod
from jepsen_tpu.checkers.queue.packed import SENTINEL, PackedKafka

SITE = "queue.check"

#: anomaly keys, the host scan's names (KafkaChecker) + stale-group
ANOMALIES = ("lost-write", "duplicate", "inconsistent-offsets",
             "nonmonotonic-poll", "poll-skip", "int-nonmonotonic-poll",
             "int-poll-skip", "nonmonotonic-send", "int-send-skip",
             "precommitted-read", "stale-consumer-group")

#: minimum same-start batches before a frozen committed offset counts
#: as a stale consumer group (1–2 re-reads happen benignly around
#: rebalances; 3 with the log moving on do not)
STALE_MIN_POLLS = 3


def _bincount(xp, x, n: int, weights=None):
    if xp is np:
        return np.bincount(x, weights=weights, minlength=n)
    return xp.bincount(x, weights=weights, length=n)


def _cummax(xp, x):
    if xp is np:
        return np.maximum.accumulate(x)
    import jax.lax as lax

    return lax.cummax(x)


def _later(xp, pair, n: int):
    """Lift a length-``n-1`` adjacent-pair mask to length ``n``,
    marking the LATER row of each flagged pair."""
    if n == 0:
        return xp.zeros(0, bool)
    return xp.concatenate([xp.zeros(1, bool), pair])


def _both(xp, pair, n: int):
    """Lift a pair mask to length ``n`` marking BOTH rows (group
    membership: every row adjacent to a same-group neighbour)."""
    if n == 0:
        return xp.zeros(0, bool)
    z = xp.zeros(1, bool)
    return xp.concatenate([pair, z]) | xp.concatenate([z, pair])


def _math(xp, off_base: int,
          s_key, s_off, s_op, s_proc,
          b_key, b_proc, b_start, b_last, b_ep, b_gen,
          m_batch, m_key, m_off, m_op, m_sendinv,
          u_comp, polled_max, key_max,
          dv_key, dv_val, av_key, av_off,
          s_by_pk, s_by_ok, b_by_pk, b_by_kg):
    """The one reduction both paths implement.  Returns the 13 masks of
    :data:`MASKS` (padding rows, ``key == -1``, never flag)."""
    S, B, M = s_key.shape[0], b_key.shape[0], m_key.shape[0]

    def member(codes):
        if u_comp.shape[0] == 0:
            return xp.zeros(codes.shape, bool)
        idx = xp.clip(xp.searchsorted(u_comp, codes),
                      0, u_comp.shape[0] - 1)
        return u_comp[idx] == codes

    def polled_between(keys, lo, hi):
        """Any polled offset o of `keys` with lo < o < hi?"""
        if u_comp.shape[0] == 0:
            return xp.zeros(keys.shape, bool)
        base = keys * off_base
        return (xp.searchsorted(u_comp, base + hi)
                > xp.searchsorted(u_comp, base + lo + 1))

    # ---- send rows: lost / unseen -----------------------------------
    s_ok = s_key >= 0
    ks = xp.where(s_ok, s_key, 0)
    seen = member(xp.where(s_ok, s_key * off_base + s_off,
                           xp.int64(-1)))
    pm = polled_max[ks]
    lost = s_ok & (pm >= 0) & (s_off < pm) & ~seen
    unseen = s_ok & ~seen

    # ---- sends by (proc, key): nonmonotonic-send --------------------
    k = s_key[s_by_pk]
    p = s_proc[s_by_pk]
    o = s_off[s_by_pk]
    pair = (k[1:] == k[:-1]) & (p[1:] == p[:-1]) & (k[1:] >= 0) \
        & (k[:-1] >= 0)
    nm_send = _later(xp, pair & (o[1:] <= o[:-1]), S)

    # ---- sends by (op, key): int-send-skip --------------------------
    k = s_key[s_by_ok]
    i = s_op[s_by_ok]
    o = s_off[s_by_ok]
    pair = (k[1:] == k[:-1]) & (i[1:] == i[:-1]) & (k[1:] >= 0) \
        & (i[1:] >= 0)
    sk_send = _later(xp, pair & (o[1:] != o[:-1] + 1), S)

    # ---- batches by (proc, key): cross-poll order, epoch-gated ------
    k = b_key[b_by_pk]
    p = b_proc[b_by_pk]
    e = b_ep[b_by_pk]
    st = b_start[b_by_pk]
    la = b_last[b_by_pk]
    pair = (k[1:] == k[:-1]) & (p[1:] == p[:-1]) & (k[1:] >= 0) \
        & (k[:-1] >= 0) & (e[1:] == e[:-1])
    nm_poll = _later(xp, pair & (st[1:] <= la[:-1]), B)
    gap = pair & (st[1:] > la[:-1] + 1)
    skip_poll = _later(
        xp, gap & polled_between(k[1:], la[:-1], st[1:]), B)

    # ---- messages within one batch: int order -----------------------
    mb = (m_batch[1:] == m_batch[:-1]) & (m_key[1:] >= 0) \
        & (m_key[:-1] >= 0)
    a, b = m_off[:-1], m_off[1:]
    inm = _later(xp, mb & (b <= a), M)
    iskip = _later(xp, mb & (b > a) & (b != a + 1)
                   & polled_between(m_key[1:], a, b), M)

    # ---- precommitted-read ------------------------------------------
    precommit = (m_key >= 0) & (m_sendinv >= 0) & (m_op < m_sendinv)

    # ---- duplicate: unique polled (key, value, offset) --------------
    pair = (dv_key[1:] == dv_key[:-1]) & (dv_val[1:] == dv_val[:-1]) \
        & (dv_key[1:] >= 0)
    dup = _both(xp, pair, dv_key.shape[0])

    # ---- inconsistent-offsets: unique (key, offset, value) ----------
    pair = (av_key[1:] == av_key[:-1]) & (av_off[1:] == av_off[:-1]) \
        & (av_key[1:] >= 0)
    incon = _both(xp, pair, av_key.shape[0])

    # ---- stale-consumer-group: (key, gen, start) runs ---------------
    k = b_key[b_by_kg]
    g = b_gen[b_by_kg]
    st = b_start[b_by_kg]
    la = b_last[b_by_kg]
    ok = (k >= 0) & (g >= 0)
    if B:
        diff = (k[1:] != k[:-1]) | (g[1:] != g[:-1]) \
            | (st[1:] != st[:-1]) | ~ok[1:] | ~ok[:-1]
        new_run = xp.concatenate([xp.ones(1, bool), diff])
        run_id = xp.cumsum(new_run.astype(xp.int64)) - 1
        run_len = _bincount(xp, run_id, B)[run_id]
        kk = xp.where(ok, k, 0)
        evid = ok & (key_max[kk] > la)
        evid_n = _bincount(xp, run_id, B,
                           weights=evid.astype(xp.int64))[run_id]
        in_group = ok & (run_len >= STALE_MIN_POLLS) & (evid_n > 0)
        stale, stale_evid = in_group, in_group & evid
    else:
        stale = stale_evid = xp.zeros(0, bool)

    return (lost, unseen, nm_send, sk_send, nm_poll, skip_poll,
            inm, iskip, precommit, dup, incon, stale, stale_evid)


#: kernel output order; pair masks are in their sort-order coordinates
MASKS = ("lost", "unseen", "nm_send", "sk_send", "nm_poll",
         "skip_poll", "inm", "iskip", "precommit", "dup", "incon",
         "stale", "stale_evid")

_KERNEL = None


def _kernel():
    """The fused jit kernel, built once (so the in-process jit cache
    and the AOT compile-cache both key one function)."""
    global _KERNEL
    if _KERNEL is None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("off_base",))
        def queue_kafka_core(*cols, off_base):
            return _math(jnp, off_base, *cols)

        _KERNEL = queue_kafka_core
    return _KERNEL


def _cols(pk: PackedKafka) -> Tuple[np.ndarray, ...]:
    return (pk.s_key, pk.s_off, pk.s_op, pk.s_proc,
            pk.b_key, pk.b_proc, pk.b_start, pk.b_last, pk.b_ep,
            pk.b_gen,
            pk.m_batch, pk.m_key, pk.m_off, pk.m_op, pk.m_sendinv,
            pk.u_comp, pk.polled_max, pk.key_max,
            pk.dv_key, pk.dv_val, pk.av_key, pk.av_off,
            pk.s_by_pk, pk.s_by_ok, pk.b_by_pk, pk.b_by_kg)


def _pad_to(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full(n, fill, np.int64)
    out[:len(a)] = a
    return out


def _pad_perm(perm: np.ndarray, n: int) -> np.ndarray:
    """Extend a permutation over the real rows with the padding rows'
    own indices — pads sort to the tail and never pair (key == -1)."""
    return np.concatenate(
        [perm, np.arange(len(perm), n, dtype=np.int64)])


def _padded_cols(pk: PackedKafka) -> Tuple[np.ndarray, ...]:
    """Bucket-pad every column to its pow2 capacity with validity
    sentinels, so nearby history sizes share one executable
    (`compilecache.bucket`)."""
    from jepsen_tpu.compilecache import bucket

    S = bucket.pow2_at_least(max(len(pk.s_key), 1))
    B = bucket.pow2_at_least(max(len(pk.b_key), 1))
    M = bucket.pow2_at_least(max(len(pk.m_key), 1))
    U = bucket.pow2_at_least(max(len(pk.u_comp), 1))
    DV = bucket.pow2_at_least(max(len(pk.dv_key), 1))
    AV = bucket.pow2_at_least(max(len(pk.av_key), 1))
    K = bucket.pow2_at_least(max(len(pk.polled_max), 1))
    return (
        _pad_to(pk.s_key, S, -1), _pad_to(pk.s_off, S, 0),
        _pad_to(pk.s_op, S, -1), _pad_to(pk.s_proc, S, -1),
        _pad_to(pk.b_key, B, -1), _pad_to(pk.b_proc, B, -1),
        _pad_to(pk.b_start, B, 0), _pad_to(pk.b_last, B, -1),
        _pad_to(pk.b_ep, B, -1), _pad_to(pk.b_gen, B, -1),
        _pad_to(pk.m_batch, M, -1), _pad_to(pk.m_key, M, -1),
        _pad_to(pk.m_off, M, 0), _pad_to(pk.m_op, M, -1),
        _pad_to(pk.m_sendinv, M, -1),
        _pad_to(pk.u_comp, U, SENTINEL),
        _pad_to(pk.polled_max, K, -1), _pad_to(pk.key_max, K, -1),
        _pad_to(pk.dv_key, DV, -1), _pad_to(pk.dv_val, DV, 0),
        _pad_to(pk.av_key, AV, -1), _pad_to(pk.av_off, AV, 0),
        _pad_perm(pk.s_by_pk, S), _pad_perm(pk.s_by_ok, S),
        _pad_perm(pk.b_by_pk, B), _pad_perm(pk.b_by_kg, B),
    )


def _reduce_host(pk: PackedKafka):
    return _math(np, pk.off_base, *_cols(pk))


def _reduce_device(pk: PackedKafka):
    from jepsen_tpu import compilecache

    out = compilecache.call(SITE, _kernel(), *_padded_cols(pk),
                            off_base=pk.off_base)
    lens = dict(zip(MASKS, (
        len(pk.s_key), len(pk.s_key), len(pk.s_key), len(pk.s_key),
        len(pk.b_key), len(pk.b_key),
        len(pk.m_key), len(pk.m_key), len(pk.m_key),
        len(pk.dv_key), len(pk.av_key),
        len(pk.b_key), len(pk.b_key))))
    return tuple(np.asarray(m)[:lens[nm]]
                 for m, nm in zip(out, MASKS))


def host_verdict(pk: PackedKafka,
                 max_reported: int = 16) -> Dict[str, Any]:
    """The exact host oracle twin — numpy only, no jax import."""
    return _render(pk, _reduce_host(pk), max_reported)


def _render(pk: PackedKafka, masks, max_reported: int) -> Dict[str, Any]:
    """Map mask indices back through the id tables into the host
    scan's exact entry shapes and iteration order (KafkaChecker —
    entry-for-entry equality is what the differential tests pin)."""
    m = dict(zip(MASKS, masks))
    K, V, P = pk.keys, pk.values, pk.procs

    lost = sorted({(K[pk.s_key[i]], int(pk.s_off[i]), V[pk.s_val[i]])
                   for i in np.nonzero(m["lost"])[0]})

    unseen: Dict[Any, int] = {}
    for i in np.nonzero(m["unseen"])[0]:
        kk = K[pk.s_key[i]]
        unseen[kk] = unseen.get(kk, 0) + 1

    by_kv: Dict[Tuple[Any, Any], List[int]] = {}
    for j in np.nonzero(m["dup"])[0]:
        by_kv.setdefault((K[pk.dv_key[j]], V[pk.dv_val[j]]),
                         []).append(int(pk.dv_off[j]))
    duplicates = sorted((k, v, sorted(offs))
                        for (k, v), offs in by_kv.items())

    by_ko: Dict[Tuple[Any, int], List[Any]] = {}
    for j in np.nonzero(m["incon"])[0]:
        by_ko.setdefault((K[pk.av_key[j]], int(pk.av_off[j])),
                         []).append(V[pk.av_val[j]])
    inconsistent = sorted((k, off, sorted(vs, key=repr))
                          for (k, off), vs in by_ko.items())

    def batch_pairs(mask, perm, shape):
        out = []
        for j in np.nonzero(mask)[0]:
            cur, prv = int(perm[j]), int(perm[j - 1])
            out.append((cur, shape(cur, prv)))
        return [e for _, e in sorted(out, key=lambda t: t[0])]

    nonmonotonic = batch_pairs(
        m["nm_poll"], pk.b_by_pk,
        lambda cur, prv: {"process": P[pk.b_proc[cur]],
                          "key": K[pk.b_key[cur]],
                          "prev": int(pk.b_last[prv]),
                          "next": int(pk.b_start[cur]),
                          "op-index": int(pk.b_op[cur])})
    skipped = batch_pairs(
        m["skip_poll"], pk.b_by_pk,
        lambda cur, prv: {"key": K[pk.b_key[cur]],
                          "from": int(pk.b_last[prv]),
                          "to": int(pk.b_start[cur]),
                          "process": P[pk.b_proc[cur]],
                          "op-index": int(pk.b_op[cur])})
    int_nonmono = [{"key": K[pk.m_key[j]],
                    "prev": int(pk.m_off[j - 1]),
                    "next": int(pk.m_off[j]),
                    "op-index": int(pk.m_op[j])}
                   for j in np.nonzero(m["inm"])[0]]
    int_skipped = [{"key": K[pk.m_key[j]],
                    "from": int(pk.m_off[j - 1]),
                    "to": int(pk.m_off[j]),
                    "op-index": int(pk.m_op[j])}
                   for j in np.nonzero(m["iskip"])[0]]
    nonmono_send = batch_pairs(
        m["nm_send"], pk.s_by_pk,
        lambda cur, prv: {"process": P[pk.s_proc[cur]],
                          "key": K[pk.s_key[cur]],
                          "prev": int(pk.s_off[prv]),
                          "next": int(pk.s_off[cur]),
                          "op-index": int(pk.s_op[cur])})
    int_send_skip = batch_pairs(
        m["sk_send"], pk.s_by_ok,
        lambda cur, prv: {"key": K[pk.s_key[cur]],
                          "from": int(pk.s_off[prv]),
                          "to": int(pk.s_off[cur]),
                          "op-index": int(pk.s_op[cur])})
    precommitted = [{"key": K[pk.m_key[j]], "value": V[pk.m_val[j]],
                     "poll-op": int(pk.m_op[j]),
                     "send-op": int(pk.m_sendinv[j])}
                    for j in np.nonzero(m["precommit"])[0]]

    groups: Dict[Tuple[Any, int, int], List[bool]] = {}
    for j in np.nonzero(m["stale"])[0]:
        row = int(pk.b_by_kg[j])
        g = (K[pk.b_key[row]], int(pk.b_gen[row]),
             int(pk.b_start[row]))
        groups.setdefault(g, []).append(bool(m["stale_evid"][j]))
    stale = [{"key": k, "generation": gen, "start": start,
              "polls": len(evs), "behind": sum(evs)}
             for (k, gen, start), evs in groups.items()]
    stale.sort(key=lambda e: (repr(e["key"]), e["generation"],
                              e["start"]))

    anomalies = {
        "lost-write": lost[:max_reported],
        "duplicate": duplicates[:max_reported],
        "inconsistent-offsets": inconsistent[:max_reported],
        "nonmonotonic-poll": nonmonotonic[:max_reported],
        "poll-skip": skipped[:max_reported],
        "int-nonmonotonic-poll": int_nonmono[:max_reported],
        "int-poll-skip": int_skipped[:max_reported],
        "nonmonotonic-send": nonmono_send[:max_reported],
        "int-send-skip": int_send_skip[:max_reported],
        "precommitted-read": precommitted[:max_reported],
        "stale-consumer-group": stale[:max_reported],
    }
    found = {k: v for k, v in anomalies.items() if v}
    out = {
        "valid?": not found,
        "anomaly-types": sorted(found),
        "anomalies": found,
        "send-count": pk.n_sends,
        "poll-count": pk.n_polls,
    }
    if unseen:
        out["unseen"] = dict(
            sorted(unseen.items(), key=repr)[:max_reported])
    for name, entries in found.items():
        telemetry.registry().counter(
            "queue-anomalies-found", anomaly=name).inc(len(entries))
    return out


def check(history, test: Optional[dict] = None, *,
          use_device: bool = True, max_reported: int = 16,
          deadline=None, plan=None, policy=None) -> Dict[str, Any]:
    """Check a kafka history.  Accepts a History / op list /
    PackedKafka.  Device path first (guarded, retried,
    deadline-polled); persistent failure degrades to the host twin
    with the standard stamp.  ``use_device=False`` IS the host twin."""
    from jepsen_tpu import resilience

    ph = telemetry.phases()
    pk = history if isinstance(history, PackedKafka) else None
    if pk is None:
        from jepsen_tpu.history.ir import HistoryIR

        ph.start("queue.pack", device=False)
        pk = (history.queue("kafka")
              if isinstance(history, HistoryIR)
              else packed_mod.pack_kafka(history))
    if pk.empty:
        ph.end()
        return {"valid?": "unknown"}
    if deadline is not None:
        deadline.check(SITE)
    use_device = use_device and pk.device_safe
    if not use_device:
        ph.start("queue.check", device=False,
                 sends=pk.n_sends, polls=pk.n_polls)
        res = host_verdict(pk, max_reported)
        ph.end()
        return res
    ph.start("queue.check", device=True,
             sends=pk.n_sends, polls=pk.n_polls)
    try:
        masks, degraded = resilience.with_fallback(
            SITE,
            lambda: _reduce_device(pk),
            lambda: _reduce_host(pk),
            deadline=deadline, plan=plan, policy=policy, test=test)
    except resilience.DeadlineExceeded:
        ph.end()
        return resilience.deadline_result(checker="kafka")
    res = _render(pk, masks, max_reported)
    if degraded:
        res["degraded"] = degraded
    ph.end()
    return res


class PackedKafkaChecker(checker_api.Checker):
    """The canonical kafka checker: packed anomaly passes on the
    HistoryIR, device path + host twin, `KafkaChecker` scan parity
    pinned differentially."""

    def name(self) -> str:
        return "kafka"

    def check(self, test, history, opts=None):
        return check(history, test,
                     deadline=(opts or {}).get("deadline"))
