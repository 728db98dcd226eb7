"""Batched multi-history checking sharded over a device mesh.

The reference's closest analogue is `jepsen.independent` (checking per-key
sub-histories "independently" on one JVM, SURVEY.md §2.1); here it becomes
true data parallelism: a batch of histories is sharded over the mesh's
`dp` axis with `shard_map`, each device runs the full single-jit core
check (`device_core.core_check`) on its shard via `vmap`, and the per-
history anomaly bitmaps are combined with an ICI `all_gather` — the
BASELINE.json config-5 shape (100 x 1M-op histories on a v5e-8).

Histories in a batch share padded capacities (pad to the max; the packed
generator or the store's chunked loader provides equal-shaped arrays).
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu import resilience, telemetry
from jepsen_tpu.checkers.elle.device_core import core_check
from jepsen_tpu.checkers.elle.device_infer import PaddedLA, pad_packed
from jepsen_tpu.history.soa import PackedTxns


def make_mesh(n_devices: int = 0, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    if n_devices:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def stack_padded(hs: Sequence[PaddedLA]) -> PaddedLA:
    """Stack equal-shaped padded histories along a leading batch axis."""
    first = hs[0]
    out = {}
    for f in ("txn_type", "txn_process", "txn_invoke_pos",
              "txn_complete_pos", "txn_mask", "mop_txn", "mop_kind",
              "mop_key", "mop_val", "mop_rd_start", "mop_rd_len", "mop_mask",
              "rd_elems", "rd_elem_mask"):
        out[f] = jnp.stack([getattr(h, f) for h in hs])
    # IR derived-order columns stack only when every member carries them
    # at the same shape (else the program derives in-program, as before)
    for f in ("run_sort", "inv_run", "key_ord_len", "key_ord_read",
              "proc_order", "barrier_order", "barrier_bi"):
        vals = [getattr(h, f) for h in hs]
        if all(v is not None for v in vals) and \
                len({v.shape for v in vals}) == 1:
            out[f] = jnp.stack(vals)
    # static layout facts must hold for EVERY stacked history (vmap shares
    # one program): AND the flags, take the widest run bucket/capacity
    return PaddedLA(
        n_keys=first.n_keys, n_vals=first.n_vals,
        txn_major=all(h.txn_major for h in hs),
        run_cap=(max(h.run_cap for h in hs)
                 if all(h.run_cap for h in hs) else 0),
        complete_monotone=all(h.complete_monotone for h in hs),
        v_cap=(max(h.v_cap for h in hs)
               if all(h.v_cap for h in hs) else 0),
        o_cap=(max(h.o_cap for h in hs)
               if all(h.o_cap for h in hs) else 0),
        app_val_mono=all(h.app_val_mono for h in hs),
        rd_start_mono=all(h.rd_start_mono for h in hs),
        proc_seq=all(h.proc_seq for h in hs),
        **out)


def batch_caps(ps: Sequence[PackedTxns]) -> tuple:
    """The shared padded capacities (T, M, R, n_keys, V, O) for a batch.
    V/O are the IR value-table / order-table capacities (the batch must
    share ONE executable, so per-history capacities are maxed)."""
    from jepsen_tpu.checkers.elle.device_infer import _ir_facts, \
        pow2_at_least

    T = pow2_at_least(max(p.n_txns for p in ps))
    M = pow2_at_least(max(p.n_mops for p in ps))
    R = pow2_at_least(max(max(len(p.rd_elems), p.n_vals, p.n_keys + 1)
                          for p in ps))
    nk = max(p.n_keys for p in ps)
    facts = {id(p): _ir_facts(p) for p in ps}
    vs = [f["v_cap"] for f in facts.values()]
    os_ = [f["o_cap"] for f in facts.values()]
    V = max(vs) if all(vs) else 0
    O = max(os_) if all(os_) else 0
    caps = (T, M, R, nk, min(V, R), min(O, R))
    return _BatchCaps(caps, facts)


class _BatchCaps(tuple):
    """The (T, M, R, nk, V, O) capacity tuple, carrying the per-history
    `_ir_facts` so `pad_batch` doesn't re-derive them (they are full
    O(n_mops) host scans).  Plain tuples remain accepted everywhere."""

    def __new__(cls, caps, facts):
        self = super().__new__(cls, caps)
        self.facts = facts
        return self


def pad_batch(ps: Sequence[PackedTxns], caps: tuple = None) -> PaddedLA:
    """Pad a list of PackedTxns to shared capacities and stack them.

    `caps` (from `batch_caps`) overrides the per-call maxima so several
    groups of one larger batch share one compiled executable.  Legacy
    4-tuples (T, M, R, nk) are accepted; V/O then derive per batch."""
    if caps is None:
        caps = batch_caps(ps)
    facts = getattr(caps, "facts", {})
    if len(caps) == 4:
        caps = (*caps, 0, 0)
    T, M, R, nk, V, O = caps
    padded = []
    for p in ps:
        h = pad_packed(p, t_pad=T, m_pad=M, r_pad=R, v_pad=V, o_pad=O,
                       ir_facts=facts.get(id(p)))
        h.n_keys = nk
        padded.append(h)
    return stack_padded(padded)


@partial(jax.jit, static_argnames=("n_keys",))
def _batched_core(batch: PaddedLA, n_keys: int):
    return jax.vmap(lambda h: core_check(h, n_keys))(batch)


@partial(jax.jit, static_argnames=("n_keys", "mesh", "axis"))
def _batched_sharded(batch: PaddedLA, *, n_keys: int, mesh: Mesh,
                     axis: str):
    """The mesh branch of check_batch as a module-level jit (statics by
    keyword) so the AOT compile cache can key and serialize it — same
    shard_map program the old per-call closure built."""
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
             out_specs=(spec, spec))
    def rows(b):
        return jax.vmap(lambda h: core_check(h, n_keys))(b)

    return rows(batch)


def check_batch(ps: Sequence[PackedTxns], mesh: Mesh = None,
                axis: str = "dp", caps: tuple = None,
                deadline=None, plan=None, policy=None) -> List[dict]:
    """Check a batch of histories, sharded across the mesh if given.

    Returns one summary dict per history: {"valid?", "bits", "exact"}.
    Batches that don't divide the mesh axis are padded internally (padding
    rows are dropped from the results).  Histories whose sweep overflowed
    the default backward-edge budget are re-run alone with a grown budget,
    so verdicts are definitive whenever the caps allow.  `caps` pins the
    padded capacities (see `batch_caps`).

    The device dispatch runs under the resilience guard: `deadline` is
    polled before it, transient failures retry per `policy`, and the
    active `plan` (explicit > JEPSEN_FAULTS chaos) fires its synthetic
    faults at the ``parallel.batch`` site — the multi-device paths are
    inside the chaos perimeter, not around it.
    """
    n_real = len(ps)
    if mesh is not None:
        # pad the batch with copies of history 0 so it divides the mesh;
        # padding rows are dropped by summarize_batch_bits (the same
        # pre-stack fill check_batch_hybrid and _checkpointed use)
        ps = list(ps) + [ps[0]] * ((-n_real) % mesh.devices.size)
    # one child span per sharded dispatch (ROADMAP telemetry open item:
    # the parallel/ paths were span-invisible, so shrink probes and
    # campaign cells over them were unattributable); bytes staged is
    # what the mesh actually holds resident during the check
    with telemetry.span("parallel.batch", histories=n_real,
                        shards=(mesh.devices.size if mesh is not None
                                else 0)) as sp:
        batch = pad_batch(ps, caps)
        n_keys = batch.n_keys
        _stage_bytes(sp, batch)

        from jepsen_tpu import compilecache

        if mesh is None:
            bits, over = resilience.device_call(
                "parallel.batch",
                lambda: compilecache.call("parallel.batch",
                                          _batched_core, batch,
                                          n_keys=n_keys),
                deadline=deadline, plan=plan, policy=policy)
        else:
            in_shard = NamedSharding(mesh, P(axis))

            def put(x):
                return jax.device_put(x, in_shard)

            batch = jax.tree_util.tree_map(put, batch)
            bits, over = resilience.device_call(
                "parallel.batch",
                lambda: compilecache.call("parallel.batch",
                                          _batched_sharded, batch,
                                          n_keys=n_keys, mesh=mesh,
                                          axis=axis),
                deadline=deadline, plan=plan, policy=policy)

        return summarize_batch_bits(bits, over, batch, n_keys, n_real)


def _stage_bytes(sp, tree) -> None:
    """Attach the staged-array byte total to a dispatch span + the
    device-bytes-staged counter (no-op when telemetry is off)."""
    if not telemetry.enabled():
        return
    n = sum(int(getattr(x, "nbytes", 0))
            for x in jax.tree_util.tree_leaves(tree))
    sp.set_attr(bytes_staged=n)
    telemetry.registry().counter("device-bytes-staged").inc(n)


def summarize_batch_bits(bits, over, batch, n_keys: int, n_real: int,
                         k_floor: int = 128) -> List[dict]:
    """Per-history summary rows from batched (bits, over) outputs, with
    the exact-rerun fallback: any inexact verdict (backward-edge
    overflow or fixpoint truncation) re-runs that history alone through
    `core_check_exact`, seeding the budget past the observed overflow so
    the failed config isn't repeated.  Shared by `check_batch` and the
    hybrid 2D path (verdicts stay identical by construction)."""
    from jepsen_tpu.checkers.elle.device_core import COUNT_NAMES, \
        core_check_exact

    bits = np.array(bits)   # writable copies — np.asarray of a jax
    over = np.array(over)   # array is a read-only view
    out: List[dict] = []
    for i in range(n_real):
        row = bits[i]
        counts = {n: int(row[j]) for j, n in enumerate(COUNT_NAMES)}
        # a positive count is computed BEFORE the cycle sweep and is
        # exact regardless of sweep convergence: the history is
        # definitively invalid, so skip the (compile-heavy at 1M-op
        # shapes) exact rerun — it could only refine the cycle list
        invalid_by_counts = any(v > 0 for v in counts.values())
        if (int(over[i]) > 0 or int(row[-1]) != 1) \
                and not invalid_by_counts:
            from jepsen_tpu.checkers.elle.device_infer import pow2_at_least

            k0 = pow2_at_least(k_floor + int(over[i]), floor=k_floor)
            h_i = jax.tree_util.tree_map(lambda x: x[i], batch)
            b2, o2 = core_check_exact(h_i, n_keys, max_k=k0)
            row = np.asarray(b2)
            over[i] = max(0, int(np.asarray(o2)))
            counts = {n: int(row[j]) for j, n in enumerate(COUNT_NAMES)}
        cycles = [bool(x) for x in row[len(COUNT_NAMES):-1]]
        converged = bool(row[-1]) and int(over[i]) == 0
        invalid = any(v > 0 for v in counts.values()) or any(cycles)
        out.append({
            "valid?": False if invalid else
                      (True if converged else "unknown"),
            "counts": counts,
            "cycles": {
                "G0": cycles[0], "G1c": cycles[1], "G2-family": cycles[2],
                "G2-family-process": cycles[3],
                "G2-family-realtime": cycles[4],
            },
            # the VERDICT is exact when the sweep converged or when the
            # invalidity stands on counts alone (the cycle dict may
            # then be under-reported — counts already decide validity)
            "exact": bool(converged or invalid),
        })
    return out


def check_batch_checkpointed(ps: Sequence[PackedTxns], ckpt_path: str,
                             mesh: Mesh = None, axis: str = "dp",
                             group_size: int = 0,
                             on_group=None) -> List[dict]:
    """`check_batch` with chunk-level progress markers (SURVEY.md §5
    checkpoint/resume: "checkpointable device checking … since a 10M-op
    SCC run is minutes").

    The batch is processed in groups of `group_size` histories (default:
    one mesh row, or 8 unsharded); after each group its verdicts are
    appended to `ckpt_path` as JSON lines {"i": …, "result": …} and
    fsync'd.  A rerun with the same path skips every history already
    judged — a crashed control process resumes mid-batch instead of
    repaying the full device run.  Grouping also bounds device memory:
    one group's padded arrays are resident at a time, not the whole
    batch (the config-5 regime: 100 x 1M-op histories).

    The checkpoint records per-history content digests; a resume against
    different histories at the same path raises instead of mixing runs.

    `on_group(info)` (optional) is called after each group's checkpoint
    record is durable, with {"group", "indices", "wall_s", "done"} —
    progress reporting and crash-injection for the config-5 artifact.
    """
    import hashlib
    import json
    import os
    import time as _time

    def digest(p: PackedTxns) -> str:
        # every packed column that inference reads: two runs with the
        # same op content but a different interleaving (process
        # assignment, invoke/complete order, read segments) must NOT
        # share a digest — process/realtime cycle bits depend on them
        h = hashlib.sha256()
        # declared metadata first: n_keys/n_vals feed padding caps and
        # inference sentinels, so identical arrays under different
        # declared spaces must not share a digest
        h.update(np.int64([p.n_keys, p.n_vals, p.n_txns,
                           p.n_mops]).tobytes())
        for a in (p.txn_type, p.txn_process, p.txn_invoke_pos,
                  p.txn_complete_pos, p.mop_txn, p.mop_kind, p.mop_key,
                  p.mop_val, p.mop_rd_start, p.mop_rd_len, p.rd_elems):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()[:16]

    if not group_size:
        group_size = mesh.devices.size if mesh is not None else 8
    done: dict = {}
    if os.path.exists(ckpt_path):
        good_bytes = 0
        with open(ckpt_path, "rb") as f:
            for line in f:
                if not line.strip():
                    good_bytes += len(line)
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    # torn trailing record from a crash mid-append — the
                    # exact scenario checkpoints exist for; drop it and
                    # resume from the last durable record
                    break
                if not line.endswith(b"\n"):
                    # parseable but unterminated: a later append would
                    # fuse with it — treat as torn too
                    break
                done[rec["i"]] = rec
                good_bytes += len(line)
        with open(ckpt_path, "r+b") as f:
            f.truncate(good_bytes)
    out: List[dict] = [None] * len(ps)
    digests = [digest(p) for p in ps]
    for i, rec in done.items():
        if i >= len(ps) or rec["digest"] != digests[i]:
            raise ValueError(
                f"checkpoint {ckpt_path} is from a different batch "
                f"(history {i} digest mismatch); refusing to mix runs")
        out[i] = rec["result"]

    # one set of padded capacities across groups: per-group maxima would
    # recompile the check whenever a group's largest history crosses a
    # pow2 bucket (a ~19 min cold compile at TPU 1M-op shapes)
    caps = batch_caps(ps)
    with open(ckpt_path, "a") as f:
        for g0 in range(0, len(ps), group_size):
            idx = [i for i in range(g0, min(g0 + group_size, len(ps)))
                   if out[i] is None]
            if not idx:
                continue
            # pad partial/resumed groups to a fixed batch dim (copies of
            # the first member, dropped below): a smaller leading dim
            # would recompile _batched_core — the very cost caps pin down
            group = [ps[i] for i in idx]
            group += [group[0]] * (group_size - len(group))
            t_g = _time.monotonic()
            results = check_batch(group, mesh=mesh, axis=axis,
                                  caps=caps)[:len(idx)]
            for i, r in zip(idx, results):
                out[i] = r
                f.write(json.dumps(
                    {"i": i, "digest": digests[i], "result": r}) + "\n")
            f.flush()
            os.fsync(f.fileno())
            if on_group is not None:
                on_group({"group": g0 // group_size, "indices": idx,
                          "wall_s": round(_time.monotonic() - t_g, 2),
                          "done": sum(r is not None for r in out)})
    return out
