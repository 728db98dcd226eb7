"""Pallas TPU kernel: single-pass segmented prefix-OR scan.

The chain-propagation op of the cycle sweep (`ops/cycle_sweep.py`
chain_pass) is a segmented prefix-OR over an (n, K) int8 label plane.
The lax fallbacks cost either ~2*log2(n) full-width HLO steps traced at
compile time (`associative_scan`) or log2(n) full HBM passes at runtime
(the Hillis-Steele `fori_loop`, `ops/segments.py`).  At 1M-txn shapes
(n = 2^21 chain rows, K = 128) that loop moves ~2 * n*K * log2(n) ≈
11 GB of HBM per chain pass, three passes per propagation round.

This kernel does the whole scan in ONE pass over HBM (read n*K + write
n*K ≈ 0.5 GB at the same shapes): TPU Pallas grids execute sequentially
on a core, so the running carry lives in VMEM scratch across grid steps —
each block loads (B, K) into VMEM, runs the in-block segmented
Hillis-Steele scan at VMEM bandwidth (log2(B) VPU steps), ORs in the
carry from the previous blocks, and writes the block back.

This is the Pallas equivalent of the reference's sequential-Java SCC
machinery hot op (SURVEY.md §2.5 #1: bifurcan `Graphs`), per the
BASELINE "Pallas parallel-SCC kernel" target: the sweep's other ops
(scatter-max relax, K×K closure matmuls) already lower well from lax
(PROFILE.md §3); the segmented chain scan is the one op where a custom
schedule beats XLA, so it is the one that gets a kernel.

Exactness: the block-scan math is shared verbatim between the kernel and
a pure-JAX grid emulator (`seg_or_blocked_reference`) that replicates the
sequential-grid + scratch-carry execution; the emulator is differential-
tested against the lax scans on adversarial layouts (`tests/
test_pallas.py`) on any backend, and the compiled kernel is differential-
tested against the emulator on the TPU backend itself (same file, gated).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_BLOCK_ROWS = 2048  # (B, 128) int8 = 256 KB/buffer in VMEM


def _block_scan(v, starts, block: int, roll):
    """In-block segmented inclusive prefix-OR (Hillis-Steele), shared by
    the Pallas kernel (roll = pltpu.roll) and the grid emulator
    (roll = jnp.roll).

    v: (B, K) int32 values; starts: (B, 1) bool.  Returns (scan, seen):
      scan[i] = OR of v over [last start <= i (or block begin) .. i]
      seen[i] = a start lies in [0, i]        (decides carry absorption)
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    #   blocked[i] = a start lies in (i - dist, i] (rows before the block
    #   count as blocked, so scans never absorb across the block boundary)
    # flags are int32 0/1 lanes, not bool: Mosaic's dynamic_rotate has no
    # i1 support ("Rotate with non-32-bit data" on the real chip)
    blocked = starts.astype(jnp.int32)
    seen = starts.astype(jnp.int32)
    one = jnp.ones_like(blocked)
    zero = jnp.zeros_like(seen)
    dist = 1
    while dist < block:
        ok = rows >= dist
        v_p = roll(v, dist, 0)
        blk_p = roll(blocked, dist, 0)
        seen_p = roll(seen, dist, 0)
        take = ok & (blocked == 0)
        v = jnp.where(take, v | v_p, v)
        blocked = blocked | jnp.where(ok, blk_p, one)
        seen = seen | jnp.where(ok, seen_p, zero)
        dist *= 2
    return v, seen != 0


def _scan_kernel(block: int, v_ref, s_ref, o_ref, carry_ref):
    """One grid step: in-block segmented scan + carry absorb/update.

    v_ref: (B, K) int8 values; s_ref: (B, 1) int8 segment-start flags;
    o_ref: (B, K) int8 out; carry_ref: (8, K) int32 VMEM scratch, row 0 =
    running OR of the segment open at the end of the previous block
    (persists across sequential grid steps on TPU).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    v = v_ref[...].astype(jnp.int32)             # (B, K)
    starts = (s_ref[...] != 0)                   # (B, 1) bool
    scan, seen = _block_scan(
        v, starts, block, lambda x, d, ax: pltpu.roll(x, shift=d, axis=ax))
    carry = carry_ref[0:1, :]                    # (1, K) int32
    out = jnp.where(seen, scan, scan | carry)    # pre-first-start rows absorb
    carry_ref[0:1, :] = out[block - 1:block, :]
    o_ref[...] = out.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block",))
def _seg_or_pallas_padded(values: jnp.ndarray, starts_i8: jnp.ndarray,
                          block: int) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = values.shape
    return pl.pallas_call(
        functools.partial(_scan_kernel, block),
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, k), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        # vma: inside shard_map the output varies over the mesh axes
        # its inputs vary over (jax >= 0.9 checks this)
        out_shape=jax.ShapeDtypeStruct(
            (n, k), jnp.int8,
            vma=jax.typeof(values).vma | jax.typeof(starts_i8).vma),
        scratch_shapes=[pltpu.VMEM((8, k), jnp.int32)],
    )(values, starts_i8)


def _pad_blocks(values, starts, block):
    from jepsen_tpu.checkers.elle.device_infer import pow2_at_least

    n, _ = values.shape
    block = min(block, pow2_at_least(max(n, 8)))
    n_pad = -n % block
    v = jnp.pad(values, ((0, n_pad), (0, 0))) if n_pad else values
    s = starts.astype(jnp.int8).reshape(-1, 1)
    s = jnp.pad(s, ((0, n_pad), (0, 0)), constant_values=1) if n_pad else s
    return v, s, block, n


def seg_or_pallas(values: jnp.ndarray, starts: jnp.ndarray,
                  block: int = _BLOCK_ROWS) -> jnp.ndarray:
    """Inclusive segmented prefix-OR of an (n, K) int8 plane on TPU.

    Pads rows to a block multiple (padding is sliced back off; carry only
    flows forward, so trailing pad rows cannot affect real rows).
    """
    v, s, block, n = _pad_blocks(values, starts, block)
    out = _seg_or_pallas_padded(v, s, block)
    return out[:n]


def seg_or_blocked_reference(values: jnp.ndarray, starts: jnp.ndarray,
                             block: int = _BLOCK_ROWS) -> jnp.ndarray:
    """Pure-JAX emulation of the kernel's execution: the same
    `_block_scan` body, driven block-by-block in Python with an explicit
    carry — exactly the sequential-grid + VMEM-scratch schedule.  The
    any-backend differential anchor for the kernel."""
    v, s, block, n = _pad_blocks(values, starts, block)
    outs = []
    carry = jnp.zeros((1, v.shape[1]), jnp.int32)
    for b in range(v.shape[0] // block):
        vb = v[b * block:(b + 1) * block].astype(jnp.int32)
        sb = s[b * block:(b + 1) * block] != 0
        scan, seen = _block_scan(vb, sb, block,
                                 lambda x, d, ax: jnp.roll(x, d, axis=ax))
        out = jnp.where(seen, scan, scan | carry)
        carry = out[block - 1:block, :]
        outs.append(out.astype(jnp.int8))
    return jnp.concatenate(outs)[:n]


#: default-on for the TPU backend: scripts/tpu_scan_bench.py validated
#: the compiled kernel bitwise against the lax scans on the real chip
#: (4 adversarial layouts + the 2^21-row bench shapes) and measured it
#: 28x faster than the loop scan (51 ms vs 1428 ms per chain pass at
#: (2^21, 128), 2026-07-30; PROFILE.md §2c)
_TPU_VALIDATED = True


def flatten_batch(values: jnp.ndarray, starts: jnp.ndarray):
    """Collapse a (B, n, K)/(B, n) batched scan input to one (B*n, K)
    scan with a forced segment start at each batch boundary.

    Exact: within the unbatched semantics row 0 of each history scans
    from nothing (there is no carry before it), which is precisely what
    a segment start at row g*n reproduces — so one flat scan equals B
    independent scans, and the sequential carry cannot leak across
    histories.
    """
    b, n, k = values.shape
    flat_v = values.reshape(b * n, k)
    flat_s = starts.reshape(b * n)
    boundary = (jnp.arange(b * n) % n) == 0
    return flat_v, flat_s | boundary


@jax.custom_batching.custom_vmap
def seg_or_auto(values: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """`seg_or_pallas` with a batching rule.

    The default pallas_call batching rule prepends the vmap axis to the
    grid, which would turn `pl.program_id(0)` into the batch index and
    corrupt the sequential VMEM carry (re-zeroing it per block of batch
    element 0, leaking it across later elements) — and because the
    dispatch decision is traced into the jaxpr before an outer vmap
    applies (vmap-of-jit re-traces nothing at the Python level), no
    call-site guard can catch it.  This wrapper owns the batching
    instead: batched calls flatten to ONE long scan with forced segment
    boundaries (`flatten_batch`), which is exact and keeps the
    single-pass kernel schedule.
    """
    return seg_or_pallas(values, starts)


@seg_or_auto.def_vmap
def _seg_or_auto_vmap(axis_size, in_batched, values, starts):
    v_b, s_b = in_batched
    if not v_b:
        values = jnp.broadcast_to(values[None], (axis_size,) + values.shape)
    if not s_b:
        starts = jnp.broadcast_to(starts[None], (axis_size,) + starts.shape)
    b, n, k = values.shape
    flat_v, flat_s = flatten_batch(values, starts)
    out = seg_or_auto(flat_v, flat_s)  # recursive: nested vmap re-applies
    return out.reshape(b, n, k), True


def pallas_scan_enabled(values: jnp.ndarray) -> bool:
    """Use the kernel for 2D int8 planes on the TPU backend (JT_PALLAS=0
    forces the lax paths; JT_PALLAS=1 forces the kernel on, still
    TPU-compiled — there is no interpret fallback, see tests/
    test_pallas.py)."""
    knob = os.environ.get("JT_PALLAS", "").strip()
    if knob == "0":
        return False
    ok_shape = values.ndim == 2 and values.dtype == jnp.int8
    if knob == "1":
        return ok_shape
    return ok_shape and _TPU_VALIDATED and jax.default_backend() == "tpu"
