"""Pallas TPU kernel: single-pass flat forward-fill (LOCF) over int32.

Edge inference (`checkers/elle/device_infer.py`) expands per-mop tables
to the R-sized read-element axis: seed a value at each segment start,
then fill holes forward ("last observed carried forward").  The lax
path does this with `lax.cummax` for monotone channels plus R-sized
gathers `table[er]` for the rest — and on TPU those gathers execute at
~0.4 GB/s (scalar loads; measured 0.45 s EACH at R = 2^24, PROFILE.md
round-5 trace), totalling ~2.3 s of the 1M-txn check.

This kernel replaces cummax + the monotone/table gathers with one pass
per channel over HBM: values are viewed as a (rows, 128) plane in flat
row-major order; each grid step loads a block into VMEM, runs a
cross-lane then cross-row doubling fill at VPU speed, absorbs the
scalar carry from previous blocks (TPU Pallas grids execute
sequentially, so the carry lives in VMEM scratch), and writes back.

Hole representation is a sentinel (-1): every filled channel here is
nonnegative (mop positions, rd_start offsets, lengths, key ids, txn
ids), so no separate mask plane is needed, and on monotone seed
channels LOCF is bitwise `lax.cummax` (the last seed IS the max).

Exactness protocol (same as `ops/pallas_scan.py`): the block math is
shared verbatim with a pure-JAX grid emulator (`locf_blocked_reference`)
differential-tested against the lax scan on any backend; the compiled
kernel is differential-tested against the emulator on the TPU backend.

vmap: a batched call must not leak the carry across batch rows; the
custom_vmap rule falls back to the O(log n)-pass lax scan per row
(exact, slower — the batched checking paths pay this, as they already
do for the dup-sort branch).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_BLOCK_ROWS = 1024   # (B, 128) int32 = 512 KB/buffer in VMEM
_LANES = 128
HOLE = -1


def locf_lax(x: jnp.ndarray, hole: int = HOLE) -> jnp.ndarray:
    """Reference semantics: out[i] = x[j] for the largest j <= i with
    x[j] != hole, else hole.  O(log n) full passes."""
    return jax.lax.associative_scan(
        lambda a, b: jnp.where(b == hole, a, b), x)


def _block_fill(v, block: int, roll):
    """In-block flat LOCF of a (B, 128) int32 plane in row-major order,
    shared by the kernel (roll = pltpu.roll) and the emulator
    (roll = jnp.roll).  Returns the filled block (holes before the
    block's first non-hole stay HOLE — the caller absorbs the carry).

    Two-level doubling: cross-lane fill within each row, then the
    row-level fill propagates each row's last value (lane 127 after the
    lane fill) downward, and rows still starting with holes prepend it.
    """
    # 1. cross-lane LOCF per row (lanes are the minor/flat-order axis)
    dist = 1
    lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    while dist < _LANES:
        v_p = roll(v, dist, 1)
        take = (lanes >= dist) & (v == HOLE)
        v = jnp.where(take, v_p, v)
        dist *= 2
    # 2. per-row last value (lane 127), filled across rows
    last = v[:, _LANES - 1:_LANES]                      # (B, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, last.shape, 0)
    dist = 1
    while dist < block:
        l_p = roll(last, dist, 0)
        take = (rows >= dist) & (last == HOLE)
        last = jnp.where(take, l_p, last)
        dist *= 2
    # 3. rows adopt the previous row's filled last value for their
    # leading holes (the lane fill left them HOLE)
    prev = roll(last, 1, 0)
    prev = jnp.where(rows >= 1, prev, HOLE)             # row 0: no prev
    return jnp.where(v == HOLE, prev, v)


def _replicate_last_lane(row, roll):
    """(1, 128) -> (1, 128) with every lane = input lane 127, via
    cyclic-roll doubling (Mosaic has no (1,1)->(1,128) broadcast; a
    full replicated row sidesteps it — the same reason the OR kernel
    carries a (1, K) row).  Shared by kernel and emulator."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    v = jnp.where(lanes == _LANES - 1, row, HOLE)
    dist = 1
    while dist < _LANES:
        # cyclic roll by -dist: lane l reads lane l+dist (mod 128);
        # only lane 127 is non-hole initially, so this backward-fills
        v_p = roll(v, _LANES - dist, 1)
        v = jnp.where(v == HOLE, v_p, v)
        dist *= 2
    return v


def _fill_kernel(block: int, v_ref, o_ref, carry_ref):
    """One grid step: in-block fill + carry absorb/update.  carry_ref is
    (8, 128) int32 VMEM scratch; row 0 holds the last non-hole value of
    all previous blocks (or HOLE), replicated across lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.full_like(carry_ref, HOLE)

    roll = lambda x, d, ax: pltpu.roll(x, shift=d, axis=ax)  # noqa: E731
    v = v_ref[...]
    out = _block_fill(v, block, roll)
    carry = carry_ref[0:1, :]                            # (1, 128)
    out = jnp.where(out == HOLE, carry, out)
    # new carry = last flat element (already carry-absorbed, so a fully
    # empty block propagates the old carry), replicated across lanes
    carry_ref[0:1, :] = _replicate_last_lane(
        out[block - 1:block, :], roll)
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("block",))
def _locf_pallas_padded(v2d: jnp.ndarray, block: int) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, lanes = v2d.shape
    return pl.pallas_call(
        functools.partial(_fill_kernel, block),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, lanes), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        # vma: inside shard_map the output varies over the mesh axes
        # its input varies over (jax >= 0.9 checks this)
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32,
                                       vma=jax.typeof(v2d).vma),
        scratch_shapes=[pltpu.VMEM((8, lanes), jnp.int32)],
    )(v2d)


def _pad_2d(x: jnp.ndarray, block: int):
    n = x.shape[0]
    rows = -(-n // _LANES)
    rows_pad = -rows % block
    total = (rows + rows_pad) * _LANES
    v = jnp.pad(x, (0, total - n), constant_values=HOLE)
    return v.reshape(rows + rows_pad, _LANES), n


def locf_pallas(x: jnp.ndarray, block: int = _BLOCK_ROWS) -> jnp.ndarray:
    """Flat forward-fill of a 1-D int32 array on TPU (holes = -1).
    Padding rows are appended as holes and sliced off; the carry flows
    only forward, so they cannot affect real elements."""
    v2d, n = _pad_2d(x, block)
    block = min(block, v2d.shape[0])
    return _locf_pallas_padded(v2d, block).reshape(-1)[:n]


def locf_blocked_reference(x: jnp.ndarray,
                           block: int = _BLOCK_ROWS) -> jnp.ndarray:
    """Pure-JAX emulation of the kernel schedule (same `_block_fill`
    body, explicit sequential carry) — the any-backend differential
    anchor for the kernel."""
    v2d, n = _pad_2d(x, block)
    block = min(block, v2d.shape[0])
    outs = []
    roll = lambda a, d, ax: jnp.roll(a, d, ax)  # noqa: E731
    carry = jnp.full((1, _LANES), HOLE, jnp.int32)
    for b in range(v2d.shape[0] // block):
        vb = v2d[b * block:(b + 1) * block]
        out = _block_fill(vb, block, roll)
        out = jnp.where(out == HOLE, carry, out)
        carry = _replicate_last_lane(out[block - 1:block, :], roll)
        outs.append(out)
    return jnp.concatenate(outs).reshape(-1)[:n]


#: default-on for the TPU backend (the kernel is held bitwise to the lax
#: scan by tests/test_pallas_fill.py through the grid emulator)
_TPU_VALIDATED = True


def fill_enabled() -> bool:
    """True when the kernel path should be used (TPU backend, or
    JT_PALLAS=1 forcing it; JT_PALLAS=0 forces the lax paths).  Callers
    branch their whole expansion strategy on this — the lax strategy
    (cummax + gathers) beats the lax LOCF scan on CPU, so the fallback
    is the legacy code, not `locf_lax`."""
    knob = os.environ.get("JT_PALLAS", "").strip()
    if knob == "0":
        return False
    if knob == "1":
        return True
    return _TPU_VALIDATED and jax.default_backend() == "tpu"


@jax.custom_batching.custom_vmap
def locf_flat(x: jnp.ndarray) -> jnp.ndarray:
    """Forward-fill holes (== -1) from the left; leading holes stay -1.

    TPU backend: single-pass Pallas kernel.  Elsewhere (or with
    JT_PALLAS=0): the O(log n) lax associative scan.  On seed arrays
    whose non-hole values are non-decreasing this is bitwise
    `lax.cummax` of the same array.
    """
    use = x.ndim == 1 and x.dtype == jnp.int32 and fill_enabled()
    if not use:
        return locf_lax(x)
    if os.environ.get("JT_PALLAS_EMULATE", "").strip() == "1":
        # tests: drive the whole kernel-branch integration (seeds,
        # hole-compat wheres, block math) on any backend through the
        # grid emulator; only kernel-vs-emulator equivalence remains
        # chip-gated
        return locf_blocked_reference(x)
    return locf_pallas(x)


@locf_flat.def_vmap
def _locf_flat_vmap(axis_size, in_batched, x):
    # per-row lax scan: exact, no cross-row carry to corrupt (the
    # sequential-carry kernel schedule is wrong under batching — same
    # hazard as pallas_scan.seg_or_auto, solved here by falling back)
    del axis_size, in_batched
    return jax.vmap(locf_lax)(x), True
