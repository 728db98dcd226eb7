"""Differential tests for the Pallas segmented-scan kernel.

The kernel must be bitwise-identical to the lax reference scans
(`segments._seg_scan` / `_seg_scan_loop`) — checkers are oracles, so the
kernel's only acceptance bar is exact equality on adversarial segment
layouts.  The block-scan math + grid/carry schedule are exercised here
via `seg_or_blocked_reference` (the pure-JAX emulator sharing
`_block_scan` verbatim with the kernel) on the CPU test backend; the
compiled `pallas_call` is compiled for a described v5e chip in
tests/test_tpu_compile.py (nothing runs there: the CPU cannot run
Mosaic).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jepsen_tpu.ops import pallas_scan
from jepsen_tpu.ops.segments import _seg_scan, _seg_scan_loop


def _random_case(n, k, p_start, seed):
    rng = np.random.default_rng(seed)
    vals = (rng.random((n, k)) < 0.08).astype(np.int8)
    starts = rng.random(n) < p_start
    starts[0] = True
    return jnp.asarray(vals), jnp.asarray(starts)


@pytest.mark.parametrize("n,k,p_start,block", [
    (8, 128, 0.3, 8),          # single tiny block
    (256, 128, 0.1, 64),       # multiple blocks, carries cross boundaries
    (300, 128, 0.05, 64),      # n not a block multiple (pad path)
    (1024, 128, 0.0, 128),     # one segment spanning every block
    (512, 128, 1.0, 128),      # every row its own segment
    (2048, 16, 0.02, 512),     # narrow lanes (sharded k_local shape)
    (777, 128, 0.3, 256),      # block > n collapses to one block
])
def test_block_schedule_matches_lax(n, k, p_start, block):
    vals, starts = _random_case(n, k, p_start, seed=n + k)
    want = np.asarray(_seg_scan(vals, starts))
    got = np.asarray(pallas_scan.seg_or_blocked_reference(
        vals, starts, block=block))
    np.testing.assert_array_equal(got, want)


def test_block_schedule_matches_loop_scan():
    # the loop scan is the large-shape lax path the kernel replaces on TPU
    vals, starts = _random_case(4096, 128, 0.01, seed=5)
    want = np.asarray(_seg_scan_loop(vals, starts))
    got = np.asarray(pallas_scan.seg_or_blocked_reference(
        vals, starts, block=1024))
    np.testing.assert_array_equal(got, want)


def test_carry_crosses_many_blocks():
    # one segment start at row 0, value only at row 0: every later row
    # (across 8 blocks) must see it through the carry
    n, k, block = 512, 128, 64
    vals = np.zeros((n, k), np.int8)
    vals[0, 3] = 1
    starts = np.zeros(n, bool)
    starts[0] = True
    got = np.asarray(pallas_scan.seg_or_blocked_reference(
        jnp.asarray(vals), jnp.asarray(starts), block=block))
    assert (got[:, 3] == 1).all()
    assert got.sum() == n


def test_start_resets_carry_mid_block():
    n, k, block = 256, 128, 64
    vals = np.zeros((n, k), np.int8)
    vals[0, 0] = 1
    starts = np.zeros(n, bool)
    starts[0] = True
    starts[130] = True  # mid-block-3 start: rows >= 130 must NOT see col 0
    got = np.asarray(pallas_scan.seg_or_blocked_reference(
        jnp.asarray(vals), jnp.asarray(starts), block=block))
    assert (got[:130, 0] == 1).all()
    assert (got[130:, 0] == 0).all()


def test_dispatch_respects_env(monkeypatch):
    vals = jnp.zeros((4, 128), jnp.int8)
    monkeypatch.setenv("JT_PALLAS", "0")
    assert not pallas_scan.pallas_scan_enabled(vals)
    monkeypatch.setenv("JT_PALLAS", "1")
    assert pallas_scan.pallas_scan_enabled(vals)
    assert not pallas_scan.pallas_scan_enabled(jnp.zeros((4, 4, 4), jnp.int8))


def _batch_case(b, n, k, p, seed0):
    vals = np.stack([np.asarray(_random_case(n, k, p, seed=seed0 + s)[0])
                     for s in range(b)])
    starts = np.stack([np.asarray(_random_case(n, k, p, seed=seed0 + s)[1])
                       for s in range(b)])
    return jnp.asarray(vals), jnp.asarray(starts)


def test_flatten_batch_is_exact():
    """The custom_vmap rule's flattening (one long scan with forced
    segment boundaries) must equal B independent scans — including when
    a history does NOT start with a segment flag (carry from the
    previous history must be cut by the forced boundary)."""
    vals, starts = _batch_case(3, 64, 128, 0.2, seed0=0)
    starts = starts.at[:, 0].set(False)  # adversarial: no natural starts
    fv, fs = pallas_scan.flatten_batch(vals, starts)
    flat = np.asarray(_seg_scan(fv, fs))
    for b in range(3):
        want = np.asarray(_seg_scan(vals[b], starts[b]))
        np.testing.assert_array_equal(flat[b * 64:(b + 1) * 64], want)


def test_custom_vmap_rule_under_jit_nesting():
    """check_batch's real nesting is jit(vmap(jit(core_check))): the
    inner trace bakes the dispatch into the jaxpr BEFORE the outer vmap
    batches it, so the only sound protection is seg_or_auto's
    custom_vmap rule.  Drive that exact nesting (with the emulator
    standing in for the Mosaic body, which CPU cannot lower) and demand
    bitwise equality with per-history scans — this fails if the default
    grid-prepend batching rule ever handles the kernel."""
    from jax import custom_batching

    @custom_batching.custom_vmap
    def auto(v, s):
        return pallas_scan.seg_or_blocked_reference(v, s, block=16)

    auto.def_vmap(lambda axis_size, in_batched, v, s: (
        pallas_scan.seg_or_blocked_reference(
            *pallas_scan.flatten_batch(v, s), block=16).reshape(v.shape),
        True))

    vals, starts = _batch_case(4, 32, 128, 0.3, seed0=9)
    got = np.asarray(jax.jit(jax.vmap(jax.jit(auto)))(vals, starts))
    for b in range(4):
        want = np.asarray(_seg_scan(vals[b], starts[b]))
        np.testing.assert_array_equal(got[b], want)


def test_seg_or_auto_vmap_rule_wiring():
    """The shipped seg_or_auto must reach _seg_or_auto_vmap under vmap
    (not the default pallas batching rule).  On CPU the kernel body
    cannot lower, so patch the body call and assert the rule fired and
    produced the flattened call shape."""
    calls = []
    import jepsen_tpu.ops.pallas_scan as ps_mod

    orig = ps_mod.seg_or_pallas

    def spy(v, s, block=2048):
        calls.append(tuple(v.shape))
        return ps_mod.seg_or_blocked_reference(v, s, block=16)

    ps_mod.seg_or_pallas = spy
    try:
        vals, starts = _batch_case(2, 32, 128, 0.3, seed0=3)
        got = np.asarray(jax.vmap(ps_mod.seg_or_auto)(vals, starts))
    finally:
        ps_mod.seg_or_pallas = orig
    # custom_vmap first traces the unbatched primal ((32,128), abstract
    # eval only); the executed path is the flattened (B*n, K) call
    assert calls[-1] == (64, 128), calls
    for b in range(2):
        want = np.asarray(_seg_scan(vals[b], starts[b]))
        np.testing.assert_array_equal(got[b], want)
