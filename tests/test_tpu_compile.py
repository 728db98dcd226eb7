"""Compile the main path's Pallas kernels for a described v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described, not attached.  What Mosaic or XLA:TPU would
refuse on the chip (an unaligned block, too much VMEM, a kernel it
cannot partition) fails here, at no chip time.  Nothing runs, so these
tests say nothing about results or speed.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from jepsen_tpu.ops import pallas_fill, pallas_scan

# (rows, 128) int32 LOCF planes of the 100k and 1M checks (R = 2^21 and
# 2^24 read elements, and their M-sized seeds)
FILL_ROWS = [1 << 11, 1 << 14, 1 << 17]
# (N, K) int8 label planes of the cycle sweep: 100k (N = 2^18) and 1M
# (N = 2^21) at max_k = 128, and a 1M shard of four (K = 32); then the
# cases test_pallas.py checks for exactness on the interpreter
SCAN_SHAPES = [(1 << 18, 128, 2048), (1 << 21, 128, 2048),
               (1 << 21, 32, 2048), (300, 128, 64), (4096, 128, 1024),
               (1024, 16, 256)]


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on a described v5e chip, with JAX's
    persistent compile cache off (a deviceless compile is written to it
    but cannot be read back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", FILL_ROWS)
def test_locf_kernel_compiles(chip, rows):
    v = _sds((rows, 128), jnp.int32, chip)
    c = pallas_fill._locf_pallas_padded.lower(v, block=1024).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n,k,block", SCAN_SHAPES)
def test_seg_or_kernel_compiles(chip, n, k, block):
    vals = _sds((n, k), jnp.int8, chip)
    starts = _sds((n,), jnp.bool_, chip)
    fn = jax.jit(lambda v, s: pallas_scan.seg_or_pallas(v, s, block=block))
    assert "tpu_custom_call" in fn.lower(vals, starts).compile().as_text()


def test_core_check_with_kernels_compiles(chip, monkeypatch):
    """A small fused core check with both kernel branches forced on (on
    the chip the backend chooses them) compiles with both kernels."""
    from jepsen_tpu.checkers.elle.device_core import core_check
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.ops import segments
    from jepsen_tpu.workloads import synth

    monkeypatch.setattr(pallas_fill, "fill_enabled", lambda: True)
    monkeypatch.setattr(pallas_scan, "pallas_scan_enabled",
                        lambda v: v.ndim == 2 and v.dtype == jnp.int8)
    monkeypatch.setattr(segments, "LOOP_SCAN_MIN_ROWS", 1)
    jax.clear_caches()  # drop any lax-branch trace of core_check
    h = pad_packed(synth.packed_la_history(n_txns=1000, n_keys=125,
                                           seed=0))
    hs = jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype, chip), h)
    lowered = core_check.lower(hs, n_keys=h.n_keys)
    text = lowered.as_text()
    assert "_fill_kernel" in text and "_scan_kernel" in text
    assert "tpu_custom_call" in lowered.compile().as_text()
