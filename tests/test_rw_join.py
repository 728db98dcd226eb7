"""The fused rw check's join (`device_rw.rw_join`): counting tables give
the same join as binary searches, bit for bit, and no binary search over
the mop rows comes back into the program.

`_searchsorted_join` is the join as it was written with
`jnp.searchsorted`, kept here as the reference."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jepsen_tpu.checkers.elle import device_rw, rw_register
from jepsen_tpu.checkers.elle.device_infer import pad_packed
from jepsen_tpu.checkers.elle.graph import REL_RW, EdgeList
from jepsen_tpu.history.soa import pack_txns
from jepsen_tpu.workloads import synth
from test_rw_register import anomaly_histories


def _searchsorted_join(r_val, r_txn, e_u, e_usable, e_dst, n_bins, cap):
    del n_bins  # the searches need no bound on the keys
    M = e_u.shape[0]
    r_ord = jnp.argsort(r_val, stable=True)
    rv_sorted = r_val[r_ord]
    rt_sorted = r_txn[r_ord]
    lo = jnp.searchsorted(rv_sorted, e_u, side="left")
    hi = jnp.searchsorted(rv_sorted, e_u, side="right")
    cnt = jnp.where(e_usable, hi - lo, 0).astype(jnp.int32)
    offsets = jnp.cumsum(cnt)
    total = offsets[-1]
    j = jnp.arange(cap, dtype=jnp.int32)
    e_j = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32)
    e_jc = jnp.clip(e_j, 0, M - 1)
    prev_off = jnp.where(e_j > 0, offsets[jnp.clip(e_j - 1, 0, M - 1)], 0)
    off = j - prev_off
    valid = (j < total) & (e_j < M)
    reader_j = rt_sorted[jnp.clip(lo[e_jc] + off, 0, M - 1)]
    return {
        "lo": lo, "cnt": cnt, "offsets": offsets, "e_j": e_j,
        "src": jnp.where(valid, reader_j, -1),
        "dst": jnp.where(valid, e_dst[e_jc], -1),
        "valid": valid,
        "overflow": jnp.maximum(total - cap, 0),
    }


def _join_inputs(seed, M, n_vals, read_frac, edge_frac):
    """Join inputs as `infer_rw` builds them: reader values in
    [0, n_vals) or n_vals + 2 (no external read), edge sources in
    [0, n_vals) or n_vals + 3 (no usable edge), n_vals + 4 bins."""
    rng = np.random.default_rng(seed)
    n_txn = M // 3 + 1
    reads = rng.random(M) < read_frac
    usable = rng.random(M) < edge_frac
    r_val = np.where(reads, rng.integers(0, n_vals, M), n_vals + 2)
    e_u = np.where(usable, rng.integers(0, n_vals, M), n_vals + 3)
    e_dst = np.where(usable, rng.integers(0, n_txn, M), -1)
    r_txn = rng.integers(0, n_txn, M)
    arrays = [jnp.asarray(a, jnp.int32) for a in (r_val, r_txn, e_u)]
    return (arrays[0], arrays[1], arrays[2], jnp.asarray(usable),
            jnp.asarray(e_dst, jnp.int32), n_vals + 4)


M_ROWS = 4096


@pytest.mark.parametrize("n_vals,read_frac,edge_frac,cap", [
    pytest.param(100, 0.0, 0.5, "M", id="no-external-reads"),
    pytest.param(100, 0.5, 0.0, "M", id="no-usable-edges"),
    pytest.param(3, 0.6, 0.02, "4M", id="few-values-many-readers"),
    pytest.param(10, 0.5, 0.05, "M", id="values-10"),
    pytest.param(3000, 0.5, 0.5, "M", id="values-3000"),
    pytest.param(50, 0.5, 0.5, "below-total", id="cap-below-total"),
    pytest.param(1000, 0.5, 0.5, "M", id="cap-M"),
    pytest.param(300, 0.5, 0.5, "4M", id="cap-4M"),
])
def test_counting_join_is_the_searchsorted_join(n_vals, read_frac, edge_frac,
                                                cap):
    args = _join_inputs(7, M_ROWS, n_vals, read_frac, edge_frac)
    total = int(np.asarray(_searchsorted_join(*args, M_ROWS)["offsets"])[-1])
    cap = {"M": M_ROWS, "4M": 4 * M_ROWS,
           "below-total": max(total // 2, 1)}[cap]
    want = jax.jit(_searchsorted_join, static_argnums=(5, 6))(*args, cap)
    got = jax.jit(device_rw.rw_join, static_argnums=(5, 6))(*args, cap)
    assert set(got) == set(want)
    for name in want:
        w, g = np.asarray(want[name]), np.asarray(got[name])
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    overflow = int(np.asarray(got["overflow"]))
    assert overflow == max(total - cap, 0)
    if read_frac and edge_frac:
        assert total > 0
    if cap < total:
        assert overflow > 0


def _histories():
    yield "faults", pack_txns(synth.rw_history(
        n_txns=150, n_keys=6, concurrency=5, fail_prob=0.05,
        info_prob=0.05, seed=1), "rw-register")
    yield "packed-valid", synth.packed_rw_history(n_txns=2000, n_keys=50,
                                                  seed=3)
    yield "packed-few-keys", synth.packed_rw_history(
        n_txns=1000, n_keys=3, read_frac=0.8, seed=5)
    for i, h in enumerate(anomaly_histories()):
        yield f"anomaly-{i}", pack_txns(h, "rw-register")


HISTORIES = dict(_histories())


def _host_rw_edges(p, monkeypatch):
    """(src, dst) of every rw edge the host report adds, before dedup."""
    made = []

    class Recording(EdgeList):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(rw_register, "EdgeList", Recording)
    rw_register.check(p, ["snapshot-isolation"], use_device=False)
    (dep,) = made
    rw = dep.rel == REL_RW
    return sorted(zip(dep.src[rw].tolist(), dep.dst[rw].tolist()))


@pytest.mark.parametrize("rw_cap", [0, 8])
@pytest.mark.parametrize("name", list(HISTORIES))
def test_infer_rw_join_matches_reference_and_host(name, rw_cap, monkeypatch):
    p = HISTORIES[name]
    h = pad_packed(p)
    got = device_rw.infer_rw(h, h.n_keys, rw_cap=rw_cap)
    with monkeypatch.context() as m:
        m.setattr(device_rw, "rw_join", _searchsorted_join)
        # a function of its own, so no trace of `infer_rw` is reused
        ref = jax.jit(lambda h, n_keys, rw_cap: device_rw.infer_rw
                      .__wrapped__(h, n_keys, rw_cap=rw_cap),
                      static_argnames=("n_keys", "rw_cap"))
        want = ref(h, n_keys=h.n_keys, rw_cap=rw_cap)
    for g, w, part in zip(got["edges"]["rw"], want["edges"]["rw"],
                          ("src", "dst", "ok")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=part)
    overflow = int(np.asarray(got["rw_overflow"]))
    assert overflow == int(np.asarray(want["rw_overflow"]))
    if overflow:
        assert rw_cap, "a join of M slots overflowed"
        return
    src, dst, ok = (np.asarray(a) for a in got["edges"]["rw"])
    dev = sorted(zip(src[ok].tolist(), dst[ok].tolist()))
    assert dev == _host_rw_edges(p, monkeypatch)


def test_no_binary_search_over_the_mop_rows():
    p = synth.packed_rw_history(n_txns=4096, n_keys=50, seed=1)
    h = pad_packed(p)
    M = h.mop_txn.shape[0]
    assert M != h.txn_type.shape[0]
    text = device_rw.infer_rw.lower(h, h.n_keys).as_text()
    loops = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    carried = [re.findall(r"tensor<(\d+)x", ln.rsplit(" : ", 1)[-1])
               for ln in loops]
    assert all(carried), loops
    assert not [c for c in carried if str(M) in c], loops
