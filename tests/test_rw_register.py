"""rw-register checker tests (reference rw_register_test.clj style)."""

import numpy as np
import pytest

from jepsen_tpu.checkers.elle import rw_register
from jepsen_tpu.history import history, invoke, ok, fail, info
from jepsen_tpu.workloads import synth


def concurrent_history(*txns):
    inv, comp = [], []
    for i, (mops_inv, mops_ok) in enumerate(txns):
        inv.append(invoke(i, "txn", mops_inv))
        if mops_ok == "fail":
            comp.append(fail(i, "txn", mops_inv))
        else:
            comp.append(ok(i, "txn", mops_ok))
    return history(inv + comp)


def test_valid_simple():
    h = concurrent_history(
        ([["w", "x", 1]], [["w", "x", 1]]),
        ([["r", "x", None]], [["r", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert res["valid?"] is True, res


def test_g1a():
    h = concurrent_history(
        ([["w", "x", 1]], "fail"),
        ([["r", "x", None]], [["r", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert res["valid?"] is False
    assert "G1a" in res["anomaly-types"]


def test_g1b_intermediate():
    h = concurrent_history(
        ([["w", "x", 1], ["w", "x", 2]], [["w", "x", 1], ["w", "x", 2]]),
        ([["r", "x", None]], [["r", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert "G1b" in res["anomaly-types"]


def test_internal():
    h = concurrent_history(
        ([["w", "x", 1], ["r", "x", None]],
         [["w", "x", 1], ["r", "x", 9]]),
        ([["w", "x", 9]], [["w", "x", 9]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert "internal" in res["anomaly-types"]


def test_lost_update():
    # T0 and T1 both read x=nil then write -> both updated the same version
    h = concurrent_history(
        ([["r", "x", None], ["w", "x", 1]],
         [["r", "x", None], ["w", "x", 1]]),
        ([["r", "x", None], ["w", "x", 2]],
         [["r", "x", None], ["w", "x", 2]]),
    )
    res = rw_register.check(h, ["snapshot-isolation"])
    assert res["valid?"] is False
    assert "lost-update" in res["anomaly-types"]


def test_g1c_wr_cycle():
    # T0 writes x=1 and reads y=9; T1 writes y=9 and reads x=1
    h = concurrent_history(
        ([["w", "x", 1], ["r", "y", None]],
         [["w", "x", 1], ["r", "y", 9]]),
        ([["w", "y", 9], ["r", "x", None]],
         [["w", "y", 9], ["r", "x", 1]]),
    )
    res = rw_register.check(h, ["read-committed"])
    assert res["valid?"] is False
    assert "G1c" in res["anomaly-types"]


def test_write_skew_g2():
    # classic write skew via rw edges from nil reads
    h = concurrent_history(
        ([["r", "x", None], ["w", "y", 10]],
         [["r", "x", None], ["w", "y", 10]]),
        ([["r", "y", None], ["w", "x", 1]],
         [["r", "y", None], ["w", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert res["valid?"] is False
    assert "G2-item" in res["anomaly-types"]
    res_si = rw_register.check(h, ["snapshot-isolation"])
    assert res_si["valid?"] is True


def test_realtime_strict_only():
    # read of a value written by a txn that invoked after the reader done
    h = history([
        invoke(0, "txn", [["r", "x", None]]),
        ok(0, "txn", [["r", "x", 1]]),
        invoke(1, "txn", [["w", "x", 1]]),
        ok(1, "txn", [["w", "x", 1]]),
    ])
    res = rw_register.check(h, ["strict-serializable"])
    assert res["valid?"] is False
    assert "G1c-realtime" in res["anomaly-types"]
    res2 = rw_register.check(h, ["serializable"])
    assert res2["valid?"] is True


@pytest.mark.parametrize("seed", range(6))
def test_synth_valid(seed):
    h = synth.rw_history(n_txns=150, n_keys=6, concurrency=5,
                         fail_prob=0.05, info_prob=0.05, seed=seed)
    res = rw_register.check(h, ["strict-serializable"])
    assert res["valid?"] is True, (res["anomaly-types"], res["anomalies"])


def test_synth_device_host_same():
    for seed in range(4):
        h = synth.rw_history(n_txns=120, n_keys=5, seed=seed)
        r_dev = rw_register.check(h, ["strict-serializable"],
                                  use_device=True)
        r_host = rw_register.check(h, ["strict-serializable"],
                                   use_device=False)
        assert r_dev["valid?"] == r_host["valid?"]
        assert r_dev["anomaly-types"] == r_host["anomaly-types"]


def test_duplicate_writes_invalidate():
    # two committed writes of the same value break the unique-write
    # contract: the history must be invalid, not just annotated
    h = concurrent_history(
        ([["w", "x", 1]], [["w", "x", 1]]),
        ([["w", "x", 1]], [["w", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert res["valid?"] is False
    assert "duplicate-writes" in res["anomaly-types"]


def test_aborted_duplicate_does_not_fabricate_g1a():
    # a FAILED duplicate of a committed write must not make readers of the
    # committed value look like aborted reads
    h = concurrent_history(
        ([["w", "x", 1]], "fail"),
        ([["w", "x", 1]], [["w", "x", 1]]),
        ([["r", "x", None]], [["r", "x", 1]]),
    )
    res = rw_register.check(h, ["serializable"])
    assert "G1a" not in res["anomaly-types"], res
    assert "duplicate-writes" in res["anomaly-types"]


def test_explainer_rw_register_edges_justified():
    h = concurrent_history(
        ([["w", "x", 1], ["r", "y", None]],
         [["w", "x", 1], ["r", "y", 9]]),
        ([["w", "y", 9], ["r", "x", None]],
         [["w", "y", 9], ["r", "x", 1]]),
    )
    res = rw_register.check(h, ["read-committed"])
    cyc = res["anomalies"]["G1c"][0]["cycle"]
    for e in cyc:
        assert e.get("why"), e
        if e["rel"] in ("ww", "wr", "rw"):
            assert e.get("key") is not None, e
    wr = [e for e in cyc if e["rel"] == "wr"]
    assert wr and wr[0]["value"] in (1, 9)


# ---- fused device rw check (device_rw.py) --------------------------------

def _host_flags(h):
    """Host-checker verdicts mapped to the device bit granularity."""
    res = rw_register.check(h, ["strict-serializable"], use_device=False)
    at = set(res["anomaly-types"])
    base = {"G0", "G1c", "G-single", "G2-item", "G-nonadjacent"}
    proc = {a + "-process" for a in base}
    rt_ = {a + "-realtime" for a in base}
    return res, {
        "counts": {n: (n in at) for n in
                   ("duplicate-writes", "internal", "G1a", "G1b",
                    "lost-update", "cyclic-versions")},
        "cycles": {
            "G0": "G0" in at,
            "G1c": bool({"G0", "G1c"} & at),
            "G2-family": bool(base & at),
            "G2-family-process": bool((base | proc) & at),
            "G2-family-realtime": bool((base | rt_) & at),
        },
    }


def _assert_device_matches_host(h):
    from jepsen_tpu.checkers.elle import device_rw
    from jepsen_tpu.history.soa import pack_txns

    res_host, want = _host_flags(h)
    got = device_rw.check(pack_txns(h, "rw-register"))
    assert got["exact"] is True
    assert got["valid?"] == res_host["valid?"], (got, res_host)
    for n, flag in want["counts"].items():
        assert (got["counts"][n] > 0) == flag, (n, got, res_host)
    for n, flag in want["cycles"].items():
        assert got["cycles"][n] == flag, (n, got, res_host)


@pytest.mark.parametrize("seed", range(6))
def test_device_rw_differential_valid(seed):
    h = synth.rw_history(n_txns=150, n_keys=6, concurrency=5,
                         fail_prob=0.05, info_prob=0.05, seed=seed)
    _assert_device_matches_host(h)


def anomaly_histories():
    """Small histories, one per anomaly the fused check reports."""
    return [
        # wr cycle (G1c)
        concurrent_history(
            ([["w", "x", 1], ["r", "y", None]],
             [["w", "x", 1], ["r", "y", 9]]),
            ([["w", "y", 9], ["r", "x", None]],
             [["w", "y", 9], ["r", "x", 1]]),
        ),
        # write skew (G2-item via rw edges)
        concurrent_history(
            ([["r", "x", None], ["w", "y", 10]],
             [["r", "x", None], ["w", "y", 10]]),
            ([["r", "y", None], ["w", "x", 1]],
             [["r", "y", None], ["w", "x", 1]]),
        ),
        # G1a: read of failed write
        concurrent_history(
            ([["w", "x", 5]], "fail"),
            ([["r", "x", None]], [["r", "x", 5]]),
        ),
        # internal: read contradicts own write
        concurrent_history(
            ([["w", "x", 7], ["r", "x", None]],
             [["w", "x", 7], ["r", "x", 3]]),
            ([["w", "x", 3]], [["w", "x", 3]]),
        ),
        # lost update: two txns read same version then write
        concurrent_history(
            ([["r", "x", None], ["w", "x", 1]],
             [["r", "x", None], ["w", "x", 1]]),
            ([["r", "x", None], ["w", "x", 2]],
             [["r", "x", None], ["w", "x", 2]]),
        ),
        # duplicate writes
        concurrent_history(
            ([["w", "x", 1]], [["w", "x", 1]]),
            ([["w", "x", 1]], [["w", "x", 1]]),
        ),
    ]


def test_device_rw_differential_anomalies():
    for i, h in enumerate(anomaly_histories()):
        try:
            _assert_device_matches_host(h)
        except AssertionError as e:
            raise AssertionError(f"case {i}: {e}") from e


def test_device_rw_realtime_cycle():
    # read-before-write in real time: strict-serializable violation only
    h = history([
        invoke(0, "txn", [["r", "x", None]]),
        ok(0, "txn", [["r", "x", 1]]),
        invoke(1, "txn", [["w", "x", 1]]),
        ok(1, "txn", [["w", "x", 1]]),
    ])
    _assert_device_matches_host(h)


def test_packed_rw_history_valid_and_matches_host():
    from jepsen_tpu.checkers.elle import device_rw

    p = synth.packed_rw_history(n_txns=2000, n_keys=50, seed=3)
    got = device_rw.check(p)
    assert got["valid?"] is True, got
    res_host = rw_register.check(p, ["strict-serializable"],
                                 use_device=False)
    assert res_host["valid?"] is True, res_host["anomaly-types"]


def test_fused_fast_path_on_large_history(monkeypatch):
    # above the threshold a clean history returns via the fused device
    # path without host inference; a seeded anomaly still gets the full
    # host report
    from jepsen_tpu.checkers.elle import rw_register as rw

    monkeypatch.setattr(rw, "FUSED_MIN_TXNS", 1000)
    p = synth.packed_rw_history(n_txns=2000, n_keys=50, seed=4)
    res = rw.check(p, ["strict-serializable"])
    assert res["valid?"] is True
    assert res.get("fused-device") is True

    h = concurrent_history(
        ([["w", "x", 1], ["r", "y", None]],
         [["w", "x", 1], ["r", "y", 9]]),
        ([["w", "y", 9], ["r", "x", None]],
         [["w", "y", 9], ["r", "x", 1]]),
    )
    # small history: host path with full anomaly report regardless
    res_bad = rw.check(h, ["read-committed"])
    assert res_bad["valid?"] is False
    assert "G1c" in res_bad["anomalies"]


# ---- spans of the fused check and the report path's sweep ----------------

def _traced(fn):
    from jepsen_tpu import telemetry

    c = telemetry.activate(telemetry.Collector())
    try:
        return fn(), c
    finally:
        telemetry.deactivate(c)


def _all_spans(c, name):
    out, stack = [], list(c.roots)
    while stack:
        sp = stack.pop()
        if sp.name == name:
            out.append(sp)
        stack += sp.children
    return out


def _blind_write_history(n):
    """n readers of x = nil and n blind writers of x, all concurrent: the
    rw join holds n * n edges, more than the fused check's first rw_cap
    (the padded micro-op count) once n passes 16."""
    return concurrent_history(
        *[([["r", "x", None]], [["r", "x", None]]) for _ in range(n)],
        *[([["w", "x", i]], [["w", "x", i]]) for i in range(n)])


def test_fused_check_spans(monkeypatch):
    monkeypatch.setattr(rw_register, "FUSED_MIN_TXNS", 0)
    p = synth.packed_rw_history(n_txns=2000, n_keys=50, seed=4)
    res, c = _traced(lambda: rw_register.check(p, ["snapshot-isolation"]))
    assert res["valid?"] is True and res.get("fused-device") is True
    (phase,) = _all_spans(c, "elle.rw-core-check")
    assert [s.name for s in phase.children] == ["elle.pad", "rw.core-call"]
    call = phase.children[1]
    assert call.attrs["retry"] is None
    assert call.attrs["rw_cap"] == 8192 and call.attrs["max_k"] == 128
    assert phase.children[0].attrs["T"] == 2048


def test_fused_check_regrows_rw_cap_in_a_second_call(monkeypatch):
    monkeypatch.setattr(rw_register, "FUSED_MIN_TXNS", 0)
    h = _blind_write_history(40)
    res, c = _traced(lambda: rw_register.check(h, ["snapshot-isolation"]))
    assert res["valid?"] is True and res.get("fused-device") is True
    calls = _all_spans(c, "rw.core-call")
    calls.sort(key=lambda s: s.t0)
    assert [s.attrs["retry"] for s in calls] == ["rw-cap", None]
    assert calls[0].attrs["rw_cap"] < 40 * 40 <= calls[1].attrs["rw_cap"]


@pytest.mark.parametrize("make", [
    lambda: synth.packed_rw_history(n_txns=2000, n_keys=50, seed=5),
    lambda: _blind_write_history(40),
    lambda: concurrent_history(
        ([["r", "x", None], ["w", "y", 10]],
         [["r", "x", None], ["w", "y", 10]]),
        ([["r", "y", None], ["w", "x", 1]],
         [["r", "y", None], ["w", "x", 1]]))],
    ids=["valid", "rw-cap-regrow", "write-skew"])
def test_fused_check_verdict_without_telemetry(monkeypatch, make):
    monkeypatch.setattr(rw_register, "FUSED_MIN_TXNS", 0)
    h = make()
    on, _ = _traced(lambda: rw_register.check(h, ["serializable"]))
    off = rw_register.check(h, ["serializable"])
    assert on["valid?"] == off["valid?"]
    assert on["anomaly-types"] == off["anomaly-types"]


G1C = concurrent_history(
    ([["w", "x", 1], ["r", "y", None]], [["w", "x", 1], ["r", "y", 9]]),
    ([["w", "y", 9], ["r", "x", None]], [["w", "y", 9], ["r", "x", 1]]))


def test_report_sweep_device_error_falls_back_on_the_record(monkeypatch):
    from jepsen_tpu.ops import cycle_sweep

    want = rw_register.check(G1C, ["read-committed"])

    def broken(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(cycle_sweep, "detect_cycles", broken)
    got, c = _traced(lambda: rw_register.check(G1C, ["read-committed"]))
    assert got["valid?"] is want["valid?"] is False
    assert got["anomaly-types"] == want["anomaly-types"]
    falls = _all_spans(c, "elle.host-fallback")
    assert falls and {s.attrs["reason"] for s in falls} == {
        "device-error:RuntimeError"}


@pytest.mark.parametrize("converged,reason", [(False, "not-converged"),
                                              (True, "no-regions")])
def test_report_sweep_fallback_reasons(monkeypatch, converged, reason):
    import dataclasses

    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.ops import cycle_sweep

    want = rw_register.check(G1C, ["read-committed"])
    orig = cycle_sweep.detect_cycles
    monkeypatch.setattr(
        cycle_sweep, "detect_cycles",
        lambda *a, **kw: dataclasses.replace(orig(*a, **kw),
                                             converged=converged))
    monkeypatch.setattr(list_append, "_witness_regions",
                        lambda *a, **kw: [])
    got, c = _traced(lambda: rw_register.check(G1C, ["read-committed"]))
    assert got["anomaly-types"] == want["anomaly-types"]
    assert {s.attrs["reason"] for s in _all_spans(
        c, "elle.host-fallback")} == {reason}


def test_report_sweep_has_no_fallback_span_on_the_device_path():
    res, c = _traced(lambda: rw_register.check(G1C, ["read-committed"]))
    assert "G1c" in res["anomaly-types"]
    assert not _all_spans(c, "elle.host-fallback")


def _chain_with_cycle(n_edges):
    """A graph of n_edges edges on 64 nodes: a forward chain and one
    backward edge closing a cycle."""
    from jepsen_tpu.checkers.elle.graph import EdgeList

    src = np.arange(n_edges - 1) % 63
    e = EdgeList()
    e.src = np.concatenate([src, [5]]).astype(np.int32)
    e.dst = np.concatenate([src + 1, [2]]).astype(np.int32)
    e.rel = np.zeros(n_edges, np.int8)
    return e


def test_report_sweep_shares_one_program_across_edge_counts(monkeypatch):
    """Two graphs whose edge counts differ within one power of two give
    the host's verdict and run one enumeration program and one sweep
    program between them."""
    import jax

    from jepsen_tpu import compilecache
    from jepsen_tpu.checkers.elle import txn_cycles

    shapes = {}
    real = compilecache.call

    def spy(name, fn, *args, **kw):
        if name in ("cycle-sweep.enumerate", "cycle-sweep.families"):
            shapes.setdefault(name, []).append(
                (tuple(np.shape(a) for a in jax.tree_util.tree_leaves(args)),
                 kw["fam_lens"]))
        return real(name, fn, *args, **kw)

    monkeypatch.setattr(compilecache, "call", spy)
    rank = np.arange(64, dtype=np.int32)
    for n in (70, 90):
        proj = _chain_with_cycle(n)
        dev = txn_cycles._cycle_regions(proj, 64, rank, use_device=True)
        host = txn_cycles._cycle_regions(proj, 64, rank, use_device=False)
        assert dev is not None and host is not None
        assert set(np.concatenate(dev)) <= set(np.concatenate(host))
    assert sorted(shapes) == ["cycle-sweep.enumerate",
                              "cycle-sweep.families"]
    for runs in shapes.values():
        assert len(runs) == 2 and len(set(runs)) == 1
        assert runs[0][0][1] == (128,) and runs[0][1] == (128,)
