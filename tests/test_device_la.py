"""Device list-append checker vs host oracle — differential tests.

The reference's pattern of checking parallel folds against serial folds
(SURVEY.md §4), upgraded to device-vs-host: every verdict and anomaly set
must match the exact host oracle (one exception: the budget-limited
G-nonadjacent family, where the device can be MORE complete — see
test_device_finds_nonadjacent_oracle_budget_misses).  `_force_no_fallback=True` ensures we are
actually testing the device path, not the oracle fallback.
"""

import numpy as np
import pytest

from jepsen_tpu.checkers.elle import list_append, oracle
from jepsen_tpu.history import history, invoke, ok, fail, info
from jepsen_tpu.workloads import synth

MODELS = ["strict-serializable"]


def both(h, models=MODELS):
    r_o = oracle.check(h, models)
    r_d = list_append.check(h, models, _force_no_fallback=True)
    assert r_o["valid?"] == r_d["valid?"], (r_o, r_d)
    assert set(r_o["anomaly-types"]) == set(r_d["anomaly-types"]), (r_o, r_d)
    return r_d


def concurrent_history(*txns):
    inv, comp = [], []
    for i, (mops_inv, mops_ok) in enumerate(txns):
        inv.append(invoke(i, "txn", mops_inv))
        if mops_ok == "fail":
            comp.append(fail(i, "txn", mops_inv))
        elif mops_ok == "info":
            comp.append(info(i, "txn", None))
        else:
            comp.append(ok(i, "txn", mops_ok))
    return history(inv + comp)


def test_device_valid_and_g1c():
    h = concurrent_history(
        ([["append", "x", 1], ["r", "y", None]],
         [["append", "x", 1], ["r", "y", [9]]]),
        ([["append", "y", 9], ["r", "x", None]],
         [["append", "y", 9], ["r", "x", [1]]]),
    )
    r = both(h)
    assert r["valid?"] is False
    assert "G1c" in r["anomaly-types"]


def test_device_g_single():
    h = concurrent_history(
        ([["append", "k", 1], ["append", "j", 10]],
         [["append", "k", 1], ["append", "j", 10]]),
        ([["append", "k", 2], ["r", "j", None]],
         [["append", "k", 2], ["r", "j", []]]),
        ([["r", "k", None], ["r", "j", None]],
         [["r", "k", [1, 2]], ["r", "j", [10]]]),
    )
    r = both(h)
    assert "G-single" in r["anomaly-types"]
    assert "G-nonadjacent" not in r["anomaly-types"]


def test_device_write_skew():
    h = concurrent_history(
        ([["r", "x", None], ["append", "y", 10]],
         [["r", "x", []], ["append", "y", 10]]),
        ([["r", "y", None], ["append", "x", 1]],
         [["r", "y", []], ["append", "x", 1]]),
        ([["r", "x", None], ["r", "y", None]],
         [["r", "x", [1]], ["r", "y", [10]]]),
    )
    r = both(h)
    assert "G2-item" in r["anomaly-types"]
    assert "G-single" not in r["anomaly-types"]


def test_device_realtime_cycle():
    h = history([
        invoke(0, "txn", [["r", "x", None]]),
        ok(0, "txn", [["r", "x", [1]]]),
        invoke(1, "txn", [["append", "x", 1]]),
        ok(1, "txn", [["append", "x", 1]]),
    ])
    r = both(h)
    assert r["valid?"] is False
    assert "G1c-realtime" in r["anomaly-types"]


def test_device_noncycle_anomalies():
    h = concurrent_history(
        ([["append", "x", 1], ["append", "x", 2]],
         [["append", "x", 1], ["append", "x", 2]]),
        ([["r", "x", None]], [["r", "x", [1]]]),          # G1b
        ([["append", "y", 7]], "fail"),
        ([["r", "y", None]], [["r", "y", [7]]]),          # G1a
        ([["append", "z", 5], ["r", "z", None]],
         [["append", "z", 5], ["r", "z", [5, 9]]]),       # internal
    )
    r = both(h)
    for a in ("G1a", "G1b", "internal"):
        assert a in r["anomaly-types"]


@pytest.mark.parametrize("seed", range(8))
def test_device_differential_synth(seed):
    h = synth.la_history(n_txns=120, n_keys=5, concurrency=4,
                         fail_prob=0.05, info_prob=0.05,
                         multi_append_prob=0.2, seed=seed)
    if seed % 4 == 1:
        synth.inject_g1a(h)
    elif seed % 4 == 2:
        synth.inject_wr_cycle(h)
    elif seed % 4 == 3:
        synth.inject_rw_cycle(h)
    both(h)


def test_device_packed_generator_valid():
    p = synth.packed_la_history(n_txns=3000, n_keys=24, seed=11)
    r = list_append.check(p, MODELS, _force_no_fallback=True)
    assert r["valid?"] is True, r["anomaly-types"]


def test_explainer_g_single_names_key_and_values():
    # VERDICT done-bar: a G-single report names the key and read/append
    # values on EVERY edge (elle/core.clj Explainer equivalence)
    h = concurrent_history(
        ([["append", "k", 1], ["append", "j", 10]],
         [["append", "k", 1], ["append", "j", 10]]),
        ([["append", "k", 2], ["r", "j", None]],
         [["append", "k", 2], ["r", "j", []]]),
        ([["r", "k", None], ["r", "j", None]],
         [["r", "k", [1, 2]], ["r", "j", [10]]]),
    )
    r = list_append.check(h, MODELS, _force_no_fallback=True)
    assert "G-single" in r["anomalies"]
    cyc = r["anomalies"]["G-single"][0]["cycle"]
    assert len(cyc) >= 2
    for e in cyc:
        assert e.get("why"), e
        if e["rel"] in ("ww", "wr", "rw"):
            assert e.get("key") is not None, e
            assert ("value" in e) or ("value'" in e), e
    # the rw (anti-dependency) edge must name the unobserved successor
    rw = [e for e in cyc if e["rel"] == "rw"]
    assert rw and rw[0]["value'"] is not None


def test_explainer_realtime_edge_positions():
    h = history([
        invoke(0, "txn", [["r", "x", None]]),
        ok(0, "txn", [["r", "x", [1]]]),
        invoke(1, "txn", [["append", "x", 1]]),
        ok(1, "txn", [["append", "x", 1]]),
    ])
    r = list_append.check(h, MODELS, _force_no_fallback=True)
    cyc = r["anomalies"]["G1c-realtime"][0]["cycle"]
    rt = [e for e in cyc if e["rel"] == "realtime"]
    assert rt and "completed-at" in rt[0] and "invoked-at" in rt[0]
    wr = [e for e in cyc if e["rel"] == "wr"]
    assert wr and wr[0]["key"] == "x" and wr[0]["value"] == 1


def test_loop_scan_path_matches_assoc_scan(monkeypatch):
    # the Hillis-Steele fori_loop scan (used at 1M+ shapes to kill the
    # associative_scan compile blowup, PROFILE.md §2) must give bitwise
    # the same verdicts as the associative_scan path
    from jepsen_tpu.checkers.elle.device_core import core_check
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.history.soa import pack_txns
    from jepsen_tpu.ops import segments

    cases = []
    h1 = synth.la_history(n_txns=120, n_keys=5, concurrency=6,
                          multi_append_prob=0.2, seed=21)
    cases.append(pack_txns(h1, "list-append"))
    h2 = synth.la_history(n_txns=120, n_keys=5, concurrency=6, seed=22)
    synth.inject_rw_cycle(h2)
    synth.inject_wr_cycle(h2)
    cases.append(pack_txns(h2, "list-append"))

    orig_threshold = segments.LOOP_SCAN_MIN_ROWS
    for p in cases:
        hp = pad_packed(p)
        ref = np.asarray(core_check(hp, p.n_keys)[0])
        monkeypatch.setattr(segments, "LOOP_SCAN_MIN_ROWS", 1)
        core_check.clear_cache()
        got = np.asarray(core_check(hp, p.n_keys)[0])
        monkeypatch.setattr(segments, "LOOP_SCAN_MIN_ROWS",
                            orig_threshold)
        core_check.clear_cache()
        assert np.array_equal(ref, got), (ref, got)


def test_device_converges_on_round_hungry_history():
    """Fuzz regression (2026-07-30): dense injected cycles can need
    hundreds of propagation rounds; detect_cycles must grow max_rounds
    (like the fused path's grow_until_exact) instead of surrendering to
    the host fallback at 64."""
    h = synth.la_history(n_txns=400, n_keys=2, concurrency=8,
                         info_prob=0.2, multi_append_prob=0.2,
                         seed=569558050)
    synth.inject_wr_cycle(h)
    synth.inject_rw_cycle(h)
    r = list_append.check(h, ["serializable"], _force_no_fallback=True)
    assert r["valid?"] is False
    assert "G1c" in r["anomaly-types"]


def test_device_duplicate_elements_fast_path():
    # dup visible in the version order (reads agree with the order):
    # the cond-gated fast path must flag it without the R-sort
    h = concurrent_history(
        ([["append", "x", 1]], [["append", "x", 1]]),
        ([["r", "x", None]], [["r", "x", [1, 1]]]),
    )
    r = both(h, ["serializable"])
    assert "duplicate-elements" in r["anomaly-types"]


def test_device_duplicate_elements_slow_path():
    # dup hidden from the orders: the longest read [1, 2] defines the
    # order, a second read [1, 1] disagrees (incompatible-order) AND
    # holds the dup — only the exact per-read sort path can see it
    h = concurrent_history(
        ([["append", "x", 1]], [["append", "x", 1]]),
        ([["append", "x", 2]], [["append", "x", 2]]),
        ([["r", "x", None]], [["r", "x", [1, 2]]]),
        ([["r", "x", None]], [["r", "x", [1, 1]]]),
    )
    r = both(h, ["serializable"])
    assert "duplicate-elements" in r["anomaly-types"]
    assert "incompatible-order" in r["anomaly-types"]


@pytest.mark.slow  # ~90 s (dense 900-txn graph) — tier-1 budget hog (ISSUE 3)
def test_device_finds_nonadjacent_oracle_budget_misses():
    """Fuzz find (2026-07-30, seed 999 case 33): on a dense 900-txn
    graph the device's witness-region search finds a genuine
    G-nonadjacent cycle that the oracle's whole-SCC budgeted DFS gives
    up on.  Pins (a) the device's stronger completeness, (b) the
    structural validity of its reported cycle, and (c) that the
    verdicts still agree (a nonadjacent cycle is also a G2-item cycle).
    """
    h = synth.la_history(n_txns=900, n_keys=5, concurrency=8,
                         fail_prob=0.05, info_prob=0.05,
                         multi_append_prob=0.2, seed=737240089)
    for _ in range(4):
        synth.inject_wr_cycle(h)
        synth.inject_rw_cycle(h)
    r_d = list_append.check(h, ["strict-serializable"],
                            _force_no_fallback=True)
    r_o = oracle.check(h, ["strict-serializable"])
    assert r_d["valid?"] is False and r_o["valid?"] is False
    na = r_d["anomalies"]["G-nonadjacent"]
    rels = [e["rel"] for e in na[0]["cycle"]]
    # structural spec check: >= 2 rw, none cyclically adjacent
    assert rels.count("rw") >= 2
    for i, rel in enumerate(rels):
        assert not (rel == "rw" and rels[(i + 1) % len(rels)] == "rw"), rels
    # every edge carries concrete evidence (the Explainer filled it in)
    assert all(e.get("why") for e in na[0]["cycle"])
    # apart from the budget-limited nonadjacent family, the sets agree
    from jepsen_tpu.checkers.elle.specs import NONADJACENT_FAMILY

    assert set(r_o["anomaly-types"]) - NONADJACENT_FAMILY == \
        set(r_d["anomaly-types"]) - NONADJACENT_FAMILY


@pytest.mark.parametrize("seed", range(6))
def test_sort_free_run_order_matches_lax_sort(seed):
    """The layout-aware inference paths (sort-free run order via
    within-txn shifted-compare ranking; barrier order via stable
    partition) must be bit-identical to the lax.sort paths they replace.
    Seeds cover valid, fail/info-bearing, and anomaly-injected histories.
    """
    import dataclasses

    import jax

    from jepsen_tpu.checkers.elle.device_infer import infer, pad_packed
    from jepsen_tpu.history.soa import pack_txns

    h = synth.la_history(n_txns=160, n_keys=5, concurrency=6,
                         fail_prob=0.08, info_prob=0.08,
                         multi_append_prob=0.25, max_mops=6, seed=seed)
    if seed % 3 == 1:
        synth.inject_g1a(h)
    elif seed % 3 == 2:
        synth.inject_wr_cycle(h)
    p = pack_txns(h)
    hp = pad_packed(p)
    assert hp.txn_major and hp.run_cap and hp.complete_monotone
    off = dataclasses.replace(hp, txn_major=False, run_cap=0,
                              complete_monotone=False)
    fast = infer(hp, p.n_keys)
    slow = infer(off, p.n_keys)
    for a, b in zip(jax.tree_util.tree_leaves(fast),
                    jax.tree_util.tree_leaves(slow)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_facts_reject_non_txn_major():
    """Hand-built packings that violate the txn-major layout must fall
    back to the sort path (flags off) and still check correctly."""
    from jepsen_tpu.checkers.elle.device_infer import infer, pad_packed
    from jepsen_tpu.history.soa import pack_txns

    h = synth.la_history(n_txns=60, n_keys=4, concurrency=4, seed=3)
    synth.inject_g1a(h)  # a nonzero count the fallback must reproduce
    p = pack_txns(h)
    ref = infer(pad_packed(p), p.n_keys)

    # Equivalent packing with txn mop-blocks in REVERSE txn order:
    # within-txn mop order is preserved (stable argsort) and the
    # read-element extents are rebuilt to match the new mop order, so
    # the packing means the same history but violates txn-major layout.
    order = np.argsort(-p.mop_txn, kind="stable")
    for f in ("mop_txn", "mop_kind", "mop_key", "mop_val",
              "mop_rd_start", "mop_rd_len"):
        setattr(p, f, getattr(p, f)[order])
    elems, new_starts, cur = [], np.full(p.n_mops, -1, np.int32), 0
    for i in range(p.n_mops):
        s, ln = p.mop_rd_start[i], p.mop_rd_len[i]
        if s >= 0:
            new_starts[i] = cur
            elems.extend(p.rd_elems[s:s + max(ln, 0)])
            cur += max(ln, 0)
    p.mop_rd_start, p.rd_elems = new_starts, np.asarray(elems, np.int32)
    hp = pad_packed(p)
    assert not hp.txn_major
    # the device-sort fallback still checks the reordered packing, and
    # the anomaly counts match the txn-major packing's exactly
    scr = infer(hp, p.n_keys)
    for name, v in ref["counts"].items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(scr["counts"][name]),
                                      err_msg=name)
    assert int(np.asarray(ref["counts"]["G1a"])) > 0

    # negative sentinel rows must disable the fast path, not crash
    p.mop_txn = np.sort(p.mop_txn)
    p.mop_txn[0] = -1
    hp2 = pad_packed(p)
    assert not hp2.txn_major


@pytest.mark.parametrize("seed", range(4))
def test_staged_core_check_matches_fused(seed):
    """core_check_staged (two XLA programs, the split used at 2^24-txn
    shapes) is bitwise-equal to the fused core_check — valid and
    injected-invalid histories both."""
    from jepsen_tpu.checkers.elle.device_core import (core_check,
                                                      core_check_staged)
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.history.soa import pack_txns

    if seed == 0:
        p = synth.packed_la_history(n_txns=2000, n_keys=16, seed=3)
    else:
        h = synth.la_history(n_txns=150, n_keys=5, concurrency=4,
                             fail_prob=0.05, info_prob=0.05,
                             multi_append_prob=0.2, seed=seed)
        [synth.inject_g1a, synth.inject_wr_cycle,
         synth.inject_rw_cycle][seed - 1](h)
        p = pack_txns(h)
    hp = pad_packed(p)
    bits_f, over_f = core_check(hp, p.n_keys, max_k=32)
    bits_s, over_s = core_check_staged(hp, p.n_keys, max_k=32)
    np.testing.assert_array_equal(np.asarray(bits_f), np.asarray(bits_s))
    assert int(over_f) == int(over_s)
