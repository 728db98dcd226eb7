"""The traffic generator: deterministic, valid, Elle-shaped, and with
sizes that do not depend on the seed."""

import numpy as np
import pytest

from benchmark import harness

CELL = harness.load_cell("la-ss-valid-256k")
SHAPE, TIMING = CELL.config["shape"], CELL.traffic["timing"]
gen = harness.load_module("gen", CELL.traffic["generator"])
entry = harness.load_module("entries", CELL.config["entry"])
ref = harness.load_module("reference", CELL.config["reference"])
SEEDS = [0, 7, 2**31 + 11]


def make(n=3000, seed=7, inject=None):
    return gen.generate(n, SHAPE, TIMING, seed, inject=inject)


def test_deterministic_from_seed():
    a, b, c = make(seed=5), make(seed=5), make(seed=6)
    for k, v in a.items():
        if isinstance(v, np.ndarray):
            assert np.array_equal(v, b[k]), k
    assert not np.array_equal(a["mop_key"], c["mop_key"])


@pytest.mark.parametrize("seed", SEEDS)
def test_sizes_do_not_depend_on_the_seed(seed):
    h, base = make(seed=seed), make(seed=1)
    for k in ("mop_txn", "mop_kind", "val_key"):
        assert len(h[k]) == len(base[k])
    assert h["n_keys"] == base["n_keys"]
    assert np.array_equal(np.bincount(h["mop_txn"]).clip(0, 9).sum(),
                          np.bincount(base["mop_txn"]).clip(0, 9).sum())
    assert int(h["mop_key"].max()) < h["n_keys"]
    # the padded read-element table lands in the same power of two
    assert 0.55 < len(h["rd_elems"]) / 2 ** int(
        np.ceil(np.log2(len(h["rd_elems"])))) < 0.95


@pytest.mark.parametrize("seed", SEEDS)
def test_the_host_oracle_judges_it_valid(seed):
    from jepsen_tpu.checkers.elle import oracle

    h = make(n=1500, seed=seed)
    res = oracle.check(entry.prepare(h), ["strict-serializable"])
    assert res["valid?"] is True, res["anomaly-types"]


def test_realtime_order_agrees_with_commit_order():
    h = make(n=20000)
    inv, cmp_ = h["txn_invoke_pos"], h["txn_complete_pos"]
    by_completion = np.argsort(cmp_)
    # the latest commit index among txns completed before each invoke
    done_max = np.maximum.accumulate(by_completion)
    k = np.searchsorted(cmp_[by_completion], inv) - 1
    before = np.where(k >= 0, done_max[np.maximum(k, 0)], -1)
    assert (before < np.arange(len(inv))).all()
    # and each client runs one txn at a time, in commit order
    for p in np.unique(h["txn_process"]):
        t = np.nonzero(h["txn_process"] == p)[0]
        assert (cmp_[t][:-1] < inv[t][1:]).all()


def test_no_key_takes_more_than_max_writes():
    h = make(n=20000)
    app = h["mop_rd_len"] < 0
    assert np.bincount(h["mop_key"][app]).max() <= \
        SHAPE["max_writes_per_key"]
    assert h["val_value"].max() == SHAPE["max_writes_per_key"]


def test_key_skew_is_exponential():
    # ten active keys, key i drawn with weight 2^-i: in any run of 40
    # consecutive micro-ops the hottest key takes about half
    h = make(n=20000)
    k = h["mop_key"][: 40 * 1000].reshape(1000, 40)
    top = np.array([np.bincount(r).max() for r in k]) / 40
    assert 0.4 < np.median(top) < 0.65


def test_txn_intervals_overlap():
    h = make(n=5000)
    inv, cmp_ = h["txn_invoke_pos"], h["txn_complete_pos"]
    ev = np.zeros(2 * len(inv), np.int64)
    ev[inv] = 1
    ev[cmp_] = -1
    assert np.cumsum(ev).max() >= 5  # txns in flight at once


def test_completions_follow_commit_order():
    cmp_ = make(n=20000)["txn_complete_pos"]
    assert (np.diff(cmp_) > 0).all()


def test_a_lag_past_one_slot_inverts_completions():
    timing = dict(TIMING, complete_lag=4.5)
    cmp_ = gen.generate(20000, SHAPE, timing, 3)["txn_complete_pos"]
    assert (np.diff(cmp_) < 0).mean() > 0.2


@pytest.mark.parametrize("seed", SEEDS)
def test_the_probe_is_judged_invalid(seed):
    from jepsen_tpu.checkers.elle import oracle

    h = make(n=1500, seed=seed, inject="stale-read")
    assert h["injected"][1] > 1500 - 100  # near the end
    want = ["G-single-realtime", "G2-item-realtime"]
    res = oracle.check(entry.prepare(h), ["strict-serializable"])
    assert res["valid?"] is False
    assert sorted(res["anomaly-types"]) == want
    assert ref.check(h) == {"valid?": False, "anomaly-types": want}
    # a checker without the realtime order sees nothing
    assert ref.check(h, "serializable")["valid?"] is True
