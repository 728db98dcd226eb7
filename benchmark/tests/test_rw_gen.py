"""The rw-register traffic generator: deterministic, serially valid,
shaped to Elle's wr-txns, with sizes that do not depend on the seed, and
a probe that only snapshot isolation's checker rejects."""

import numpy as np
import pytest

from benchmark import harness

CELL = harness.load_cell("rw-si-valid-512k")
SHAPE, TIMING = CELL.config["shape"], CELL.traffic["timing"]
gen = harness.load_module("gen", CELL.traffic["generator"])
entry = harness.load_module("entries", CELL.config["entry"])
ref = harness.load_module("reference", CELL.config["reference"])
SEEDS = [0, 7, 2**31 + 11]


def make(n=2000, seed=7, inject=None):
    return gen.generate(n, SHAPE, TIMING, seed, inject=inject)


@pytest.mark.parametrize("n,seed", [(600, 3), (2000, 7), (20000, 2**31 + 11)])
def test_the_draw_is_the_list_append_draw_read_as_writes(n, seed):
    """Each read returns the last element its list-append twin reads; all
    else is the list-append draw of the same seed, column for column."""
    la = harness.load_module("gen", "elle_append").generate(
        n, SHAPE, TIMING, seed)
    h = make(n=n, seed=seed)
    rd = la["mop_kind"] == 1
    ln = la["mop_rd_len"]
    last = la["rd_elems"][np.maximum(la["mop_rd_start"] + ln - 1, 0)]
    want = np.where(rd, np.where(ln > 0, last, -1), la["mop_val"])
    assert np.array_equal(h["mop_val"], want)
    for k in ("txn_process", "txn_invoke_pos", "txn_complete_pos",
              "mop_txn", "mop_kind", "mop_key", "val_key", "val_value"):
        assert np.array_equal(h[k], la[k]), k
    assert (h["n_keys"], h["n_events"]) == (la["n_keys"], la["n_events"])


def test_deterministic_from_seed():
    a, b, c = make(seed=5), make(seed=5), make(seed=6)
    for k, v in a.items():
        if isinstance(v, np.ndarray):
            assert np.array_equal(v, b[k]), k
    assert not np.array_equal(a["mop_key"], c["mop_key"])


@pytest.mark.parametrize("seed", SEEDS)
def test_sizes_do_not_depend_on_the_seed(seed):
    h, base = make(seed=seed), make(seed=1)
    for k in ("mop_txn", "mop_kind", "val_key", "txn_process"):
        assert len(h[k]) == len(base[k]), k
    assert h["n_keys"] == base["n_keys"]
    assert (h["mop_kind"] == 1).sum() == (base["mop_kind"] == 1).sum()
    assert np.array_equal(np.bincount(np.bincount(h["mop_txn"])),
                          np.bincount(np.bincount(base["mop_txn"])))


@pytest.mark.parametrize("seed", SEEDS)
def test_every_read_returns_its_keys_latest_value(seed):
    h = make(seed=seed)
    latest = {}
    for kind, k, v in zip(h["mop_kind"].tolist(), h["mop_key"].tolist(),
                          h["mop_val"].tolist()):
        if kind == 0:
            latest[k] = v
        else:
            assert v == latest.get(k, -1)
    assert (h["mop_val"][h["mop_kind"] == 1] == -1).any()  # nil reads


@pytest.mark.parametrize("seed", SEEDS)
def test_the_host_oracle_judges_it_valid(seed):
    from jepsen_tpu.checkers.elle import rw_register

    res = rw_register.check(entry.prepare(make(seed=seed)),
                            ["snapshot-isolation"], use_device=False)
    assert res["valid?"] is True, res["anomaly-types"]


def test_writes_are_fresh_values_and_keys_retire():
    h = make(n=20000)
    w = h["mop_kind"] == 0
    vals = h["mop_val"][w]
    assert len(np.unique(vals)) == len(vals) == len(h["val_key"])
    assert np.array_equal(h["val_key"][vals], h["mop_key"][w])
    assert np.bincount(h["mop_key"][w]).max() <= SHAPE["max_writes_per_key"]
    assert h["val_value"].max() == SHAPE["max_writes_per_key"]
    assert abs((~w).mean() - SHAPE["read_share"]) < 1e-3


def test_txn_lengths_come_in_equal_counts():
    counts = np.bincount(np.bincount(make(n=20000)["mop_txn"]))[1:]
    assert counts.max() - counts.min() <= 1 and len(counts) == 4


def test_key_skew_is_exponential():
    h = make(n=20000)
    k = h["mop_key"][: 40 * 1000].reshape(1000, 40)
    top = np.array([np.bincount(r).max() for r in k]) / 40
    assert 0.4 < np.median(top) < 0.65


def test_completions_follow_commit_order():
    cmp_ = make(n=20000)["txn_complete_pos"]
    assert (np.diff(cmp_) > 0).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_probe_is_one_read_skew(seed):
    from jepsen_tpu.checkers.elle import rw_register

    base, h = make(seed=seed), make(seed=seed, inject="read-skew")
    a, b = h["injected"]
    assert a < b and b > 2000 // 2
    # one value changes, and no size
    for k, v in base.items():
        if isinstance(v, np.ndarray) and k != "mop_val":
            assert np.array_equal(v, h[k]), k
    (at,) = np.nonzero(base["mop_val"] != h["mop_val"])
    assert h["mop_txn"][at] == b
    for model, want in (("snapshot-isolation", ["G-single"]),
                        ("read-committed", [])):
        truth = {"valid?": not want, "anomaly-types": want}
        assert ref.check(h, model) == truth
        res = rw_register.check(entry.prepare(h), [model],
                                use_device=False)
        assert entry.answer(res) == truth
