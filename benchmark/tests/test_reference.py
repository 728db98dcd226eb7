"""The plain reference against the program's host oracle on small
histories that hold each anomaly the cells can meet, and on generated
ones."""

import numpy as np
import pytest

from benchmark import harness

CELL = harness.load_cell("la-ss-valid-256k")
gen = harness.load_module("gen", CELL.traffic["generator"])
entry = harness.load_module("entries", CELL.config["entry"])
ref = harness.load_module("reference", CELL.config["reference"])


def build(txns):
    """Columns from [(process, invoke_pos, complete_pos, ops)], ops of
    ("a", key, value) and ("r", key, [values])."""
    vals = {}
    for _, _, _, ops in txns:
        for f, k, v in ops:
            if f == "a":
                vals.setdefault((k, v), len(vals))
    keys = sorted({k for _, _, _, ops in txns for _, k, _ in ops})
    cols = {c: [] for c in ("mop_txn", "mop_kind", "mop_key", "mop_val",
                            "mop_rd_start", "mop_rd_len")}
    elems = []
    for t, (_, _, _, ops) in enumerate(txns):
        for f, k, v in ops:
            cols["mop_txn"].append(t)
            cols["mop_key"].append(keys.index(k))
            if f == "a":
                cols["mop_kind"].append(0)
                cols["mop_val"].append(vals[(k, v)])
                cols["mop_rd_start"].append(-1)
                cols["mop_rd_len"].append(-1)
            else:
                cols["mop_kind"].append(1)
                cols["mop_val"].append(-1)
                cols["mop_rd_start"].append(len(elems))
                cols["mop_rd_len"].append(len(v))
                elems += [vals[(k, e)] for e in v]
    by_id = sorted(vals, key=vals.get)
    h = {c: np.array(v, np.int8 if c == "mop_kind" else np.int32)
         for c, v in cols.items()}
    h.update(
        txn_process=np.array([t[0] for t in txns], np.int32),
        txn_invoke_pos=np.array([t[1] for t in txns], np.int32),
        txn_complete_pos=np.array([t[2] for t in txns], np.int32),
        rd_elems=np.array(elems, np.int32),
        val_key=np.array([keys.index(k) for k, _ in by_id], np.int32),
        val_value=np.array([v for _, v in by_id], np.int32),
        n_keys=len(keys), n_events=2 * len(txns))
    return h


def concurrent(*ops_per_txn):
    """Txns on processes 0, 1, ... all in flight at once."""
    n = len(ops_per_txn)
    return [(i, i, n + i, ops) for i, ops in enumerate(ops_per_txn)]


CASES = {
    "valid": concurrent([("a", "x", 1)], [("r", "x", [1]), ("a", "y", 1)]),
    "G0": concurrent([("a", "x", 1), ("a", "y", 1)],
                     [("a", "x", 2), ("a", "y", 2)],
                     [("r", "x", [1, 2]), ("r", "y", [2, 1])]),
    "G1c": concurrent([("a", "x", 1), ("r", "y", [1])],
                      [("a", "y", 1), ("r", "x", [1])]),
    "G-single": concurrent([("r", "x", []), ("r", "y", [1])],
                           [("a", "x", 1), ("a", "y", 1)],
                           [("r", "x", [1])]),
    "G2-item": concurrent([("r", "x", []), ("a", "y", 1)],
                          [("r", "y", []), ("a", "x", 1)],
                          [("r", "x", [1]), ("r", "y", [1])]),
    "G-nonadjacent": concurrent([("r", "x", []), ("r", "w", [1])],
                                [("a", "x", 1), ("a", "y", 1)],
                                [("r", "y", [1]), ("r", "z", [])],
                                [("a", "z", 1), ("a", "w", 1)],
                                [("r", "x", [1]), ("r", "z", [1])]),
    "realtime": [(0, 0, 1, [("a", "x", 1)]), (1, 2, 3, [("r", "x", [])]),
                 (2, 0, 4, [("r", "x", [1])])],
    "process": [(0, 0, 1, [("a", "x", 1)]), (0, 2, 3, [("r", "x", [])]),
                (2, 0, 4, [("r", "x", [1])])],
    "internal": concurrent([("a", "x", 1), ("r", "x", [])]),
    "G1b": concurrent([("a", "x", 1), ("a", "x", 2)], [("r", "x", [1])],
                      [("r", "x", [1, 2])]),
    "duplicate": concurrent([("a", "x", 1)], [("r", "x", [1, 1])]),
    "incompatible-order": concurrent([("a", "x", 1)], [("a", "x", 2)],
                                     [("r", "x", [1, 2])],
                                     [("r", "x", [2, 1])]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_agrees_with_the_oracle(case):
    from jepsen_tpu.checkers.elle import oracle

    h = build(CASES[case])
    want = entry.answer(oracle.check(entry.prepare(h),
                                     ["strict-serializable"]))
    got = ref.check(h)
    assert got == want
    assert got["valid?"] is (case == "valid")


@pytest.mark.parametrize("seed", [3, 2**31 + 3])
@pytest.mark.parametrize("inject", [None, "stale-read"])
def test_reference_agrees_on_generated_histories(seed, inject):
    from jepsen_tpu.checkers.elle import oracle

    h = gen.generate(2000, CELL.config["shape"], CELL.traffic["timing"],
                     seed, inject=inject)
    want = entry.answer(oracle.check(entry.prepare(h),
                                     ["strict-serializable"]))
    assert ref.check(h) == want


def _internal_loop(h) -> bool:
    """The reference's internal-consistency check, one micro-op at a
    time: what the vectorised one must equal."""
    mt, mk = h["mop_txn"].astype(np.int64), h["mop_key"].astype(np.int64)
    state = {}  # (txn, key) -> (whole list known?, list or own suffix)
    for m in range(len(mt)):
        known, lst = state.get((mt[m], mk[m]), (False, []))
        if h["mop_rd_len"][m] < 0:
            state[(mt[m], mk[m])] = (known, lst + [int(h["mop_val"][m])])
            continue
        s, n = int(h["mop_rd_start"][m]), int(h["mop_rd_len"][m])
        seen = h["rd_elems"][s:s + n].tolist()
        if known and seen != lst:
            return True
        if not known and (n < len(lst) or seen[n - len(lst):] != lst):
            return True
        state[(mt[m], mk[m])] = (True, seen)
    return False


@pytest.mark.parametrize("seed", range(12))
def test_internal_check_equals_the_loop(seed):
    """On generated histories with one read element altered or dropped
    at random (which sometimes breaks internal consistency)."""
    rng = np.random.default_rng(seed)
    shape = dict(CELL.config["shape"], max_txn_length=8, key_count=2)
    timing = CELL.traffic["timing"]
    h = gen.generate(600, shape, timing, seed)
    reads = np.nonzero(h["mop_rd_len"] > 0)[0]
    r = reads[rng.integers(len(reads))]
    at = h["mop_rd_start"][r] + rng.integers(h["mop_rd_len"][r])
    if seed % 2:
        h["rd_elems"] = h["rd_elems"].copy()
        h["rd_elems"][at] = h["rd_elems"][h["mop_rd_start"][r]]
    else:
        h["rd_elems"] = np.delete(h["rd_elems"], at)
        h["mop_rd_len"] = h["mop_rd_len"].copy()
        h["mop_rd_len"][r] -= 1
        h["mop_rd_start"] = np.where(
            (np.arange(len(h["mop_rd_start"])) > r) & (h["mop_rd_start"] >= 0),
            h["mop_rd_start"] - 1, h["mop_rd_start"])
    assert ref._internal(h) == _internal_loop(h)
    assert not ref._internal(gen.generate(600, shape, timing, seed))


@pytest.mark.parametrize("case", sorted(CASES))
def test_internal_check_equals_the_loop_on_the_cases(case):
    h = build(CASES[case])
    assert ref._internal(h) == _internal_loop(h)
