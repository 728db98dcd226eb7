"""The plain rw-register reference against the program's host oracle
(`rw_register.check(..., use_device=False)`) on small histories that
hold each anomaly the cell's models can meet, and on generated ones."""

import numpy as np
import pytest

from benchmark import harness

CELL = harness.load_cell("rw-si-valid-512k")
gen = harness.load_module("gen", CELL.traffic["generator"])
entry = harness.load_module("entries", CELL.config["entry"])
ref = harness.load_module("reference", CELL.config["reference"])
MODELS = CELL.config["consistency_models"] + CELL.config["control_models"]


def build(txns):
    """Columns from [(committed?, ops)], ops of ("w", key, value) and
    ("r", key, value or None); all txns in flight at once."""
    vals = {}
    for _, ops in txns:
        for f, k, v in ops:
            if f == "w":
                vals.setdefault((k, v), len(vals))
    keys = sorted({k for _, ops in txns for _, k, _ in ops})
    rows = [(t, f, k, v) for t, (_, ops) in enumerate(txns)
            for f, k, v in ops]
    by_id = sorted(vals, key=vals.get)
    n = len(txns)
    return {
        "txn_process": np.arange(n, dtype=np.int32),
        "txn_invoke_pos": np.arange(n, dtype=np.int32),
        "txn_complete_pos": np.arange(n, 2 * n, dtype=np.int32),
        "txn_ok": np.array([c for c, _ in txns], bool),
        "mop_txn": np.array([r[0] for r in rows], np.int32),
        "mop_kind": np.array([r[1] == "r" for r in rows], np.int8),
        "mop_key": np.array([keys.index(r[2]) for r in rows], np.int32),
        "mop_val": np.array([vals.get((r[2], r[3]), -1) for r in rows],
                            np.int32),
        "val_key": np.array([keys.index(k) for k, _ in by_id], np.int32),
        "val_value": np.array([v for _, v in by_id], np.int32),
        "n_keys": len(keys), "n_events": 2 * n}


def ok(*ops):
    return (True, list(ops))


def aborted(*ops):
    return (False, list(ops))


def w(k, v):
    return ("w", k, v)


def r(k, v=None):
    return ("r", k, v)


CASES = {
    "valid": [ok(w("x", 1)), ok(r("x", 1), w("y", 1))],
    "G0": [ok(w("x", 1), r("y", 1), w("y", 2)),
           ok(r("x", 1), w("x", 2), w("y", 1))],
    "G1a": [aborted(w("x", 1)), ok(r("x", 1))],
    "G1b": [ok(w("x", 1), w("x", 2)), ok(r("x", 1))],
    "G1c": [ok(w("x", 1), r("y", 1)), ok(w("y", 1), r("x", 1))],
    "G-single": [ok(w("x", 1)), ok(r("x", 1), w("x", 2), w("y", 1)),
                 ok(r("x", 1), r("y", 1))],
    "G2-item": [ok(r("x"), w("y", 1)), ok(r("y"), w("x", 1))],
    "lost-update": [ok(r("x"), w("x", 1)), ok(r("x"), w("x", 2))],
    "internal": [ok(w("x", 1), r("x", 9)), ok(w("x", 9))],
    "duplicate-writes": [ok(w("x", 1)), ok(w("x", 1))],
    "cyclic-versions": [ok(r("x", 1), w("x", 2)), ok(r("x", 2), w("x", 1))],
}
#: what each case breaks under snapshot isolation; read committed allows
#: G-single, G2-item, lost-update and internal
WANT_SI = {
    "valid": [], "G0": ["G0", "G1c"], "G1a": ["G1a"],
    "G1b": ["G-single", "G1b"],
    "G1c": ["G1c"], "G-single": ["G-single"], "G2-item": [],
    "lost-update": ["lost-update"], "internal": ["internal"],
    "duplicate-writes": ["duplicate-writes"],
}


def oracle(h, model):
    from jepsen_tpu.checkers.elle import rw_register

    return entry.answer(rw_register.check(entry.prepare(h), [model],
                                          use_device=False))


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_agrees_with_the_oracle(case, model):
    h = build(CASES[case])
    got = ref.check(h, model)
    assert got == oracle(h, model)
    if model == "snapshot-isolation" and case in WANT_SI:
        assert got["anomaly-types"] == WANT_SI[case]


@pytest.mark.parametrize("seed", [3, 2**31 + 3])
@pytest.mark.parametrize("inject", [None, "read-skew"])
def test_reference_agrees_on_generated_histories(seed, inject):
    h = gen.generate(2000, CELL.config["shape"], CELL.traffic["timing"],
                     seed, inject=inject)
    for model in MODELS:
        assert ref.check(h, model) == oracle(h, model)


def test_an_unknown_model_is_refused():
    with pytest.raises(ValueError):
        ref.check(build(CASES["valid"]), "serializable")
