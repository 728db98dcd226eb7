"""The rw-register cell end to end (rehearsal mode, CPU, a small
history): `correct` is true for the program as it is, false for the
control and for each fault the cell can have, and a traced run reads
the cell's per-layer metrics.  The fused device program runs at any
size here: its threshold (`rw_register.FUSED_MIN_TXNS`) is patched
to 0."""

import json
import time
from types import SimpleNamespace

import pytest

from benchmark import harness

CELL = "rw-si-valid-512k"


@pytest.fixture(autouse=True)
def fused_at_any_size(monkeypatch):
    from jepsen_tpu.checkers.elle import rw_register

    monkeypatch.setattr(rw_register, "FUSED_MIN_TXNS", 0)


def _run(capsys, control=False, seed=2**31 + 7, trace=0):
    args = SimpleNamespace(workload=CELL, seed=seed, seconds=0.5,
                           trace=trace, rehearse=1000, control=control)
    assert harness.run(args, time.perf_counter()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_program_as_it_is_is_correct(capsys, seed):
    out = _run(capsys, seed=seed)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_control_is_not_correct(capsys, seed):
    # read committed allows the probe's G-single: it then reads valid
    out = _run(capsys, control=True, seed=seed)
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] == 1


def _wrap(monkeypatch, before=None, after=None):
    from jepsen_tpu.checkers.elle import rw_register

    orig = rw_register.check

    def check(p, models, **kw):
        res = orig(before(p) if before else p, models, **kw)
        return after(res) if after else res

    monkeypatch.setattr(rw_register, "check", check)


def _first_half(p):
    """The packed history with its later half of txns left out."""
    import dataclasses

    T = p.n_txns // 2
    m = p.mop_txn < T
    return dataclasses.replace(
        p, txn_type=p.txn_type[:T], txn_process=p.txn_process[:T],
        txn_invoke_pos=p.txn_invoke_pos[:T],
        txn_complete_pos=p.txn_complete_pos[:T],
        txn_orig_index=p.txn_orig_index[:T],
        mop_txn=p.mop_txn[m], mop_kind=p.mop_kind[m], mop_key=p.mop_key[m],
        mop_val=p.mop_val[m], mop_rd_start=p.mop_rd_start[m],
        mop_rd_len=p.mop_rd_len[m])


def test_an_answer_altered_where_it_is_produced(capsys, monkeypatch):
    _wrap(monkeypatch, after=lambda res: {**res, "valid?": not res["valid?"]})
    assert _run(capsys)["correct"] is False


def test_half_of_the_history_left_out(capsys, monkeypatch):
    """Only the earlier half is checked: the window's valid histories
    still read valid, the probe, whose pair lies in the later half for
    this seed, reads valid too."""
    seed = 1
    cell = harness.load_cell(CELL)
    gen = harness.load_module("gen", cell.traffic["generator"])
    probe = gen.generate(1000, cell.config["shape"], cell.traffic["timing"],
                         harness._seed(seed, cell.traffic["histories"]),
                         inject=cell.traffic["probe"])
    assert min(probe["injected"]) >= 500
    _wrap(monkeypatch, before=_first_half)
    out = _run(capsys, seed=seed)
    assert out["compared"]["wrong_answers"]["value"] >= 1
    assert out["correct"] is False


def test_an_inexact_fused_check_sends_every_answer_to_the_host(
        capsys, monkeypatch):
    """The fused program's verdict comes back inexact: the host report
    answers every check, rightly, but not on the device."""
    from jepsen_tpu.checkers.elle import device_rw

    orig = device_rw.check
    monkeypatch.setattr(device_rw, "check", lambda *a, **kw: {
        **orig(*a, **kw), "valid?": "unknown", "exact": False})
    out = _run(capsys)
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert out["compared"]["host_answers"]["value"] == out["attempted"]
    assert out["correct"] is False


def test_a_report_sweep_on_the_host_is_a_host_answer(capsys, monkeypatch):
    """The report path's sweep raises: the probe, the one check that
    takes that path, is answered by host Tarjan."""
    from jepsen_tpu.ops import cycle_sweep

    def broken(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(cycle_sweep, "detect_cycles", broken)
    out = _run(capsys)
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert out["compared"]["host_answers"]["value"] == 1
    assert out["correct"] is False


@pytest.mark.parametrize("case,fused_valid", [("G2-item", False),
                                              ("valid", True),
                                              ("G-single", False)])
def test_a_fused_verdict_the_report_overturns_is_a_host_answer(
        case, fused_valid):
    """A write skew (G2-item), which snapshot isolation allows, makes the
    fused program flag a G2-family cycle; the host report then finds the
    history valid, so that answer was not decided on the device.  A valid
    history, and an invalid one the report confirms, are device answers."""
    from benchmark.tests.test_rw_reference import CASES, build

    from jepsen_tpu.checkers.elle import device_rw

    entry = harness.load_module("entries", "elle_rw_register")
    p = entry.prepare(build(CASES[case]))
    assert device_rw.check(p)["valid?"] is fused_valid
    res = entry.check(p, ["snapshot-isolation"])
    assert entry.answer(res)["valid?"] is (case != "G-single")
    assert entry.fell_back(res) is (case == "G2-item")


def test_a_traced_run_reads_the_rw_metrics(capsys):
    out = _run(capsys, trace=1)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # device_idle_share reads the device lines of the trace, which a CPU
    # run has none of
    assert set(m) == {"rw_phase_s", "rw_pad_s", "rw_core_call_s",
                      "rw_core_calls", "window_compiles"}
    assert m["window_compiles"] == 0
    assert m["rw_core_calls"] == 1
    assert m["rw_phase_s"] >= m["rw_pad_s"] + m["rw_core_call_s"] > 0


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def test_readers_on_a_fixture():
    ctx = SimpleNamespace(checks=2, spans={
        "elle.rw-core-check": [3.0, 5.0], "elle.pad": [0.5, 0.7],
        "rw.core-call": [2.0, 1.0, 3.0]})
    assert _read("rw_phase_s", ctx) == pytest.approx(4.0)
    assert _read("rw_pad_s", ctx) == pytest.approx(0.6)
    assert _read("rw_core_call_s", ctx) == pytest.approx(3.0)
    assert _read("rw_core_calls", ctx) == pytest.approx(1.5)


@pytest.mark.parametrize("metric", ["rw_pad_s", "rw_core_call_s",
                                    "rw_core_calls"])
def test_readers_find_nothing_in_a_program_without_the_spans(metric):
    """The parent program has the phase span and none inside it."""
    ctx = SimpleNamespace(checks=3, spans={"elle.rw-core-check": [1.0]})
    assert _read(metric, ctx) is None
