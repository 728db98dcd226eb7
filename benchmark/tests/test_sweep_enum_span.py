"""The reader of the `sweep.enumerate` span (CPU, tiny size)."""

from types import SimpleNamespace

import pytest

from benchmark import harness


def _read(ctx):
    return harness.load_module("metrics", "sweep_enum_s").read(ctx)


def test_reader_on_a_fixture():
    ctx = SimpleNamespace(checks=3, spans={
        "sweep.enumerate": [0.05, 0.04, 0.06], "sweep.call": [0.01] * 27})
    assert _read(ctx) == pytest.approx(0.05)


def test_reader_finds_nothing_in_a_program_without_the_span():
    ctx = SimpleNamespace(checks=3, spans={"sweep.call": [0.1] * 27,
                                           "elle.cycle-sweep": [1.0] * 3})
    assert _read(ctx) is None


def test_one_span_per_check_inside_the_sweep_phase():
    from jepsen_tpu import telemetry
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.workloads import synth

    h = synth.la_history(n_txns=200, n_keys=6, concurrency=4, seed=7)
    c = telemetry.Collector()
    telemetry.activate(c)
    try:
        for _ in range(2):
            list_append.check(h, ["strict-serializable"])
    finally:
        telemetry.deactivate(c)
    spans = harness._span_durations(c.roots)
    assert len(spans["sweep.enumerate"]) == 2
    ctx = SimpleNamespace(checks=2, spans=spans)
    assert 0 < _read(ctx) < sum(spans["elle.cycle-sweep"]) / 2
