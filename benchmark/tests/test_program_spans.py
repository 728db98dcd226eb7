"""The readers of the spans inside one check, and those spans on the
profiler's host plane, on the device trace's clock (CPU, tiny size)."""

from types import SimpleNamespace

import pytest

from benchmark import harness, trace

#: span -> the span it opens inside
PARENT = {"elle.pad": "elle.infer", "elle.infer.run": "elle.infer",
          "sweep.call": "elle.cycle-sweep",
          "sweep.witness-map": "elle.cycle-sweep"}


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def test_readers_on_a_fixture():
    ctx = SimpleNamespace(checks=2, spans={
        "elle.pad": [0.25, 0.35],
        "elle.infer.run": [1.0, 1.2],
        "sweep.call": [0.1] * 18,
        "sweep.witness-map": [0.02] * 18})
    assert _read("pad_s", ctx) == pytest.approx(0.3)
    assert _read("infer_run_s", ctx) == pytest.approx(1.1)
    assert _read("sweep_call_s", ctx) == pytest.approx(0.9)
    assert _read("witness_map_s", ctx) == pytest.approx(0.18)
    assert _read("sweep_calls", ctx) == 9


@pytest.mark.parametrize("metric", ["pad_s", "infer_run_s", "sweep_call_s",
                                    "witness_map_s", "sweep_calls"])
def test_readers_find_nothing_in_a_program_without_the_spans(metric):
    ctx = SimpleNamespace(checks=3, spans={"elle.infer": [1.0],
                                           "elle.cycle-sweep": [1.0]})
    assert _read(metric, ctx) is None


def test_inner_spans_sit_inside_their_parents_on_the_trace(tmp_path):
    import jax

    from jepsen_tpu import telemetry
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.workloads import synth

    h = synth.la_history(n_txns=200, n_keys=6, concurrency=4, seed=7)
    list_append.check(h, ["strict-serializable"])  # compile outside
    c = telemetry.Collector()
    c.annotate = True
    telemetry.activate(c)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = list_append.check(h, ["strict-serializable"])
    finally:
        jax.profiler.stop_trace()
        telemetry.deactivate(c)
    assert res["valid?"] is True
    names = set(PARENT) | set(PARENT.values())
    host = trace.load(trace.xplane_file(str(tmp_path)), names)["host"]
    by = {n: [(a, b) for m, a, b in host if m == n] for n in names}
    spans = harness._span_durations(c.roots)
    for child, parent in PARENT.items():
        # each span the collector holds is on the host plane once
        assert len(by[child]) == len(spans[child]) > 0, child
        (p0, p1), = by[parent]
        assert all(p0 <= a <= b <= p1 for a, b in by[child]), child
    # one sweep and one witness map per projection
    assert len(by["sweep.call"]) == len(by["sweep.witness-map"]) > 1
