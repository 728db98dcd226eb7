"""Trace reduction on a small recorded trace, and the peaks table."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def red():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        raw = json.load(f)
    return trace.Reduced(raw, 0, 1000)


def test_busy_is_the_union_of_op_intervals(red):
    # overlapping ops count once; ops are clipped to the window
    assert red.busy_s() == pytest.approx(
        {"/device:TPU:0": 450e-9, "/device:TPU:1": 500e-9})
    assert red.window_s == pytest.approx(1e-6)


def test_kernel_and_collective_time(red):
    assert red.op_s(lambda n: "fill_kernel" in n)["/device:TPU:0"] == \
        pytest.approx(100e-9)
    assert red.op_s(lambda n: "scan_kernel" in n)["/device:TPU:0"] == \
        pytest.approx(200e-9)
    assert red.collective_s() == pytest.approx(
        {"/device:TPU:0": 50e-9, "/device:TPU:1": 0.0})


def test_idle_gaps_are_labelled_by_the_open_host_span(red):
    gaps = dict(red.idle_gaps())
    assert gaps == pytest.approx({"elle.cycle-sweep": 450e-9,
                                  "elle.infer": 100e-9})
    assert sum(gaps.values()) == pytest.approx(
        red.window_s - red.busy_s()["/device:TPU:0"])


def test_top_ops_average_over_devices(red):
    top = dict(red.top_ops(3))
    assert top["fusion.1"] == pytest.approx((100e-9 + 500e-9) / 2)
    assert list(top)[0] == "fusion.1"


def test_readers_on_the_fixture(red):
    ctx = SimpleNamespace(trace=red, checks=2, spans={
        "elle.infer": [0.5, 0.7]}, counters={"window_compiles": 0})
    read = lambda m: harness.load_module("metrics", m).read(ctx)  # noqa
    assert read("device_idle_share") == pytest.approx(
        100 * (1 - (450 + 500) / 2 / 1000))
    assert read("fill_kernel_s") == pytest.approx(100e-9 / 2)
    assert read("collective_s") == pytest.approx(50e-9 / 2)
    assert read("infer_phase_s") == pytest.approx(0.6)
    assert read("sweep_phase_s") is None  # nothing to read: no value
    assert read("window_compiles") == 0


def test_load_reads_host_annotations_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    raw = trace.load(trace.xplane_file(str(tmp_path)), {"bench.window"})
    assert [e[0] for e in raw["host"]] == ["bench.window"]
    assert raw["host"][0][2] > raw["host"][0][1]


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for("TPU v99")
