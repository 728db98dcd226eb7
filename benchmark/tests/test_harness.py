"""The harness finds a configuration, a traffic mix and a per-layer
metric by name: added as files alone, with no edit to any file there,
a new cell runs end to end (rehearsal mode, CPU, tiny size)."""

import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmark import harness


def _args(workload, trace=0, control=False):
    return SimpleNamespace(workload=workload, seed=2**31 + 5, seconds=0.5,
                           trace=trace, rehearse=600, control=control)


def _run(capsys, root, args):
    assert harness.run(args, time.perf_counter(), root=root) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of BENCHMARK.json and the benchmark's files, with a
    dummy configuration, mix and metric added as new files."""
    tmp = tmp_path_factory.mktemp("checkout")
    src = harness.ROOT
    shutil.copytree(os.path.join(src, "benchmark"), tmp / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((open(os.path.join(src, "BENCHMARK.json")).read()))
    b = tmp / "benchmark"
    cfg = json.loads((b / "configs" / "elle-la-ss-256k.json").read_text())
    cfg["name"] = "dummy-cfg"
    (b / "configs" / "dummy-cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "la-valid.json").read_text())
    mix.update(histories=2, trace_checks=2)
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "dummy_checks.py").write_text(
        "def read(ctx):\n    return len(ctx.spans.get('bench.check', []))\n")
    bench["configs"].append({"name": "dummy-cfg", "source": "test",
                             "file": "benchmark/configs/dummy-cfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-cfg",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_checks", "unit": "count",
                               "better": "lower", "source": "program_span",
                               "layer": "entry", "moves": "check_s",
                               "workloads": ["dummy-cell"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp)


def test_a_cell_added_as_files_is_found_by_name(root):
    cell = harness.load_cell("dummy-cell", root)
    assert cell.config["name"] == "dummy-cfg"
    assert cell.traffic["histories"] == 2
    assert [m["name"] for m in cell.per_layer] == ["dummy_checks"]
    assert {m["name"] for m in cell.end_to_end} == {
        "check_s", "peak_hbm_bytes", "setup_s"}


def test_the_added_cell_runs_untraced(root, capsys):
    out = _run(capsys, root, _args("dummy-cell"))
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"check_s", "peak_hbm_bytes", "setup_s"}
    assert list(out)[-1] == "compared"
    assert out["compared"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_the_added_metric_is_read_in_a_traced_run(root, capsys):
    out = _run(capsys, root, _args("dummy-cell", trace=1))
    assert out["correct"] is True
    assert out["metrics"]["dummy_checks"] == {"value": 2, "unit": "count"}
    assert out["device"]["window_s"] > 0


def test_an_unknown_cell_is_refused(root):
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root)


@pytest.mark.parametrize("chips,seen,ok", [(1, 1, True), (1, 4, True),
                                           (4, 4, True), (4, 2, False),
                                           (4, 8, False)])
def test_a_cell_runs_on_exactly_its_chips(monkeypatch, chips, seen, ok):
    """A cell of more than one chip refuses a host of another count (the
    program would shard over all it sees); a one-chip cell takes one."""
    import jax

    devs = [SimpleNamespace(platform="tpu", id=i) for i in range(seen)]
    monkeypatch.setattr(jax, "devices", lambda: devs)
    if ok:
        assert harness._devices(chips, rehearse=False) == devs[:chips]
    else:
        with pytest.raises(SystemExit):
            harness._devices(chips, rehearse=False)
