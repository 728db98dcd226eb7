"""Telemetry subsystem tests (ISSUE 1): span tree, metrics registry,
Chrome-trace export, core.run wiring, and the off-by-default-cheap
contract."""

import json
import os
import threading
import time

import pytest

from jepsen_tpu import core, store, telemetry
from jepsen_tpu.checkers import api as checker_api
from jepsen_tpu.generator import core as g
from jepsen_tpu.workloads.mem import MemClient


# ---------------------------------------------------------------- spans

def test_span_nesting_and_attrs():
    c = telemetry.Collector()
    with c.span("a", x=1) as a:
        with c.span("b") as b:
            b.set_attr(y=2)
    assert [r.name for r in c.roots] == ["a"]
    assert a.attrs == {"x": 1}
    assert a.children[0] is b and b.attrs == {"y": 2}
    assert a.duration_ns >= b.duration_ns >= 0


def test_span_threads_get_own_roots():
    c = telemetry.activate()
    try:
        def worker():
            with telemetry.span("w"):
                pass
        with telemetry.span("main"):
            t = threading.Thread(target=worker, name="w-thread")
            t.start()
            t.join()
    finally:
        telemetry.deactivate(c)
    names = sorted(r.name for r in c.roots)
    assert names == ["main", "w"]
    w = next(r for r in c.roots if r.name == "w")
    assert w.thread_name == "w-thread"


def test_traced_decorator_and_current():
    c = telemetry.activate()
    try:
        @telemetry.traced("deco", kind="t")
        def fn():
            assert telemetry.current().name == "deco"
            return 7

        assert fn() == 7
    finally:
        telemetry.deactivate(c)
    assert c.roots[0].name == "deco"
    assert c.roots[0].attrs == {"kind": "t"}


def test_phase_timer_sequential_siblings():
    c = telemetry.Collector()
    with c.span("parent"):
        ph = telemetry.PhaseTimer(c)
        ph.start("p1")
        ph.start("p2", n=3)
        ph.end()
        ph.end()  # idempotent
    (parent,) = c.roots
    assert [s.name for s in parent.children] == ["p1", "p2"]
    assert all(s.duration_ns is not None for s in parent.children)


def test_disabled_is_noop_singleton():
    assert telemetry.active() is telemetry.NOOP
    assert not telemetry.enabled()
    s1 = telemetry.span("x", a=1)
    s2 = telemetry.span("y")
    assert s1 is s2  # one shared object, nothing allocated
    with s1 as sp:
        sp.set_attr(z=2)  # no-op, no error
    assert telemetry.current() is None
    ph = telemetry.phases()
    ph.start("p")
    ph.end()


def test_activate_restores_previous():
    a = telemetry.activate()
    b = telemetry.activate()
    assert telemetry.active() is b
    telemetry.deactivate(b)
    assert telemetry.active() is a
    telemetry.deactivate(a)
    assert telemetry.active() is telemetry.NOOP


def test_open_span_gets_provisional_close():
    c = telemetry.Collector()
    ctx = c.span("never-closed")
    ctx.__enter__()
    c.close_open_spans()
    (root,) = c.roots
    assert root.t1 is not None
    assert root.attrs.get("open") is True


# -------------------------------------------------------------- metrics

def test_metrics_counter_gauge_histogram():
    reg = telemetry.Registry()
    reg.counter("ops", worker="0").inc()
    reg.counter("ops", worker="0").inc(2)
    reg.counter("ops", worker="1").inc()
    reg.gauge("speed").set(3.5)
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0, 0.1):
        h.observe(v)
    snap = reg.snapshot()
    counters = {(c["name"], c["labels"].get("worker")): c["value"]
                for c in snap["counters"]}
    assert counters[("ops", "0")] == 3
    assert counters[("ops", "1")] == 1
    assert snap["gauges"][0]["value"] == 3.5
    (hist,) = snap["histograms"]
    assert hist["counts"] == [2, 1, 1]  # <=1, <=10, +inf
    assert hist["count"] == 4 and hist["sum"] == pytest.approx(55.6)


def test_metrics_same_instrument_cached_and_type_checked():
    reg = telemetry.Registry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", a=1) is not reg.counter("x", a=2)
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_metrics_thread_safety():
    reg = telemetry.Registry()

    def hammer():
        for _ in range(1000):
            reg.counter("n").inc()

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("n").value == 4000


# --------------------------------------------------------------- export

def _collect_sample():
    c = telemetry.Collector()
    with c.span("run", name="s"):
        with c.span("workload") as w:
            time.sleep(0.001)
            w.set_attr(ops=4)
    return c


def test_chrome_trace_shape():
    c = _collect_sample()
    doc = telemetry.chrome_trace(c)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert all(e["ph"] in ("X", "M") for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["run", "workload"]
    run, wl = xs
    # nesting holds on the timeline: child contained within parent
    assert run["ts"] <= wl["ts"]
    assert wl["ts"] + wl["dur"] <= run["ts"] + run["dur"] + 1e-3
    # round-trips through json
    json.loads(json.dumps(doc))


def test_snapshot_jsonable_attrs():
    import numpy as np

    c = telemetry.Collector()
    with c.span("s", arr=np.int64(3), st={"a"}, obj=object()):
        pass
    doc = telemetry.snapshot(c, telemetry.Registry())
    attrs = doc["spans"][0]["attrs"]
    assert attrs["arr"] == 3 and attrs["st"] == ["a"]
    assert isinstance(attrs["obj"], str)
    json.dumps(doc)


def test_write_run_and_summarize(tmp_path):
    c = _collect_sample()
    reg = telemetry.Registry()
    reg.counter("interpreter-ops", worker="0", type="ok").inc(4)
    paths = telemetry.write_run(str(tmp_path), c, reg, meta={"name": "s"})
    assert os.path.exists(paths["telemetry"])
    assert os.path.exists(paths["trace"])
    out = telemetry.summarize(str(tmp_path))
    assert "run" in out and "workload" in out
    assert "interpreter-ops" in out


# ------------------------------------------------- core.run integration

def _mem_test(tmp_path, n_ops=12, **kw):
    t = dict(
        name="tel-test",
        client=MemClient(),
        concurrency=2,
        generator=g.clients(g.limit(
            n_ops, lambda t, c: {"f": "write", "value": 1})),
        checker=checker_api.Stats(),
        telemetry=True,
        **{"store-dir": str(tmp_path / "s")},
    )
    t.update(kw)
    return t


def test_noop_test_run_writes_valid_telemetry(tmp_path):
    """Tier-1 smoke (ISSUE 1 satellite): a noop_test run with telemetry
    writes a valid telemetry.json."""
    done = core.run(core.noop_test(
        telemetry=True, **{"store-dir": str(tmp_path / "s")}))
    d = store.test_dir(done)
    doc = json.load(open(os.path.join(d, "telemetry.json")))
    assert doc["version"] == 1
    names = [r["name"] for r in doc["spans"]]
    assert "run" in names
    run = next(r for r in doc["spans"] if r["name"] == "run")
    child_names = [c["name"] for c in run["children"]]
    assert "workload" in child_names
    assert "store.save_0" in child_names and "store.save_1" in child_names
    # trace.json is valid Chrome trace-event JSON
    tr = json.load(open(os.path.join(d, "trace.json")))
    assert isinstance(tr["traceEvents"], list) and tr["traceEvents"]
    assert all(e["ph"] in ("X", "M") for e in tr["traceEvents"])


def test_run_span_tree_matches_phases(tmp_path):
    done = core.run(_mem_test(tmp_path))
    d = store.test_dir(done)
    doc = json.load(open(os.path.join(d, "telemetry.json")))
    run = next(r for r in doc["spans"] if r["name"] == "run")
    kids = [c["name"] for c in run["children"]]
    # phase order: workload before save_0 before check before save_1
    assert kids.index("workload") < kids.index("store.save_0") \
        < kids.index("check:Stats") < kids.index("store.save_1")
    wl = next(c for c in run["children"] if c["name"] == "workload")
    assert wl["attrs"]["ops"] == 24  # 12 invokes + 12 completions
    chk = next(c for c in run["children"] if c["name"] == "check:Stats")
    assert chk["attrs"]["checker"] == "Stats"
    assert chk["attrs"]["valid"] is True
    # interpreter metrics flushed: per-worker invoke/ok counts
    counters = {(c["name"], c["labels"].get("worker"),
                 c["labels"].get("type")): c["value"]
                for c in doc["metrics"]["counters"]}
    # ops are handed to whichever worker asks first, so the per-worker
    # split is scheduling-dependent — assert the labeled totals instead
    op_keys = [k for k in counters if k[0] == "interpreter-ops"]
    assert all(w in ("0", "1") for _, w, _ in op_keys)
    assert sum(counters[k] for k in op_keys if k[2] == "invoke") == 12
    assert sum(counters[k] for k in op_keys if k[2] == "ok") == 12
    assert ("generator-stall-ns", None, None) in counters
    gauges = {c["name"]: c["value"] for c in doc["metrics"]["gauges"]}
    assert gauges["interpreter-concurrency"] == 2
    assert gauges.get("checker-ops-per-s", 0) > 0
    # the collector is deactivated after the run
    assert telemetry.active() is telemetry.NOOP


def test_run_without_telemetry_writes_nothing(tmp_path):
    t = _mem_test(tmp_path)
    t.pop("telemetry")
    done = core.run(t)
    d = store.test_dir(done)
    assert not os.path.exists(os.path.join(d, "telemetry.json"))
    assert not os.path.exists(os.path.join(d, "trace.json"))
    assert telemetry.active() is telemetry.NOOP


def test_composed_checkers_get_named_spans(tmp_path):
    done = core.run(_mem_test(tmp_path, checker=checker_api.compose({
        "stats": checker_api.Stats(),
        "uids": checker_api.UniqueIds()})))
    d = store.test_dir(done)
    doc = json.load(open(os.path.join(d, "telemetry.json")))
    run = next(r for r in doc["spans"] if r["name"] == "run")
    comp = next(c for c in run["children"]
                if c["name"] == "check:Compose")
    sub = sorted(c["name"] for c in comp["children"])
    assert sub == ["check:Stats", "check:UniqueIds"]


def test_analyze_writes_suffixed_telemetry_keeps_run_artifacts(tmp_path):
    t = _mem_test(tmp_path)
    done = core.run(t)
    d = store.test_dir(done)
    run_doc_before = json.load(open(os.path.join(d, "telemetry.json")))
    re = core.analyze(d, checker=checker_api.Stats())
    assert re["results"]["valid?"] is True
    # the original run's artifacts are untouched ...
    run_doc_after = json.load(open(os.path.join(d, "telemetry.json")))
    assert run_doc_after == run_doc_before
    assert os.path.exists(os.path.join(d, "trace.json"))
    # ... and the re-check got its own suffixed set
    doc = json.load(open(os.path.join(d, "telemetry-analyze.json")))
    names = [r["name"] for r in doc["spans"]]
    assert "analyze" in names
    assert os.path.exists(os.path.join(d, "trace-analyze.json"))


def test_consecutive_runs_have_independent_metrics(tmp_path):
    """Two telemetric runs in one process: each run's telemetry.json
    reports only its own counters (per-collector registry)."""
    d1 = store.test_dir(core.run(_mem_test(tmp_path, n_ops=4)))
    d2 = store.test_dir(core.run(_mem_test(tmp_path, n_ops=6)))

    def invokes(d):
        doc = json.load(open(os.path.join(d, "telemetry.json")))
        return sum(c["value"] for c in doc["metrics"]["counters"]
                   if c["name"] == "interpreter-ops"
                   and c["labels"].get("type") == "invoke")

    assert invokes(d1) == 4
    assert invokes(d2) == 6  # not 4 + 6


def test_check_safe_crash_attributes_checker_name():
    """Satellite: composed-checker failures are attributable."""
    class Exploder(checker_api.Checker):
        def check(self, test, history, opts=None):
            raise RuntimeError("kaboom")

    from jepsen_tpu.history.ops import history
    res = checker_api.check_safe(Exploder(), {}, history([]))
    assert res["valid?"] == "unknown"
    assert res["checker"] == "Exploder"
    assert "kaboom" in res["error"]
    # composed: the sub-result carries the failing sub-checker's name
    comp = checker_api.compose({"bad": Exploder(),
                               "ok": checker_api.NoopChecker()})
    res = checker_api.check_safe(comp, {}, history([]))
    assert res["bad"]["checker"] == "Exploder"
    assert res["ok"]["valid?"] is True


def test_check_safe_crash_attribution_with_telemetry_enabled():
    class Exploder(checker_api.Checker):
        def check(self, test, history, opts=None):
            raise RuntimeError("pow")

    from jepsen_tpu.history.ops import history
    c = telemetry.activate()
    try:
        res = checker_api.check_safe(Exploder(), {}, history([]))
    finally:
        telemetry.deactivate(c)
    assert res["valid?"] == "unknown" and res["checker"] == "Exploder"
    (sp,) = c.roots
    assert sp.name == "check:Exploder"
    assert sp.attrs.get("crashed") is True


def _txn(p, t, mops):
    from jepsen_tpu.history.ops import Op

    return [Op(type="invoke", process=p, f="txn", value=mops, time=t),
            Op(type="ok", process=p, f="txn", value=mops, time=t + 1000)]


def _traced_la_check(ops):
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.history.ops import history

    c = telemetry.activate()
    try:
        with telemetry.span("check:elle"):
            res = list_append.check(history(ops))
    finally:
        telemetry.deactivate(c)
    (root,) = c.roots
    return res, root


def _child(sp, name):
    return next(x for x in sp.children if x.name == name)


#: the spans inside one device check of a list-append history
LA_INNER_SPANS = ("elle.pad", "elle.stage", "elle.infer.run",
                  "sweep.enumerate", "sweep.call", "sweep.witness-map",
                  "elle.classify", "elle.host-fallback", "elle.verdict")


def test_elle_checker_child_spans(tmp_path):
    res, root = _traced_la_check(_txn(0, 0, [["append", "x", 1]]) +
                                 _txn(1, 5000, [["r", "x", [1]]]))
    assert res["valid?"] is True
    names = [s["name"] for s in
             [telemetry.export.span_to_dict(x) for x in root.children]]
    assert "elle.infer" in names
    assert "elle.graph-build" in names and "elle.cycle-sweep" in names
    infer = _child(root, "elle.infer")
    assert infer.attrs["device"] is True
    assert "warm" not in infer.attrs
    # inside inference: the host pad, then the device run (one device:
    # no reshard onto a mesh)
    assert [x.name for x in infer.children] == ["elle.pad", "elle.infer.run"]
    pad = infer.children[0]
    assert pad.attrs["T"] >= 2 and pad.attrs["bytes_staged"] > 0
    # one sweep program run and one witness map per projection
    sweep = _child(root, "elle.cycle-sweep")
    calls = [x for x in sweep.children if x.name == "sweep.call"]
    maps = [x for x in sweep.children if x.name == "sweep.witness-map"]
    assert len(calls) == len(maps) == sweep.attrs["projections"] > 0
    for c in calls:
        assert c.attrs["max_k"] == 128 and c.attrs["n_backward"] == 0
        assert c.attrs["converged"] is True
        assert c.attrs["sharded"] is False
    assert all(m.attrs["witnesses"] == 0 for m in maps)
    assert not any(x.name == "elle.classify" for x in sweep.children)
    assert names[-1] == "elle.verdict"
    assert _child(root, "elle.verdict").attrs["valid"] is True


def test_elle_stage_span_on_the_sharded_path(monkeypatch):
    monkeypatch.setenv("JEPSEN_SHARDS", "4")
    res, root = _traced_la_check(_txn(0, 0, [["append", "x", 1]]) +
                                 _txn(1, 5000, [["r", "x", [1]]]))
    assert res["valid?"] is True
    infer = _child(root, "elle.infer")
    assert [x.name for x in infer.children] == \
        ["elle.pad", "elle.stage", "elle.infer.run"]
    assert infer.children[1].attrs["devices"] == 4
    calls = [x for x in _child(root, "elle.cycle-sweep").children
             if x.name == "sweep.call"]
    assert calls and all(c.attrs["sharded"] is True for c in calls)


def test_elle_classify_span_on_a_cycle():
    # G1c: each txn reads the other's append
    res, root = _traced_la_check(
        _txn(0, 0, [["append", "x", 1], ["r", "y", [1]]]) +
        _txn(1, 10, [["append", "y", 1], ["r", "x", [1]]]))
    assert res["valid?"] is False and "G1c" in res["anomaly-types"]
    sweep = _child(root, "elle.cycle-sweep")
    cls = [x for x in sweep.children if x.name == "elle.classify"]
    assert cls and all(c.attrs["regions"] >= 1 for c in cls)
    assert sum(c.attrs["found"] for c in cls) >= 1
    maps = [x for x in sweep.children if x.name == "sweep.witness-map"]
    assert max(m.attrs["witnesses"] for m in maps) >= 1
    assert _child(root, "elle.verdict").attrs["valid"] is False


@pytest.mark.parametrize("n_backward,reason", [
    (0, "not-converged"), (8193, "max-k-cap")])
def test_elle_host_fallback_span(monkeypatch, n_backward, reason):
    import dataclasses

    from jepsen_tpu.checkers.elle import list_append

    orig = list_append.detect_cycles

    def not_converged(*a, **kw):
        return dataclasses.replace(orig(*a, **kw), converged=False,
                                   n_backward=n_backward)

    monkeypatch.setattr(list_append, "detect_cycles", not_converged)
    res, root = _traced_la_check(_txn(0, 0, [["append", "x", 1]]) +
                                 _txn(1, 5000, [["r", "x", [1]]]))
    assert res["valid?"] is True
    fb = _child(root, "elle.host-fallback")
    assert fb.attrs == {"reason": reason, "n_backward": n_backward}
    assert fb.children  # the host oracle's own phases
    assert not any(x.name == "elle.verdict" for x in root.children)


def test_elle_inner_spans_unrecorded_with_telemetry_off(monkeypatch):
    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.history.ops import history
    from jepsen_tpu.parallel import batch
    from jepsen_tpu.telemetry import spans

    opened = []

    def span(name, /, **attrs):
        opened.append(name)
        return spans._NOOP_SPAN

    def no_bytes(*a):
        raise AssertionError("staged bytes counted with telemetry off")

    monkeypatch.setattr(telemetry.NOOP, "span", span, raising=False)
    monkeypatch.setattr(batch, "_stage_bytes", no_bytes)
    assert telemetry.active() is telemetry.NOOP
    res = list_append.check(history(
        _txn(0, 0, [["append", "x", 1], ["r", "y", [1]]]) +
        _txn(1, 10, [["append", "y", 1], ["r", "x", [1]]])))
    assert "G1c" in res["anomaly-types"] and "degraded" not in res
    assert telemetry.active() is telemetry.NOOP
    assert telemetry.NOOP.roots == []
    # every inner span was opened on the no-op collector, nothing else
    assert {"elle.pad", "elle.infer.run", "sweep.call", "sweep.witness-map",
            "elle.classify", "elle.verdict"} <= set(opened)
    assert set(opened) - {"elle.pack", "elle.infer", "elle.graph-build",
                          "elle.cycle-sweep", "elle.sessions"} <= \
        set(LA_INNER_SPANS)


# -------------------------------------------------------------- cli/web

def test_cli_trace_command(tmp_path, capsys):
    from jepsen_tpu import cli

    def fn(opts):
        return _mem_test(tmp_path, **{k: v for k, v in opts.items()
                                      if k in ("store-dir", "telemetry")})

    rc = cli.run(cli.single_test_cmd(fn),
                 ["--store-dir", str(tmp_path / "s"), "test",
                  "--telemetry", "--time-limit", "5"])
    assert rc == 0
    capsys.readouterr()
    d = store.latest("tel-test", base=str(tmp_path / "s"))
    rc = cli.run(cli.single_test_cmd(fn), ["trace", d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run" in out and "workload" in out and "interpreter-ops" in out


def test_cli_trace_no_telemetry(tmp_path, capsys):
    from jepsen_tpu import cli
    t = _mem_test(tmp_path)
    t.pop("telemetry")
    done = core.run(t)
    d = store.test_dir(done)
    rc = cli.run(cli.single_test_cmd(lambda o: t), ["trace", d])
    assert rc == 2
    assert "telemetry" in capsys.readouterr().err


def test_web_telemetry_page(tmp_path):
    import urllib.request

    from jepsen_tpu import web

    base = str(tmp_path / "s")
    done = core.run(_mem_test(tmp_path))
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        rel = os.path.relpath(store.test_dir(done), base)

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, r.read()

        status, body = get("/")
        assert status == 200 and b"/telemetry/" in body
        from urllib.parse import quote
        status, body = get(f"/telemetry/{quote(rel)}")
        assert status == 200
        assert b"workload" in body and b"trace.json" in body
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------------------- overhead

@pytest.mark.slow
def test_enabled_overhead_under_two_percent(tmp_path):
    """ISSUE 1 acceptance: enabled-collector overhead <2% on a 100k-op
    in-memory run vs disabled.  Slow (two 100k-op runs); excluded from
    tier-1 by the `not slow` marker filter."""
    n = 50_000  # 100k history ops: 50k invokes + 50k completions

    def run_once(with_tel):
        t = _mem_test(tmp_path, n_ops=n)
        if not with_tel:
            t.pop("telemetry")
        t0 = time.perf_counter()
        core.run(t)
        return time.perf_counter() - t0

    run_once(False)  # warm caches/imports
    off = min(run_once(False) for _ in range(2))
    on = min(run_once(True) for _ in range(2))
    assert on <= off * 1.02 + 0.05, (on, off)
