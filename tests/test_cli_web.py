"""CLI + web UI tests (reference cli/web layer, SURVEY.md §2.1 L7/§3.5)."""

import json
import os
import time
import urllib.request
import zipfile

import pytest

from jepsen_tpu import cli, core, store, web
from jepsen_tpu.checkers.api import Stats
from jepsen_tpu.generator import core as g
from jepsen_tpu.workloads.mem import MemClient


# ---------------------------------------------------------------- cli bits

def test_parse_concurrency():
    assert cli.parse_concurrency("30", 5) == 30
    assert cli.parse_concurrency("10n", 5) == 50
    assert cli.parse_concurrency("3n", 0) == 3
    with pytest.raises(ValueError):
        cli.parse_concurrency("x2", 3)


def test_parse_nodes(tmp_path):
    f = tmp_path / "nodes.txt"
    f.write_text("n4\nn5\n")
    assert cli.parse_nodes(["n1,n2", "n3"], str(f)) == \
        ["n1", "n2", "n3", "n4", "n5"]
    assert cli.parse_nodes(None, None) == []


def _test_fn(opts):
    return {
        **opts,
        "name": "cli-test",
        "nodes": opts.get("nodes") or ["n1"],
        "concurrency": 2,
        "client": MemClient(),
        "generator": g.clients(g.limit(
            6, lambda t, c: {"f": "read", "value": None})),
        "checker": Stats(),
    }


def test_cli_run_test(tmp_path, capsys):
    rc = cli.run(cli.single_test_cmd(_test_fn),
                 ["--store-dir", str(tmp_path / "s"),
                  "test", "--time-limit", "10", "--test-count", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run 1/2" in out and "run 2/2" in out
    assert "valid? = True" in out
    assert len(store.tests("cli-test", base=str(tmp_path / "s"))) == 2


def test_cli_analyze(tmp_path, capsys):
    rc = cli.run(cli.single_test_cmd(_test_fn, checker_fn=Stats),
                 ["--store-dir", str(tmp_path / "s"),
                  "test", "--time-limit", "5"])
    assert rc == 0
    d = store.latest("cli-test", base=str(tmp_path / "s"))
    rc = cli.run(cli.single_test_cmd(_test_fn, checker_fn=Stats),
                 ["analyze", d])
    assert rc == 0
    assert "valid? = True" in capsys.readouterr().out


def test_cli_test_all(tmp_path, capsys):
    fns = {"a": _test_fn, "b": _test_fn}
    rc = cli.run(cli.test_all_cmd(fns),
                 ["--store-dir", str(tmp_path / "s"),
                  "test-all", "--time-limit", "5"])
    assert rc == 0
    assert capsys.readouterr().out.count("valid? = True") == 2


def test_cli_demo_suite(tmp_path, capsys):
    from jepsen_tpu.__main__ import DEMOS
    rc = cli.run(cli.test_all_cmd(DEMOS),
                 ["--store-dir", str(tmp_path / "s"),
                  "test-all", "--only", "bank", "--time-limit", "2"])
    assert rc == 0
    assert "demo-bank" in capsys.readouterr().out


# ---------------------------------------------------------------- web

@pytest.fixture
def served_store(tmp_path):
    base = str(tmp_path / "s")
    t = core.run(_test_fn({"store-dir": base}))
    srv = web.serve(port=0, base=base, background=True)
    port = srv.server_address[1]
    yield base, port, t
    srv.shutdown()
    srv.server_close()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_web_index_and_files(served_store):
    base, port, t = served_store
    status, ctype, body = _get(port, "/")
    assert status == 200 and b"cli-test" in body
    # run dir listing
    rel = os.path.relpath(store.test_dir(t), base)
    status, _, body = _get(port, f"/files/{rel}/")
    assert status == 200 and b"results.json" in body
    # file fetch
    status, ctype, body = _get(port, f"/files/{rel}/results.json")
    assert status == 200 and json.loads(body)["valid?"] is True


def test_web_zip_download(served_store, tmp_path):
    base, port, t = served_store
    rel = os.path.relpath(store.test_dir(t), base)
    status, ctype, body = _get(port, f"/zip/{rel}")
    assert status == 200 and ctype == "application/zip"
    zp = tmp_path / "run.zip"
    zp.write_bytes(body)
    names = zipfile.ZipFile(zp).namelist()
    assert any(n.endswith("results.json") for n in names)


def test_web_traversal_blocked(served_store):
    base, port, _ = served_store
    import urllib.error
    # encoded traversal out of the store dir must 404
    try:
        status, _, _ = _get(port, "/files/..%2f..%2fetc%2fpasswd")
    except urllib.error.HTTPError as e:
        status = e.code
    assert status == 404


# -- review regressions ----------------------------------------------------

def test_cli_extra_opts_reach_test_fn(tmp_path):
    seen = {}

    def fn(opts):
        seen.update(opts)
        return _test_fn(opts)

    rc = cli.run(cli.single_test_cmd(
        fn, extra_opts=lambda p: p.add_argument("--rate", type=int)),
        ["--store-dir", str(tmp_path / "s"), "test", "--rate", "7",
         "--time-limit", "5"])
    assert rc == 0
    assert seen.get("rate") == 7


def test_cli_analyze_without_checker_clean_error(tmp_path, capsys):
    cli.run(cli.single_test_cmd(_test_fn),
            ["--store-dir", str(tmp_path / "s"), "test", "--time-limit", "5"])
    d = store.latest("cli-test", base=str(tmp_path / "s"))
    rc = cli.run(cli.single_test_cmd(_test_fn), ["analyze", d])
    assert rc == 2
    assert "checker" in capsys.readouterr().err


def test_cli_test_all_unknown_name(capsys):
    rc = cli.run(cli.test_all_cmd({"a": _test_fn}),
                 ["test-all", "--only", "bogus"])
    assert rc == 2
    assert "bogus" in capsys.readouterr().err


def test_json_log_formatter_escapes():
    import logging
    rec = logging.LogRecord("x", logging.INFO, "f", 1,
                            'he said "boom"\nline2', (), None)
    out = cli._JsonFormatter().format(rec)
    assert json.loads(out)["msg"] == 'he said "boom"\nline2'


def test_drain_survives_transient_fails():
    from jepsen_tpu.workloads.queue import _Drain, _is_empty_fail
    assert not _is_empty_fail({"type": "fail", "f": "dequeue",
                               "error": "simulated-abort"})
    assert _is_empty_fail({"type": "fail", "f": "dequeue", "error": "empty"})
    d = _Drain()
    d2 = d.update({}, None, {"type": "fail", "f": "dequeue",
                             "error": "timeout"})
    assert not d2.done
    d3 = d2.update({}, None, {"type": "fail", "f": "dequeue",
                              "error": "empty"})
    assert d3.done


def test_cli_shrink_smoke(tmp_path, capsys):
    """`cli shrink <dir>` (ISSUE 4): shrink a stored invalid run to a
    minimal witness, then serve its /run/<rel>/witness page."""
    from jepsen_tpu.checkers.elle import oracle
    from jepsen_tpu.workloads import synth

    base = str(tmp_path / "s")
    h = synth.la_history(n_txns=60, n_keys=5, concurrency=4, seed=7)
    assert synth.inject_wr_cycle(h)
    t = core.noop_test(name="shrink-smoke")
    t["store-dir"] = base
    t["history"] = h
    store.save_0(t)
    t["results"] = oracle.check(h, ["serializable"])
    store.save_1(t)
    d = store.test_dir(t)

    rc = cli.run(cli.single_test_cmd(_test_fn),
                 ["shrink", d, "--host-oracle", "--anomaly", "G1c"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "witness:" in out and "G1c" in out
    assert os.path.exists(os.path.join(d, "witness.json"))
    assert os.path.exists(os.path.join(d, "witness.jsonl"))
    # cached second run reports [cached]
    rc = cli.run(cli.single_test_cmd(_test_fn),
                 ["shrink", d, "--host-oracle", "--anomaly", "G1c"])
    assert rc == 0
    assert "[cached]" in capsys.readouterr().out

    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        rel = os.path.relpath(d, base)
        status, _, body = _get(port, f"/run/{rel}/witness")
        assert status == 200
        assert b"minimal witness" in body and b"G1c" in body
        # the run page links to it
        status, _, body = _get(port, f"/run/{rel}")
        assert status == 200 and b"/witness" in body
        # a run without a witness 404s cleanly
        import urllib.error
        try:
            status, _, _ = _get(port, "/run/nope/witness")
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 404
    finally:
        srv.shutdown()
        srv.server_close()


def test_web_telemetry_percentile_table(tmp_path):
    """The per-run telemetry page renders p50/p95/p99 computed from
    the fixed-bucket histograms (ROADMAP telemetry open item) instead
    of raw bucket dumps."""
    import json as _json

    from jepsen_tpu import telemetry

    base = str(tmp_path / "s")
    coll = telemetry.activate()
    coll.registry.histogram("demo-latency-s",
                            buckets=(0.01, 0.1, 1.0)).observe(0.05)
    for v in (0.02, 0.03, 0.5, 2.0):
        coll.registry.histogram("demo-latency-s",
                                buckets=(0.01, 0.1, 1.0)).observe(v)
    t = core.run(_test_fn({"store-dir": base}))
    d = store.test_dir(t)
    telemetry.deactivate(coll)
    telemetry.write_run(d, coll)
    status_doc = _json.load(open(os.path.join(d, "telemetry.json")))
    assert status_doc["metrics"]["histograms"]

    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        rel = os.path.relpath(d, base)
        status, _, body = _get(port, f"/telemetry/{rel}")
        assert status == 200
        assert b"latency percentiles" in body
        assert b"demo-latency-s" in body
        assert b"p50" in body and b"p99" in body
    finally:
        srv.shutdown()
        srv.server_close()


def test_cli_tail_smoke(tmp_path, capsys):
    """`cli tail <run-dir>` (ISSUE 5): renders the streamed
    events.jsonl with the open-span / final-counter footer."""
    base = str(tmp_path / "s")
    t = core.run(_test_fn({"store-dir": base, "telemetry": True}))
    d = store.test_dir(t)
    assert os.path.exists(os.path.join(d, "events.jsonl"))
    rc = cli.run(cli.single_test_cmd(_test_fn), ["tail", d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run ended cleanly" in out
    assert "workload" in out and "interpreter-ops" in out
    # -n limits the event lines
    rc = cli.run(cli.single_test_cmd(_test_fn), ["tail", d, "-n", "2"])
    assert rc == 0
    assert "earlier events" in capsys.readouterr().out
    # -n 0 is footer-only, not everything (lst[-0:] is the whole list)
    rc = cli.run(cli.single_test_cmd(_test_fn), ["tail", d, "-n", "0"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run ended cleanly" in out and "open  " not in out
    # an unstreamed run dir gets a clean error, not a stack trace
    t2 = core.run(_test_fn({"store-dir": str(tmp_path / "s2")}))
    rc = cli.run(cli.single_test_cmd(_test_fn),
                 ["tail", store.test_dir(t2)])
    assert rc == 2
    assert "events.jsonl" in capsys.readouterr().err


def test_cli_tail_follow_exits_on_end_mid_batch(tmp_path, capsys):
    """`tail -f` must exit when "end" is not the poll batch's LAST
    event — a sampler tick racing the recorder's close can append one
    straggler line after it."""
    import threading

    from jepsen_tpu.telemetry import stream as tel_stream

    d = str(tmp_path / "r")
    os.makedirs(d)
    s = tel_stream.EventStream(os.path.join(d, "events.jsonl"))
    s.emit("span-open", name="run", tid=1)
    s.emit("end", valid=True)
    s.emit("sample", gauges={"process-rss-bytes": 1})  # straggler
    rc = {}
    th = threading.Thread(
        target=lambda: rc.setdefault("rc", cli.run(
            cli.single_test_cmd(_test_fn), ["tail", d, "-f"])),
        daemon=True)
    th.start()
    th.join(timeout=15)
    assert not th.is_alive(), "tail -f never saw the mid-batch end"
    assert rc["rc"] == 0


def test_web_live_run_page(tmp_path):
    """/live/<rel> (ISSUE 5): the auto-refreshing in-flight view —
    ended runs render statically, missing streams 404."""
    import urllib.error

    base = str(tmp_path / "s")
    t = core.run(_test_fn({"store-dir": base, "telemetry": True}))
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        rel = os.path.relpath(store.test_dir(t), base)
        status, _, body = _get(port, f"/live/{rel}")
        assert status == 200
        assert b"ended" in body and b"event tail" in body
        assert b"http-equiv" not in body  # finished: no auto-refresh
        # the index and run pages link to it
        status, _, body = _get(port, "/")
        assert status == 200 and b"/live/" in body
        status, _, body = _get(port, f"/run/{rel}")
        assert status == 200 and b"/live/" in body
        # an in-flight (still-open) stream auto-refreshes and names
        # the open span chain
        d2 = os.path.join(base, "cli-test", "20990101T000000.000Z")
        os.makedirs(d2)
        from jepsen_tpu.telemetry import stream as tel_stream

        s = tel_stream.EventStream(os.path.join(d2, "events.jsonl"))
        s.emit("span-open", name="run", tid=1)
        s.emit("span-open", name="check:wedged", tid=1)
        rel2 = os.path.relpath(d2, base)
        status, _, body = _get(port, f"/live/{rel2}")
        assert status == 200
        assert b"http-equiv" in body  # refreshing
        assert b"check:wedged" in body and b"in flight" in body
        # a long-quiet stream (crashed run that never emits "end")
        # stops auto-refreshing but keeps the open-span post-mortem
        old = time.time() - 3600
        os.utime(os.path.join(d2, "events.jsonl"), (old, old))
        status, _, body = _get(port, f"/live/{rel2}")
        assert status == 200
        assert b"http-equiv" not in body
        assert b"stream idle" in body and b"check:wedged" in body
        try:
            status, _, _ = _get(port, "/live/nope")
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 404
    finally:
        srv.shutdown()
        srv.server_close()


def test_web_campaign_live_and_witness_diff(tmp_path):
    """/campaign/<name>/live + /campaign/<name>/witness-diff (ISSUE 5):
    the fleet heartbeat dashboard and the cross-generation witness
    comparison."""
    import urllib.error

    from jepsen_tpu import telemetry
    from jepsen_tpu.campaign.core import live_path
    from jepsen_tpu.campaign.index import Index

    base = str(tmp_path / "s")
    os.makedirs(os.path.join(base, "campaigns"))
    hb = telemetry.Heartbeat(live_path("demo", base), campaign="demo",
                             total=4, done=1, min_interval_s=0.0)
    hb.worker("campaign-worker-0", {"run": "run-abc", "workload":
                                    "append", "fault": "nofault",
                                    "seed": 3, "slot": 0})
    idx = Index(os.path.join(base, "campaigns", "demo.jsonl"))
    for gen, ops, dig in (("g1", 6, "aaa"), ("g2", 4, "bbb")):
        idx.append({"run": "r1", "key": "append|f|0", "valid?": False,
                    "gen": gen, "witness": {"ops": ops, "digest": dig,
                                            "anomaly-types": ["G1c"]}})
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        status, _, body = _get(port, "/campaign/demo/live")
        assert status == 200
        assert b"run-abc" in body and b"1/4" in body
        assert b"http-equiv" in body  # not finished: refreshing
        # a killed scheduler never writes finished=True: once the
        # heartbeat goes stale the dashboard stops auto-refreshing
        hb.state["updated"] = time.time() - 3600
        doc = json.dumps(hb.state)
        with open(live_path("demo", base), "w") as f:
            f.write(doc)
        status, _, body = _get(port, "/campaign/demo/live")
        assert status == 200
        assert b"http-equiv" not in body and b"stalled?" in body
        status, _, body = _get(port, "/campaign/demo/witness-diff")
        assert status == 200
        assert b"append|f|0" in body
        assert b"6 &rarr; 4" in body and b"changed" in body
        # the campaign page links to both
        status, _, body = _get(port, "/campaign/demo")
        assert status == 200
        assert b"/campaign/demo/live" in body
        assert b"/campaign/demo/witness-diff" in body
        try:
            status, _, _ = _get(port, "/campaign/nope/live")
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 404
    finally:
        srv.shutdown()
        srv.server_close()


def test_cli_demo_causal(tmp_path, capsys):
    from jepsen_tpu.__main__ import DEMOS
    rc = cli.run(cli.test_all_cmd(DEMOS),
                 ["--store-dir", str(tmp_path / "s"),
                  "test-all", "--only", "causal", "--time-limit", "2",
                  "--ops", "4000"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "demo-causal" in out and "valid? = True" in out


def test_cli_cpu_flag_forces_cpu_backend(tmp_path, monkeypatch):
    """--cpu (or JT_FORCE_CPU) must force the CPU backend before the
    checkers' first jax init, e.g. where another process holds the TPU.
    The spy pins that the flag actually CALLS the force (the conftest
    already CPU-forces this process, so the backend alone proves
    nothing); JT_FORCE_CPU=0/false/no must NOT trigger it."""
    from jepsen_tpu import cli
    from jepsen_tpu.__main__ import DEMOS
    from jepsen_tpu.utils import backend as backend_mod

    calls = []
    real = backend_mod.force_cpu_backend
    monkeypatch.setattr(backend_mod, "force_cpu_backend",
                        lambda *a, **k: (calls.append(1), real(*a, **k)))
    rc = cli.run(cli.test_all_cmd(DEMOS, prog="demo"),
                 ["--store-dir", str(tmp_path), "--cpu",
                  "test-all", "--only", "set", "--time-limit", "1"])
    assert rc == 0
    assert calls, "--cpu did not invoke force_cpu_backend"
    import jax

    assert jax.default_backend() == "cpu"

    # falsy env spellings must not silently downgrade a TPU box
    calls.clear()
    monkeypatch.setenv("JT_FORCE_CPU", "0")
    rc = cli.run(cli.test_all_cmd(DEMOS, prog="demo"),
                 ["--store-dir", str(tmp_path / "b"),
                  "test-all", "--only", "set", "--time-limit", "1"])
    assert rc == 0
    assert not calls, "JT_FORCE_CPU=0 must not force the CPU backend"
    # and a truthy spelling does
    calls.clear()
    monkeypatch.setenv("JT_FORCE_CPU", "1")
    rc = cli.run(cli.test_all_cmd(DEMOS, prog="demo"),
                 ["--store-dir", str(tmp_path / "c"),
                  "test-all", "--only", "set", "--time-limit", "1"])
    assert rc == 0
    assert calls, "JT_FORCE_CPU=1 must force the CPU backend"


# ------------------------------------------- ISSUE 6: the observatory

def test_parse_since():
    now = 1_000_000_000.0
    assert cli.parse_since("90s", now) == now - 90
    assert cli.parse_since("5m", now) == now - 300
    assert cli.parse_since("2h", now) == now - 7200
    assert cli.parse_since("1d", now) == now - 86400
    assert cli.parse_since("45", now) == now - 45  # bare small: duration
    assert cli.parse_since("1722650000", now) == 1722650000.0  # epoch
    assert cli.parse_since("1970-01-01T00:01:40", now) == 100.0
    with pytest.raises(ValueError):
        cli.parse_since("next tuesday", now)


def test_cli_tail_since_scan_and_warehouse_agree(tmp_path, capsys):
    """`tail --since` filters to recent events — from the stream scan
    when no warehouse covers the run, from the indexed event table
    when one does; both views must render identically."""
    base = str(tmp_path / "s")
    t = core.run(_test_fn({"store-dir": base, "telemetry": True}))
    d = store.test_dir(t)
    disp = cli.single_test_cmd(_test_fn)
    assert cli.run(disp, ["tail", d, "--since", "1h"]) == 0
    scan_out = capsys.readouterr().out
    assert "run ended cleanly" in scan_out
    # --since now: every event is older, nothing renders but the
    # truncated-stream footer
    assert cli.run(disp, ["tail", d, "--since", "0s"]) == 0
    out = capsys.readouterr().out
    assert "no open spans" in out and " span " not in out
    # bad spec: clean error
    assert cli.run(disp, ["tail", d, "--since", "nope"]) == 2
    capsys.readouterr()
    # now build the warehouse: same question, indexed answer
    from jepsen_tpu.telemetry import warehouse as wmod

    wh = wmod.open_or_create(base)
    wh.ingest_store(base)
    assert wh.events_fresh(d, base)
    assert cli.run(disp, ["tail", d, "--since", "1h"]) == 0
    assert capsys.readouterr().out == scan_out


def test_web_metrics_endpoint(tmp_path):
    """/metrics (ISSUE 6): Prometheus text exposition with the
    pinned content type; campaign heartbeats and warehouse rollups
    appear when present."""
    base = str(tmp_path / "s")
    os.makedirs(os.path.join(base, "campaigns"))
    with open(os.path.join(base, "campaigns", "soak.jsonl"), "w") as f:
        f.write(json.dumps({"campaign": "soak", "run": "r1",
                            "key": "k", "valid?": True, "gen": "g1",
                            "spans": {"check:la": 1.0}}) + "\n")
    with open(os.path.join(base, "campaigns",
                           "soak.live.json"), "w") as f:
        json.dump({"campaign": "soak", "updated": time.time(),
                   "total": 4, "done": 1, "workers": {},
                   "finished": False}, f)
    from jepsen_tpu.telemetry import warehouse as wmod

    wmod.open_or_create(base).ingest_store(base)
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        status, ctype, body = _get(port, "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain; version=0.0.4")
        text = body.decode()
        assert "# TYPE jepsen_campaign_runs_done gauge" in text
        assert 'jepsen_campaign_runs_done{campaign="soak"} 1' in text
        assert ('jepsen_warehouse_campaign_runs{campaign="soak",'
                'valid="true"} 1') in text
        assert text.endswith("\n")
        # the index page links to it
        status, _, body = _get(port, "/")
        assert b"/metrics" in body
    finally:
        srv.shutdown()
        srv.server_close()


def test_web_trend_page_and_run_page_warehouse_spans(tmp_path):
    """/campaign/<name>/trend (ISSUE 6): the per-generation span p95
    table the gate enforces; and the run page's warehouse-backed span
    profile."""
    base = str(tmp_path / "s")
    t = core.run(_test_fn({"store-dir": base, "telemetry": True}))
    rel = os.path.relpath(store.test_dir(t), base)
    os.makedirs(os.path.join(base, "campaigns"), exist_ok=True)
    with open(os.path.join(base, "campaigns", "soak.jsonl"), "w") as f:
        for gen, dur in (("g1", 1.0), ("g1", 1.1), ("g2", 2.0)):
            f.write(json.dumps({
                "campaign": "soak", "run": f"r-{gen}-{dur}", "key": "k",
                "valid?": True, "gen": gen,
                "spans": {"check:la": dur}}) + "\n")
        # check:aaa sorts FIRST and skips g2 (samples in g1 + g3 only):
        # column order must stay chronological (g1 g2 g3), not
        # per-span first-seen — which would yield g1 g3 g2 and
        # mis-pair every row's adjacent-column delta highlight
        for gen in ("g1", "g3"):
            f.write(json.dumps({
                "campaign": "soak", "run": f"r-{gen}-aaa", "key": "k2",
                "valid?": True, "gen": gen,
                # aaa doubles g1 -> g3, but with NO g2 sample between:
                # the highlight promises adjacent-generation deltas,
                # so the gap must suppress it (asserted below)
                "spans": {"check:aaa": 2.0 if gen == "g3" else 1.0,
                          **({"check:la": 2.1} if gen == "g3"
                             else {})}}) + "\n")
    from jepsen_tpu.telemetry import warehouse as wmod

    wmod.open_or_create(base).ingest_store(base)
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        status, _, body = _get(port, "/campaign/soak/trend")
        assert status == 200
        text = body.decode()
        assert "check:la" in text
        assert "<th>g1</th>" in text and "<th>g2</th>" in text
        # chronological columns even though check:aaa (sorted first)
        # has no g2 samples
        assert text.index("<th>g1</th>") < text.index("<th>g2</th>") \
            < text.index("<th>g3</th>")
        assert "obs gate" in text  # tells you how to enforce it
        # >25% step vs the previous generation is highlighted — and
        # ONLY for adjacent generations: check:la's g1->g2 step is the
        # single red cell; check:aaa's g1->g3 doubling straddles a
        # missing g2 and must not be compared across the gap
        assert text.count("background:#f2a3a3") == 1
        # the campaign page links to the trend page
        status, _, body = _get(port, "/campaign/soak")
        assert status == 200 and b"/campaign/soak/trend" in body
        # run page: span profile from the warehouse's run_spans table
        status, _, body = _get(port, f"/run/{rel}")
        assert status == 200
        assert b"warehouse" in body and b"check:Stats" in body
    finally:
        srv.shutdown()
        srv.server_close()
