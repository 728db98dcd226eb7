"""Campaign subsystem tests (ISSUE 3): spec expansion determinism,
fleet scheduling, index resume, regression detection, CLI + web
surfaces, and the degraded/deadline verdict badges."""

import json
import os
import urllib.request

import pytest

from jepsen_tpu import campaign, cli, report, store, web
from jepsen_tpu.campaign import core as ccore
from jepsen_tpu.campaign.index import Index
from jepsen_tpu.campaign.plan import RunSpec, build_test, expand, load_spec
from jepsen_tpu.campaign.scheduler import DeviceSlots, Scheduler

SPEC = {
    "name": "t",
    "workloads": ["noop", "set"],
    "faults": [None, {"seed": 3, "p": 0.5, "kinds": "oom|xla"}],
    "seeds": [0, 1, 2],
    "opts": {"time-limit": 0.2, "concurrency": 2},
}


# ----------------------------------------------------------------- plan

def test_expand_deterministic_and_stable():
    a = expand(SPEC)
    b = expand(SPEC)
    assert [r.run_id for r in a] == [r.run_id for r in b]
    assert len(a) == 2 * 2 * 3
    assert len({r.run_id for r in a}) == 12  # all distinct
    # ids are stable across orthogonal spec edits (opts change -> new
    # ids; seed list extension keeps existing ids)
    wider = dict(SPEC, seeds=[0, 1, 2, 3])
    ids_wider = {r.run_id for r in expand(wider)}
    assert {r.run_id for r in a} < ids_wider


def test_expand_key_is_opts_independent():
    # the regression KEY survives opts tweaks (ids don't — they pin the
    # exact cell config)
    a = expand(SPEC)
    tweaked = dict(SPEC, opts={"time-limit": 9.9, "concurrency": 2})
    b = expand(tweaked)
    assert [r.key for r in a] == [r.key for r in b]
    assert [r.run_id for r in a] != [r.run_id for r in b]


def test_expand_device_classification():
    rs = expand({"name": "d", "workloads": ["append", "set"],
                 "seeds": [0]})
    by_wl = {r.workload: r for r in rs}
    assert by_wl["append"].device is True
    assert by_wl["set"].device is False


def test_expand_dedupes_aliasing_entries():
    # faults that all normalize to None (null/""/{}), duplicate seeds,
    # and duplicate workloads must collapse to ONE cell each — two
    # RunSpecs with identical run_ids would race in the store
    rs = expand({"name": "d", "workloads": ["noop", "noop"],
                 "faults": [None, "", {}], "seeds": [0, 0, 1]})
    assert len(rs) == 2  # 1 workload x 1 fault x 2 seeds
    assert len({r.run_id for r in rs}) == 2


def test_telemetric_thread_runs_serialized(tmp_path):
    """Two concurrent telemetric thread-executor runs would record
    each other's spans (the collector is process-global): the
    scheduler must never run two at once."""
    import threading
    import time as _t

    def mk(i):
        return RunSpec(run_id=f"r{i}", campaign="c", workload="w",
                       seed=i, workload_label="w",
                       opts={"telemetry": True})

    active = []
    worst = []
    lk = threading.Lock()

    def execute(rs):
        with lk:
            active.append(rs.run_id)
            worst.append(len(active))
        _t.sleep(0.03)
        with lk:
            active.remove(rs.run_id)
        return {"run": rs.run_id, "key": rs.key, "valid?": True}

    recs = Scheduler(3).run([mk(i) for i in range(4)], execute)
    assert len(recs) == 4
    assert max(worst) == 1


def test_telemetric_serialization_honors_env_optin(monkeypatch):
    """JEPSEN_TELEMETRY=1 makes EVERY core.run telemetric, so the token
    must engage even when the spec opts don't mention telemetry."""
    import threading
    import time as _t

    monkeypatch.setenv("JEPSEN_TELEMETRY", "1")
    active, worst, lk = [], [], threading.Lock()

    def mk(i):
        return RunSpec(run_id=f"r{i}", campaign="c", workload="w",
                       seed=i, workload_label="w")

    def execute(rs):
        with lk:
            active.append(1)
            worst.append(len(active))
        _t.sleep(0.03)
        with lk:
            active.pop()
        return {"run": rs.run_id, "key": rs.key, "valid?": True}

    Scheduler(3).run([mk(i) for i in range(4)], execute)
    assert max(worst) == 1


def test_subprocess_executor_one_device_child_per_host(monkeypatch):
    """A child that initializes the TPU backend claims every chip of
    the host: the subprocess executor runs device children one at a
    time (whatever device_slots asks), each with the parent's backend
    env, and holds host-only children to the CPU backend."""
    import subprocess as _sp
    import threading
    import time as _t

    from jepsen_tpu.campaign import scheduler as sched_mod

    monkeypatch.delenv("JT_FORCE_CPU", raising=False)
    active, worst, envs, lk = [], [], {}, threading.Lock()

    def fake_run(cmd, input, env, **kw):
        rs = json.loads(input)["runspec"]
        with lk:
            envs[rs["run_id"]] = env
            if rs["device"]:
                active.append(1)
                worst.append(len(active))
        _t.sleep(0.03)
        with lk:
            if rs["device"]:
                active.pop()
        rec = {"run": rs["run_id"], "valid?": True}
        return _sp.CompletedProcess(cmd, 0, json.dumps(rec) + "\n", "")

    monkeypatch.setattr(sched_mod.subprocess, "run", fake_run)
    specs = [RunSpec(run_id=f"d{i}", campaign="c", workload="w", seed=i,
                     workload_label="w", device=True) for i in range(4)]
    specs += [RunSpec(run_id="h0", campaign="c", workload="w", seed=9,
                      workload_label="w")]
    sched = Scheduler(4, device_slots=4, executor="subprocess")
    recs = sched.run(specs, None)
    assert len(recs) == 5
    assert sched.slots.n == 1
    assert max(worst) == 1
    for i in range(4):
        assert "JT_FORCE_CPU" not in envs[f"d{i}"]
        assert not any(k.startswith("JEPSEN_CAMPAIGN_DEVICE_SLOT")
                       for k in envs[f"d{i}"])
    assert envs["h0"]["JT_FORCE_CPU"] == "1"
    assert envs["h0"]["JAX_PLATFORMS"] == "cpu"


def test_op_shard_guard_not_nested():
    """The sharded sweep's fault site must fire ONCE per dispatch
    (site parallel.op-shard), not once per nesting level — nested
    guards would multiply retries and shift the deterministic fault
    schedule."""
    from jepsen_tpu.parallel.batch import make_mesh
    from jepsen_tpu.parallel.op_shard import check_sharded
    from jepsen_tpu.resilience import FaultPlan, RetryPolicy
    from jepsen_tpu.workloads import synth

    p = synth.packed_la_history(n_txns=48, n_keys=4, seed=2)
    plan = FaultPlan(at={0: "oom"})  # first dispatch faults, once
    r = check_sharded(p, mesh=make_mesh(2), plan=plan,
                      policy=RetryPolicy(max_attempts=2,
                                         base_delay_s=0.0))
    assert r["valid?"] is True
    assert plan.injected == [(0, "parallel.op-shard", "oom")]
    # exactly one guarded site saw the calls: the retry (call 1) plus
    # the grow loop's later dispatches all carry the op-shard label
    assert plan._n_calls >= 2


def test_load_spec_rejects_garbage(tmp_path):
    with pytest.raises(ValueError, match="workloads"):
        load_spec({"name": "x"})
    with pytest.raises(ValueError):
        load_spec({"workloads": [{"opts": {}}]})
    with pytest.raises(ValueError):  # unknown fault kind caught at plan time
        load_spec({"workloads": ["noop"],
                   "faults": [{"kinds": "frobnicate"}]})


def test_build_test_carries_fault_and_seed(tmp_path):
    rs = expand(dict(SPEC, workloads=["set"]))[3]  # faulted cell
    assert rs.fault is not None
    t = build_test(rs, str(tmp_path))
    assert t["faults"] == rs.fault
    assert t["seed"] == rs.seed
    assert t["campaign-run-id"] == rs.run_id
    assert t["store-dir"] == str(tmp_path)


# ------------------------------------------------------------ scheduler

def test_device_slots_serialize():
    import threading
    import time as _t

    slots = DeviceSlots(1)
    active = []
    worst = []

    def job():
        s = slots.acquire()
        active.append(s)
        worst.append(len(active))
        _t.sleep(0.02)
        active.remove(s)
        slots.release(s)

    ts = [threading.Thread(target=job) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert max(worst) == 1  # never two device runs at once


def test_scheduler_crash_becomes_attributable_record():
    rs = expand({"name": "c", "workloads": ["noop"], "seeds": [0]})[0]

    def boom(_):
        raise RuntimeError("kaboom")

    recs = Scheduler(1).run([rs], boom)
    assert len(recs) == 1
    assert recs[0]["valid?"] == "unknown"
    assert "kaboom" in recs[0]["error"]
    assert recs[0]["attempt"] == 2  # default policy retried once


def test_scheduler_retry_then_succeed():
    rs = expand({"name": "c", "workloads": ["noop"], "seeds": [0]})[0]
    calls = []

    def flaky(r):
        calls.append(1)
        if len(calls) < 2:
            raise RuntimeError("transient")
        return {"run": r.run_id, "key": r.key, "valid?": True}

    recs = Scheduler(1).run([rs], flaky)
    assert recs[0]["valid?"] is True and recs[0]["attempt"] == 2


def test_scheduler_host_runs_not_starved_by_device_queue():
    """A device run waiting for the (busy) slot must not wedge a
    worker: host-only runs queued behind it keep flowing."""
    import threading
    import time as _t

    def mk(i, device):
        return RunSpec(run_id=f"r{i}", campaign="c", workload="w",
                       seed=i, workload_label="w", device=device)

    release = threading.Event()
    done_at = {}

    def execute(rs):
        if rs.device:
            release.wait(5)
        done_at[rs.run_id] = _t.monotonic()
        return {"run": rs.run_id, "key": rs.key, "valid?": True}

    specs = [mk(0, True), mk(1, True), mk(2, False), mk(3, False)]
    t0 = _t.monotonic()
    sched = Scheduler(2, device_slots=1)
    t = threading.Thread(target=lambda: sched.run(specs, execute))
    t.start()
    # both host runs must finish while the device runs still hold/await
    # the single slot
    deadline = _t.monotonic() + 3
    while _t.monotonic() < deadline and \
            not {"r2", "r3"} <= set(done_at):
        _t.sleep(0.01)
    assert {"r2", "r3"} <= set(done_at), done_at
    assert "r0" not in done_at and "r1" not in done_at
    release.set()
    t.join(timeout=5)
    assert not t.is_alive()
    assert set(done_at) == {"r0", "r1", "r2", "r3"}


def test_campaign_thread_executor_retries_crashed_run(tmp_path):
    """execute_run crashes must reach the scheduler's retry loop (they
    are NOT absorbed into a record early): a run that fails once and
    then succeeds is indexed with its real verdict, attempt 2."""
    from jepsen_tpu.campaign.plan import register_workload

    calls = []

    def flaky_builder(opts):
        calls.append(1)
        if len(calls) < 2:
            raise RuntimeError("env flake")
        from jepsen_tpu import core as jcore

        return jcore.noop_test(name="flaky")

    register_workload("flaky", flaky_builder)
    try:
        summary = campaign.run_campaign(
            {"name": "fl", "workloads": ["flaky"], "seeds": [0]},
            str(tmp_path), workers=1)
    finally:
        from jepsen_tpu.campaign import plan as plan_mod

        plan_mod._EXTRA_WORKLOADS.pop("flaky", None)
    assert summary["counts"]["true"] == 1
    rec = Index(summary["index"]).records[0]
    assert rec["valid?"] is True and rec["attempt"] == 2


# ---------------------------------------------------------------- index

def test_index_torn_line_heals(tmp_path):
    p = str(tmp_path / "c.jsonl")
    idx = Index(p)
    idx.append({"run": "a", "key": "k", "valid?": True})
    idx.append({"run": "b", "key": "k2", "valid?": False})
    # crash mid-append: torn trailing bytes
    with open(p, "ab") as f:
        f.write(b'{"run": "c", "valid?"')
    size_torn = os.path.getsize(p)
    idx2 = Index(p)
    assert idx2.completed_ids() == {"a", "b"}
    # a read-only load must NOT touch the file — its "torn line" could
    # be a live writer's append in flight
    assert os.path.getsize(p) == size_torn
    # the WRITER heals on its next append: parseable ledger, no fusing
    idx2.append({"run": "c", "key": "k3", "valid?": True})
    assert Index(p).completed_ids() == {"a", "b", "c"}


def test_index_clean_load_never_arms_truncation(tmp_path):
    # a CLEAN ledger load must not arm the heal — a file that grows
    # after our read (concurrent writer) is not crash debris
    p = str(tmp_path / "c.jsonl")
    idx = Index(p)
    idx.append({"run": "a", "key": "k", "valid?": True})
    idx2 = Index(p)
    assert idx2._good_bytes is None
    # another writer lands a record between idx2's load and append
    idx.append({"run": "b", "key": "k2", "valid?": True})
    idx2.append({"run": "c", "key": "k3", "valid?": True})
    assert Index(p).completed_ids() == {"a", "b", "c"}  # nothing lost


def test_index_flip_reported_as_regression(tmp_path):
    idx = Index(str(tmp_path / "c.jsonl"))
    idx.append({"run": "r1", "key": "append|nofault|s2", "valid?": True,
                "gen": "g1"})
    idx.append({"run": "r1", "key": "append|nofault|s2", "valid?": False,
                "gen": "g2"})
    idx.append({"run": "r2", "key": "append|nofault|s3",
                "valid?": "unknown", "gen": "g1"})
    idx.append({"run": "r2", "key": "append|nofault|s3", "valid?": True,
                "gen": "g2"})
    flips = idx.flips()
    assert len(flips) == 2
    regs = idx.regressions()
    assert len(regs) == 1
    assert regs[0]["key"] == "append|nofault|s2"
    assert regs[0]["from"] is True and regs[0]["to"] is False
    # the rollup surfaces it
    txt = report.render_campaign({"campaign": "c", "total": 2,
                                  "counts": idx.verdict_counts(),
                                  "regressions": regs, "rows": [],
                                  "seeds": []})
    assert "REGRESSIONS" in txt and "append|nofault|s2" in txt


def test_index_span_stats_and_trend(tmp_path):
    idx = Index(str(tmp_path / "c.jsonl"))
    for gen, dur in (("g1", 1.0), ("g1", 2.0), ("g2", 4.0)):
        idx.append({"run": f"r-{gen}-{dur}", "key": "k", "valid?": True,
                    "gen": gen, "spans": {"check:append": dur}})
    st = idx.span_stats()["check:append"]
    assert st["count"] == 3 and st["min"] == 1.0 and st["max"] == 4.0
    trend = idx.span_trend("check:append")
    assert [g for g, _ in trend] == ["g1", "g2"]
    assert trend[1][1] == 4.0


# ----------------------------------------------- the fleet, end to end

@pytest.fixture(scope="module")
def campaign_store(tmp_path_factory):
    """One 12-run campaign (2 workloads x 2 fault plans x 3 seeds) run
    via the CLI on 2 workers — the ISSUE 3 acceptance fleet."""
    base = str(tmp_path_factory.mktemp("cstore"))
    spec_path = os.path.join(base, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(SPEC, f)
    rc = cli.run(cli.single_test_cmd(lambda o: o),
                 ["--store-dir", base, "campaign", "run", spec_path,
                  "--workers", "2"])
    return base, spec_path, rc


def test_cli_campaign_completes_fully_indexed(campaign_store, capsys):
    base, spec_path, rc = campaign_store
    assert rc == 0
    idx = Index(ccore.index_path("t", base))
    specs = expand(SPEC)
    assert idx.completed_ids() == {r.run_id for r in specs}
    for rec in idx.records:  # every run attributable, never a crash
        assert rec["valid?"] in (True, False, "unknown")
        assert rec["dir"] is None or \
            os.path.isdir(os.path.join(base, rec["dir"]))


def test_cli_campaign_report_rollup(campaign_store, capsys):
    base, spec_path, _ = campaign_store
    rc = cli.run(cli.single_test_cmd(lambda o: o),
                 ["--store-dir", base, "campaign", "report", spec_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "campaign t — 12 runs" in out
    assert "no regressions" in out


def test_cli_campaign_resumes_instantly(campaign_store, capsys):
    base, spec_path, _ = campaign_store
    n_before = len(Index(ccore.index_path("t", base)).records)
    summary = campaign.run_campaign(SPEC, base, workers=2)
    assert summary["executed"] == 0
    assert summary["skipped"] == 12
    # 0 runs re-executed -> 0 new records
    assert len(Index(ccore.index_path("t", base)).records) == n_before


def test_campaign_kill_and_resume(tmp_path):
    """A campaign killed mid-flight (simulated: an index holding only a
    prefix of the records) resumes by executing ONLY the missing runs."""
    base = str(tmp_path)
    spec = dict(SPEC, name="kr", seeds=[0, 1])
    full = campaign.run_campaign(spec, base, workers=2)
    assert full["executed"] == 8
    path = ccore.index_path("kr", base)
    kept = Index(path).records[:3]  # "kill" after 3 runs landed
    with open(path, "w") as f:
        for r in kept:
            f.write(json.dumps(r) + "\n")
    resumed = campaign.run_campaign(spec, base, workers=2)
    assert resumed["skipped"] == 3
    assert resumed["executed"] == 5
    assert Index(path).completed_ids() == \
        {r.run_id for r in expand(spec)}


def test_campaign_interrupt_preserves_live_state(tmp_path, monkeypatch):
    """An interrupted run_campaign (Ctrl-C mid-fleet) must NOT mark the
    heartbeat finished — a killed campaign's live.json is the
    post-mortem naming exactly the cells that were in flight."""
    base = str(tmp_path)

    def interrupted(self, *a, **kw):
        self.heartbeat.worker("campaign-worker-0",
                              {"run": "r-inflight", "slot": 0})
        raise KeyboardInterrupt

    monkeypatch.setattr(Scheduler, "run", interrupted)
    with pytest.raises(KeyboardInterrupt):
        campaign.run_campaign(dict(SPEC, name="intr", seeds=[0]), base)
    doc = json.load(open(ccore.live_path("intr", base)))
    assert doc["finished"] is False
    assert "campaign-worker-0" in doc["workers"]
    assert doc["workers"]["campaign-worker-0"]["run"] == "r-inflight"


def test_campaign_status(campaign_store, capsys):
    base, spec_path, _ = campaign_store
    rc = cli.run(cli.single_test_cmd(lambda o: o),
                 ["--store-dir", base, "campaign", "status", spec_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "12 runs, 0 pending" in out


def test_campaign_bad_spec_clean_error(tmp_path, capsys):
    p = str(tmp_path / "bad.json")
    with open(p, "w") as f:
        f.write("{}")
    rc = cli.run(cli.single_test_cmd(lambda o: o),
                 ["campaign", "run", p])
    assert rc == 2
    assert "bad spec" in capsys.readouterr().err


def test_campaign_crashing_workload_indexed_unknown(tmp_path):
    from jepsen_tpu.campaign.plan import register_workload

    def bad_builder(opts):
        raise RuntimeError("builder exploded")

    register_workload("exploder", bad_builder)
    try:
        summary = campaign.run_campaign(
            {"name": "x", "workloads": ["exploder"], "seeds": [0]},
            str(tmp_path), workers=1)
    finally:
        from jepsen_tpu.campaign import plan as plan_mod

        plan_mod._EXTRA_WORKLOADS.pop("exploder", None)
    assert summary["counts"]["unknown"] == 1
    rec = Index(summary["index"]).records[0]
    assert "builder exploded" in rec["error"]


def test_result_flags_nested():
    flags = ccore.result_flags({
        "valid?": "unknown",
        "sub": {"valid?": "unknown", "error": "deadline-exceeded"},
        "other": {"valid?": True, "degraded": "host-fallback"},
    })
    assert flags["deadline"] is True
    assert flags["degraded"] == "host-fallback"
    assert flags["error"] == "deadline-exceeded"


def test_bench_emits_campaign_spec(tmp_path):
    import bench

    p = str(tmp_path / "ladder.json")
    spec = bench.emit_campaign_spec(p, sizes=[100, 200])
    # the emitted file is a valid, expandable campaign spec
    rs = expand(p)
    assert len(rs) == 2
    assert {r.workload_label for r in rs} == {"la-100", "la-200"}
    assert all(r.device for r in rs)
    assert all(r.opts["telemetry"] for r in rs)


def test_campaign_append_device_runs_with_degradation(tmp_path):
    """Seeded noop_test/append campaign on 2 workers (the satellite
    fleet): the append cells run the device elle pipeline; the faulted
    plan is PERSISTENT at the infer seam, so those runs must degrade to
    the host oracle — and the index must say so (degraded attribution,
    same verdicts)."""
    spec = {
        "name": "dev",
        "workloads": ["noop", "append"],
        "faults": [None, {"label": "kill-infer",
                          "spec": {"persistent": ["elle.infer"]}}],
        "seeds": [0, 1],
        "opts": {"time-limit": 0.2, "concurrency": 2},
    }
    summary = campaign.run_campaign(spec, str(tmp_path), workers=2)
    assert summary["executed"] == 8
    c = summary["counts"]
    assert c["true"] == 8  # tiny mem-cluster histories are all valid
    assert c["degraded"] == 2  # both faulted append cells fell back
    idx = Index(summary["index"])
    degraded = [r for r in idx.records if r.get("degraded")]
    assert {r["fault"] for r in degraded} == {"kill-infer"}
    assert all(r["workload"] == "append" for r in degraded)
    assert all(r["degraded"] == "host-fallback" for r in degraded)
    # the rollup marks them with the ·h flag
    assert "ok·h" in report.render_campaign(summary)


# ------------------------------------------------------------------ web

@pytest.fixture(scope="module")
def served_campaign(campaign_store):
    base, _, _ = campaign_store
    srv = web.serve(port=0, base=base, background=True)
    yield base, srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.read().decode()


def test_web_campaign_dashboard(served_campaign):
    base, port = served_campaign
    status, body = _get(port, "/campaigns")
    assert status == 200 and ">t<" in body
    status, body = _get(port, "/campaign/t")
    assert status == 200
    # the grid: both workloads, both fault labels, a seed column per seed
    assert "noop" in body and "set" in body and "nofault" in body
    assert "<th>s0</th>" in body and "<th>s2</th>" in body
    assert body.count("b-true") >= 12
    # index page links to campaigns
    status, body = _get(port, "/")
    assert status == 200 and 'href="/campaigns"' in body


def test_web_deadline_and_degraded_badges(tmp_path):
    """The satellite contract: unknown+deadline-exceeded and
    host-fallback degraded runs render as DISTINCT badges on the index
    and the run page."""
    base = str(tmp_path)
    d1 = os.path.join(base, "dl-run", "20260101T000000.000Z")
    os.makedirs(d1)
    with open(os.path.join(d1, "results.json"), "w") as f:
        json.dump({"valid?": "unknown", "error": "deadline-exceeded"}, f)
    d2 = os.path.join(base, "deg-run", "20260101T000001.000Z")
    os.makedirs(d2)
    with open(os.path.join(d2, "results.json"), "w") as f:
        json.dump({"valid?": True,
                   "append": {"valid?": True,
                              "degraded": "host-fallback"}}, f)
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        status, body = _get(port, "/")
        assert status == 200
        assert "b-deadline" in body and "deadline" in body
        assert "b-degraded" in body and "host-fallback" in body
        # run pages carry the same badges
        _, run1 = _get(port, "/run/dl-run/20260101T000000.000Z")
        assert "b-deadline" in run1
        _, run2 = _get(port, "/run/deg-run/20260101T000001.000Z")
        assert "b-degraded" in run2 and "host-fallback" in run2
    finally:
        srv.shutdown()
        srv.server_close()


def test_web_campaign_regression_highlighted(tmp_path):
    base = str(tmp_path)
    idx = Index(os.path.join(base, "campaigns", "r.jsonl"))
    idx.append({"run": "r1", "key": "append|nofault|s0",
                "workload": "append", "fault": "nofault", "seed": 0,
                "valid?": True, "gen": "g1"})
    idx.append({"run": "r1", "key": "append|nofault|s0",
                "workload": "append", "fault": "nofault", "seed": 0,
                "valid?": False, "gen": "g2"})
    srv = web.serve(port=0, base=base, background=True)
    try:
        port = srv.server_address[1]
        _, body = _get(port, "/campaign/r")
        assert "regressions" in body
        assert "append|nofault|s0" in body
        assert "b-false" in body  # latest verdict shown in the grid
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------- subprocess executor (slow)

@pytest.mark.slow
def test_campaign_subprocess_executor(tmp_path):
    """One noop run through the real `python -m
    jepsen_tpu.campaign.runner` isolation path."""
    os.environ.setdefault("JT_FORCE_CPU", "1")
    spec = {"name": "sub", "workloads": ["noop"], "seeds": [0]}
    summary = campaign.run_campaign(spec, str(tmp_path), workers=1,
                                    executor="subprocess",
                                    run_deadline_s=120)
    assert summary["counts"]["true"] == 1


# ------------------------------------- checker-span perf gate (ISSUE 12)

def test_perf_gate_over_checker_spans_two_generations(tmp_path):
    """The CI sharding-regression gate: a small list-append + bank
    campaign run for TWO generations, `cli obs gate` evaluated over the
    real ``check:list-append`` / ``check:bank`` spans — then a
    synthesized +60% generation must trip rc 1, so a genuine slowdown
    of the (sharded-by-default) checking path fails the suite
    deterministically instead of depending on ambient timing."""
    import time as _time

    base = str(tmp_path)
    spec = {
        "name": "perfgate",
        "workloads": [
            {"name": "append", "label": "la",
             "opts": {"ops": 120, "time-limit": None}},
            {"name": "bank", "label": "bank",
             "opts": {"ops": 120, "time-limit": None}},
        ],
        "faults": [None],
        "seeds": [0, 1, 2, 3, 4, 5],
        "opts": {"telemetry": True, "concurrency": 2,
                 "checker-time-limit": 60},
    }
    s1 = campaign.run_campaign(spec, base, workers=2)
    assert s1["counts"].get("true") == 12
    _time.sleep(1.1)  # generations are second-resolution timestamps
    s2 = campaign.run_campaign(spec, base, workers=2, rerun=True)
    assert s2["counts"].get("true") == 12

    disp = cli.single_test_cmd(lambda o: {})
    argv = ["--store-dir", base]
    assert cli.run(disp, argv + ["obs", "ingest"]) == 0
    for span in ("check:list-append", "check:bank"):
        rc = cli.run(disp, argv + ["obs", "gate", "--campaign",
                                   "perfgate", "--span", span,
                                   "--min-runs", "3"])
        # two identical back-to-back generations: a real verdict (0
        # expected; 1 tolerated under ambient load), never rc 2
        assert rc in (0, 1), (span, rc)

    # synthesize a +60% generation from the REAL gen-2 records: the
    # gate must flag it for both checker spans (rc 1, deterministic).
    # Durations come from the generation MAX per span, not each
    # record's own values — real cross-run spread on ms-scale spans
    # can exceed the 1.6x factor, and a slow record built from a fast
    # run's values would not stochastically dominate the old
    # generation (Mann-Whitney would not trip).
    idx = Index(ccore.index_path("perfgate", base))
    last_gen = idx.records[-1]["gen"]
    slow = [dict(r) for r in idx.records if r.get("gen") == last_gen]
    peak = {}
    base_mean = {}
    phase_mean = {}
    for r in slow:
        for k, v in (r.get("spans") or {}).items():
            peak[k] = max(peak.get(k, 0.0), v)
            base_mean.setdefault(k, []).append(v)
        for k, ph in (r.get("phases") or {}).items():
            for b, v in ph.items():
                phase_mean.setdefault(k, {}).setdefault(b, []).append(v)
    base_mean = {k: sum(v) / len(v) for k, v in base_mean.items()}
    phase_mean = {k: {b: sum(v) / len(v) for b, v in ph.items()}
                  for k, ph in phase_mean.items()}
    for i, r in enumerate(slow):
        r["run"] = f"slow-{i}"
        r["gen"] = "zslow"
        spans = {k: round(v * 1.6 + i * 1e-6, 6)
                 for k, v in peak.items()}
        r["spans"] = spans
        # compile-heavy composition (ISSUE 16): 90% of each span's
        # delta vs the old generation's mean lands in compile_s, so
        # the forensics diff must attribute the regression there
        r["phases"] = {
            k: {"compile_s": round(
                    phase_mean.get(k, {}).get("compile_s", 0.0)
                    + 0.9 * (spans[k] - base_mean[k]), 6),
                "execute_s": round(
                    phase_mean.get(k, {}).get("execute_s", 0.0)
                    + 0.1 * (spans[k] - base_mean[k]), 6)}
            for k in spans}
        r["counters"] = {"compile-cache-miss{site=checker}": 40.0 + i}
        idx.append(r)
    assert cli.run(disp, argv + ["obs", "ingest"]) == 0
    for span in ("check:list-append", "check:bank"):
        rc = cli.run(disp, argv + ["obs", "gate", "--campaign",
                                   "perfgate", "--span", span,
                                   "--min-runs", "3"])
        assert rc == 1, (span, rc)
    # satellite 1: one gate invocation over repeated --span flags and
    # globs — rc is the worst single-span verdict (regression here)
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "perfgate",
                               "--span", "check:*",
                               "--span", "check:bank",
                               "--min-runs", "3"])
    assert rc == 1, rc

    # ISSUE 16 forensics: `obs diff` must attribute the synthesized
    # compile-heavy regression to compile_s (>= half the delta), name
    # the compile-cache-miss counter delta, and exit deterministically
    # (rc 1 — never 2 on real data)
    out_path = os.path.join(base, "diff.json")
    rc = cli.run(disp, argv + ["obs", "diff", "perfgate",
                               "--min-runs", "3", "--json", out_path])
    assert rc == 1, rc
    with open(out_path) as f:
        rep = json.load(f)
    assert rep["status"] == "regression"
    assert rep["to-gen"] == "zslow"
    by_span = {e["span"]: e for e in rep["spans"]}
    for span in ("check:list-append", "check:bank"):
        e = by_span[span]
        assert e["status"] == "regression", e
        assert e["dominant"] == "compile_s", e
        comp = next(p for p in e["phases"]
                    if p["bucket"] == "compile_s")
        assert comp["share"] >= 0.5, comp
        assert any(c["name"].startswith("compile-cache-miss")
                   and c["delta"] > 0
                   for c in e["counters"]), e["counters"]

    # backend parity: the warehouse fast path and the raw jsonl scan
    # must feed forensics the identical record shape (same verdict)
    p = ccore.index_path("perfgate", base)
    assert Index(p).forensic_records() == \
        Index(p, use_warehouse=False).forensic_records()


def test_perf_gate_applies_to_live_verifier_sweep_span(tmp_path):
    """ISSUE 13 satellite: live-checked cells (in-proc verifier) land
    their ``verifier.sweep`` spans in the run records, so `cli obs
    gate` regression-gates the batched sweep path exactly like a
    checker span — rc 0/1 on real data (never 2/inapplicable), rc 1
    deterministically on a synthesized +60% generation."""
    import time as _time

    base = str(tmp_path)
    spec = {
        "name": "sweepgate", "workloads": ["append"],
        "seeds": [0, 1, 2],
        "opts": {"telemetry": True, "ops": 100, "time-limit": None,
                 "concurrency": 2, "live-check": {"inproc": True}},
    }
    s1 = campaign.run_campaign(spec, base, workers=2)
    assert s1["counts"].get("true") == 3
    _time.sleep(1.1)  # generations are second-resolution timestamps
    s2 = campaign.run_campaign(spec, base, workers=2, rerun=True)
    assert s2["counts"].get("true") == 3

    disp = cli.single_test_cmd(lambda o: {})
    argv = ["--store-dir", base]
    assert cli.run(disp, argv + ["obs", "ingest"]) == 0
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign",
                               "sweepgate", "--span", "verifier.sweep",
                               "--min-runs", "3"])
    assert rc in (0, 1), rc
    idx = Index(ccore.index_path("sweepgate", base))
    assert all("verifier.sweep" in (r.get("spans") or {})
               for r in idx.records)
    last_gen = idx.records[-1]["gen"]
    slow = [dict(r) for r in idx.records if r.get("gen") == last_gen]
    # generation MAX per span (same reasoning as the perfgate test):
    # ms-scale sweep spans spread more than 1.6x across runs, and the
    # synthesized generation must stochastically dominate for rc 1 to
    # be deterministic
    peak = {}
    for r in slow:
        for k, v in (r.get("spans") or {}).items():
            peak[k] = max(peak.get(k, 0.0), v)
    for i, r in enumerate(slow):
        r["run"] = f"slow-{i}"
        r["gen"] = "zslow"
        r["spans"] = {k: round(v * 1.6 + i * 1e-6, 6)
                      for k, v in peak.items()}
        idx.append(r)
    assert cli.run(disp, argv + ["obs", "ingest"]) == 0
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign",
                               "sweepgate", "--span", "verifier.sweep",
                               "--min-runs", "3"])
    assert rc == 1, rc
