"""fleet/autopilot.py — continuous verification as a self-healing,
self-scaling service (ISSUE 17).

Covers the tentpole contracts:

- the **journal**: replay reaches the identical digest, a torn final
  line is ignored by readers and healed writer-side only, scale audit
  events are digest-excluded;
- the **crash window**: kill -9 between the ``gen-open`` journal
  append and the queue enqueue — a restarted autopilot re-admits the
  journaled generation with ZERO duplicate cells and an identical
  journal digest, and a second restart changes nothing;
- **gate rc 2 degrades gracefully**: a streak of unevaluable
  generations (no gateable spans) closes every generation and never
  quarantines;
- **gate rc 1 reacts**: a seeded span regression is gate-caught,
  attributed to the regressing cell key, quarantined (gauge + future
  plans exclude it), auto-shrunk to a witness record in the campaign
  index, with an ``obs diff`` forensics artifact on disk;
- **chaos**: a seeded FaultPlan on every ``autopilot.*`` decision seam
  never wedges the loop — generations still close with attributable
  verdicts;
- the satellites: queue claim-latency p95, ``obs gc`` retention
  archival, `jepsen_fleet_host_info` cardinality, and the
  ``scripts/soak_autopilot.py --fast`` acceptance (kill -9 resume +
  rolling upgrade) as a subprocess smoke.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from jepsen_tpu import resilience, store, telemetry
from jepsen_tpu.fleet import (
    Autopilot,
    AutopilotJournal,
    WorkQueue,
    autopilot_path,
)

SPEC = {"name": "ap", "workloads": ["bank"], "seeds": [0, 1, 2],
        "opts": {"time-limit": 0.2}}


# ---------------------------------------------------------- helpers

def _drainer(ap, spans_for=None):
    """A synthetic fleet: claim + complete every cell with a verdict
    record (no real execution).  `spans_for(spec) -> dict | None`
    shapes the telemetry the gate sees."""
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            code, out = ap.coordinator.claim({"worker": "syn"})
            if code != 200 or not out.get("spec"):
                time.sleep(0.01)
                continue
            sp = out["spec"]
            key = (f'{sp["workload_label"]}|{sp["fault_label"]}'
                   f'|s{sp["seed"]}')
            rec = {"run": sp["run_id"], "key": key,
                   "workload": sp["workload_label"],
                   "fault": sp["fault_label"], "seed": sp["seed"],
                   "valid?": True, "dir": None}
            if spans_for is not None:
                extra = spans_for(sp)
                if extra:
                    rec.update(extra)
            ap.coordinator.complete({"worker": "syn",
                                     "run": sp["run_id"],
                                     "record": rec})

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return stop, t


def _run(ap, spans_for=None):
    stop, t = _drainer(ap, spans_for)
    try:
        return ap.run()
    finally:
        ap.stop.set()
        stop.set()
        t.join(timeout=5)
        ap.coordinator.close()


# ---------------------------------------------------------- journal

def test_journal_replay_and_torn_tail(tmp_path):
    p = str(tmp_path / "a.autopilot.jsonl")
    j = AutopilotJournal(p)
    j.open_gen("g0000", seeds=[0, 1], runs=2)
    j.close_gen("g0000", [{"span": None, "status": "insufficient-data",
                           "rc": 2}])
    j.quarantine("bank|nofault|s1", gen="g0001", span="workload",
                 rel_delta=0.6)
    j.shrink("bank|nofault|s1", gen="g0001", outcome={"ops": 3})
    j.scale("spawn", worker="w1", version="v1")
    d = j.digest()
    # replay = identical state; scale events are audit, not state
    r = AutopilotJournal(p)
    assert r.digest() == d
    assert r.scale_events == 1
    assert r.closed_labels() == ["g0000"]
    assert "bank|nofault|s1" in r.quarantined
    # torn tail (crash mid-append): readers ignore it...
    with open(p, "ab") as f:
        f.write(b'{"ev": "quarantine", "key": "to')
    torn = AutopilotJournal(p)
    assert torn.digest() == d
    # ...and only the WRITER heals — the reader left the file alone
    assert open(p, "rb").read().endswith(b'"to')
    torn.scale("drain", worker="w1")
    for line in open(p, "rb").read().splitlines():
        json.loads(line)  # every line whole again
    assert AutopilotJournal(p).digest() == d


# ------------------------------------------------------ crash window

def test_crash_between_gen_open_and_enqueue_resumes_zero_dupes(tmp_path):
    base = str(tmp_path / "store")
    ap1 = Autopilot(SPEC, base, generations=1, poll_s=0.02)
    out = _run(ap1, lambda sp: {"spans": {"workload": 0.1}})
    assert out["generations"] == 1
    # kill -9 window: gen-open journaled, cells never enqueued
    ap1.journal.open_gen("g0001", seeds=[1, 2, 0], runs=3)
    d = AutopilotJournal(autopilot_path("ap", base)).digest()

    # restart: re-admit heals the window — g0000 counts done from the
    # index, g0001 enqueues fresh, nothing duplicates
    ap2 = Autopilot(SPEC, base, poll_s=0.02)
    c = ap2.coordinator.queue.counts()
    assert c["duplicates"] == 0
    assert c["done"] == 3 and c["queued"] == 3
    assert ap2.journal.digest() == d
    ap2.coordinator.close()

    # a second restart is a no-op: enqueue is idempotent on run ids
    ap3 = Autopilot(SPEC, base, poll_s=0.02)
    c = ap3.coordinator.queue.counts()
    assert c["duplicates"] == 0 and c["queued"] == 3 \
        and c["cells"] == 6
    assert ap3.journal.digest() == d
    ap3.coordinator.close()


# -------------------------------------------------- gate rc 2 streak

def test_rc2_streak_closes_generations_never_quarantines(tmp_path):
    base = str(tmp_path / "store")
    ap = Autopilot(SPEC, base, generations=3, poll_s=0.02)
    out = _run(ap, None)  # records carry NO spans: nothing gateable
    assert out["generations"] == 3
    assert out["quarantined"] == []
    for label in ap.journal.closed_labels():
        for v in ap.journal.gens[label]["verdicts"]:
            assert v["rc"] == 2
            assert v["status"] in ("insufficient-data", "gate-error")


# ------------------------------------- regression -> quarantine+shrink

def _regressing_spans(sp):
    """g0001 regresses every cell, seed 2 hardest — attribution is
    deterministic (largest relative delta)."""
    gen = (sp.get("opts") or {}).get("autopilot-gen")
    s = int(sp["seed"])
    dur = (0.3 + 0.01 * s) if gen == "g0001" else (0.1 + 0.001 * s)
    return {"spans": {"workload": dur}, "valid?": gen != "g0001",
            "dir": f"runs/{sp['run_id']}"}


def test_regression_quarantined_and_autoshrunk(tmp_path, monkeypatch):
    from jepsen_tpu import minimize

    shrunk = {}

    def fake_shrink(run_dir, **kw):
        shrunk["dir"] = run_dir
        return {"ops": 3, "source-ops": 12, "digest": "abc123",
                "anomaly-types": ["G-single"], "probes": 5,
                "cached": 1, "fault-windows": []}

    monkeypatch.setattr(minimize, "shrink", fake_shrink)
    base = str(tmp_path / "store")
    ap = Autopilot(SPEC, base, generations=2, spans=("workload",),
                   poll_s=0.02)
    out = _run(ap, _regressing_spans)
    key = "bank|nofault|s2"
    assert out["quarantined"] == [key]
    v = ap.journal.gens["g0001"]["verdicts"][0]
    assert v["status"] == "regression" and v["rc"] == 1
    assert v["key"] == key and v["key-rel-delta"] > 2.0
    # the shrink ran on the quarantined cell's g0001 run dir and its
    # witness record landed in the campaign index
    assert shrunk["dir"].startswith(os.path.join(base, "runs"))
    sk = ap.journal.shrinks[key]
    assert sk["gen"] == "g0001"
    assert sk["outcome"]["digest"] == "abc123"
    wit = [r for r in ap.coordinator.idx.records if r.get("witness")]
    assert len(wit) == 1 and wit[0]["key"] == key
    assert wit[0]["autopilot"]["quarantined"] == "g0001"
    assert wit[0]["witness"]["anomaly-types"] == ["G-single"]
    # forensics artifact on disk, referenced from the witness
    art = wit[0]["autopilot"]["forensics"]
    assert art and os.path.exists(os.path.join(base, art))
    rep = json.load(open(os.path.join(base, art)))
    assert rep["status"] in ("regression", "pass",
                             "insufficient-data")
    # gauge + future plans exclude the cell
    g = {m["name"]: m["value"]
         for m in telemetry.registry().snapshot()["gauges"]}
    assert g["fleet-quarantined-cells"] == 1
    assert [rs.key for rs in ap._plan(2)] == \
        ["bank|nofault|s0", "bank|nofault|s1"]
    # ...but a REPLAY of g0001 (quarantined AT g0001) still plans it
    assert key in [rs.key for rs in ap._plan(1)]
    # the satellites' status surface
    st = ap.coordinator._status()[1]
    assert "queue-depth" in st and "claim-latency-p95-s" in st
    assert st["autopilot"]["quarantined"][key]["span"] == "workload"
    assert st["autopilot"]["journal-digest"] == ap.journal.digest()


# ------------------------------------------- quarantine parole (5d)

def _fixed_then_reoffending_spans(sp):
    """g0001 regresses every cell (seed 2 hardest), the bug is
    'fixed' for two clean generations, then g0005 regresses again —
    the paroled cell re-offends."""
    gen = (sp.get("opts") or {}).get("autopilot-gen")
    s = int(sp["seed"])
    bad = gen in ("g0001", "g0005")
    dur = (0.3 + 0.01 * s) if bad else (0.1 + 0.001 * s)
    return {"spans": {"workload": dur}, "valid?": not bad,
            "dir": f"runs/{sp['run_id']}"}


def test_quarantine_parole_readmits_then_requarantines(
        tmp_path, monkeypatch):
    from jepsen_tpu import minimize

    monkeypatch.setattr(minimize, "shrink", lambda run_dir, **kw: {
        "ops": 3, "source-ops": 12, "digest": "abc123",
        "anomaly-types": ["G-single"], "probes": 5, "cached": 1,
        "fault-windows": []})
    # the shrink above is synthetic (no witness on disk): stand in a
    # passing host-twin verdict so the parole path itself is exercised
    # (the twin gate has its own denial tests below)
    monkeypatch.setattr(
        Autopilot, "_twin_recheck",
        lambda self, key, digest: (True, {"digest": digest,
                                          "checker": "stub",
                                          "valid?": True}))
    base = str(tmp_path / "store")
    ap = Autopilot(SPEC, base, generations=6, spans=("workload",),
                   poll_s=0.02, parole_after=2)
    out = _run(ap, _fixed_then_reoffending_spans)
    key = "bank|nofault|s2"
    assert out["generations"] == 6

    # g0001: quarantined; g0002+g0003 close clean without it ->
    # paroled at g0003's close, back in the plan from g0004 on
    v = ap.journal.quarantined[key]
    assert v["history"] == [{"gen": "g0001", "paroled-gen": "g0003"}]
    assert [g["runs"] for g in
            (ap.journal.gens[l] for l in
             ("g0000", "g0001", "g0002", "g0003", "g0004"))] == \
        [3, 3, 2, 2, 3]

    # g0005 regresses again: the re-offender is re-quarantined with
    # the prior stint archived, and is NOT paroled anew
    assert v["gen"] == "g0005" and "paroled-gen" not in v
    g5 = ap.journal.gens["g0005"]["verdicts"][0]
    assert g5["status"] == "regression" and g5["key"] == key

    # plan membership per generation honors BOTH stints on replay
    plans = {i: [rs.key for rs in ap._plan(i)] for i in range(7)}
    assert key in plans[1]          # quarantined AT g0001's close
    assert key not in plans[2] and key not in plans[3]
    assert key in plans[4] and key in plans[5]
    assert key not in plans[6]      # second stint

    # gauges split active vs paroled; journal replay reaches the
    # identical digest with parole + re-quarantine events applied
    g = {m["name"]: m["value"]
         for m in telemetry.registry().snapshot()["gauges"]}
    assert g["fleet-quarantined-cells"] == 1
    assert g["fleet-paroled-cells"] == 0
    assert AutopilotJournal(ap.journal.path).digest() == \
        ap.journal.digest()


# ------------------------------------------------------------- chaos

def test_chaos_on_every_seam_never_wedges(tmp_path):
    base = str(tmp_path / "store")
    plan = resilience.FaultPlan(
        seed=7, p=0.35, kinds=("oom", "stall"), stall_s=0.005,
        sites="autopilot.enqueue|autopilot.gate|autopilot.shrink"
              "|autopilot.scale")
    ap = Autopilot(SPEC, base, generations=2, spans=("workload",),
                   poll_s=0.02)
    with resilience.use(plan):
        out = _run(ap, lambda sp: {"spans": {"workload": 0.1}})
    assert out["generations"] == 2
    for label in ap.journal.closed_labels():
        for v in ap.journal.gens[label]["verdicts"]:
            assert v["to-gen"] == label  # attributable
            assert v["rc"] in (0, 1, 2)
    # same plan, same call sequence -> the injections were real
    assert plan.injected or plan.p == 0.0


# -------------------------------------------------------- satellites

def test_queue_claim_latency_p95(tmp_path):
    q = WorkQueue(str(tmp_path / "q.jsonl"))
    assert q.claim_latency_p95() is None
    for i in range(4):
        q.enqueue({"run_id": f"r{i}", "campaign": "q",
                   "workload": "set", "seed": i, "opts": {},
                   "fault": None, "fault_label": "nofault",
                   "workload_label": "set", "device": False})
    for _ in range(3):
        q.claim("w", lease_s=9.0)
    lats = q.claim_latencies()
    assert len(lats) == 3 and all(l >= 0 for l in lats)
    assert q.claim_latency_p95() == sorted(lats)[-1]


def test_obs_gc_archives_landed_runs_only(tmp_path):
    base = str(tmp_path / "store")
    now = time.time()

    def mk(name, age_s, landed):
        d = os.path.join(base, name, store.timestamp(now - age_s))
        os.makedirs(d)
        if landed:
            with open(os.path.join(d, "results.json"), "w") as f:
                f.write("{}")
        return d

    old = mk("t", 5000, landed=True)
    fresh = mk("t", 10, landed=True)
    crashed = mk("u", 5000, landed=False)
    stats = store.gc_runs(base, retention_s=3600, now=now)
    assert stats == {"archived": 1, "kept": 1, "skipped": 1}
    assert not os.path.exists(old) and os.path.exists(crashed)
    arch = os.path.join(store.archive_dir(base), "t",
                        os.path.basename(old))
    assert os.path.exists(os.path.join(arch, "results.json"))
    # archived runs leave every live scan (store.tests + warehouse)
    live = store.tests(base=base)
    assert fresh in live and old not in live
    assert all("_archive" not in os.path.relpath(d, base)
               for d in live)
    # idempotent second sweep
    assert store.gc_runs(base, retention_s=3600,
                         now=now)["archived"] == 0


def test_host_info_series_pinned_to_alive_versioned_workers():
    from jepsen_tpu.telemetry import prometheus

    class Fleet:
        name = "f"

        def federated_metrics(self):
            return {"w2": {"version": "v2", "rows": []},
                    "w1": {"version": "v1", "rows": []},
                    "old": {"rows": []}}  # pre-17 worker: no series

        def counts(self):
            return {}

    lines = prometheus.render_fleet(Fleet())
    info = [l for l in lines if "jepsen_fleet_host_info" in l
            and not l.startswith("#")]
    assert info == [
        'jepsen_fleet_host_info{host="w1",version="v1"} 1',
        'jepsen_fleet_host_info{host="w2",version="v2"} 1']


def test_spawned_workers_one_device_worker_per_host(tmp_path,
                                                   monkeypatch):
    """A `fleet work` process that initializes the TPU backend claims
    every chip of the host: only one live spawned worker may use the
    device, the rest are held to the CPU backend — and when the device
    worker dies, the next spawn takes the device over."""
    monkeypatch.delenv("JT_FORCE_CPU", raising=False)
    envs = []

    class FakeProc:
        def __init__(self, cmd, env, **kw):
            envs.append(env)
            self.rc = None

        def poll(self):
            return self.rc

    monkeypatch.setattr(subprocess, "Popen", FakeProc)
    ap = Autopilot(SPEC, str(tmp_path / "store"),
                   coordinator_url="http://127.0.0.1:1")
    names = [ap._spawn_worker() for _ in range(3)]
    assert [ap.workers[n]["device"] for n in names] == [True, False, False]
    assert "JT_FORCE_CPU" not in envs[0]
    assert all(e["JT_FORCE_CPU"] == "1" and e["JAX_PLATFORMS"] == "cpu"
               for e in envs[1:])
    ap.workers[names[0]]["proc"].rc = -9
    assert ap.workers[ap._spawn_worker()]["device"] is True
    assert "JT_FORCE_CPU" not in envs[3]


def test_soak_autopilot_fast():
    """The unattended acceptance: generations streamed, a seeded
    regression gate-caught -> quarantined -> auto-shrunk, the
    gate-regression alert walking pending -> firing -> resolved with a
    second kill -9 landing MID-FIRING (alert journal replays to the
    identical digest, zero duplicate notifications), coordinator
    kill -9 resume with zero duplicate cells, rolling worker upgrade
    with flat /metrics cardinality."""
    script = os.path.join(os.path.dirname(__file__), "..",
                          "scripts", "soak_autopilot.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script, "--fast"],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    sys.stdout.write(out.stdout[-3000:])
    sys.stderr.write(out.stderr[-3000:])
    assert out.returncode == 0
    assert "SOAK PASS" in out.stdout
    assert "duplicates=0" in out.stdout
    assert "quarantined=" in out.stdout
    assert "alert-arc=pending->firing->resolved" in out.stdout
