"""Warehouse + /metrics + regression-gate tests (ISSUE 6).

The observatory contract under test:

- ingest is INCREMENTAL (byte cursors / stat digests: an unchanged
  store is a no-op) and REBUILDABLE (the jsonl ledgers stay the source
  of truth, even for torn/partial/mid-crash stores);
- the SQL fast paths return exactly what the jsonl scans return, and
  the hot pair (``flips`` + ``span_trend``) is >= 10x faster on a
  1k-run campaign (the acceptance criterion);
- the Prometheus exposition is pinned byte-for-byte by a golden file;
- ``cli obs gate`` passes an unchanged generation pair and fails an
  injected +50% p95 regression, with distinct exit codes.
"""

import json
import os
import random
import time

import pytest

from jepsen_tpu import cli
from jepsen_tpu.campaign.index import Index
from jepsen_tpu.telemetry import gate, metrics, prometheus
from jepsen_tpu.telemetry import warehouse as wmod


# ------------------------------------------------------------ helpers

def _write_ledger(base, name="soak", gens=("g1", "g2"), n=20,
                  scale=None, seed=0, witness_every=0, flip_every=0):
    """A synthetic campaign ledger: ``n`` runs per generation, span
    durations ~U(0.9, 1.1) * scale[gen]."""
    scale = scale or {}
    cdir = os.path.join(str(base), "campaigns")
    os.makedirs(cdir, exist_ok=True)
    path = os.path.join(cdir, name + ".jsonl")
    rng = random.Random(seed)
    with open(path, "a") as f:
        for gen in gens:
            m = scale.get(gen, 1.0)
            for i in range(n):
                rec = {
                    "campaign": name, "run": f"r-{gen}-{i}",
                    "key": f"la|none|{i}", "workload": "la",
                    "fault": None, "seed": i,
                    "valid?": (False if flip_every and gen != gens[0]
                               and i % flip_every == 0 else True),
                    "dir": f"d/{gen}/{i}", "ops": 100,
                    "wall_s": round(rng.uniform(5, 20), 2), "gen": gen,
                    "ts": "2026-08-03T00:00:00Z",
                    "spans": {
                        "check:la": round(rng.uniform(0.9, 1.1) * m, 6),
                        "workload": round(rng.uniform(1, 3), 6),
                    },
                }
                if witness_every and i % witness_every == 0:
                    rec["witness"] = {
                        "ops": 2 + (i + (0 if gen == gens[0] else 1)) % 5,
                        "digest": f"w{(i + len(gen)) % 3}",
                        "anomaly-types": ["G1c"]}
                f.write(json.dumps(rec) + "\n")
    return path


def _fresh(base, path):
    wh = wmod.open_or_create(str(base))
    wh.ingest_ledger(path, str(base))
    return wh


# ------------------------------------------------- incremental ingest

def test_ingest_is_cursor_incremental(tmp_path):
    path = _write_ledger(tmp_path, n=10)
    wh = wmod.open_or_create(str(tmp_path))
    assert wh.ingest_ledger(path, str(tmp_path)) == 20
    # unchanged ledger: a no-op (cursor == size)
    assert wh.ingest_ledger(path, str(tmp_path)) == 0
    assert wh.ledger_fresh(path, str(tmp_path))
    # appended records: only the new lines are parsed
    _write_ledger(tmp_path, gens=("g3",), n=5)
    assert wh.ingest_ledger(path, str(tmp_path)) == 5
    assert wh.counts()["campaign_records"] == 25


def test_torn_tail_left_unconsumed_until_healed(tmp_path):
    path = _write_ledger(tmp_path, n=3)
    with open(path, "a") as f:
        f.write('{"run": "torn", "valid?": tru')  # no newline
    wh = wmod.open_or_create(str(tmp_path))
    assert wh.ingest_ledger(path, str(tmp_path)) == 6
    assert not wh.ledger_fresh(path, str(tmp_path))  # fast path gated
    assert Index(path)._warehouse() is None
    # the writer heals (truncates) the torn line -> file shrinks below
    # the durable content... here it completes the line instead
    with open(path, "a") as f:
        f.write('e, "key": "k", "gen": "g9"}\n')
    assert wh.ingest_ledger(path, str(tmp_path)) == 1
    assert wh.ledger_fresh(path, str(tmp_path))


def test_shrunken_ledger_wiped_and_reingested(tmp_path):
    path = _write_ledger(tmp_path, n=10)
    wh = wmod.open_or_create(str(tmp_path))
    wh.ingest_ledger(path, str(tmp_path))
    # a heal/rewrite shrank the file: derived rows are rebuilt from 0
    lines = open(path).readlines()
    with open(path, "w") as f:
        f.writelines(lines[:7])
    wh.ingest_ledger(path, str(tmp_path))
    assert wh.counts()["campaign_records"] == 7
    assert Index(path).flips() == Index(path, use_warehouse=False).flips()


def test_mid_ingest_crash_rolls_back_whole_unit(tmp_path, monkeypatch):
    path = _write_ledger(tmp_path, n=10)
    wh = wmod.open_or_create(str(tmp_path))
    real = wmod.Warehouse._insert_record
    calls = []

    def dying(self, ledger, rec):
        if len(calls) == 12:
            raise RuntimeError("simulated crash mid-ingest")
        calls.append(rec)
        return real(self, ledger, rec)

    monkeypatch.setattr(wmod.Warehouse, "_insert_record", dying)
    with pytest.raises(RuntimeError):
        wh.ingest_ledger(path, str(tmp_path))
    monkeypatch.setattr(wmod.Warehouse, "_insert_record", real)
    # the transaction rolled back: no partial rows, no cursor movement
    assert wh.counts()["campaign_records"] == 0
    assert not wh.ledger_fresh(path, str(tmp_path))
    # ... and the next ingest simply redoes the unit, to the same state
    assert wh.ingest_ledger(path, str(tmp_path)) == 20
    assert wh.ledger_fresh(path, str(tmp_path))


# -------------------------------------------- fast path == jsonl scan

def test_sql_queries_equal_jsonl_scan(tmp_path):
    path = _write_ledger(tmp_path, n=30, scale={"g2": 1.4},
                         witness_every=4, flip_every=5)
    _fresh(tmp_path, path)
    slow = Index(path, use_warehouse=False)
    fast = Index(path)
    assert fast._warehouse() is not None, "fast path not engaged"
    assert fast.flips() == slow.flips()
    assert fast.regressions() == slow.regressions()
    assert fast.span_stats() == slow.span_stats()
    assert fast.span_trend("check:la") == slow.span_trend("check:la")
    assert fast.span_samples("workload") == slow.span_samples("workload")
    assert fast.witness_diffs() == slow.witness_diffs()
    assert fast.verdict_counts() == slow.verdict_counts()
    # latest_by_run: the warehouse returns the grid PROJECTION
    la, lb = slow.latest_by_run(), fast.latest_by_run()
    assert set(la) == set(lb)
    for run in la:
        for fld in ("run", "key", "workload", "fault", "seed", "valid?",
                    "dir", "ops", "wall_s", "gen", "ts"):
            assert la[run].get(fld) == lb[run].get(fld), (run, fld)
        assert la[run].get("witness") == lb[run].get("witness")


def test_runless_and_empty_run_records_agree_across_backends(tmp_path):
    """Records with a missing or empty run id: both backends apply the
    SAME selection rule (verdict-bearing AND truthy run), so the
    campaign grid and verdict counts can't change with warehouse
    freshness."""
    path = _write_ledger(tmp_path, n=4)
    with open(path, "a") as f:
        f.write(json.dumps({"campaign": "soak", "key": "k-norun",
                            "valid?": False, "gen": "g2"}) + "\n")
        f.write(json.dumps({"campaign": "soak", "run": "", "key": "k-e",
                            "valid?": False, "gen": "g2"}) + "\n")
    _fresh(tmp_path, path)
    slow = Index(path, use_warehouse=False)
    fast = Index(path)
    assert fast._warehouse() is not None
    assert set(fast.latest_by_run()) == set(slow.latest_by_run())
    assert fast.verdict_counts() == slow.verdict_counts()
    assert slow.verdict_counts()["false"] == 0  # run-less rows excluded


def test_corrupt_midfile_event_line_same_prefix_both_backends(tmp_path):
    """A complete-but-corrupt mid-file event line: the warehouse ingest
    stops where the read_events scan stops (same indexed prefix) and
    pins cursor < size, so events_fresh gates the fast path off and
    `tail --since` answers identically from either backend."""
    from jepsen_tpu.telemetry import stream as ts
    d = _mk_run(tmp_path, "a-test", "t9", events=5)
    p = os.path.join(d, "events.jsonl")
    with open(p, "a") as f:
        f.write('{"t": 200.0, "ev": "corrupt"\n')  # complete, bad JSON
        f.write(json.dumps({"t": 201.0, "ev": "tick", "i": 99}) + "\n")
    wh = wmod.open_or_create(str(tmp_path))
    n = wh.ingest_events(d, str(tmp_path))
    scan = ts.read_events(p)
    assert [e.get("i") for e in wh.events_since(d, str(tmp_path))] == \
        [e.get("i") for e in scan]
    assert n == 5 and not wh.events_fresh(d, str(tmp_path))
    # re-ingest: cursor parked before the bad line, no double-indexing
    assert wh.ingest_events(d, str(tmp_path)) == 0


def test_stale_warehouse_falls_back_to_scan(tmp_path):
    path = _write_ledger(tmp_path, n=5, flip_every=2)
    _fresh(tmp_path, path)
    assert Index(path)._warehouse() is not None
    # a writer appends a fresh flip (g2 left this key False): coverage
    # is stale, the scan answers
    idx = Index(path)
    idx.append({"run": "r-new", "key": "la|none|0", "valid?": True,
                "gen": "g3"})
    assert idx._warehouse() is None
    flips = idx.flips()
    assert any(f["run"] == "r-new" for f in flips)
    # a fresh reader also refuses the stale warehouse
    assert Index(path)._warehouse() is None
    assert Index(path).flips() == flips


def test_1k_campaign_speedup_10x(tmp_path):
    """THE acceptance criterion: warehouse-backed flips() + span_trend()
    >= 10x faster than the jsonl scan on a synthetic >=1k-run campaign,
    with both paths returning identical results.  (2k records: the
    scan cost scales with the ledger while SQL stays ~flat, so the
    bigger campaign doubles the timing margin this load-sensitive
    gate runs with.)"""
    path = _write_ledger(tmp_path, gens=("g1", "g2"), n=1000,
                         scale={"g2": 1.2}, flip_every=9)
    _fresh(tmp_path, path)

    def scan():
        idx = Index(path, use_warehouse=False)
        return idx.flips(), idx.span_trend("check:la")

    def sql():
        idx = Index(path)
        return idx.flips(), idx.span_trend("check:la")

    assert scan() == sql()

    # INTERLEAVED best-of reps: timing the two phases back-to-back let
    # an ambient load burst land entirely on one side (observed: all 7
    # sql reps slow while scan ran unloaded — a false <10x under the
    # full suite); alternating them each rep exposes both paths to the
    # same noise, and best-of still measures the unloaded cost
    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    t_scan = min(timed(scan) for _ in range(9))
    t_sql = float("inf")
    for _ in range(9):
        timed(scan)  # interleave: noise hits both paths alike
        t_sql = min(t_sql, timed(sql))
    assert t_scan >= 10 * t_sql, \
        f"scan {t_scan * 1e3:.2f}ms vs sql {t_sql * 1e3:.2f}ms " \
        f"({t_scan / t_sql:.1f}x, need >= 10x)"


# ---------------------------------- batching + compaction (ISSUE 20)

def test_batched_ingest_equivalent_and_fewer_commits(tmp_path):
    """ROADMAP 5a: batch_units groups N ingest units into ONE sqlite
    transaction.  Equivalence (tables + query results identical to the
    per-unit path) and economy (commit count shrinks with the batch)
    are both pinned."""
    for sub in ("a", "b"):
        b = tmp_path / sub
        os.makedirs(b)
        _write_ledger(b, name="s1", n=8, flip_every=3, seed=1)
        _write_ledger(b, name="s2", n=8, flip_every=3, seed=2)
        _write_ledger(b, name="s3", n=8, flip_every=3, seed=3)

    def commits(wh, fn):
        seen = []
        wh.db.set_trace_callback(
            lambda s: seen.append(s) if "COMMIT" in s.upper() else None)
        try:
            fn()
        finally:
            wh.db.set_trace_callback(None)
        return len(seen)

    wa = wmod.open_or_create(str(tmp_path / "a"))
    wb = wmod.open_or_create(str(tmp_path / "b"))
    na = commits(wa, lambda: wa.ingest_store(str(tmp_path / "a"),
                                             events=False,
                                             batch_units=1))
    nb = commits(wb, lambda: wb.ingest_store(str(tmp_path / "b"),
                                             events=False,
                                             batch_units=64))
    assert wa.counts() == wb.counts()
    for name in ("s1", "s2", "s3"):
        pa = os.path.join(str(tmp_path / "a"), "campaigns",
                          name + ".jsonl")
        pb = os.path.join(str(tmp_path / "b"), "campaigns",
                          name + ".jsonl")
        assert Index(pa).flips() == Index(pb).flips()
    assert nb < na, (na, nb)
    # both paths leave the cursors flush: re-ingest is a no-op
    again = wb.ingest_store(str(tmp_path / "b"), events=False)
    assert again["records"] == 0


def test_compaction_parity_for_safe_queries(tmp_path):
    """Folding old generations into gen_compact/key_compact must not
    change what flips/span_trend/witness_diffs answer (rollups are
    never touched), while raw rows below the horizon are dropped and
    witness-bearing records survive."""
    path = _write_ledger(tmp_path, gens=("g1", "g2", "g3", "g4"),
                         n=30, scale={"g4": 1.3}, flip_every=7,
                         witness_every=10)
    wh = _fresh(tmp_path, path)
    idx = Index(path)
    before = (idx.flips(), idx.span_trend("check:la"),
              idx.witness_diffs())
    n_before = wh.counts()["campaign_records"]

    stats = wh.compact_ledger(path, str(tmp_path), keep_gens=2)
    assert stats["gens-compacted"] == 2
    assert stats["dropped-records"] > 0
    assert stats["kept-witnesses"] > 0
    rel = os.path.relpath(path, str(tmp_path))
    assert wh.ledger_compacted(rel)
    assert wh.counts()["campaign_records"] < n_before

    idx2 = Index(path)
    after = (idx2.flips(), idx2.span_trend("check:la"),
             idx2.witness_diffs())
    assert after == before
    # ...and all three still match the raw jsonl scan
    scan = Index(path, use_warehouse=False)
    assert after == (scan.flips(), scan.span_trend("check:la"),
                     scan.witness_diffs())
    # the safe set answers from SQL; everything else falls back to
    # the scan (still identical — the jsonl is untouched)
    assert idx2._warehouse("flips") is not None
    assert idx2._warehouse("span_stats") is None
    assert idx2.span_stats() == scan.span_stats()
    # compaction never moves the byte cursor: re-ingest is a no-op
    again = wh.ingest_store(str(tmp_path), events=False)
    assert again["records"] == 0


def test_flip_detection_across_compaction_horizon(tmp_path):
    """A key's last verdict below the horizon lives only in
    key_compact; a NEW record flipping against it must still roll up
    as a flip, identically to the jsonl scan (which sees every raw
    line)."""
    path = _write_ledger(tmp_path, gens=("g1", "g2"), n=12,
                         flip_every=5)
    wh = _fresh(tmp_path, path)
    wh.compact_ledger(path, str(tmp_path), keep_gens=0)
    assert wh.counts()["campaign_records"] == 0
    # append g3 flipping every 4th key against its g2 verdict
    _write_ledger(tmp_path, gens=("g3",), n=12, flip_every=4)
    wh.ingest_store(str(tmp_path), events=False)
    assert Index(path).flips() ==         Index(path, use_warehouse=False).flips()


def test_alert_signals_touch_rollup_tables_only(tmp_path):
    """THE O(rollup rows) pin: the alert tick's warehouse leg may not
    read campaign_records or record_spans — trace-asserted, so a
    future 'quick join' cannot quietly make the tick O(runs)."""
    path = _write_ledger(tmp_path, gens=("g1", "g2"), n=25,
                         flip_every=6)
    wh = _fresh(tmp_path, path)
    stmts = []
    wh.db.set_trace_callback(stmts.append)
    try:
        sig = wh.alert_signals()
    finally:
        wh.db.set_trace_callback(None)
    for s in stmts:
        low = s.lower()
        assert "campaign_records" not in low, s
        assert "record_spans" not in low, s
    assert sig["flips"] > 0
    assert sig["span-p95-s:check:la"] > 0
    # compaction only shifts rows between tables the signals already
    # aggregate — the answers survive it
    wh.compact_ledger(path, str(tmp_path), keep_gens=1)
    sig2 = wh.alert_signals()
    assert sig2["flips"] == sig["flips"]
    assert sig2["compacted-gens"] == 1.0


def test_100k_store_speedup_compacted(tmp_path):
    """THE ISSUE 20 acceptance criterion: a synthetic 100k-run store —
    batched ingest, compacted rollups — answers flips + span_trend +
    the alert-signal query >= 10x faster than the jsonl scan with
    identical results; re-ingest is a digest no-op; the alert tick
    stays O(rollup rows).  (Timing is interleaved best-of like the 1k
    pin, so ambient suite load hits both paths alike.)"""
    cdir = tmp_path / "campaigns"
    os.makedirs(cdir)
    path = str(cdir / "big.jsonl")
    rng = random.Random(0)
    with open(path, "w") as f:
        for gen in ("g1", "g2"):
            for i in range(50000):
                f.write(json.dumps({
                    "campaign": "big", "run": f"r-{gen}-{i}",
                    "key": f"la|none|{i % 500}", "workload": "la",
                    "fault": None, "seed": i,
                    "valid?": not (gen == "g2" and i % 97 == 0),
                    "dir": f"d/{gen}/{i}", "ops": 100, "wall_s": 9.0,
                    "gen": gen, "ts": "2026-08-03T00:00:00Z",
                    "spans": {
                        "check:la": round(rng.uniform(0.9, 1.1), 6),
                        "workload": round(rng.uniform(1, 3), 6),
                    }}) + "\n")
    wh = wmod.open_or_create(str(tmp_path))
    stats = wh.ingest_store(str(tmp_path), events=False)
    assert stats["records"] == 100000
    wh.compact_ledger(path, str(tmp_path), keep_gens=1)

    def scan():
        idx = Index(path, use_warehouse=False)
        return idx.flips(), idx.span_trend("check:la")

    def sql():
        idx = Index(path)
        return idx.flips(), idx.span_trend("check:la")

    assert scan() == sql()

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    t_scan = min(timed(scan) for _ in range(3))
    t_sql = float("inf")
    for _ in range(3):
        timed(scan)  # interleave: noise hits both paths alike
        t_sql = min(t_sql, timed(sql))
    assert t_scan >= 10 * t_sql, \
        f"scan {t_scan * 1e3:.2f}ms vs sql {t_sql * 1e3:.2f}ms " \
        f"({t_scan / t_sql:.1f}x, need >= 10x)"
    # the alert tick is rollup-bounded: orders of magnitude under the
    # scan even on the 100k store
    t_alert = min(timed(wh.alert_signals) for _ in range(3))
    assert t_alert * 10 <= t_sql + t_scan, \
        f"alert tick {t_alert * 1e3:.2f}ms is not O(rollup rows)"
    sig = wh.alert_signals()
    assert sig["flips"] > 0 and sig["compacted-gens"] == 1.0
    # batched ingest left every cursor flush: the re-ingest is a no-op
    again = wh.ingest_store(str(tmp_path), events=False)
    assert again["records"] == 0 and again["ledgers"] == 1


# ------------------------------------------------- run dirs + rebuild

def _mk_run(base, name, ts, valid=True, telemetry=True, witness=False,
            events=None, torn_events=False):
    d = os.path.join(str(base), name, ts)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "results.json"), "w") as f:
        json.dump({"valid?": valid}, f)
    if telemetry:
        with open(os.path.join(d, "telemetry.json"), "w") as f:
            json.dump({
                "spans": [{"name": "run", "dur_ns": 2_000_000_000,
                           "children": [{"name": "check:la",
                                         "dur_ns": 500_000_000}]}],
                "metrics": {"counters": [{"name": "ops-ok", "labels": {},
                                          "value": 42}],
                            "gauges": [], "histograms": []},
            }, f)
    if witness:
        with open(os.path.join(d, "witness.json"), "w") as f:
            json.dump({"ops": 4, "source-ops": 100, "digest": "wd",
                       "anomaly-types": ["G1c"], "probes": 9}, f)
    if events is not None:
        with open(os.path.join(d, "events.jsonl"), "w") as f:
            for i in range(events):
                f.write(json.dumps({"t": 100.0 + i, "ev": "tick",
                                    "i": i}) + "\n")
            if torn_events:
                f.write('{"t": 999.0, "ev": "to')  # crash mid-append
    return d


def test_run_dir_ingest_digest_noop_and_missing_artifacts(tmp_path):
    d = _mk_run(tmp_path, "a-test", "t1", witness=True)
    _mk_run(tmp_path, "a-test", "t2", valid=False, telemetry=False)
    wh = wmod.open_or_create(str(tmp_path))
    stats = wh.ingest_store(str(tmp_path))
    assert stats["runs"] == 2
    # unchanged store: full no-op
    assert wh.ingest_store(str(tmp_path)) == \
        {"ledgers": 0, "records": 0, "runs": 0, "events": 0,
         "sessions": 0, "fleet-events": 0, "archived": 0}
    c = wh.counts()
    assert c["runs"] == 2 and c["witnesses"] == 1
    assert c["run_spans"] == 2   # run + check:la (telemetric run only)
    rel = os.path.relpath(d, str(tmp_path))
    spans = dict((n, (t, c)) for n, t, c in wh.run_spans(rel))
    assert spans["run"] == (2.0, 1) and spans["check:la"] == (0.5, 1)
    # touching an artifact re-ingests just that run
    time.sleep(0.01)
    with open(os.path.join(d, "results.json"), "w") as f:
        json.dump({"valid?": "unknown", "error": "x"}, f)
    assert wh.ingest_store(str(tmp_path))["runs"] == 1
    assert wh.rollups()["runs_by_verdict"] == {"false": 1, "unknown": 1}


def test_in_progress_run_recorded_as_running(tmp_path):
    """ISSUE 7 satellite: a run dir with no results.json yet (still
    executing, or crashed before analysis) lands as status='running'
    instead of an indistinguishable NULL-verdict row; when results
    appear the digest changes and the row flips to done."""
    d = os.path.join(str(tmp_path), "a-test", "t-live")
    os.makedirs(d)
    wh = wmod.open_or_create(str(tmp_path))
    assert wh.ingest_store(str(tmp_path))["runs"] == 1
    assert wh.rollups()["runs_by_verdict"] == {"running": 1}
    row = wh.query("SELECT status, valid FROM runs")[1][0]
    assert row == ("running", None)
    # unchanged: no-op; results appearing re-ingests to done
    assert wh.ingest_store(str(tmp_path))["runs"] == 0
    time.sleep(0.01)
    with open(os.path.join(d, "results.json"), "w") as f:
        json.dump({"valid?": True}, f)
    assert wh.ingest_store(str(tmp_path))["runs"] == 1
    assert wh.rollups()["runs_by_verdict"] == {"true": 1}
    assert wh.query("SELECT status FROM runs")[1][0] == ("done",)


def test_verifier_session_ingest_and_rollup(tmp_path):
    """ISSUE 7 satellite: verifier session.json snapshots land in the
    warehouse (one upserted row per session) and roll up by state on
    /metrics."""
    from jepsen_tpu.verifier import VerifierService
    from jepsen_tpu.workloads import synth

    svc = VerifierService(str(tmp_path))
    h = synth.la_history(n_txns=60, n_keys=3, seed=0)
    body = b"".join(json.dumps(op.to_dict()).encode() + b"\n"
                    for op in h)
    svc.ingest("wh-a", body, cursor=0)
    svc.verdict("wh-a")
    svc.ingest("wh-b", body, cursor=0)
    svc.verdict("wh-b")
    svc.seal("wh-b")
    svc.close()
    wh = wmod.open_or_create(str(tmp_path))
    stats = wh.ingest_store(str(tmp_path))
    assert stats["sessions"] == 2
    rows = {r["name"]: r for r in wh.verifier_sessions()}
    assert rows["wh-a"]["state"] == "open" and \
        rows["wh-a"]["valid"] is True
    assert rows["wh-b"]["state"] == "sealed" and \
        rows["wh-b"]["seal_equal"] == 1
    assert rows["wh-a"]["txns"] == rows["wh-b"]["txns"] > 0
    assert wh.rollups()["verifier_by_state"] == {"open": 1, "sealed": 1}
    # sessions are NOT runs: the run table stays empty
    assert wh.rollups()["runs_by_verdict"] == {}
    ex = prometheus.exposition(base=str(tmp_path),
                               registry=metrics.Registry())
    assert 'jepsen_warehouse_verifier_sessions{state="open"} 1' in ex
    assert 'jepsen_warehouse_verifier_sessions{state="sealed"} 1' in ex


def test_rebuild_from_torn_partial_store(tmp_path):
    """Satellite: a store with a truncated events.jsonl tail, a run
    missing telemetry.json, and a corrupt results.json still rebuilds
    into a consistent, re-ingestable warehouse."""
    _mk_run(tmp_path, "a-test", "t1", events=5, torn_events=True)
    _mk_run(tmp_path, "a-test", "t2", telemetry=False)
    d3 = _mk_run(tmp_path, "a-test", "t3", telemetry=False)
    with open(os.path.join(d3, "results.json"), "w") as f:
        f.write("{not json")
    _write_ledger(tmp_path, n=4)
    wh = wmod.open_or_create(str(tmp_path))
    stats = wh.rebuild(str(tmp_path))
    assert stats["runs"] == 3 and stats["records"] == 8
    assert stats["events"] == 5  # torn tail dropped, not ingested
    c1 = wh.counts()
    assert c1["runs"] == 3 and c1["events"] == 5
    # rebuild is idempotent: same state from scratch again
    assert wh.rebuild(str(tmp_path))["runs"] == 3
    assert wh.counts() == c1
    # ... and a plain re-ingest on top is a no-op
    assert wh.ingest_store(str(tmp_path)) == \
        {"ledgers": 1, "records": 0, "runs": 0, "events": 0,
         "sessions": 0, "fleet-events": 0, "archived": 0}


def test_v4_to_v5_migration_on_populated_store(tmp_path):
    """Satellite: opening a v4-era (PR 14) warehouse migrates it in
    place — rollups and timelines survive untouched, the new
    span_profile table and phase/counter columns stay empty until a
    re-ingest, and ``rebuild`` over span_profile is idempotent."""
    import sqlite3

    d = _mk_run(tmp_path, "a-test", "t1")
    tp = os.path.join(d, "telemetry.json")
    with open(tp) as f:
        doc = json.load(f)
    doc["spans"][0]["children"][0]["attrs"] = {"profile": {
        "elle.infer|i32[1024]": {"calls": 3, "compile_s": 0.21,
                                 "execute_s": 0.05,
                                 "device_dispatch_s": 0.012}}}
    doc["meta"] = {"host": "host-a"}
    with open(tp, "w") as f:
        json.dump(doc, f)
    path = _write_ledger(tmp_path, n=6)
    # graft phase buckets + forensic counters onto the ledger so
    # campaign_records exercises the v5 columns
    recs = [json.loads(ln) for ln in open(path)]
    for r in recs:
        r["phases"] = {"check:la": {"compile_s": 0.1,
                                    "execute_s": 0.2}}
        r["counters"] = {"compile-cache-miss{site=checker}": 2.0}
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rel = os.path.relpath(path, str(tmp_path))
    whp = wmod.warehouse_path(str(tmp_path))
    wh = wmod.Warehouse(whp)
    wh.ingest_store(str(tmp_path))
    PROF_SQL = ("SELECT dir, host, site, shape, calls, compile_s, "
                "execute_s, device_dispatch_s FROM span_profile "
                "ORDER BY dir, site, shape")
    prof0 = wh.query(PROF_SQL)[1]
    assert prof0 and prof0[0][1] == "host-a" and \
        prof0[0][2] == "elle.infer"
    roll0 = wh.query("SELECT * FROM span_rollup ORDER BY 1, 2")[1]
    gen0 = wh.query("SELECT * FROM span_gen_rollup ORDER BY 1, 2, 3")[1]
    nrec = wh.query("SELECT COUNT(*) FROM campaign_records")[1][0][0]
    assert roll0 and nrec == 12
    fr0 = wh.forensic_records(rel)
    assert fr0 and all(p and c for _, _, p, c in fr0)
    wh.close()

    # demote the file to v4: drop the ISSUE-16 surface wholesale.
    # DROP COLUMN needs sqlite >= 3.35, so the columns go via the
    # portable rename-copy-drop dance (which is also exactly what a
    # real PR-14-era file looks like: no phases/counters at all).
    V4_COLS = ("id, ledger, campaign, run, key, workload, fault, "
               "seed, valid, error, degraded, deadline, dir, ops, "
               "wall_s, gen, spec, ts, witness, trace")
    db = sqlite3.connect(whp)
    with db:
        db.execute("DROP TABLE span_profile")
        db.execute("ALTER TABLE campaign_records "
                   "RENAME TO campaign_records_v5")
        db.execute("""CREATE TABLE campaign_records(
            id INTEGER PRIMARY KEY, ledger TEXT NOT NULL,
            campaign TEXT, run TEXT, key TEXT, workload TEXT,
            fault TEXT, seed TEXT, valid TEXT, error TEXT,
            degraded TEXT, deadline INTEGER, dir TEXT, ops INTEGER,
            wall_s REAL, gen TEXT, spec TEXT, ts TEXT, witness TEXT,
            trace TEXT)""")
        db.execute(f"INSERT INTO campaign_records({V4_COLS}) "
                   f"SELECT {V4_COLS} FROM campaign_records_v5")
        db.execute("DROP TABLE campaign_records_v5")
        db.execute("CREATE INDEX IF NOT EXISTS cr_ledger_key ON "
                   "campaign_records(ledger, key, id)")
        db.execute("CREATE INDEX IF NOT EXISTS cr_ledger_run ON "
                   "campaign_records(ledger, run, id)")
        db.execute("INSERT OR REPLACE INTO meta(key, value) "
                   "VALUES ('schema_version', '4')")
    db.close()

    wh = wmod.Warehouse(whp)
    assert wh.query("SELECT value FROM meta WHERE key = "
                    "'schema_version'")[1][0][0] == str(
                        wmod.SCHEMA_VERSION)
    # rollups and timelines are untouched by the migration...
    assert wh.query("SELECT * FROM span_rollup "
                    "ORDER BY 1, 2")[1] == roll0
    assert wh.query("SELECT * FROM span_gen_rollup "
                    "ORDER BY 1, 2, 3")[1] == gen0
    assert wh.query("SELECT COUNT(*) FROM "
                    "campaign_records")[1][0][0] == nrec
    # ...but the new surface stays empty until a re-ingest; the
    # incremental path is a digest no-op, so rebuild is the
    # documented recovery route
    assert wh.query("SELECT COUNT(*) FROM span_profile")[1][0][0] == 0
    assert all(p == {} and c == {}
               for _, _, p, c in wh.forensic_records(rel))
    assert wh.ingest_store(str(tmp_path))["records"] == 0
    assert wh.query("SELECT COUNT(*) FROM span_profile")[1][0][0] == 0
    wh.rebuild(str(tmp_path))
    assert wh.query(PROF_SQL)[1] == prof0
    assert wh.forensic_records(rel) == fr0
    # rebuild twice: span_profile lands identical (idempotent)
    wh.rebuild(str(tmp_path))
    assert wh.query(PROF_SQL)[1] == prof0
    wh.close()


def test_event_ingest_rotation_resets_and_since_filter(tmp_path):
    from jepsen_tpu.telemetry.stream import EventStream

    d = os.path.join(str(tmp_path), "a-test", "t1")
    os.makedirs(d)
    with open(os.path.join(d, "results.json"), "w") as f:
        json.dump({"valid?": True}, f)
    p = os.path.join(d, "events.jsonl")
    s = EventStream(p, max_bytes=256, keep=9)
    for i in range(12):
        s.emit("tick", i=i)
    wh = wmod.open_or_create(str(tmp_path))
    n1 = wh.ingest_store(str(tmp_path))["events"]
    assert n1 > 12  # ticks + rotate/rotate-cont markers, all segments
    assert wh.events_fresh(d, str(tmp_path))
    # more events (and maybe another rotation): re-ingest catches up
    for i in range(12, 18):
        s.emit("tick", i=i)
    wh.ingest_store(str(tmp_path))
    evs = wh.events_since(d, str(tmp_path))
    ticks = [e["i"] for e in evs if e.get("ev") == "tick"]
    assert ticks == list(range(18))
    tick_evs = [e for e in evs if e.get("ev") == "tick"]
    cut = tick_evs[9]["t"]
    since = [e.get("i") for e in
             wh.events_since(d, str(tmp_path), since=cut)
             if e.get("ev") == "tick"]
    # compare against the same filter applied in python: two ticks
    # emitted within one timestamp-rounding quantum share a t, so the
    # cut may legitimately include a neighbor — the pin is that the
    # warehouse filter matches the scan semantics, not the clock
    assert since == [e["i"] for e in tick_evs if e["t"] >= cut]
    assert 9 in since and 17 in since and 0 not in since


def test_event_ingest_new_session_regrow_not_spliced(tmp_path):
    """A truncate-and-regrow NEW session that outgrows the old byte
    cursor must trigger a full re-ingest (the live file's first line
    is the session id), never an incremental append of new-session
    bytes after the old session's rows."""
    import time as _time

    from jepsen_tpu.telemetry.stream import EventStream

    d = os.path.join(str(tmp_path), "a-test", "t1")
    os.makedirs(d)
    with open(os.path.join(d, "results.json"), "w") as f:
        json.dump({"valid?": True}, f)
    p = os.path.join(d, "events.jsonl")
    s = EventStream(p)
    for i in range(3):
        s.emit("tick", i=i, session=1)
    wh = wmod.open_or_create(str(tmp_path))
    wh.ingest_store(str(tmp_path))
    assert wh.events_fresh(d, str(tmp_path))
    _time.sleep(0.01)  # a distinct first-event timestamp
    s2 = EventStream(p)  # new session: truncates the file
    for i in range(20):  # ...and regrows PAST the old cursor
        s2.emit("tick", i=i, session=2)
    assert not wh.events_fresh(d, str(tmp_path))
    wh.ingest_store(str(tmp_path))
    evs = [e for e in wh.events_since(d, str(tmp_path))
           if e.get("ev") == "tick"]
    assert {e.get("session") for e in evs} == {2}, \
        "old-session rows spliced in front of the new session"
    assert [e["i"] for e in evs] == list(range(20))


def test_cached_handle_detects_deleted_and_replaced_file(tmp_path):
    """A long-lived process (the web server) must not keep serving a
    warehouse that was rm'd or rebuilt on disk: the handle cache
    validates the path still names the inode it opened."""
    path = _write_ledger(tmp_path, n=3)
    wh = wmod.open_or_create(str(tmp_path))
    wh.ingest_ledger(path, str(tmp_path))
    assert wmod.open_if_exists(str(tmp_path)) is wh
    # deleted: open_if_exists returns None again (not the dead handle)
    os.remove(wmod.warehouse_path(str(tmp_path)))
    assert wmod.open_if_exists(str(tmp_path)) is None
    # rebuilt by "another process" (fresh inode): a NEW handle with
    # the new file's contents, not the unlinked one
    wh2 = wmod.Warehouse(wmod.warehouse_path(str(tmp_path)))
    wh2.ingest_ledger(path, str(tmp_path))
    wh2.close()
    wh3 = wmod.open_if_exists(str(tmp_path))
    assert wh3 is not None and wh3 is not wh
    assert wh3.counts()["campaign_records"] == 6


def test_bench_self_ingest_never_creates_a_store(tmp_path, monkeypatch):
    """bench.py's contract is one JSON line on stdout: the warehouse
    self-ingest only fires into an EXISTING store/ (or an explicit
    BENCH_WAREHOUSE) — it never grows a new filesystem footprint."""
    sys_path = os.path.dirname(os.path.dirname(
        os.path.abspath(wmod.__file__)))
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(os.path.dirname(sys_path), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    payload = {"metric": "m", "value": 1.0, "unit": "ops/s"}
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("BENCH_WAREHOUSE", raising=False)
    bench._ingest_warehouse(payload)
    assert not os.path.exists(tmp_path / "store"), "store/ created"
    # an existing store/ opts in
    os.makedirs(tmp_path / "store")
    bench._ingest_warehouse(payload)
    assert os.path.exists(tmp_path / "store" / "warehouse.sqlite")
    # explicit BENCH_WAREHOUSE always opts in
    monkeypatch.setenv("BENCH_WAREHOUSE", str(tmp_path / "w.sqlite"))
    bench._ingest_warehouse(payload)
    assert os.path.exists(tmp_path / "w.sqlite")


def test_bench_refuses_cpu_backend(tmp_path, monkeypatch, capsys):
    """bench.py measures on the chip only: run in-process under the
    test's CPU backend, its entry exits non-zero and prints an error
    line, never a CPU number."""
    import importlib.util
    import json as _json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(repo, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("BENCH_SIZES", "64")
    monkeypatch.delenv("BENCH_EMIT_CAMPAIGN_SPEC", raising=False)
    assert bench.main() != 0
    line = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 0 and "n_txns" not in line
    assert "no TPU" in line["error"]


def test_obs_sql_cte_write_refused_at_engine_level(tmp_path):
    """`WITH x AS (SELECT 1) DELETE ...` passes a keyword prefix check
    — the read-only guard must hold at the sqlite level."""
    import sqlite3

    wh = wmod.open_or_create(str(tmp_path))
    wh.ingest_bench({"metric": "m", "value": 1.0}, "BENCH_r09.json")
    with pytest.raises(sqlite3.OperationalError):
        wh.query("WITH x AS (SELECT 1) DELETE FROM bench")
    assert len(wh.bench_series()) == 1  # nothing was deleted
    cols, rows = wh.query("SELECT COUNT(*) FROM bench")  # reads fine
    assert rows == [(1,)]


# ----------------------------------------------------- bench series

def test_bench_ingest_series_and_bad_file(tmp_path):
    wh = wmod.open_or_create(str(tmp_path))
    for i, v in ((3, 133000.0), (4, 186000.0), (5, 277000.0)):
        wh.ingest_bench({"metric": "check-throughput", "value": v,
                         "unit": "ops/s", "n_txns": 1000000,
                         "backend": "cpu"}, f"BENCH_r0{i}.json")
    series = wh.bench_series()
    assert [r["source"] for r in series] == \
        ["BENCH_r03.json", "BENCH_r04.json", "BENCH_r05.json"]
    assert [r["value"] for r in series] == [133000.0, 186000.0, 277000.0]
    # re-ingest overwrites by source key (no duplicate rows)
    wh.ingest_bench({"metric": "check-throughput", "value": 140000.0},
                    "BENCH_r03.json")
    assert len(wh.bench_series()) == 3
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert wh.ingest_bench_file(str(bad)) is False
    # the committed BENCH_r0*.json are driver wrappers: the payload
    # rides under "parsed" and must be unwrapped, not ingested as 0s
    wrapped = tmp_path / "BENCH_r06.json"
    wrapped.write_text(json.dumps({
        "n": 6, "cmd": "python bench.py", "rc": 0, "tail": "...",
        "parsed": {"metric": "check-throughput", "value": 300000.0,
                   "unit": "ops/s", "backend": "cpu"}}))
    assert wh.ingest_bench_file(str(wrapped)) is True
    r06 = [r for r in wh.bench_series()
           if r["source"] == "BENCH_r06.json"][0]
    assert r06["value"] == 300000.0 and r06["unit"] == "ops/s"
    # a dict with no metric anywhere is refused, not ingested empty
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"rc": 0, "tail": "no json line"}))
    assert wh.ingest_bench_file(str(empty)) is False


# ------------------------------------------------------ the gate

def test_mann_whitney_u_detects_shift_and_ignores_ties():
    rng = random.Random(7)
    a = [rng.uniform(1.0, 1.2) for _ in range(20)]
    b = [x * 1.5 for x in a]
    assert gate.mann_whitney_u(a, b)["p"] < 0.001
    assert gate.mann_whitney_u(b, a)["p"] > 0.99  # one-sided: b larger
    assert gate.mann_whitney_u([1.0] * 10, [1.0] * 10)["p"] == 1.0
    assert gate.mann_whitney_u([], [1.0])["p"] == 1.0


def test_gate_samples_statuses():
    rng = random.Random(3)
    old = [rng.uniform(1.0, 1.2) for _ in range(15)]
    same = [rng.uniform(1.0, 1.2) for _ in range(15)]
    assert gate.gate_samples(old, same)["status"] == "pass"
    worse = [x * 1.5 for x in old]
    res = gate.gate_samples(old, worse)
    assert res["status"] == "regression" and res["rel_delta"] > 0.25
    # statistically detectable but operationally tiny: pass
    tiny = [x * 1.05 for x in old]
    assert gate.gate_samples(old, tiny)["status"] == "pass"
    # a huge delta on 2 runs: insufficient data, not a silent verdict
    assert gate.gate_samples([1.0, 1.0], [9.9, 9.9])["status"] == \
        "insufficient-data"


def test_cli_obs_gate_pass_and_regression_exit_codes(tmp_path, capsys):
    """Acceptance: gate passes an unchanged generation pair (rc 0) and
    fails an injected +50% p95 regression (rc 1); unknown span rc 2."""
    _write_ledger(tmp_path, gens=("g1", "g2", "g3"),
                  scale={"g3": 1.5}, n=20)
    argv = ["--store-dir", str(tmp_path)]
    disp = cli.single_test_cmd(lambda o: {})
    assert cli.run(disp, argv + ["obs", "ingest"]) == 0
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "soak",
                               "--span", "check:la",
                               "--from-gen", "g1", "--to-gen", "g2"])
    assert rc == 0
    assert "PASS" in capsys.readouterr().out
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "soak",
                               "--span", "check:la",
                               "--from-gen", "g2", "--to-gen", "g3"])
    assert rc == 1
    assert "REGRESSION" in capsys.readouterr().out
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "soak",
                               "--span", "no-such-span"])
    assert rc == 2
    # default generation pair = the two most recent
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "soak",
                               "--span", "check:la"])
    assert rc == 1
    # a half-specified pair resolving to self-comparison is refused
    # (would otherwise pass forever), not silently passed
    capsys.readouterr()
    rc = cli.run(disp, argv + ["obs", "gate", "--campaign", "soak",
                               "--span", "check:la",
                               "--from-gen", "g3"])
    assert rc == 2
    assert "from-gen == to-gen" in capsys.readouterr().out


def test_gate_works_without_warehouse_via_scan(tmp_path, capsys):
    _write_ledger(tmp_path, gens=("g1", "g2"), n=10)
    disp = cli.single_test_cmd(lambda o: {})
    rc = cli.run(disp, ["--store-dir", str(tmp_path), "obs", "gate",
                        "--campaign", "soak", "--span", "check:la"])
    assert rc == 0  # jsonl fallback: no warehouse was ever built


# ------------------------------------------------- obs CLI + sql

def test_cli_obs_ingest_rebuild_sql_bench(tmp_path, capsys):
    _write_ledger(tmp_path, n=5)
    _mk_run(tmp_path, "a-test", "t1")
    bench = tmp_path / "BENCH_r05.json"
    bench.write_text(json.dumps({"metric": "m", "value": 277000.0,
                                 "unit": "ops/s", "n_txns": 1000000,
                                 "backend": "cpu"}))
    argv = ["--store-dir", str(tmp_path)]
    disp = cli.single_test_cmd(lambda o: {})
    rc = cli.run(disp, argv + ["obs", "ingest", "--bench", str(bench)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "10 records" in out and "1 runs" in out and "1 bench" in out
    assert cli.run(disp, argv + ["obs", "rebuild"]) == 0
    capsys.readouterr()
    rc = cli.run(disp, argv + [
        "obs", "sql", "SELECT COUNT(*) FROM campaign_records"])
    assert rc == 0
    assert capsys.readouterr().out.splitlines()[1] == "10"
    # writes refused
    assert cli.run(disp, argv + ["obs", "sql",
                                 "DELETE FROM campaign_records"]) == 2
    capsys.readouterr()
    assert cli.run(disp, argv + ["obs", "bench"]) == 0
    assert "BENCH_r05.json" in capsys.readouterr().out
    # a --bench that matches/ingests nothing (typo'd glob) fails loudly
    # instead of leaving CI green with a silently stale bench series
    assert cli.run(disp, argv + ["obs", "ingest", "--bench",
                                 str(tmp_path / "BENCH_r0*.jsn")]) == 2


def test_cli_obs_query_surfaces_need_warehouse(tmp_path, capsys):
    disp = cli.single_test_cmd(lambda o: {})
    rc = cli.run(disp, ["--store-dir", str(tmp_path), "obs", "sql",
                        "SELECT 1"])
    assert rc == 2
    assert "no warehouse" in capsys.readouterr().err


# ------------------------------------------------ prometheus golden

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "prometheus-golden.txt")


class _GoldenFleet:
    """A deterministic stand-in for the coordinator's federated-
    metrics surface (ISSUE 14): two alive workers' pushed snapshots."""

    def federated_metrics(self):
        return {
            "w1": {"host": "h1", "age-s": 1.0, "version": "v1",
                   "rows": [
                {"name": "worker-cells-done", "kind": "counter",
                 "labels": {}, "value": 3},
                {"name": "jit-cache-entries", "kind": "gauge",
                 "labels": {}, "value": 7},
                {"name": "worker-rss-peak-bytes", "kind": "gauge",
                 "labels": {}, "value": 120_000_000},
                {"name": "compile-cache-hits", "kind": "counter",
                 "labels": {}, "value": 9},
            ]},
            "w2": {"host": "h2", "age-s": 2.0, "version": "v2",
                   "rows": [
                {"name": "worker-cells-done", "kind": "counter",
                 "labels": {}, "value": 5},
                {"name": "jit-cache-entries", "kind": "gauge",
                 "labels": {}, "value": 4},
                {"name": "worker-rss-peak-bytes", "kind": "gauge",
                 "labels": {}, "value": 95_000_000},
            ]},
        }


def _golden_exposition(base):
    """A deterministic exposition: fixed registry (including the ISSUE 7
    verifier instruments), the ISSUE 14 federated fleet series, one
    heartbeat at a pinned age, and a warehouse with one ledger + one
    running run + one verifier session + one bench row."""
    reg = metrics.Registry()
    reg.counter("ops-invoked", worker=0).inc(42)
    reg.counter("resilience-faults-injected", site="elle.infer").inc(3)
    reg.gauge("checker-ops-per-s", checker="list-append").set(277000.5)
    reg.gauge("unset-gauge")  # never set: skipped from the exposition
    h = reg.histogram("probe-s", (0.1, 1.0), path='a"b\\c\nd')
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    # verifier gauges (ISSUE 7 satellite): sessions active, ops
    # ingested, per-session verdict freshness, sweep duration buckets
    reg.gauge("verifier-sessions-active").set(2)
    reg.counter("verifier-ops-ingested").inc(1234)
    reg.gauge("verifier-verdict-freshness-s", session="s1").set(0.25)
    sw = reg.histogram("verifier-sweep-s", (0.001, 0.01, 0.1, 1.0, 10.0))
    for v in (0.005, 0.02, 0.02, 0.3):
        sw.observe(v)
    # session lifecycle + live checking + store federation (ISSUE 13):
    # journal bytes bounded by compaction, compaction count, degraded
    # live streams, artifact uploads by protocol state
    reg.gauge("verifier-journal-bytes").set(5120)
    reg.counter("verifier-compactions").inc(3)
    reg.counter("verifier-live-degraded").inc(1)
    for state, n in (("started", 2), ("chunk", 9), ("resumed", 1),
                     ("landed", 2), ("rejected", 1)):
        reg.counter("fleet-artifact-uploads", state=state).inc(n)
    # fleet gauges (ISSUE 9 satellite): the coordinator's control-plane
    # view — workers alive by heartbeat freshness, active leases, cells
    # by state, requeue/duplicate counters attributed per worker
    reg.gauge("fleet-workers-alive").set(3)
    reg.gauge("fleet-leases-active").set(2)
    for state, n in (("queued", 4), ("claimed", 2), ("done", 6)):
        reg.gauge("fleet-cells", state=state).set(n)
    reg.counter("fleet-requeues", worker="w1",
                reason="lease-expired").inc(2)
    reg.counter("fleet-duplicate-completions", worker="w1").inc(1)
    # coordinated chaos (ISSUE 11 satellite): currently-open
    # synchronized nemesis windows by fault family, and worker-affine
    # placement deferrals
    reg.gauge("fleet-nemesis-windows-active", campaign="soak",
              fault="skew").set(1)
    reg.gauge("fleet-nemesis-windows-active", campaign="soak",
              fault="partition").set(0)
    reg.counter("fleet-affinity-deferrals", worker="w1").inc(3)
    # fleet observability (ISSUE 14): staging retention + compile-cost
    # groundwork gauges on the coordinator/worker registries
    reg.gauge("fleet-artifact-staging-bytes").set(4096)
    reg.gauge("jit-cache-entries").set(11)
    reg.counter("compile-cache-miss", site="elle.infer").inc(2)
    # AOT compile cache (ISSUE 18): hit/miss/byte counters + the entry
    # gauge on the live registry (federated over the fleet heartbeat
    # like every registry series), fall-through by seam site, and
    # fleet entry-transfer states
    reg.counter("compile-cache-hits").inc(9)
    reg.counter("compile-cache-misses").inc(4)
    reg.counter("compile-cache-bytes").inc(3131146)
    reg.counter("compile-cache-fallthrough",
                site="elle.core-check").inc(1)
    reg.gauge("compile-cache-entries").set(3)
    reg.counter("compile-cache-transfers", state="pushed").inc(2)
    reg.counter("compile-cache-transfers", state="absorbed").inc(2)
    # memory watermarks (ISSUE 16): peak-RSS / per-device / jit-cache
    # high-watermark gauges published by the resource sampler
    reg.gauge("process-rss-peak-bytes").set(104857600)
    reg.gauge("device-memory-peak-bytes", device="cpu:0").set(8388608)
    reg.gauge("jit-cache-entries-peak").set(13)
    # autopilot (ISSUE 17): the scaler's two inputs (queue depth +
    # claim-latency p95) and the continuous loop's own state gauges
    reg.gauge("fleet-queue-depth").set(4)
    reg.gauge("fleet-claim-latency-p95-s").set(0.42)
    reg.gauge("fleet-quarantined-cells").set(1)
    reg.gauge("fleet-paroled-cells").set(1)
    reg.gauge("fleet-autopilot-generations").set(5)
    # queue family (ISSUE 19): anomalies the packed checkers attribute
    # and adversarial-client injections by shape
    reg.counter("queue-anomalies-found", anomaly="lost-write").inc(2)
    reg.counter("queue-anomalies-found", anomaly="duplicate").inc(3)
    reg.counter("queue-adversarial-injections",
                shape="torn-send").inc(2)
    reg.counter("queue-adversarial-injections",
                shape="zombie-resend").inc(1)
    cdir = os.path.join(str(base), "campaigns")
    os.makedirs(cdir, exist_ok=True)
    with open(os.path.join(cdir, "soak.live.json"), "w") as f:
        json.dump({"campaign": "soak", "updated": 990.0, "total": 12,
                   "done": 7, "workers": {"0": {"run": "x"}},
                   "finished": False}, f)
    path = _write_ledger(base, n=2, flip_every=1)
    # one in-progress run (no results.json yet) -> status=running row
    os.makedirs(os.path.join(str(base), "live-test", "t0"),
                exist_ok=True)
    # one verifier session snapshot -> warehouse verifier gauge
    vdir = os.path.join(str(base), "verifier", "s1")
    os.makedirs(vdir, exist_ok=True)
    with open(os.path.join(vdir, "session.json"), "w") as f:
        json.dump({"session": "s1", "state": "open", "txns": 10,
                   "ops": 40, "segments": 2, "updated": 995.0,
                   "verdict": {"valid?": True, "anomaly-types": []}}, f)
    wh = wmod.open_or_create(str(base))
    wh.ingest_store(str(base), events=False)
    wh.ingest_bench({"metric": "check-throughput", "value": 277000.0,
                     "unit": "ops/s", "n_txns": 1000000,
                     "backend": "cpu"}, "BENCH_r05.json")
    # the watchtower (ISSUE 20): one firing + one pending alert in the
    # durable journal -> literal ALERTS{...} series on the exposition
    # (deterministic: state comes from the injected evaluation `now`)
    from jepsen_tpu.telemetry import alerts as alerts_mod

    eng = alerts_mod.AlertEngine(str(base), rules=alerts_mod.load_rules([
        {"name": "claim-latency-blowout", "kind": "threshold",
         "severity": "page", "signal": "gauge:x", "op": ">",
         "value": 0.0, "for": 0.0},
        {"name": "journal-growth", "kind": "threshold",
         "severity": "warn", "signal": "gauge:x", "op": ">",
         "value": 0.0, "for": 3600.0}]), sinks=[])
    eng.evaluate(signals={"gauge:x": 1.0}, now=990.0)
    return prometheus.exposition(base=str(base), registry=reg,
                                 now=1000.0, fleet=_GoldenFleet())


def test_prometheus_exposition_matches_golden(tmp_path):
    """Satellite: the exposition format is pinned byte-for-byte —
    # HELP/# TYPE blocks, cumulative histogram _bucket/_sum/_count,
    label escaping — so the endpoint stays scrape-compatible.  If this
    fails because of an INTENTIONAL format change, regenerate with:
    python -m tests.test_warehouse"""
    got = _golden_exposition(tmp_path)
    with open(GOLDEN) as f:
        want = f.read()
    assert got == want


def test_exposition_names_and_escaping():
    assert prometheus.metric_name("checker-ops-per-s") == \
        "jepsen_checker_ops_per_s"
    assert prometheus.metric_name("9bad") == "jepsen__9bad"
    assert prometheus.escape_label_value('a"b\\c\nd') == \
        'a\\"b\\\\c\\nd'


def test_exposition_histogram_buckets_cumulative_and_ordered():
    reg = metrics.Registry()
    h = reg.histogram("lat-s", (0.1, 1.0))
    for v in (0.05, 0.06, 0.5, 5.0):
        h.observe(v)
    lines = prometheus.render_registry(reg)
    buckets = [ln for ln in lines if "_bucket" in ln]
    assert buckets == [
        'jepsen_lat_s_bucket{le="0.1"} 2',
        'jepsen_lat_s_bucket{le="1"} 3',
        'jepsen_lat_s_bucket{le="+Inf"} 4',
    ]
    assert "jepsen_lat_s_sum 5.61" in lines
    assert "jepsen_lat_s_count 4" in lines


# ------------------------------------------------- the gate smoke

def test_gate_bench_script_smoke():
    """scripts/gate_bench.py end-to-end (ISSUE 6 satellite): a real
    mini-campaign + synthesized unchanged/regressed generations, gated
    through the obs CLI on both backends — regression gating runs in
    tier-1."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "gate_bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script, "--runs", "4"],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "gate smoke OK" in proc.stdout
    assert "REGRESSION" in proc.stdout and "PASS" in proc.stdout


if __name__ == "__main__":  # regenerate the golden file
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix="prom-golden-")
    try:
        doc = _golden_exposition(tmp)
        with open(GOLDEN, "w") as f:
            f.write(doc)
        print(f"wrote {GOLDEN}:\n{doc}")
    finally:
        shutil.rmtree(tmp)
