"""Resilience layer tests (ISSUE 2): deterministic fault injection,
retry/backoff, checker deadlines, and device -> host graceful
degradation.  The acceptance contract: under an injected persistent
device fault an elle list-append check degrades to the host oracle with
the fault-free verdict and a ``"degraded": "host-fallback"`` stamp;
under a short deadline a knossos WGL check returns unknown with
``error: deadline-exceeded`` instead of hanging."""

import time

import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.resilience import (
    DEGRADED_HOST,
    Deadline,
    DeadlineExceeded,
    FaultInjected,
    FaultPlan,
    RetryPolicy,
    deadline_result,
    device_call,
    is_transient,
    parse_spec,
    plan_for,
    use,
    with_fallback,
)
from jepsen_tpu.workloads import synth


class _XlaRuntimeError(RuntimeError):
    """Stand-in named like jaxlib's error (the classifier matches on
    type NAME, jaxlib's de-facto ABI)."""


_XlaRuntimeError.__name__ = "XlaRuntimeError"


# ---------------------------------------------------------------- classifier

def test_transient_classifier_xla_errors():
    assert is_transient(_XlaRuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert is_transient(_XlaRuntimeError("UNAVAILABLE: device lost"))
    assert is_transient(_XlaRuntimeError("INTERNAL: failed to compile"))
    # python-side bugs are never transient
    assert not is_transient(TypeError("bad shape"))
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED"))  # wrong type
    assert not is_transient(DeadlineExceeded("x"))


def test_synthetic_faults_carry_transience():
    assert is_transient(FaultInjected("oom", "s", 0, transient=True))
    assert not is_transient(FaultInjected("device-lost", "s", 0,
                                          transient=False))


# ---------------------------------------------------------------- FaultPlan

def _fire_seq(plan, n=40, site="site"):
    out = []
    for _ in range(n):
        try:
            plan.fire(site)
            out.append(None)
        except FaultInjected as e:
            out.append(e.kind)
    return out


def test_fault_plan_deterministic():
    # same seed -> same injected faults; different seed -> different
    a = _fire_seq(FaultPlan(seed=7, p=0.3, kinds=("oom", "xla")))
    b = _fire_seq(FaultPlan(seed=7, p=0.3, kinds=("oom", "xla")))
    assert a == b
    assert any(a), "p=0.3 over 40 calls should inject"
    seqs = {tuple(_fire_seq(FaultPlan(seed=s, p=0.3))) for s in range(8)}
    assert len(seqs) > 1, "seed must drive the schedule"


def test_fault_plan_explicit_indices_and_cap():
    plan = FaultPlan(at={1: "xla", 3: "oom"}, max_faults=1)
    seq = _fire_seq(plan, n=6)
    assert seq == [None, "xla", None, None, None, None]  # capped after 1
    assert plan.injected == [(1, "site", "xla")]


def test_fault_plan_site_filter_and_persistent():
    plan = FaultPlan(persistent=("elle.infer",))
    assert _fire_seq(plan, 3, site="other") == [None] * 3
    assert _fire_seq(plan, 2, site="elle.infer") == ["oom", "oom"]


def test_fault_plan_stall_sleeps_not_raises():
    plan = FaultPlan(at={0: "stall"}, stall_s=0.01)
    t0 = time.monotonic()
    plan.fire("s")  # must not raise
    assert time.monotonic() - t0 >= 0.009


def test_parse_spec_env_string():
    d = parse_spec("seed=7, p=0.1, kinds=oom|stall")
    plan = FaultPlan.from_spec(d)
    assert plan.seed == 7 and plan.p == 0.1
    assert plan.kinds == ("oom", "stall")
    assert parse_spec("") is None
    with pytest.raises(ValueError):
        parse_spec("whatisthis")


def test_plan_resolution_order(monkeypatch):
    monkeypatch.setenv("JEPSEN_FAULTS", "seed=3,p=0.5")
    env_plan = plan_for(None)
    assert env_plan is not None and env_plan.seed == 3
    explicit = FaultPlan(seed=9)
    with use(explicit):
        assert plan_for(None) is explicit
        # test-map spec still wins over the installed plan for that run
        t = {"faults": {"seed": 4}}
        assert plan_for(t).seed == 4
        assert plan_for(t) is t["faults-plan"]  # cached: one counter/run
    monkeypatch.delenv("JEPSEN_FAULTS")
    assert plan_for(None) is None


def test_nemesis_style_faults_set_is_not_a_resilience_spec():
    # nemesis/combined.py uses test["faults"] as a set of package names;
    # the resilience resolver must not misread it as an injection spec
    assert plan_for({"faults": {"partition", "kill"}}) is None


# ---------------------------------------------------------------- retry/guard

def test_retry_then_succeed_with_counters():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise _XlaRuntimeError("RESOURCE_EXHAUSTED: transient")
        return 42

    col = telemetry.activate()
    try:
        pol = RetryPolicy(max_attempts=3, base_delay_s=0.0)
        assert device_call("t.flaky", flaky, policy=pol) == 42
    finally:
        telemetry.deactivate(col)
    retries = [c for c in col.registry.snapshot()["counters"]
               if c["name"] == "resilience-retries"]
    assert retries and retries[0]["value"] == 2


def test_retry_exhaustion_reraises_original_error():
    def always():
        raise _XlaRuntimeError("RESOURCE_EXHAUSTED: persistent")

    with pytest.raises(_XlaRuntimeError):
        device_call("t.persistent", always,
                    policy=RetryPolicy(max_attempts=2, base_delay_s=0.0))


def test_non_transient_raises_immediately():
    calls = []

    def buggy():
        calls.append(1)
        raise TypeError("actual bug")

    with pytest.raises(TypeError):
        device_call("t.bug", buggy,
                    policy=RetryPolicy(max_attempts=5, base_delay_s=0.0))
    assert len(calls) == 1, "non-transient errors must not retry"


def test_retry_policy_delays_seeded():
    p = RetryPolicy(max_attempts=4, base_delay_s=0.1, seed=11)
    assert list(p.delays()) == list(p.delays())
    assert list(p.delays()) != list(
        RetryPolicy(max_attempts=4, base_delay_s=0.1, seed=12).delays())


def test_with_fallback_degrades_and_counts():
    col = telemetry.activate()
    try:
        res, degraded = with_fallback(
            "t.fb", lambda: (_ for _ in ()).throw(
                _XlaRuntimeError("RESOURCE_EXHAUSTED: dead")),
            lambda: "host-answer",
            policy=RetryPolicy(max_attempts=1))
    finally:
        telemetry.deactivate(col)
    assert (res, degraded) == ("host-answer", DEGRADED_HOST)
    names = [c["name"] for c in col.registry.snapshot()["counters"]]
    assert "resilience-fallbacks" in names


# ---------------------------------------------------------------- deadline

def test_deadline_basics():
    assert Deadline(None).remaining() is None
    assert not Deadline(None).expired()
    dl = Deadline(0.0)
    assert dl.expired() and dl.remaining() == 0.0
    with pytest.raises(DeadlineExceeded):
        dl.check("here")
    assert Deadline(60.0).bound_sleep(0.5) == 0.5
    assert Deadline(0.0).bound_sleep(0.5) == 0.0
    assert deadline_result(x=1) == {"valid?": "unknown",
                                    "error": "deadline-exceeded", "x": 1}


def test_deadline_resolution_order():
    shared = Deadline(5.0)
    assert Deadline.resolve({"deadline": shared}) is shared
    assert Deadline.resolve({"time-limit": 1.0}).remaining() <= 1.0
    assert Deadline.resolve({}, {"checker-time-limit": 2.0}) is not None
    assert Deadline.resolve({}, {}) is None
    assert Deadline.resolve(None, None) is None


# --------------------------------------------- elle: degrade + deadline

def test_elle_persistent_fault_degrades_to_host_same_verdict():
    from jepsen_tpu.checkers.elle import list_append

    h = synth.la_history(n_txns=60, seed=3)
    col = telemetry.activate()
    try:
        clean = list_append.check(h)
        faulted = list_append.check(
            h, plan=FaultPlan(persistent=("elle.infer",)),
            policy=RetryPolicy(max_attempts=2, base_delay_s=0.0))
    finally:
        telemetry.deactivate(col)
    assert faulted["valid?"] == clean["valid?"]
    assert faulted["degraded"] == DEGRADED_HOST
    assert "FaultInjected" in faulted["device-error"]
    counters = {c["name"] for c in col.registry.snapshot()["counters"]}
    assert {"resilience-faults-injected", "resilience-retries",
            "resilience-fallbacks"} <= counters


def test_elle_invalid_history_same_verdict_through_fallback():
    # degradation must preserve INVALID verdicts too, not just valid ones
    from jepsen_tpu.checkers.elle import list_append

    h = synth.la_history(n_txns=60, seed=5)
    assert synth.inject_wr_cycle(h), "injector must land for this seed"
    clean = list_append.check(h)
    faulted = list_append.check(
        h, plan=FaultPlan(persistent=("elle.infer",)),
        policy=RetryPolicy(max_attempts=1))
    assert clean["valid?"] is False
    assert faulted["valid?"] is False
    assert faulted["degraded"] == DEGRADED_HOST
    assert faulted["anomaly-types"] == clean["anomaly-types"]


def test_elle_transient_fault_recovers_on_device():
    from jepsen_tpu.checkers.elle import list_append

    h = synth.la_history(n_txns=60, seed=3)
    faulted = list_append.check(
        h, plan=FaultPlan(at={0: "oom"}),
        policy=RetryPolicy(max_attempts=3, base_delay_s=0.0))
    assert faulted["valid?"] is True
    assert "degraded" not in faulted  # retry succeeded, no fallback


def test_elle_deadline_returns_unknown():
    from jepsen_tpu.checkers.elle import list_append

    h = synth.la_history(n_txns=60, seed=3)
    res = list_append.check(h, deadline=Deadline(0.0))
    assert res["valid?"] == "unknown"
    assert res["error"] == "deadline-exceeded"


def test_expired_deadline_blocks_host_fallback():
    # an expired budget must not buy an unbounded host-oracle run: the
    # deadline trips during the retry backoff, so the result is the
    # canonical deadline unknown — NOT a degraded host verdict
    from jepsen_tpu.checkers.elle import list_append

    h = synth.la_history(n_txns=40, seed=3)
    res = list_append.check(
        h, plan=FaultPlan(persistent=("elle.infer",)),
        policy=RetryPolicy(max_attempts=2, base_delay_s=0.15, jitter=0.0),
        deadline=Deadline(0.05))
    assert res["valid?"] == "unknown"
    assert res["error"] == "deadline-exceeded"
    assert "degraded" not in res


def test_degrade_to_host_stamps_dict_results():
    from jepsen_tpu.resilience import degrade_to_host

    res = degrade_to_host("t.site", lambda: {"valid?": True},
                          _XlaRuntimeError("RESOURCE_EXHAUSTED: x"))
    assert res["degraded"] == DEGRADED_HOST
    assert "RESOURCE_EXHAUSTED" in res["device-error"]
    with pytest.raises(DeadlineExceeded):
        degrade_to_host("t.site", lambda: {"valid?": True},
                        _XlaRuntimeError("RESOURCE_EXHAUSTED: x"),
                        deadline=Deadline(0.0))


def test_rw_register_fault_degrades_to_host(monkeypatch):
    from jepsen_tpu.checkers.elle import rw_register
    from jepsen_tpu.workloads.synth import rw_history

    # shrink the fused-device threshold so the fast path engages
    monkeypatch.setattr(rw_register, "FUSED_MIN_TXNS", 1)
    h = rw_history(n_txns=50, seed=2)
    clean = rw_register.check(h)
    faulted = rw_register.check(
        h, plan=FaultPlan(persistent=("elle.rw-core-check",)),
        policy=RetryPolicy(max_attempts=1))
    assert faulted["valid?"] == clean["valid?"]
    assert faulted.get("degraded") == DEGRADED_HOST


# --------------------------------------------- knossos: deadline

def test_knossos_wgl_deadline_returns_unknown_fast():
    # the tier-1 hog: seed 5's info-dense history held the device
    # blocked search >90s; a 1s deadline must bound it with the
    # canonical verdict shape
    from jepsen_tpu.checkers.knossos import device_wgl
    from jepsen_tpu.checkers.knossos.prep import prepare
    from jepsen_tpu.checkers.knossos.search import Search
    from jepsen_tpu.models import cas_register

    h = synth.lin_register_history(n_ops=120, concurrency=5,
                                   stale_read_prob=0.25, info_prob=0.3,
                                   seed=5)
    ops = prepare(h)
    t0 = time.monotonic()
    res = device_wgl._blocked_and_check(
        list(ops), cas_register(), ctl=Search(deadline=Deadline(1.0)))
    dt = time.monotonic() - t0
    assert res["valid?"] == "unknown"
    assert res["error"] == "deadline-exceeded"
    assert res.get("explored", 0) >= 0  # partial stats ride along
    assert dt < 15, f"deadline did not bound the search ({dt:.1f}s)"


def test_knossos_analysis_deadline_plumbs_through():
    from jepsen_tpu.checkers.knossos import analysis
    from jepsen_tpu.models import cas_register

    h = synth.lin_register_history(n_ops=120, concurrency=5,
                                   stale_read_prob=0.25, info_prob=0.3,
                                   seed=5)
    res = analysis(h, cas_register(), algorithm="device",
                   deadline=Deadline(1.0))
    assert res["valid?"] == "unknown"
    assert res["error"] == "deadline-exceeded"


# --------------------------------------------- check_safe integration

def test_check_safe_creates_deadline_from_test_map():
    from jepsen_tpu.checkers import api as checker_api

    seen = {}

    class Slow(checker_api.Checker):
        def check(self, test, history, opts=None):
            seen["deadline"] = (opts or {}).get("deadline")
            seen["deadline"].check("slow-checker")
            return {"valid?": True}

    res = checker_api.check_safe(Slow(), {"checker-time-limit": 0.0},
                                 [], None)
    assert isinstance(seen["deadline"], Deadline)
    assert res == {"valid?": "unknown", "checker": "Slow",
                   "error": "deadline-exceeded"}


def test_check_safe_composed_checkers_share_one_deadline():
    from jepsen_tpu.checkers import api as checker_api

    seen = []

    class Probe(checker_api.Checker):
        def check(self, test, history, opts=None):
            seen.append((opts or {}).get("deadline"))
            return {"valid?": True}

    chk = checker_api.compose({"a": Probe(), "b": Probe()})
    res = checker_api.check_safe(chk, {"checker-time-limit": 30.0}, [],
                                 None)
    assert res["valid?"] is True
    assert len(seen) == 2 and seen[0] is seen[1] is not None


def test_check_safe_no_limit_no_deadline():
    from jepsen_tpu.checkers import api as checker_api

    seen = {}

    class Probe(checker_api.Checker):
        def check(self, test, history, opts=None):
            seen["opts"] = opts
            return {"valid?": True}

    checker_api.check_safe(Probe(), {}, [], None)
    assert not (seen["opts"] or {}).get("deadline")


def test_append_checker_deadline_via_checker_time_limit():
    # end-to-end: test map "checker-time-limit" -> check_safe ->
    # AppendChecker -> list_append deadline poll
    from jepsen_tpu.checkers import api as checker_api
    from jepsen_tpu.workloads.append import AppendChecker

    h = synth.la_history(n_txns=40, seed=1)
    res = checker_api.check_safe(AppendChecker(),
                                 {"checker-time-limit": 0.0}, h, None)
    assert res["valid?"] == "unknown"
    assert res["error"] == "deadline-exceeded"


# --------------------------------------------- nemesis satellites

def test_partitioner_works_without_net_key():
    # nemesis/core.py:164 used to KeyError on tests without "net"
    from jepsen_tpu.nemesis.core import Partitioner, partition_halves

    t = {"nodes": ["n1", "n2"]}
    nem = Partitioner(partition_halves).setup(t)
    comp = nem.invoke(t, {"f": "start-partition", "value": None})
    assert comp["type"] == "info"
    nem.invoke(t, {"f": "stop-partition", "value": None})
    nem.teardown(t)


def test_noop_test_has_net():
    from jepsen_tpu import core, net

    assert isinstance(core.noop_test()["net"], net.Net)


def test_traffic_shaper_drives_net_protocol():
    from jepsen_tpu import net as net_
    from jepsen_tpu.nemesis.core import TrafficShaper

    t = {"nodes": ["n1", "n2"], "net": net_.SimNet()}
    nem = TrafficShaper().setup(t)
    nem.invoke(t, {"f": "slow", "value": {"mean_ms": 100.0}})
    assert t["net"].shaping == ["slow", {"mean_ms": 100.0}]
    nem.invoke(t, {"f": "flaky", "value": None})
    assert t["net"].shaping[0] == "flaky"
    nem.invoke(t, {"f": "shape", "value": ["delay", "50ms"]})
    assert t["net"].shaping == ["delay", "50ms"]
    comp = nem.invoke(t, {"f": "fast", "value": None})
    assert comp["type"] == "info" and t["net"].shaping is None
    with pytest.raises(ValueError):
        nem.invoke(t, {"f": "nonsense"})
    nem.teardown(t)


def test_traffic_package_composes():
    from jepsen_tpu import net as net_
    from jepsen_tpu.nemesis import combined

    pkg = combined.nemesis_package({"faults": {"traffic"}, "interval": 0})
    assert pkg["generator"] is not None
    t = {"nodes": ["n1"], "net": net_.SimNet()}
    nem = pkg["nemesis"].setup(t)
    comp = nem.invoke(t, {"f": "slow", "value": {"mean_ms": 10.0}})
    assert comp["type"] == "info"
    assert t["net"].shaping is not None
    nem.invoke(t, {"f": "fast", "value": None})
    assert t["net"].shaping is None
    assert combined.traffic_package({"faults": {"partition"}}) is None


# ------------------------------------------------- interpreter fault site

def _interp_test(concurrency, plan, seed=0, ops=24):
    import random

    from jepsen_tpu import core as jcore
    from jepsen_tpu.generator import core as g
    from jepsen_tpu.workloads.mem import MemClient

    return jcore.noop_test(
        name="interp-faults", concurrency=concurrency,
        client=MemClient(),
        generator=g.clients(g.limit(ops, synth.la_generator(
            n_keys=3, rng=random.Random(seed)))),
        faults=plan)


def test_interpreter_fault_site_is_opt_in():
    """A checker-chaos plan that does not NAME the interpreter site
    must never touch the workload — even at p=1 (ISSUE 4 satellite:
    client-side chaos is requested by naming the site)."""
    from jepsen_tpu.generator import interpreter

    plan = FaultPlan(p=1.0, kinds=("oom",))
    assert not plan.targets_site(interpreter.FAULT_SITE)
    h = interpreter.run(_interp_test(2, plan))
    assert len(plan.injected) == 0
    assert all(op.type != "info" for op in h), \
        "opt-out plan crashed client ops"


def test_interpreter_stalls_and_infos_deterministic():
    """sites=("interpreter",): crash kinds complete ops as attributed
    :info (process re-opened), stalls just add latency; a single-worker
    run pair injects and completes identically (seeded determinism)."""
    from jepsen_tpu.generator import interpreter

    def run_once():
        plan = FaultPlan(seed=5, p=0.4, kinds=("oom", "stall"),
                         stall_s=0.001, sites=("interpreter",))
        h = interpreter.run(_interp_test(1, plan, seed=5))
        return plan, h

    p1, h1 = run_once()
    p2, h2 = run_once()
    assert p1.injected == p2.injected and p1.injected, p1.injected
    shape = lambda h: [(op.type, op.process, op.f, op.value, op.error)
                       for op in h]
    assert shape(h1) == shape(h2)
    infos = [op for op in h1 if op.type == "info"]
    assert infos, "no crash-kind faults landed (raise p or ops)"
    assert all(str(op.error).startswith("fault-injected") for op in infos)
    # crashed processes were re-opened on a fresh process id
    # (concurrency=1: process 0 crashes -> next incarnation is 1)
    assert any(isinstance(op.process, int) and op.process >= 1
               for op in h1)


def test_interpreter_fault_site_persistent_form():
    """persistent=("interpreter",) also targets the site: EVERY op
    info-completes, and the run still terminates with a history."""
    from jepsen_tpu.generator import interpreter

    plan = FaultPlan(persistent=("interpreter",), kinds=("oom",))
    assert plan.targets_site(interpreter.FAULT_SITE)
    h = interpreter.run(_interp_test(2, plan, ops=10))
    infos = [op for op in h if op.type == "info"]
    assert len(infos) == 10
    assert len(plan.injected) == 10
