"""The device-call guard: retry transients, degrade to host, stay observable.

One entry point, :func:`device_call`, wraps every device pipeline seam
(elle infer, cycle sweeps, the knossos device WGL, the fused rw check):

1. polls the cooperative :class:`~.policy.Deadline` before each attempt;
2. consults the active :class:`~.faults.FaultPlan` (chaos mode / test
   harness) — the plan may raise a synthetic device fault here;
3. retries transient JAX/XLA failures per :class:`~.policy.RetryPolicy`
   with seeded backoff;
4. re-raises once the policy is exhausted (or the failure is
   non-transient) so the caller can degrade to its host oracle via
   :func:`with_fallback`, stamping ``"degraded": "host-fallback"``.

Every retry/fallback increments a telemetry counter and annotates the
innermost open span, so a degraded run is diagnosable straight from
``telemetry.json``.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from jepsen_tpu.resilience import faults as faults_mod
from jepsen_tpu.resilience.policy import (
    DEFAULT_POLICY,
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
)

logger = logging.getLogger("jepsen.resilience")

__all__ = ["device_call", "with_fallback", "degrade_to_host",
           "env_anomaly", "DEGRADED_HOST", "NO_PLAN",
           "compile_cache_stats", "reset_compile_cache_stats"]

DEGRADED_HOST = "host-fallback"

#: sentinel for "definitely no fault plan": a hot loop that resolved the
#: plan ONCE (and found none) passes this so device_call skips the
#: per-call plan_for/env lookup entirely — plan=None means "resolve"
NO_PLAN = object()


def _registry():
    from jepsen_tpu import telemetry

    return telemetry.registry()


def _stream_event(ev: str, **fields: Any) -> None:
    from jepsen_tpu import telemetry

    telemetry.stream_event(ev, **fields)


def env_anomaly(site: str, kind: str = "anomaly", **fields: Any) -> None:
    """Record an ENVIRONMENT anomaly — a backend-init hang survived by
    retrying, a degraded accelerator — as a
    structured resilience signal instead of a free-text field (ISSUE 6
    satellite: bench r05 buried a 544 s backend-init hang in a prose
    string).  Bumps the ``resilience-env-anomalies`` counter (visible
    on ``/metrics`` and in telemetry snapshots) and streams an
    ``env-anomaly`` event (visible to ``cli tail`` / ``/live`` and
    counted by ``replay()``).  Never raises."""
    try:
        _registry().counter("resilience-env-anomalies", site=site,
                            kind=kind).inc()
        _stream_event("env-anomaly", site=site, kind=kind, **fields)
    except Exception:  # noqa: BLE001 — observability must not fail work
        logger.debug("env_anomaly(%s) failed", site, exc_info=True)


def _annotate(**attrs: Any) -> None:
    from jepsen_tpu import telemetry

    sp = telemetry.current()
    if sp is not None:
        sp.set_attr(**attrs)


# ---------------------------------------------------------------------------
# Compile-cost observability (ISSUE 14 satellite — ROADMAP item 2
# groundwork).  jax.jit recompiles per argument-shape class; the first
# call of a (site, shape-vocabulary) pair therefore pays compile +
# execute while repeats pay execute only.  Tracking first sightings
# process-wide gives the AOT-cache PR its measured baseline: how much
# wall time is compile (`compile_s` span attrs, warehouse-queryable),
# how many distinct executables the process accumulated
# (`jit-cache-entries`), how often a new shape missed
# (`compile-cache-miss`).
# ---------------------------------------------------------------------------

_compile_lock = threading.Lock()
_seen_shapes: set = set()
_compile_misses = 0


def _shape_key(args: tuple, kw: dict) -> Tuple:
    """The call's shape-class key: (shape, dtype) of every array-like
    leaf one or two levels down — the same facts jax.jit keys its
    executable cache on (weak types and static args aside, close
    enough for attribution)."""
    parts = []

    def add(v: Any, depth: int) -> None:
        shape = getattr(v, "shape", None)
        if shape is not None:
            parts.append((str(tuple(shape)),
                          str(getattr(v, "dtype", ""))))
        elif depth < 2 and isinstance(v, (list, tuple)):
            for x in v[:8]:
                add(x, depth + 1)

    for a in args:
        add(a, 0)
    for v in kw.values():
        add(v, 0)
    return tuple(parts)


def compile_cache_stats() -> Dict[str, int]:
    """Process-wide jit shape-cache stats: distinct (site, shape)
    classes seen (= executables the process holds warm) and total
    first-sighting misses."""
    with _compile_lock:
        return {"entries": len(_seen_shapes),
                "misses": _compile_misses}


def reset_compile_cache_stats() -> None:
    """Tests only — the live set mirrors jax's own cache, which is not
    reset between runs either."""
    global _compile_misses
    with _compile_lock:
        _seen_shapes.clear()
        _compile_misses = 0


def _peek_shape(site: str, args: tuple, kw: dict) -> Optional[Tuple]:
    """This call's shape-class key — WITHOUT recording it.  The commit
    happens only after the attempt SUCCEEDS (:func:`_commit_shape`): a
    transient failure before compile completed must leave the shape
    unseen, so the retry that actually pays the compile is the one
    booked as ``compile_s``."""
    try:
        return (site,) + _shape_key(args, kw)
    except Exception:  # noqa: BLE001 — exotic args must not fail a call
        return None


def _commit_shape(key: Optional[Tuple]) -> bool:
    """Record a successfully-executed shape class; True if this commit
    was its first."""
    global _compile_misses
    if key is None:
        return False
    with _compile_lock:
        if key in _seen_shapes:
            return False
        _seen_shapes.add(key)
        _compile_misses += 1
        return True


def _shape_label(shape_key: Optional[Tuple]) -> str:
    """A compact human/SQL-stable label for a call's shape class —
    the ``shape`` column of the warehouse ``span_profile`` table.
    ``shape_key`` is ``(site, (shape, dtype), ...)``; scalars-only
    calls label as ``scalar``."""
    if not shape_key or len(shape_key) < 2:
        return "scalar"
    return "+".join(f"{s}:{d}" if d else s for s, d in shape_key[1:])


def _stamp_device_time(site: str, fn: Callable, args: tuple,
                       kw: dict) -> Any:
    """Run one device attempt, stamping its block-until-ready wall time
    onto the enclosing telemetry span as ``device_time_ns`` (summed
    across calls under that span) — the device-time attribution that
    puts host spans and XLA work on one timeline.  Only reached when
    telemetry is enabled; device failures surfacing at the sync point
    propagate to the caller's retry/fallback classifier."""
    from jepsen_tpu import telemetry

    shape_key = _peek_shape(site, args, kw)
    t0 = time.perf_counter_ns()
    out = fn(*args, **kw)
    # dispatch wall: tracing + executable lookup + async enqueue — what
    # the call cost BEFORE the sync point forced device completion
    disp = time.perf_counter_ns() - t0
    jx = sys.modules.get("jax")
    if jx is not None:
        try:  # force completion so the delta covers the device work
            jx.block_until_ready(out)
        except (TypeError, AttributeError):  # non-blockable results
            pass
        # anything else (XlaRuntimeError, RESOURCE_EXHAUSTED, ...) is a
        # REAL device failure surfacing at the sync point — let it reach
        # device_call's retry/fallback classifier instead of returning
        # the poisoned value as success
    dt = time.perf_counter_ns() - t0
    # commit only now: the attempt survived its sync point, so THIS is
    # the attempt that compiled (a transient failure above leaves the
    # shape unseen for the retry to claim)
    first = _commit_shape(shape_key)
    sp = telemetry.current()
    if sp is not None and sp.attrs is not None:
        try:
            sp.attrs["device_time_ns"] = \
                int(sp.attrs.get("device_time_ns", 0)) + dt
            # compile vs execute attribution (ISSUE 14 satellite): a
            # first-call-per-shape attempt's wall is compile-dominated
            # — stamped separately so "where did this cell's 40 s go"
            # can answer "32 s of it was XLA compiles"
            k = "compile_s" if first else "execute_s"
            sp.attrs[k] = float(sp.attrs.get(k, 0.0)) + dt / 1e9
            sp.attrs["device_dispatch_s"] = float(
                sp.attrs.get("device_dispatch_s", 0.0)) + disp / 1e9
            # per-(site, shape-class) profile (ISSUE 16 tentpole a):
            # accumulated on the span, exploded into the warehouse's
            # span_profile table at ingest — the `cli obs profile`
            # treemap's raw material
            prof = sp.attrs.get("profile")
            if not isinstance(prof, dict):
                prof = sp.attrs["profile"] = {}
            cell = prof.setdefault(
                f"{site}|{_shape_label(shape_key)}",
                {"calls": 0, "compile_s": 0.0, "execute_s": 0.0,
                 "device_dispatch_s": 0.0})
            cell["calls"] += 1
            cell[k] = float(cell.get(k, 0.0)) + dt / 1e9
            cell["device_dispatch_s"] = float(
                cell.get("device_dispatch_s", 0.0)) + disp / 1e9
        except Exception:  # noqa: BLE001 — noop-span attrs are shared
            pass
    reg = telemetry.registry()
    reg.counter("device-time-ns", site=site).inc(dt)
    if first:
        reg.counter("compile-cache-miss", site=site).inc()
    with _compile_lock:
        n = len(_seen_shapes)
    reg.gauge("jit-cache-entries").set(n)
    return out


def device_call(site: str, fn: Callable, *args: Any,
                policy: Optional[RetryPolicy] = None,
                deadline: Optional[Deadline] = None,
                plan: Optional[faults_mod.FaultPlan] = None,
                test: Optional[dict] = None,
                **kw: Any) -> Any:
    """Run a device entry point under the resilience policy.

    `site` names the seam for fault targeting and telemetry labels
    (e.g. ``"elle.infer"``).  `plan` defaults to the run's resolved
    plan (`faults.plan_for(test)` — explicit install > test map >
    JEPSEN_FAULTS); pass ``plan=...`` to pin one.  Raises the last
    error when retries are exhausted or the failure is non-transient;
    :class:`DeadlineExceeded` always propagates immediately.
    """
    policy = policy or DEFAULT_POLICY
    if plan is NO_PLAN:
        plan = None
    elif plan is None:
        plan = faults_mod.plan_for(test)
    delays = policy.delays()
    attempt = 0
    while True:
        if deadline is not None:
            deadline.check(site)
        attempt += 1
        try:
            if plan is not None:
                plan.fire(site)
            from jepsen_tpu import telemetry

            if telemetry.enabled():
                return _stamp_device_time(site, fn, args, kw)
            return fn(*args, **kw)
        except DeadlineExceeded:
            raise
        except Exception as e:  # noqa: BLE001 — classified below
            if not policy.classify(e):
                raise
            delay = next(delays, None)
            if delay is None:  # attempts exhausted: the original error
                _annotate(retries=attempt - 1, retry_exhausted=True)
                raise
            _registry().counter("resilience-retries", site=site,
                                kind=type(e).__name__).inc()
            _stream_event("retry", site=site, attempt=attempt,
                          kind=type(e).__name__)
            _annotate(retries=attempt)
            logger.warning("transient device failure at %s (attempt "
                           "%d/%d), retrying in %.3fs: %s", site, attempt,
                           policy.max_attempts, delay, e)
            if deadline is not None:
                delay = deadline.bound_sleep(delay)
            if delay > 0:
                time.sleep(delay)


def degrade_to_host(site: str, host_fn: Callable[[], Any],
                    exc: BaseException, *,
                    deadline: Optional[Deadline] = None) -> Any:
    """The shared degradation tail every device->host fallback goes
    through: count the fallback, annotate the open span, poll the
    deadline (an expired budget must NOT be converted into a possibly
    much slower host run — expiry raises :class:`DeadlineExceeded`),
    run the host oracle, and stamp dict results with
    ``"degraded": "host-fallback"`` plus the device error."""
    _registry().counter("resilience-fallbacks", site=site).inc()
    _stream_event("fallback", site=site, error=type(exc).__name__)
    _annotate(degraded=DEGRADED_HOST, device_error=type(exc).__name__)
    logger.warning("persistent device failure at %s; degrading to "
                   "host oracle: %s", site, exc)
    if deadline is not None:
        deadline.check(site)
    res = host_fn()
    if isinstance(res, dict):
        res["degraded"] = DEGRADED_HOST
        res["device-error"] = f"{type(exc).__name__}: {exc}"
    return res


def with_fallback(site: str, device_fn: Callable[[], Any],
                  host_fn: Callable[[], Any], *,
                  policy: Optional[RetryPolicy] = None,
                  deadline: Optional[Deadline] = None,
                  plan: Optional[faults_mod.FaultPlan] = None,
                  test: Optional[dict] = None
                  ) -> Tuple[Any, Optional[str]]:
    """Run `device_fn` under :func:`device_call`; on persistent device
    failure run `host_fn` via :func:`degrade_to_host`.  Returns
    ``(result, degraded)`` where `degraded` is None on the device path
    and :data:`DEGRADED_HOST` after the oracle fallback (dict results
    also carry the stamp).  Only :class:`DeadlineExceeded` escapes."""
    try:
        return device_call(site, device_fn, policy=policy,
                           deadline=deadline, plan=plan, test=test), None
    except DeadlineExceeded:
        raise
    except Exception as e:  # noqa: BLE001 — any persistent device failure
        return degrade_to_host(site, host_fn, e,
                               deadline=deadline), DEGRADED_HOST
