"""Shape-bucketed AOT executable cache (ROADMAP item 1).

Three layers:

- :mod:`.bucket` — the shape-class policy: calls over pow2-padded
  arrays key into (site, dtype-signature, padded dims) classes, so
  shrink probes, campaign cells, verifier sweep chunks, and fleet
  workers share executables instead of compiling per exact shape;
- :mod:`.store` — the persistent entries (see Enablement):
  AOT-serialized executables keyed by a content fingerprint (program
  HLO digest x shape class x backend/platform string x jax version),
  self-verifying on read;
- this module — the guarded load-or-compile seam, :func:`call`:
  in-memory executable table hit -> dispatch the cached ``Compiled``
  directly; miss -> lower, try the disk entry
  (``compilecache.load`` fault seam), else compile + serialize
  (``compilecache.compile`` fault seam).  ANY failure anywhere —
  injected fault, corrupt entry, version/topology skew, serialization
  gap — falls through to the plain jit call, stamped
  ``compilecache_degraded`` on the open span: the cache can make a
  run faster, never wrong, and never wedge it.

Enablement: on by default.  ``JT_COMPILECACHE=0|off`` disables;
``JT_COMPILECACHE=mem`` keeps the in-process executable table but no
disk persistence; ``JT_COMPILECACHE=<path>`` pins the store
directory.  Unset, the store lives under JAX's own persistent cache
directory (``$JAX_COMPILATION_CACHE_DIR/aot``) when that variable is
set, else memory-only: the process never picks a disk location of its
own, so whoever places JAX's cache places this one too.

The in-memory table is LRU-bounded (``JT_COMPILECACHE_MEM``, default
64 executables) and :func:`clear`-able — tests clear it between
modules alongside ``jax.clear_caches()`` so held executables can't
defeat the suite's memory cap.

Metrics (live registry, federated over the fleet heartbeat):
``compile-cache-hits`` / ``compile-cache-misses`` /
``compile-cache-bytes`` counters + the ``compile-cache-entries``
gauge.  :func:`stats` mirrors them process-locally for tests.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

from jepsen_tpu.compilecache import bucket, store
from jepsen_tpu.resilience import faults as faults_mod

logger = logging.getLogger("jepsen.compilecache")

__all__ = ["call", "ensure", "enabled", "cache_dir", "set_cache_dir",
           "adopt_base", "clear", "stats", "reset_stats", "bucket",
           "store", "SITE_LOAD", "SITE_COMPILE", "SITE_WARM"]

#: the chaos seams (`scripts/fuzz_faults.py --compilecache`): strictly
#: opt-in — a plan must NAME them (sites= / persistent=) to fire here,
#: so a bare p= checker-chaos plan doesn't double-fire its counter
SITE_LOAD = "compilecache.load"
SITE_COMPILE = "compilecache.compile"
SITE_WARM = "compilecache.warm"

_UNSET = object()

_lock = threading.Lock()
_mem: "OrderedDict[Tuple, Any]" = OrderedDict()
_dir_override: Any = _UNSET
_stats = {"hits": 0, "misses": 0, "bytes": 0, "fallthroughs": 0}


def _registry():
    from jepsen_tpu import telemetry

    return telemetry.registry()


def _mem_cap() -> int:
    try:
        return max(1, int(os.environ.get("JT_COMPILECACHE_MEM", "64")))
    except ValueError:
        return 64


def enabled() -> bool:
    return os.environ.get("JT_COMPILECACHE", "").strip().lower() \
        not in ("0", "off", "no", "false")


def cache_dir() -> Optional[str]:
    """The persistent store directory, or None for memory-only mode."""
    if _dir_override is not _UNSET:
        return _dir_override
    env = os.environ.get("JT_COMPILECACHE", "").strip()
    low = env.lower()
    if low in ("0", "off", "no", "false", "mem"):
        return None
    if env and low not in ("1", "on", "true"):
        return env  # an explicit path
    return _jax_cache_subdir()


def _jax_cache_subdir() -> Optional[str]:
    jax_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return os.path.join(jax_dir, "aot") if jax_dir else None


def set_cache_dir(path: Optional[str]) -> None:
    """Pin (or, with None, disable) the persistent directory for this
    process — overrides env/default resolution.  Tests and the fleet
    worker use this."""
    global _dir_override
    _dir_override = path


def adopt_base(base: str) -> Optional[str]:
    """Point the persistent store at ``<base>/compilecache`` unless an
    explicit JT_COMPILECACHE setting, a prior override, or
    ``JAX_COMPILATION_CACHE_DIR`` already placed it — the fleet
    worker's store-base adoption."""
    env = os.environ.get("JT_COMPILECACHE", "").strip()
    if _dir_override is not _UNSET:
        return cache_dir()
    if (env and env.lower() not in ("1", "on", "true")) \
            or _jax_cache_subdir():
        return cache_dir()
    d = os.path.join(base, "compilecache")
    set_cache_dir(d)
    return d


def clear() -> None:
    """Drop the in-memory executable table and the fleet digest memo
    (disk entries persist).  Conftest calls this alongside
    ``jax.clear_caches()``; ``cli cache clear`` calls it after
    deleting entries so no stale digest outlives its file."""
    with _lock:
        _mem.clear()
    from jepsen_tpu.compilecache import fleet as cc_fleet

    cc_fleet.clear_digest_memo()


def stats() -> Dict[str, int]:
    with _lock:
        out = dict(_stats)
    out["mem_entries"] = len(_mem)
    d = cache_dir()
    out["entries"] = len(store.entries(d)) if d else out["mem_entries"]
    return out


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0


def _bump(key: str, n: int = 1) -> None:
    with _lock:
        _stats[key] += n


def _count(name: str, n: float = 1) -> None:
    try:
        _registry().counter(name).inc(n)
    except Exception:  # noqa: BLE001 — observability only
        pass


def _set_entries_gauge() -> None:
    try:
        d = cache_dir()
        n = len(store.entries(d)) if d else len(_mem)
        _registry().gauge("compile-cache-entries").set(n)
    except Exception:  # noqa: BLE001 — observability only
        pass


def _annotate(**attrs: Any) -> None:
    try:
        from jepsen_tpu import telemetry

        sp = telemetry.current()
        if sp is not None:
            sp.set_attr(**attrs)
    except Exception:  # noqa: BLE001 — observability only
        pass


def _fire(seam: str) -> None:
    """Fire the active fault plan at a compilecache seam — opt-in only
    (the plan must name the site), so cache plumbing never perturbs a
    checker-chaos plan's deterministic call counter."""
    plan = faults_mod.active_plan()
    if plan is not None and plan.targets_site(seam):
        plan.fire(seam)


def _fn_ident(jitfn: Callable) -> str:
    w = getattr(jitfn, "__wrapped__", jitfn)
    return f"{getattr(w, '__module__', '?')}." \
           f"{getattr(w, '__qualname__', repr(w))}"


def _platform() -> str:
    import jax

    try:
        ver = jax.devices()[0].client.platform_version
    except Exception:  # noqa: BLE001 — backend-specific attr
        ver = "?"
    return f"{jax.default_backend()}|{ver}|jax-{jax.__version__}"


def _devices_by_id(ids) -> list:
    """The executable's own devices, in its device-assignment order: a
    reload without them binds to every local device and then rejects
    a single-device call (jax >= 0.9)."""
    import jax

    by_id = {dv.id: dv for dv in jax.devices()}
    return [by_id[i] for i in ids]


def _fingerprint(lowered: Any, site: str, args: tuple,
                 static: dict) -> str:
    """The content fingerprint: program HLO digest x shape class x
    backend/platform string (of JAX's own cache-key components only
    platform/accelerator vary across backends, so these three factors
    are the sufficient key)."""
    hlo = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    cls = bucket.class_digest(site, args, static)
    plat = hashlib.sha256(_platform().encode()).hexdigest()[:16]
    return hashlib.sha256(
        f"{hlo}|{cls}|{plat}".encode()).hexdigest()[:40]


def _mem_key(site: str, jitfn: Callable, args: tuple,
             static: dict) -> Optional[Tuple]:
    import jax

    try:
        # the tree structure carries static pytree fields (e.g.
        # PaddedLA.spmd), which select a different program
        return (site, _fn_ident(jitfn), bucket.signature(args),
                jax.tree_util.tree_structure(args),
                bucket.static_signature(static))
    except Exception:  # noqa: BLE001 — exotic args must not fail a call
        return None


def _mem_get(key: Optional[Tuple]) -> Any:
    if key is None:
        return None
    with _lock:
        ent = _mem.get(key)
        if ent is not None:
            _mem.move_to_end(key)
        return ent


def _mem_put(key: Optional[Tuple], compiled: Any) -> None:
    if key is None:
        return
    cap = _mem_cap()
    with _lock:
        _mem[key] = compiled
        _mem.move_to_end(key)
        while len(_mem) > cap:
            _mem.popitem(last=False)


def _mem_drop(key: Optional[Tuple]) -> None:
    if key is None:
        return
    with _lock:
        _mem.pop(key, None)


def _obtain(site: str, jitfn: Callable, args: tuple, static: dict
            ) -> Tuple[Any, str, Optional[Tuple[str, str]]]:
    """Lower, then load-or-compile:
    ``(Compiled, "loaded"|"compiled", (cache_dir, fingerprint)|None)``.
    The third element locates the persistent entry so :func:`call` can
    delete it if a *loaded* executable then raises at dispatch (skew
    that only surfaces at execute time must self-heal like
    deserialize failures do).  Raises on any failure — callers map
    that to plain-jit fall-through (:func:`call`) or a skipped rung
    (:mod:`.warm`)."""
    from jax.experimental import serialize_executable as _se

    _fire(SITE_LOAD)
    lowered = jitfn.lower(*args, **static)
    d = cache_dir()
    fp = _fingerprint(lowered, site, args, static) if d else None
    if d and fp:
        got = store.get(d, fp)
        if got is not None:
            doc, size = got
            try:
                devs = _devices_by_id(doc["meta"]["devices"])
                compiled = _se.deserialize_and_load(
                    *doc["payload"], execution_devices=devs)
                _bump("bytes", size)
                _count("compile-cache-bytes", size)
                return compiled, "loaded", (d, fp)
            except Exception:  # noqa: BLE001 — skew/corruption: the
                # entry deserialized but won't load here (topology or
                # jaxlib drift inside one fingerprint epoch) — drop it
                # so the recompile below re-serializes a good one
                logger.warning("compilecache: entry %s failed to "
                               "load; recompiling", fp, exc_info=True)
                store.delete(d, fp)
    _fire(SITE_COMPILE)
    compiled = lowered.compile()
    if d and fp:
        try:
            payload = _se.serialize(compiled)
            n = store.put(d, fp, {
                "site": site,
                "class": bucket.class_label(site, args, static),
                "platform": _platform(),
                "devices": [dv.id for dv in
                            compiled.runtime_executable().local_devices()],
            }, payload)
            _bump("bytes", n)
            _count("compile-cache-bytes", n)
        except Exception:  # noqa: BLE001 — an unserializable program
            # still runs from the in-memory table; persistence is an
            # optimization, not a contract
            logger.warning("compilecache: serialize of %s failed",
                           site, exc_info=True)
    return compiled, "compiled", (d, fp) if d and fp else None


def call(site: str, jitfn: Callable, *args: Any, **static: Any) -> Any:
    """Dispatch one bucketed device call through the cache.

    `jitfn` is a ``jax.jit``-wrapped callable; `args` are the dynamic
    (array) arguments, `static` the static keyword arguments.  Fast
    path: the in-memory table already holds this class's ``Compiled``
    — dispatch it directly (statics are baked in at lowering).  Miss:
    :func:`_obtain` loads the disk entry or compiles + persists one.
    Any failure falls through to ``jitfn(*args, **static)`` — the
    exact call every caller made before this seam existed."""
    if not enabled() or not hasattr(jitfn, "lower"):
        return jitfn(*args, **static)
    mk = _mem_key(site, jitfn, args, static)
    compiled = _mem_get(mk)
    if compiled is not None:
        try:
            out = compiled(*args)
        except Exception:  # noqa: BLE001 — a stale executable (device
            # set changed under us) must not fail the call
            _mem_drop(mk)
            return _fallthrough(site, jitfn, args, static)
        _bump("hits")
        _count("compile-cache-hits")
        return out
    try:
        compiled, how, loc = _obtain(site, jitfn, args, static)
    except Exception:  # noqa: BLE001 — injected fault, corrupt entry,
        # serialization gap: plain jit is always correct
        return _fallthrough(site, jitfn, args, static)
    try:
        out = compiled(*args)
    except Exception:  # noqa: BLE001 — plain jit is always correct
        if how == "loaded" and loc:
            # the entry deserialized but its executable raises at
            # dispatch ("Symbols not found"-style skew can surface
            # here too): delete it, mirroring the deserialize-failure
            # path, so the next call recompiles and re-serializes a
            # good one instead of paying deserialize + fall-through
            # forever
            logger.warning("compilecache: loaded entry %s raised at "
                           "dispatch; dropped", loc[1], exc_info=True)
            store.delete(*loc)
        return _fallthrough(site, jitfn, args, static)
    _mem_put(mk, compiled)
    if how == "loaded":
        _bump("hits")
        _count("compile-cache-hits")
    else:
        _bump("misses")
        _count("compile-cache-misses")
    _set_entries_gauge()
    return out


def _fallthrough(site: str, jitfn: Callable, args: tuple,
                 static: dict) -> Any:
    """The degradation tail: count + stamp, then run the plain jit —
    bitwise the same program, just without amortization."""
    _bump("fallthroughs")
    try:
        _registry().counter("compile-cache-fallthrough",
                            site=site).inc()
    except Exception:  # noqa: BLE001 — observability only
        pass
    _annotate(compilecache_degraded=site)
    logger.debug("compilecache: %s fell through to plain jit", site,
                 exc_info=True)
    return jitfn(*args, **static)


def ensure(site: str, jitfn: Callable, *args: Any,
           **static: Any) -> str:
    """Warm one class WITHOUT executing: `args` may be abstract
    (``ShapeDtypeStruct``) — lowering works on either, and the
    in-memory key signs identically, so a later concrete call is a
    straight table hit.  Returns "cached" | "loaded" | "compiled";
    raises on failure (the warmer skips the rung)."""
    if not enabled() or not hasattr(jitfn, "lower"):
        return "disabled"
    mk = _mem_key(site, jitfn, args, static)
    if _mem_get(mk) is not None:
        return "cached"
    compiled, how, _loc = _obtain(site, jitfn, args, static)
    _mem_put(mk, compiled)
    if how == "loaded":
        _bump("hits")
        _count("compile-cache-hits")
    else:
        _bump("misses")
        _count("compile-cache-misses")
    _set_entries_gauge()
    return how
