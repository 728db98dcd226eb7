"""Benchmark: list-append check throughput (the north-star metric).

Generates strict-serializable packed list-append histories, runs the
fused device core check (edge inference + 5 projection cycle sweeps),
and reports verified ops/sec.  Baseline = the BASELINE.json target of a
10M-op history in 60 s on a v5e-8 (166,667 ops/s); vs_baseline > 1
beats it.

Progressive sizing: the bench climbs a size ladder (default 100k -> 1M
txns) and reports the LARGEST size; every rung must complete.

Measures on the chip only: a run that finds no TPU fails (rc 1) and
prints an error line, never a CPU number.  A hard deadline watchdog
(BENCH_DEADLINE, s, default 2700) fails the run the same way.  Any rung
that raises fails the run too; the payload then carries the error.

Env knobs: BENCH_TXNS (single fixed size, disables the ladder),
BENCH_SIZES (comma-separated ladder, default "100000,1000000"),
BENCH_KEYS, BENCH_REPEATS, BENCH_DEADLINE.  JAX's persistent compile
cache is on (``utils.backend.enable_compile_cache``).

Sharded rows (``--shards N`` argv or BENCH_SHARDS > 1, ISSUE 12
satellite): after the headline (sharded-default) measurement, time the
SAME padded history through the single-device path and the all-device
default, asserting identical verdict bits — the per-shard-count rows
land under ``"shards"`` in the payload.

Streaming mode (``--streaming`` argv or BENCH_STREAMING=1, ISSUE 7
satellite): additionally feeds each rung's history through the
incremental ``verifier.VerifierSession`` in BENCH_STREAM_SEG-txn
segments (default 100000) and reports incremental ops/s next to the
batch number under ``"streaming"`` in the payload — the
batch-vs-always-on throughput comparison, self-ingested into the
warehouse with the rest of the payload.

Warm-twice mode (``--warm-twice`` argv or BENCH_WARM_TWICE=1, ISSUE 18
satellite): after each rung completes, drop every in-memory executable
(``jax.clear_caches()`` + the AOT mem table) and run the rung again —
the second run must reload its executables from the persistent AOT
compile cache (``jepsen_tpu.compilecache``), so its
``compile_or_warmup_s`` collapses to ~dispatch time.  The comparison
lands under ``"warm_twice"`` in the payload (self-ingested with the
rest); a cold second run or any cache fall-through fails the bench
(rc 1).  The AOT store then lives under JAX's cache directory.

Exit status: 0 with a real value; 1 on any error or deadline (the JSON
line is still printed — consumers may read either the rc or the
"error" field).
"""

import json
import os
import sys
import threading
import time
import traceback

BASELINE_OPS_PER_SEC = 10_000_000 / 60.0  # BASELINE.json: 10M ops in 60 s


def _shards_arg() -> int:
    """--shards N argv (or BENCH_SHARDS): add the single-device vs
    all-device rows.  0 = unset."""
    if "--shards" in sys.argv:
        try:
            return int(sys.argv[sys.argv.index("--shards") + 1])
        except (ValueError, IndexError):
            return 0
    try:
        return int(os.environ.get("BENCH_SHARDS", 0))
    except ValueError:
        return 0


def _init_backend() -> str:
    """The platform JAX runs on; raises unless it is a TPU — a
    measuring run never falls back to the CPU."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(f"no TPU found (jax platform {platform!r}); "
                           "bench.py measures on the chip only")
    return platform


_BEST = [None]  # best completed rung payload; single-slot atomic rebind


def _arm_watchdog(deadline_s: float):
    """If the bench hasn't finished by the deadline (a hung backend
    init or a cold compile at the biggest rung), emit the error line
    and hard-exit 1: a partial ladder is not a result."""
    done = threading.Event()

    def fire():
        if not done.wait(deadline_s):
            best = _BEST[0]  # single read: rebind in main() is atomic
            _emit({"metric": "elle-list-append-check-throughput",
                   "value": 0, "unit": "ops/sec", "vs_baseline": 0,
                   "completed_n_txns": best and best["n_txns"],
                   "error": f"bench exceeded {deadline_s:.0f}s deadline"})
            os._exit(1)

    threading.Thread(target=fire, daemon=True).start()
    return done


_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit(payload):
    """Print the result line exactly once, even when the deadline
    watchdog and the main thread race at the boundary."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        print(json.dumps(payload))
        sys.stdout.flush()


def _span_durations_s(doc):
    """Flatten a telemetry snapshot's span forest into
    {name: [durations_s...]} (bench spans repeat per timed run)."""
    out = {}

    def walk(sp):
        d = sp.get("dur_ns")
        if d is not None:
            out.setdefault(sp["name"], []).append(d / 1e9)
        for c in sp.get("children") or []:
            walk(c)

    for r in doc.get("spans", []):
        walk(r)
    return out


def _memory_section():
    """Peak-memory snapshot for a completed rung (ISSUE 16): process
    RSS + the kernel's VmHWM high watermark, and per-device
    bytes-in-use / peak from ``memory_stats()`` — so a rung's footprint
    rides in the BENCH payload (and the warehouse) next to its ops/s.
    Never fails the bench."""
    try:
        from jepsen_tpu.telemetry.stream import (_device_memory_stats,
                                                 _hwm_bytes, _rss_bytes)

        out = {}
        rss = _rss_bytes()
        if rss:
            out["rss_bytes"] = rss
        hwm = _hwm_bytes()
        if hwm or rss:
            out["rss_peak_bytes"] = max(hwm or 0, rss or 0)
        devices = {}
        for dev, (used, pk) in _device_memory_stats().items():
            row = {"bytes_in_use": used}
            if pk is not None:
                row["peak_bytes_in_use"] = pk
            devices[dev] = row
        if devices:
            out["devices"] = devices
        return out or None
    except Exception:  # noqa: BLE001 — observability only
        return None


def _run_size(n_txns: int, repeats: int):
    """One ladder rung: returns the result payload (raises on failure)."""
    import jax

    from jepsen_tpu import telemetry
    from jepsen_tpu.checkers.elle.device_core import core_check_auto as check
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.utils import prestage

    # keys scale with size so per-key list lengths stay bounded (~12
    # appends/key) — matching how real list-append workloads bound
    # read-list growth (elle's gen rotates keys)
    n_keys = int(os.environ.get("BENCH_KEYS", max(64, n_txns // 8)))

    # telemetry rides along (ISSUE 1 satellite): checker span durations
    # + ops/s land in the BENCH_*.json payload so the perf trajectory
    # is machine-readable from PR 1 onward
    coll = telemetry.activate()
    try:
        # prestaged inputs (scripts/prestage_inputs.py) load in seconds; a
        # cold miss falls back to generation (~153 s at 10M)
        t_gen = time.perf_counter()
        with telemetry.span("bench.gen", n_txns=n_txns):
            p = prestage.la_history(n_txns=n_txns, n_keys=n_keys,
                                    verbose=False)
            h = pad_packed(p)
        t_gen = time.perf_counter() - t_gen

        # stage inputs on device BEFORE timing: first dispatch otherwise
        # pays a synchronous host->device transfer of every padded array
        # (measured ~30 s at 100k txns in round 2)
        t_stage = time.perf_counter()
        with telemetry.span("bench.stage"):
            h = jax.device_put(h)
            jax.block_until_ready(h)
        t_stage = time.perf_counter() - t_stage

        # warmup (compile — or persistent-cache hit on reruns)
        t_compile = time.perf_counter()
        with telemetry.span("bench.compile-or-warmup"):
            bits, over = check(h, p.n_keys)
            jax.block_until_ready(bits)
        t_compile = time.perf_counter() - t_compile
        assert int(bits[-1]) == 1, "sweep did not converge on bench history"
        assert int(bits[:12].sum()) == 0, "bench history must be valid"

        from jepsen_tpu.utils.profiling import trace

        best = float("inf")
        with trace(os.environ.get("BENCH_PROFILE_DIR")):
            for _ in range(repeats):
                t0 = time.perf_counter()
                with telemetry.span("bench.check", n_txns=n_txns):
                    bits, over = check(h, p.n_keys)
                    jax.block_until_ready(bits)
                best = min(best, time.perf_counter() - t0)

        ops_per_sec = n_txns / best
        telemetry.registry().gauge(
            "checker-ops-per-s", checker="device-core").set(
            round(ops_per_sec, 1))
        # --shards: quote single-device vs sharded-default on the SAME
        # padded history, verdict-asserted identical (ISSUE 12)
        shard_rows = (_run_shard_rows(h, p, repeats, check)
                      if _shards_arg() > 1 else None)
        streaming = (_run_streaming(p, n_txns)
                     if _streaming_enabled() else None)
        doc = telemetry.snapshot(coll)
    finally:
        telemetry.deactivate(coll)
    spans = _span_durations_s(doc)
    out = {
        "metric": "elle-list-append-check-throughput",
        "value": round(ops_per_sec, 1),
        "unit": "ops/sec",
        "vs_baseline": round(ops_per_sec / BASELINE_OPS_PER_SEC, 3),
        "n_txns": n_txns,
        "wall_s": round(best, 3),
        "gen_s": round(t_gen, 2),
        "stage_s": round(t_stage, 2),
        "compile_or_warmup_s": round(t_compile, 2),
        "telemetry": {
            "checker_span_s": {name: round(min(ds), 6)
                               for name, ds in sorted(spans.items())},
            "checker_span_runs": {name: len(ds)
                                  for name, ds in sorted(spans.items())},
            "check_ops_per_s": round(ops_per_sec, 1),
        },
    }
    memory = _memory_section()
    if memory is not None:
        out["memory"] = memory
    if shard_rows is not None:
        out["shards"] = shard_rows
    if streaming is not None:
        out["streaming"] = streaming
    return out


def _run_shard_rows(h, p, repeats: int, check):
    """Per-shard-count rows: the same padded history through the
    single-device path (JEPSEN_SHARDS=1) and the sharded default
    (all visible devices), bits asserted identical."""
    import jax
    import numpy as np

    n_dev = len(jax.devices())
    rows = {}
    ref = None
    for n in (1, n_dev):
        prev = os.environ.get("JEPSEN_SHARDS")
        os.environ["JEPSEN_SHARDS"] = str(n)
        try:
            bits, _ = check(h, p.n_keys)  # warm / compile
            jax.block_until_ready(bits)
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                bits, _ = check(h, p.n_keys)
                jax.block_until_ready(bits)
                best = min(best, time.perf_counter() - t0)
            b = np.asarray(bits)
            if ref is None:
                ref = b
            else:
                assert np.array_equal(b, ref), \
                    "sharded verdict bits != single-device bits"
            rows[str(n)] = {"value": round(p.n_txns / best, 1),
                            "unit": "ops/sec", "wall_s": round(best, 3)}
        finally:
            if prev is None:
                os.environ.pop("JEPSEN_SHARDS", None)
            else:
                os.environ["JEPSEN_SHARDS"] = prev
    return {"devices": n_dev, "rows": rows}


def _streaming_enabled():
    return "--streaming" in sys.argv or os.environ.get("BENCH_STREAMING")


def _warm_twice_enabled():
    return ("--warm-twice" in sys.argv
            or os.environ.get("BENCH_WARM_TWICE"))


def _ensure_aot_dir(jax_cache_dir: str):
    """--warm-twice needs a persistent AOT store to reload from; when
    the default resolution lands memory-only, pin one under JAX's
    cache directory."""
    from jepsen_tpu import compilecache

    if compilecache.cache_dir() is None:
        compilecache.set_cache_dir(os.path.join(jax_cache_dir, "aot"))
    return compilecache.cache_dir()


def _warm_twice_rerun(n_txns, repeats, first_payload):
    """ISSUE 18 satellite: run the rung AGAIN with every in-memory
    executable dropped (jit caches + the AOT mem table) but the
    persistent AOT store intact — the second run's
    compile_or_warmup_s then measures deserialize-and-load, not
    compile.  `ok` demands it collapse (≤ max(3 s, 30% of the first
    run), no cache fall-throughs, at least one AOT hit)."""
    import jax

    from jepsen_tpu import compilecache

    compilecache.clear()
    jax.clear_caches()
    compilecache.reset_stats()
    second = _run_size(n_txns, repeats)
    st = compilecache.stats()
    w1 = first_payload["compile_or_warmup_s"]
    w2 = second["compile_or_warmup_s"]
    ok = (w2 <= max(3.0, 0.3 * w1)
          and st.get("fallthroughs", 0) == 0
          and st.get("hits", 0) > 0)
    return {
        "first_compile_s": w1,
        "second_compile_s": w2,
        "second_value": second["value"],
        "ok": bool(ok),
        "cache": {k: st.get(k, 0)
                  for k in ("hits", "misses", "fallthroughs")},
    }


def _run_streaming(p, n_txns):
    """ISSUE 7 satellite: the same history through the incremental
    VerifierSession in segments — incremental ops/s next to batch
    ops/s.  The final rolling verdict must be valid (the generator
    emits strict-serializable histories)."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.verifier import VerifierSession, iter_packed_segments

    seg = int(os.environ.get("BENCH_STREAM_SEG", 100_000))
    ses = VerifierSession("bench", ("strict-serializable",))
    n_segs = 0
    t0 = time.perf_counter()
    with telemetry.span("bench.streaming", n_txns=n_txns, seg=seg):
        for cols, rd, base in iter_packed_segments(p, seg):
            ses.append_columns(cols, rd_elems=rd, rd_base=base)
            ses.verdict()  # rolling: sweep at every segment boundary
            n_segs += 1
        verdict = ses.verdict()
    wall = time.perf_counter() - t0
    return {
        "value": round(n_txns / wall, 1),
        "unit": "ops/sec",
        "wall_s": round(wall, 3),
        "segments": n_segs,
        "segment_txns": seg,
        "valid?": verdict.get("valid?"),
    }


def _ingest_warehouse(payload):
    """Best-effort: land the completed bench payload in the store's
    sqlite warehouse (ISSUE 6) so the throughput trajectory is a
    queryable series, not loose BENCH_*.json files.  Target:
    BENCH_WAREHOUSE (explicit opt-in), else <cwd>/store/
    warehouse.sqlite ONLY when a store/ dir already exists — the
    bench's documented contract is one JSON line on stdout, so it
    never grows a new filesystem footprint by itself.  Never fails
    the bench."""
    try:
        path = os.environ.get("BENCH_WAREHOUSE")
        if path is None:
            if not os.path.isdir("store"):
                return
            path = os.path.join("store", "warehouse.sqlite")
        if not path:
            return
        from jepsen_tpu.telemetry.warehouse import Warehouse

        tag = "bench@" + time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        Warehouse(path).ingest_bench(payload, source=tag)
    except Exception:  # noqa: BLE001 — the JSON line is the contract
        pass


def emit_campaign_spec(path, sizes=None, seeds=(0,)):
    """Write the bench ladder as a `jepsen_tpu.campaign` spec, so BENCH
    trajectories and soak runs drive the same fleet engine (`cli
    campaign run <spec>`): one labeled list-append workload entry per
    rung, op-count-bound (no wall-clock cap), telemetry on so the
    campaign index accumulates checker span durations across
    generations (`Index.span_trend`)."""
    if sizes is None:
        sizes = [int(s) for s in os.environ.get(
            "BENCH_SIZES", "100000,1000000").split(",") if s.strip()]
    spec = {
        "name": "bench-ladder",
        "workloads": [
            {"name": "append", "label": f"la-{n}",
             "opts": {"ops": n, "time-limit": None}}
            for n in sizes
        ],
        "faults": [None],
        "seeds": list(seeds),
        "opts": {"telemetry": True,
                 "checker-time-limit": float(
                     os.environ.get("BENCH_DEADLINE", 2700))},
    }
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return spec


def main():
    # emit-spec mode: no backend init, no watchdog — just the ladder as
    # campaign data (BENCH_EMIT_CAMPAIGN_SPEC=<path>)
    emit_path = os.environ.get("BENCH_EMIT_CAMPAIGN_SPEC")
    if emit_path:
        spec = emit_campaign_spec(emit_path)
        _emit({"campaign_spec": emit_path,
               "runs": len(spec["workloads"]) * len(spec["seeds"])})
        return 0

    # arm the watchdog before anything that can raise or hang — the
    # one-JSON-line contract must survive malformed env knobs too.
    # Default 2700 s leaves room for a cold compile of the 1M rung.
    try:
        deadline = float(os.environ.get("BENCH_DEADLINE", 2700))
    except ValueError:
        deadline = 2700.0
    done = _arm_watchdog(deadline)
    platform = "unknown"
    try:
        if os.environ.get("BENCH_TXNS"):
            sizes = [int(os.environ["BENCH_TXNS"])]
        else:
            sizes = [int(s) for s in os.environ.get(
                "BENCH_SIZES", "100000,1000000").split(",") if s.strip()]
        if not sizes:
            raise ValueError("BENCH_SIZES is empty")
        repeats = int(os.environ.get("BENCH_REPEATS", 3))

        platform = _init_backend()
        from jepsen_tpu.utils.backend import enable_compile_cache

        jax_cache_dir = enable_compile_cache()
        if _warm_twice_enabled():
            _ensure_aot_dir(jax_cache_dir)
    except Exception as e:
        done.set()
        _emit({"metric": "elle-list-append-check-throughput", "value": 0,
               "unit": "ops/sec", "vs_baseline": 0, "backend": platform,
               "error": f"bench setup failed: {type(e).__name__}: {e}",
               "trace": traceback.format_exc(limit=3)})
        return 1

    last_err = None
    last_err_tb = ""
    for n_txns in sizes:
        try:
            payload = _run_size(n_txns, repeats)
            payload["backend"] = platform
            if _warm_twice_enabled():
                payload["warm_twice"] = _warm_twice_rerun(
                    n_txns, repeats, payload)
            if _BEST[0] is None or payload["n_txns"] > _BEST[0]["n_txns"]:
                _BEST[0] = payload  # atomic rebind, watchdog-safe
        except Exception as e:
            last_err = f"{type(e).__name__}: {e}"
            last_err_tb = traceback.format_exc(limit=3)
            break

    done.set()
    if _BEST[0] is not None:
        payload = dict(_BEST[0])
        if last_err:
            payload["error"] = f"larger size failed: {last_err}"
        wt = payload.get("warm_twice")
        if wt is not None and not wt.get("ok"):
            payload["error"] = (
                "warm-twice: second run not warm "
                f"({wt['second_compile_s']}s vs {wt['first_compile_s']}s"
                f", cache {wt['cache']})")
        _ingest_warehouse(payload)
        _emit(payload)
        return 1 if "error" in payload else 0
    _emit({"metric": "elle-list-append-check-throughput", "value": 0,
           "unit": "ops/sec", "vs_baseline": 0, "backend": platform,
           "error": last_err or "no size completed",
           "trace": last_err_tb})
    return 1


if __name__ == "__main__":
    sys.exit(main())
