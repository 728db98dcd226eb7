"""One run of one cell: set-up, a closed loop of checks for `--seconds`,
then the comparison with the plain reference.

Everything cell-specific is found by name (see `__init__.py`); nothing
here names a configuration, a mix or a metric.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from types import ModuleType, SimpleNamespace
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: the spans the benchmark opens itself, around each check and the window
CHECK_SPAN, WINDOW_SPAN = "bench.check", "bench.window"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = BENCH) -> ModuleType:
    """`<bench>/<kind>/<name>.py`, imported from its path."""
    path = os.path.join(bench, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> SimpleNamespace:
    """The cell `workload` of `<root>/BENCHMARK.json`, with its
    configuration, traffic mix and the metrics it reports."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bdir = os.path.join(root, bench["paths"][0])

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    return SimpleNamespace(
        name=workload, chips=int(w["chips"]), bench_dir=bdir,
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(bdir, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)])


def peaks_for(kind: str, bench: str = BENCH) -> dict:
    table = load_json(os.path.join(bench, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# the run


def _seed(seed: int, i: int) -> list:
    """Entropy for history i of run `seed` (any whole number)."""
    return [int(seed) % (1 << 64), i]


def _devices(chips: int, rehearse: bool):
    """The cell's chips.  A one-chip cell runs with sharding off
    (`run.py` sets `JEPSEN_SHARDS=1`); a cell of more chips needs
    exactly that many, as the program shards over all it sees."""
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (JAX platform "
                         f"{devs[0].platform!r}); refusing to run")
    if len(devs) < chips or (chips > 1 and len(devs) != chips):
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:chips]


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class _CompileCount:
    """Backend compiles, from JAX's own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def _span_durations(roots) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    stack = list(roots)
    while stack:
        sp = stack.pop()
        if sp.t1 is not None:
            out.setdefault(sp.name, []).append((sp.t1 - sp.t0) / 1e9)
        stack += sp.children
    return out


def _check(entry, h, models):
    """One check through the entry: (answer, or None if it raised; 1 if
    it failed: it raised, or did not run on the device)."""
    try:
        res = entry.check(entry.prepare(h), models)
        return entry.answer(res), int(entry.fell_back(res))
    except Exception as e:  # noqa: BLE001 -- a check that raised
        log(f"a check raised {type(e).__name__}: {e}")
        return None, 1


def run(args, t_start: float, root: str = ROOT) -> int:
    cell = load_cell(args.workload, root)
    bench = cell.bench_dir
    cfg, traffic = cell.config, cell.traffic
    rehearse = bool(args.rehearse)
    n_txns = args.rehearse or int(cfg["n_txns"])
    devs = _devices(cell.chips, rehearse)
    kind = devs[0].device_kind
    peaks = peaks_for(kind, bench) if not rehearse else {}
    log(f"device: {kind} x{len(devs)}; cell {cell.name}: {n_txns} txns")

    gen = load_module("gen", traffic["generator"], bench)
    entry = load_module("entries", cfg["entry"], bench)
    ref = load_module("reference", cfg["reference"], bench)
    models = cfg["control_models"] if args.control else \
        cfg["consistency_models"]
    k = int(traffic["histories"])
    hists = [gen.generate(n_txns, cfg["shape"], traffic["timing"],
                          _seed(args.seed, i)) for i in range(k)]
    probe = gen.generate(n_txns, cfg["shape"], traffic["timing"],
                         _seed(args.seed, k), inject=traffic["probe"])
    log(f"set-up: {k} histories and one probe ({traffic['probe']}) made "
        f"at {time.perf_counter() - t_start:.3f} s")

    # warm-up: one check of the cell's shapes compiles (or loads) every
    # program the window runs
    entry.check(entry.prepare(hists[0]), models)
    compiles = _CompileCount()

    collector, trace_dir = None, None
    if args.trace:
        import jax

        from jepsen_tpu import telemetry

        collector = telemetry.Collector()
        collector.annotate = True
        telemetry.activate(collector)
        trace_dir = os.path.join(root, ".benchcache", "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    span = collector.span if collector else \
        (lambda name: contextlib.nullcontext())
    # traced runs stop after a few checks: the trace is for the layers
    n_max = int(traffic["trace_checks"]) if args.trace else 0

    answers: List[Optional[dict]] = []
    failed = 0  # checks that raised or did not run on the device
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    ends = []
    with span(WINDOW_SPAN):
        while True:
            with span(CHECK_SPAN):
                a, f = _check(entry, hists[len(answers) % k], models)
            answers.append(a)
            failed += f
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= args.seconds or len(answers) == n_max:
                break
    window_s = time.perf_counter() - t0
    if collector is not None:
        import jax

        from jepsen_tpu import telemetry

        jax.profiler.stop_trace()
        telemetry.deactivate(collector)
    window_compiles = compiles.n
    peak = _peak_bytes(devs)
    log(f"window: {len(answers)} checks in {window_s:.6f} s, "
        f"{window_compiles} compiles, peak {peak} bytes; check seconds "
        f"{[round(b - a, 4) for a, b in zip([0.0] + ends, ends)]}")

    # the probe: one more check through the same entry, after the window
    t_probe = time.perf_counter()
    probe_answer, f = _check(entry, probe, models)
    failed += f
    log(f"probe: checked in {time.perf_counter() - t_probe:.3f} s")

    # the comparison with the plain reference, under the configuration's
    # own models
    t_ref = time.perf_counter()
    model = cfg["consistency_models"][0]
    truth = [ref.check(h, model) for h in hists]
    probe_truth = ref.check(probe, model)
    pairs = [(a, truth[i % k]) for i, a in enumerate(answers)]
    pairs.append((probe_answer, probe_truth))
    missing = sum(a is None for a, _ in pairs)
    wrong = sum(a is not None and a != t for a, t in pairs)
    off_device = failed - missing
    log(f"reference: {k + 1} histories in "
        f"{time.perf_counter() - t_ref:.3f} s; probe: program "
        f"{probe_answer} reference {probe_truth}")
    compared = {"wrong_answers": {"value": wrong, "limit": 0},
                "missing_answers": {"value": missing, "limit": 0},
                "host_answers": {"value": off_device, "limit": 0}}

    if args.trace:
        metrics, device_extra, breakdown = _per_layer(
            cell, entry, collector, compiles=window_compiles, peaks=peaks,
            n_checks=len(answers), trace_dir=trace_dir)
    else:
        measured = {"check_s": window_s / len(answers),
                    "peak_hbm_bytes": peak, "setup_s": setup_s}
        metrics = {m["name"]: measured[m["name"]] for m in cell.end_to_end}
        device_extra, breakdown = {}, None
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    out = {"correct": wrong == 0 and missing == 0 and off_device == 0,
           "attempted": len(answers) + 1, "failed": failed,
           "metrics": {n: {"value": v, "unit": units[n]}
                       for n, v in metrics.items()},
           "device": {"platform": devs[0].platform, "kind": kind,
                      "count": len(devs),
                      "memory_peak_bytes": peak, **device_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    for name, c in compared.items():
        log(f"compare {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


def _per_layer(cell, entry, collector, compiles, peaks, n_checks,
               trace_dir):
    """The cell's per-layer metrics, and what the result line's device
    and breakdown take from the trace.

    Each metric's reader, `metrics/<name>.py`, has `read(ctx)`, which
    returns a number or None (nothing to read).  `ctx` holds `trace` (a
    `trace.Reduced` of the traced window), `spans` (span name -> the
    seconds of each span of that name), `checks` (checks in the window),
    `counters` (`window_compiles`) and `peaks` (the device's row of
    `peaks.json`)."""
    from benchmark import trace as tr

    spans = _span_durations(collector.roots)
    names = set(entry.SPANS) | {CHECK_SPAN, WINDOW_SPAN}
    raw = tr.load(tr.xplane_file(trace_dir), names)
    wins = [(a, b) for n, a, b in raw["host"] if n == WINDOW_SPAN]
    if not wins:
        raise RuntimeError("the trace holds no window span")
    red = tr.Reduced(raw, *wins[-1])
    ctx = SimpleNamespace(trace=red, spans=spans, checks=n_checks,
                          counters={"window_compiles": compiles},
                          peaks=peaks)
    metrics = {}
    for m in cell.per_layer:
        v = load_module("metrics", m["name"], cell.bench_dir).read(ctx)
        if v is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = v
    busy = red.busy_s()
    for d, b in busy.items():
        log(f"device {d}: busy {b:.6f} s of {red.window_s:.6f} s, idle "
            f"{100 * (1 - b / red.window_s):.4f}%")
    extra = {"busy_s": statistics.fmean(busy.values()) if busy else 0.0,
             "window_s": red.window_s}
    breakdown = {"device_ops": red.top_ops(10),
                 "idle_gaps": red.idle_gaps(10)}
    return metrics, extra, breakdown
