"""Run one cell of the chip benchmark once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints one JSON object as the last line of standard output (see
`harness.run`), and the numbers compared with the reference, each beside
its limit, as the last lines of standard error.  Refuses to run without
a TPU, or with fewer chips than the cell asks for.

`--rehearse <n_txns>` runs the cell at that size on whatever JAX finds,
the CPU included (four virtual devices for a four-chip cell);
`--control` checks under the configuration's `control_models`, which
break a guarantee the configuration states, so the comparison must fail.
Neither is used by the benchmark's own runs.
"""

import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N_TXNS")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    # JAX's compile cache and the program's AOT store
    # ($JAX_COMPILATION_CACHE_DIR/aot) live at one fixed path inside the
    # checkout, so that only a cell's first run there compiles
    cache = os.path.join(ROOT, ".benchcache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    chips = int(cells[args.workload]["chips"])
    if chips == 1:
        # the program shards a large check over every chip it sees: a
        # one-chip cell keeps to the one-chip path on any host
        os.environ["JEPSEN_SHARDS"] = "1"
    if args.rehearse:
        if chips > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       f" --xla_force_host_platform_device_"
                                       f"count={chips}").strip()
            os.environ["JEPSEN_SHARDS"] = str(chips)
            os.environ["JEPSEN_SHARD_MIN_TXNS"] = "0"
    sys.path.insert(0, ROOT)
    from benchmark import harness

    return harness.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
