"""The chip benchmark: `python benchmark/run.py --workload <cell> ...`.

Everything a cell needs is found by name from `BENCHMARK.json`: its
configuration in `configs/`, its traffic mix in `traffic/`, the
generator the mix names in `gen/`, the checker entry and the plain
reference the configuration names in `entries/` and `reference/`, and one
reader per per-layer metric in `metrics/`.  Later PRs add files; the
harness needs no edit to find them.
"""
