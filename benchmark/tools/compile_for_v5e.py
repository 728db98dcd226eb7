"""Rehearsal without the chip: compile the programs a cell's checks run,
at the cell's real size (or `n_txns`), for a described v5e (`v5e:2x2`),
and print each program's `memory_analysis()` bytes per device.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_for_v5e.py <workload> \
        [<n_txns> [<max_k>]]

One chip: `elle.infer` with the Pallas fill (forced, as the chip's
backend chooses it) and one projection's cycle sweep at `max_k` (128 if
not given), with the Pallas scan where the chip would take it.  Four
chips: the same programs as the sharded default runs them: GSPMD `infer`
over the op axes (lax fills) and the sweep in `shard_map`.  Nothing
runs; this says what the chip's compiler accepts and how much memory
each program asks, not how fast it is.
"""

import dataclasses
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JT_PALLAS"] = "1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str, n_txns: int = 0, max_k: int = 128,
         seed: int = 1) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from jepsen_tpu.checkers.elle.device_infer import infer, pad_packed
    from jepsen_tpu.ops import cycle_sweep, pallas_scan

    jax.config.update("jax_enable_compilation_cache", False)
    # the TPU backend's own choice: the Pallas scan for int8 planes of
    # segments.LOOP_SCAN_MIN_ROWS rows or more, lax scans below
    pallas_scan.pallas_scan_enabled = \
        lambda v: v.ndim == 2 and v.dtype == jnp.int8

    cell = harness.load_cell(workload)
    cfg, tr = cell.config, cell.traffic
    gen = harness.load_module("gen", tr["generator"])
    entry = harness.load_module("entries", cfg["entry"])
    h = pad_packed(entry.prepare(gen.generate(
        n_txns or int(cfg["n_txns"]), cfg["shape"], tr["timing"], seed)))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell.chips == 1:
        place = SingleDeviceSharding(topo.devices[0])
        rep = lambda x: place  # noqa: E731
        mesh = None
    else:
        mesh = Mesh(np.array(topo.devices[:cell.chips]), ("batch",))
        shard, whole = NamedSharding(mesh, P("batch")), \
            NamedSharding(mesh, P())
        rep = lambda x: whole  # noqa: E731
        place = None
        h = dataclasses.replace(h, spmd=True)

    def sds(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def put(x):
        if place is not None:
            return sds(x, place)
        ok = x.ndim > 0 and x.shape[0] % cell.chips == 0
        return sds(x, shard if ok else whole)

    hs = jax.tree_util.tree_map(put, h)
    report = []

    def compile_(name, lowered):
        t = time.perf_counter()
        c = lowered.compile()
        m = c.memory_analysis()
        report.append(
            f"{name}: compiled in {time.perf_counter() - t:.1f} s; per "
            f"device: temp {m.temp_size_in_bytes}, arguments "
            f"{m.argument_size_in_bytes}, output {m.output_size_in_bytes}"
            f"; Pallas calls {c.as_text().count('custom_call_target=\"tpu_custom_call\"')}")
        print(report[-1], flush=True)
        return c

    compile_("elle.infer", infer.lower(hs, n_keys=h.n_keys))
    out = jax.eval_shape(lambda x: infer(x, n_keys=h.n_keys), h)
    T = h.txn_type.shape[0]
    kinds = ("ww", "wr", "rw", "tb", "bt")
    E = sum(out["edges"][k][0].shape[0] for k in kinds)
    C = sum(out["chains"][c][0].shape[0] for c in ("process", "barrier"))
    i32, b = jnp.int32, jnp.bool_
    args = [jax.ShapeDtypeStruct(s, d, sharding=rep(None)) for s, d in
            (((2 * T,), i32), ((E,), i32), ((E,), i32), ((E,), b),
             ((C,), i32), ((C,), b), ((C,), b))]
    if mesh is None:
        lowered = cycle_sweep._sweep_kw.lower(
            *args, n_nodes=2 * T, max_k=max_k, max_rounds=64)
    else:
        lowered = cycle_sweep._sweep_sharded_kw.lower(
            *args, n_nodes=2 * T, max_k=max_k, max_rounds=64, mesh=mesh,
            axis="batch")
    compile_(f"cycle-sweep (max_k {max_k})", lowered)
    print(f"sizes: T={T} M={h.mop_txn.shape[0]} R={h.rd_elems.shape[0]} "
          f"E={E} C={C} n_keys={h.n_keys}")


if __name__ == "__main__":
    main(sys.argv[1], *map(int, sys.argv[2:4]))
