"""Chip smoke: the Elle list-append device check, end to end, on a TPU.

    python chip_smoke.py                # one chip (what the driver runs)
    python chip_smoke.py --four-chips   # the sharded phases, four chips

One process, no children: a chip belongs to one process at a time.

Default phases, through the user's entry point
``jepsen_tpu.checkers.elle.list_append.check`` with the host-oracle
fallback off (``_force_no_fallback``):

1. device check — JAX must report a ``tpu`` platform; no CPU fallback;
2. a 2,000-txn op-level history, checked as generated and again after
   an injected wr-cycle (G1c): ``valid?`` and ``anomaly-types`` must
   equal the host oracle's (``checkers.elle.oracle.check``);
3. a 100k-txn packed history (BASELINE.json config 2), checked
   strict-serializable: ``valid? true``, no ``degraded`` or
   ``device-error`` stamp, and the programs it runs must hold the Pallas
   kernels (``tpu_custom_call``) — the fill in inference and the
   segmented scan in the cycle sweep.

``--four-chips`` runs only:

1. a 2^18-txn history checked sharded over 4 chips (``JEPSEN_SHARDS=4``)
   and on one chip (``JEPSEN_SHARDS=1``) through
   ``device_core.core_check_auto``: the verdict bits must be identical,
   and every chip must have held part of the sharded state;
2. ``parallel.batch.check_batch`` over a 4-device mesh: 8 valid
   histories plus one with a seeded wr-cycle, which must come back G1c.

The sharded history is 2^18 txns, not the 1M that ``bench.py`` names:
the one-chip reference program compiles cold in 349 s at 2^19 and did
not finish in 15 minutes at 2^20 (deviceless compiles for v5e, PR 21),
which four chips would pay four times over.

Lines before the last are information (seconds, device memory), not
metrics.  The last line is the JSON result; any failed phase exits
non-zero without it.  Data is made from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def device_check(n_chips: int):
    """The platform JAX runs on must be a TPU with at least `n_chips`
    chips; raises otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax platform "
                         f"{devs[0].platform!r}); refusing to run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: need {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    for knob in ("JT_PALLAS", "JT_PALLAS_EMULATE"):
        if knob in os.environ:
            raise SystemExit(f"chip_smoke: {knob} is set; the smoke checks "
                             "the default kernel choice")
    log(f"device: {devs[0].device_kind} x{len(devs)} "
        f"(jax {jax.__version__})")
    return devs


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _verdict(res: dict) -> tuple:
    return res["valid?"], sorted(res.get("anomaly-types", []))


def _assert_clean(res: dict, what: str) -> None:
    for stamp in ("degraded", "device-error"):
        assert stamp not in res, f"{what}: {stamp} stamp {res.get(stamp)!r}"


def phase_small(seed: int) -> None:
    """2,000-txn op-level histories: device verdicts equal the oracle's."""
    from jepsen_tpu.checkers.elle import list_append, oracle
    from jepsen_tpu.workloads import synth

    models = ["strict-serializable"]
    for label in ("valid", "G1c"):
        h = synth.la_history(n_txns=2000, n_keys=8, concurrency=5,
                             seed=seed)
        if label == "G1c":
            assert synth.inject_wr_cycle(h), "wr-cycle injection failed"
        dev, secs = _timed(lambda: list_append.check(
            h, models, _force_no_fallback=True))
        ref = oracle.check(h, models)
        _assert_clean(dev, f"small {label}")
        assert _verdict(dev) == _verdict(ref), \
            f"small {label}: device {_verdict(dev)} != oracle {_verdict(ref)}"
        if label == "valid":
            assert dev["valid?"] is True, dev
        else:
            assert dev["valid?"] is False and "G1c" in dev["anomaly-types"], \
                dev
        log(f"small {label}: device == oracle: valid?={dev['valid?']} "
            f"anomaly-types={sorted(dev['anomaly-types'])} "
            f"(device check {secs:.2f} s)")


def _kernel_names(text: str) -> set:
    return {name for name in ("_fill_kernel", "_scan_kernel")
            if name in text} if "tpu_custom_call" in text else set()


def phase_full(seed: int, dev0) -> None:
    """100k-txn strict-serializable check through the user entry point."""
    import jax

    from jepsen_tpu.checkers.elle import list_append
    from jepsen_tpu.checkers.elle.device_core import core_check
    from jepsen_tpu.checkers.elle.device_infer import infer
    from jepsen_tpu.history.ir import HistoryIR
    from jepsen_tpu.workloads import synth

    p, gen_s = _timed(lambda: synth.packed_la_history(
        n_txns=100_000, n_keys=12_500, seed=seed))
    ir = HistoryIR(p)
    h, pad_s = _timed(lambda: ir.padded("list-append"))
    _, stage_s = _timed(lambda: jax.block_until_ready(jax.device_put(h)))
    log(f"full: {p.n_txns} txns, {p.n_keys} keys; gen {gen_s:.2f} s, pad "
        f"{pad_s:.2f} s, stage {stage_s:.2f} s (T={h.txn_type.shape[0]} "
        f"M={h.mop_txn.shape[0]} R={h.rd_elems.shape[0]})")
    check = lambda: list_append.check(  # noqa: E731
        ir, ["strict-serializable"], _force_no_fallback=True)
    res, cold_s = _timed(check)
    _assert_clean(res, "full")
    assert res["valid?"] is True, f"full: {_verdict(res)}"
    res2, warm_s = _timed(check)
    assert _verdict(res2) == _verdict(res)
    log(f"full: valid?={res['valid?']} anomaly-types="
        f"{sorted(res['anomaly-types'])}; cold check {cold_s:.2f} s, "
        f"warm check {warm_s:.2f} s, compile ~{cold_s - warm_s:.2f} s "
        f"(cold - warm); peak device memory {peak_bytes(dev0)} B")
    # the kernel choice is made while tracing, from the backend: lower
    # the inference program that ran, and the fused core check (its
    # sweep is the one the per-projection sweeps share), and look for
    # the Mosaic custom calls
    inf = _kernel_names(infer.lower(h, n_keys=h.n_keys).as_text())
    core = _kernel_names(core_check.lower(h, n_keys=h.n_keys).as_text())
    assert "_fill_kernel" in inf, f"inference holds no fill kernel: {inf}"
    assert core == {"_fill_kernel", "_scan_kernel"}, \
        f"core check kernels: {core}"
    log(f"full: tpu_custom_call present: infer {sorted(inf)}, core check "
        f"{sorted(core)}")


#: txns of the sharded-vs-single history (see the module docstring)
SHARDED_TXNS = 1 << 18


def phase_sharded(seed: int, devs) -> None:
    """4-chip sharded verdict bits == one-chip bits."""
    import jax
    import numpy as np

    from jepsen_tpu.checkers.elle.device_core import core_check_auto
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.workloads import synth

    p, gen_s = _timed(lambda: synth.packed_la_history(
        n_txns=SHARDED_TXNS, n_keys=SHARDED_TXNS // 8, seed=seed))
    h, pad_s = _timed(lambda: pad_packed(p))
    log(f"sharded: {p.n_txns} txns, {p.n_keys} keys; gen {gen_s:.2f} s, pad "
        f"{pad_s:.2f} s (T={h.txn_type.shape[0]} M={h.mop_txn.shape[0]} "
        f"R={h.rd_elems.shape[0]})")
    bits = {}
    for shards in ("4", "1"):
        os.environ["JEPSEN_SHARDS"] = shards

        def run():
            b, over = core_check_auto(h, p.n_keys)
            return np.asarray(jax.block_until_ready(b)), int(over)

        (b, over), cold_s = _timed(run)
        (b2, _), warm_s = _timed(run)
        assert np.array_equal(b, b2)
        bits[shards] = (b, over)
        peaks = [peak_bytes(d) for d in devs[:4]]
        log(f"sharded: JEPSEN_SHARDS={shards}: bits {b.tolist()} "
            f"overflow {over}; cold {cold_s:.2f} s, warm {warm_s:.2f} s; "
            f"peak bytes per device {peaks}")
        if shards == "4":
            assert all(pk > 0 for pk in peaks), \
                f"sharded state not on every chip: {peaks}"
    os.environ.pop("JEPSEN_SHARDS")
    b4, o4 = bits["4"]
    b1, o1 = bits["1"]
    assert np.array_equal(b4, b1) and o4 == o1, \
        f"sharded bits {b4.tolist()} != single {b1.tolist()}"
    assert b1[-1] == 1 and b1[:-1].sum() == 0 and o1 == 0, \
        f"history not valid and exact: {b1.tolist()}"
    log("sharded: 4-chip verdict bits == 1-chip verdict bits")


def phase_batch(seed: int) -> None:
    """check_batch over a 4-device mesh catches the seeded G1c."""
    from jepsen_tpu.history.soa import pack_txns
    from jepsen_tpu.parallel.batch import check_batch, make_mesh
    from jepsen_tpu.workloads import synth

    ps = [synth.packed_la_history(n_txns=500, n_keys=64, seed=seed + s)
          for s in range(8)]
    bad = synth.la_history(n_txns=500, n_keys=8, concurrency=5,
                           seed=seed + 13)
    assert synth.inject_wr_cycle(bad), "wr-cycle injection failed"
    ps.append(pack_txns(bad, "list-append"))
    res, secs = _timed(lambda: check_batch(ps, mesh=make_mesh(4)))
    assert len(res) == 9
    assert all(r["valid?"] is True for r in res[:8]), res[:8]
    assert res[-1]["valid?"] is False and res[-1]["cycles"]["G1c"], res[-1]
    log(f"batch: 4-device mesh, 9 histories: 8 valid, seeded one caught "
        f"as G1c ({secs:.2f} s incl. compile)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-single check and the "
                         "batch, over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "jepsen_tpu")):
        raise SystemExit("chip_smoke: run from a checkout of the repo "
                         "(jepsen_tpu/ not found beside this script)")
    sys.path.insert(0, HERE)
    n_chips = 4 if args.four_chips else 1
    devs = device_check(n_chips)
    from jepsen_tpu.utils.backend import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        phase_sharded(args.seed, devs)
        phase_batch(args.seed)
    else:
        # the one-chip smoke checks on one chip, whatever the host holds
        os.environ["JEPSEN_SHARDS"] = "1"
        phase_small(args.seed)
        phase_full(args.seed, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
