"""Prestaged bench inputs: pay generation cost once, outside the timed
run.

At 10M txns the synthetic generator alone costs ~153 s on the host.
`scripts/prestage_inputs.py` pre-generates every ladder input to disk;
bench.py and the ladder scripts then load the .npz in seconds instead
(and generate on a miss).

Filenames are keyed by every generator parameter, so a generator change
that alters kwargs can never silently reuse stale inputs.  (A change to
generator *internals* must bump `synth.PACKED_GEN_VERSION`.)
"""

from __future__ import annotations

import os
import time

from jepsen_tpu.history.soa import PackedTxns, load_packed, save_packed


def prestage_dir() -> str:
    d = os.environ.get("JT_PRESTAGE_DIR")
    if d:
        return d
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "scripts", "prestaged")


def _path(kind: str, **kw) -> str:
    from jepsen_tpu.workloads.synth import PACKED_GEN_VERSION

    name = f"{kind}_v{PACKED_GEN_VERSION}_" + "_".join(
        f"{k}{kw[k]}" for k in sorted(kw)) + ".npz"
    return os.path.join(prestage_dir(), name)


def _get(kind: str, gen, save: bool, verbose: bool, **kw) -> PackedTxns:
    path = _path(kind, **kw)
    if os.path.exists(path):
        t0 = time.perf_counter()
        p = load_packed(path)
        if verbose:
            print(f"prestaged load {os.path.basename(path)} "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
        return p
    p = gen(**kw)
    if save or os.environ.get("JT_PRESTAGE_SAVE"):
        os.makedirs(prestage_dir(), exist_ok=True)
        # pid-unique tmp: two processes may save the same input
        # concurrently (np.savez appends .npz)
        tmp = path[:-len(".npz")] + f".tmp{os.getpid()}.npz"
        save_packed(tmp, p)
        os.replace(tmp, path)
    return p


def la_history(n_txns: int, n_keys: int, concurrency: int = 10,
               mops_per_txn: int = 4, read_frac: float = 0.25,
               seed: int = 7, save: bool = False,
               verbose: bool = True) -> PackedTxns:
    """Bench list-append input: prestaged if on disk, else generated."""
    from jepsen_tpu.workloads import synth

    return _get("la", synth.packed_la_history, save, verbose,
                n_txns=n_txns, n_keys=n_keys, concurrency=concurrency,
                mops_per_txn=mops_per_txn, read_frac=read_frac, seed=seed)


def rw_history(n_txns: int, n_keys: int, concurrency: int = 10,
               mops_per_txn: int = 3, read_frac: float = 0.5,
               seed: int = 11, save: bool = False,
               verbose: bool = True) -> PackedTxns:
    """Bench rw-register input: prestaged if on disk, else generated."""
    from jepsen_tpu.workloads import synth

    return _get("rw", synth.packed_rw_history, save, verbose,
                n_txns=n_txns, n_keys=n_keys, concurrency=concurrency,
                mops_per_txn=mops_per_txn, read_frac=read_frac, seed=seed)
