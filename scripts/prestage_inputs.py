"""Pre-generate every TPU-ladder bench input to disk, so a timed run
pays zero generation time.  Idempotent — existing files are kept.

Usage: JAX_PLATFORMS=cpu python scripts/prestage_inputs.py
(CPU platform: generation is pure numpy and leaves the chip free.)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JT_PRESTAGE_SAVE", "1")

from jepsen_tpu.utils import prestage  # noqa: E402

LADDER = [
    ("la", 100_000), ("la", 1_000_000), ("la", 10_000_000),
    ("rw", 1_000_000),
]


def main():
    for kind, n in LADDER:
        t0 = time.perf_counter()
        if kind == "la":
            p = prestage.la_history(n_txns=n, n_keys=max(64, n // 8),
                                    save=True)
        else:
            p = prestage.rw_history(n_txns=n, n_keys=max(64, n // 8),
                                    save=True)
        print(f"{kind}_{n}: n_txns={p.n_txns} n_mops={p.n_mops} "
              f"rd_elems={len(p.rd_elems)} in {time.perf_counter()-t0:.1f}s",
              flush=True)
    print("prestage dir:", prestage.prestage_dir())


if __name__ == "__main__":
    main()
