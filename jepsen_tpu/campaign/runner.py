"""Subprocess executor entry: one isolated campaign run.

``python -m jepsen_tpu.campaign.runner`` reads ``{"runspec": {...},
"base": "store"}`` JSON on stdin, executes the run, and prints the
index record as its LAST stdout line (the scheduler parses bottom-up,
so workload logging above it is harmless).  A crashing run exits
non-zero with NO record line — the scheduler treats that as a failed
attempt, retries per its policy, and only then indexes the crash
record; a clean exit always carries a record.

Honors ``JT_FORCE_CPU`` before the first jax init (same contract as
the CLI's ``--cpu``).
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    payload = json.loads(sys.stdin.read() or "{}")
    if os.environ.get("JT_FORCE_CPU", "").strip().lower() in (
            "1", "true", "yes", "on"):
        from jepsen_tpu.utils.backend import force_cpu_backend

        force_cpu_backend()
    import logging

    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    from jepsen_tpu.campaign.core import execute_run
    from jepsen_tpu.campaign.plan import RunSpec

    rs = RunSpec.from_dict(payload["runspec"])
    rec = execute_run(rs, payload.get("base") or "store")
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
