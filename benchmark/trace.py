"""From a JAX profiler trace to device busy time, kernel and collective
time, and idle gaps labelled by the host span open during them.

`load` reads an `.xplane.pb` into a plain dict (the form the unit test's
fixture has):

    {"devices": {"/device:TPU:0": [[op, start_ns, dur_ns], ...], ...},
     "host": [[span, start_ns, end_ns], ...]}

Device events are the per-op line of each device plane ("XLA Ops");
host events are the profiler annotations on the host plane, which the
program's telemetry spans become when its collector annotates.  Both
share the profiler's clock.  `Reduced` then answers every question the
per-layer readers ask, inside a window [t0, t1].
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

#: device op names that move data between chips
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
OP_LINES = ("XLA Ops",)
#: "%name = type opcode(operands), attributes": the op's own part
_HLO = re.compile(r"^(%?[\w.\-]+ = \S+ [\w\-]+)\(")


def op_name(event_name: str) -> str:
    """An XLA op event as the trace names it (its whole HLO text) cut to
    "%name = type opcode", without operands and attributes."""
    m = _HLO.match(event_name)
    return m.group(1) if m else event_name[:160]


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_names: Iterable[str]) -> dict:
    """Device op events and the host annotations named in `host_names`."""
    from jax.profiler import ProfileData

    wanted = set(host_names)
    out: dict = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    [e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns)]
                    for e in line.events if e.name in wanted]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


class Reduced:
    """A trace cut to a window [t0, t1] (ns on the trace's clock)."""

    def __init__(self, trace: dict, t0: float, t1: float):
        self.t0, self.t1 = float(t0), float(t1)
        self.ops: Dict[str, List[Tuple[str, float, float]]] = {}
        for dev, evs in sorted(trace["devices"].items()):
            clipped = []
            for name, s, d in evs:
                a, b = max(s, self.t0), min(s + d, self.t1)
                if b > a:
                    clipped.append((name, a, b))
            self.ops[dev] = clipped
        self.host = [(n, max(a, self.t0), min(b, self.t1))
                     for n, a, b in trace["host"] if b > self.t0 and a < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def devices(self) -> List[str]:
        return [d for d, evs in self.ops.items() if evs]

    def busy_s(self) -> Dict[str, float]:
        """Per device: seconds in which some op ran (interval union)."""
        return {d: sum(b - a for a, b in _union([(a, b) for _, a, b in evs]))
                / 1e9 for d, evs in self.ops.items() if evs}

    def op_s(self, match) -> Dict[str, float]:
        """Per device: summed seconds of the ops whose name `match` takes."""
        return {d: sum(b - a for n, a, b in evs if match(n)) / 1e9
                for d, evs in self.ops.items() if evs}

    def collective_s(self) -> Dict[str, float]:
        return self.op_s(lambda n: any(c in n for c in COLLECTIVES))

    def top_ops(self, n: int = 10) -> List[list]:
        """The ops that took most device time, summed over devices, in
        seconds per device."""
        tot: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, a, b in evs:
                tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
        k = max(len(self.devices), 1)
        return [[name, s / k] for name, s in
                sorted(tot.items(), key=lambda x: -x[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds of the device (the first one with ops), summed by
        the innermost host span open at each gap's middle ("host-other"
        where none is), the largest `n`."""
        devs = self.devices
        if not devs:
            return []
        busy = _union([(a, b) for _, a, b in self.ops[devs[0]]])
        gaps, at = [], self.t0
        for a, b in busy + [(self.t1, self.t1)]:
            if a > at:
                gaps.append((at, a))
            at = max(at, b)
        by: Dict[str, float] = {}
        for a, b in gaps:
            label = self.host_span_at((a + b) / 2) or "host-other"
            by[label] = by.get(label, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in sorted(by.items(), key=lambda x: -x[1])[:n]]

    def host_span_at(self, t: float) -> Optional[str]:
        """The innermost (latest-starting) host span open at time t."""
        best = None
        for name, a, b in self.host:
            if a <= t < b and (best is None or a >= best[1]):
                best = (name, a)
        return best[0] if best else None
