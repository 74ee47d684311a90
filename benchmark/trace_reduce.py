"""Reduce a `jax.profiler` trace to what the benchmark reports: device
busy time (the union of the intervals in which a program ran on each
device plane), the programs that took the most device time, and the
longest device-idle gaps, each named after the program span open on the
host at the time.

Device time is read from each device plane's "XLA Modules" line: one
event per execution of a compiled program. Its "XLA Ops" line holds an
event per operation, per iteration of every loop: millions in a window
of the bulk or serial engines, too many to read in a run's time limit.

The program's spans reach the trace as `TraceAnnotation`s on the host
plane (the span bridge of `simtpu/obs/profile.py`); the benchmark's own
`bench.window` annotation bounds the measured window and `bench.unit`
each answer or query inside it.

    python benchmark/trace_reduce.py <file.xplane.pb> [span names...]
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
UNIT = "bench.unit"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def program_name(event_name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`: the program, not its build."""
    return event_name.split("(", 1)[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals (ns) into disjoint sorted ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of [lo, hi) covered by disjoint sorted intervals."""
    total = 0
    for a, b in merged:
        if b <= lo:
            continue
        if a >= hi:
            break
        total += min(b, hi) - max(a, lo)
    return total


class Reduced:
    """A trace, read once: host annotations and per-device op intervals."""

    def __init__(self, pd):
        self.host: List[Tuple[str, int, int]] = []  # (name, start, end)
        self.devices: Dict[str, List[Tuple[str, int, int]]] = {}
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        self.host.append((e.name, int(e.start_ns), int(e.end_ns)))
            elif plane.name.startswith("/device:") and "CPU" not in plane.name:
                mods = [line for line in plane.lines if line.name == MODULES_LINE]
                if not mods:
                    continue
                self.devices[plane.name] = [
                    (program_name(e.name), int(e.start_ns), int(e.end_ns))
                    for e in mods[0].events if e.end_ns > e.start_ns
                ]
        self.busy = {d: union([(a, b) for _n, a, b in evs])
                     for d, evs in self.devices.items()}
        wins = [(a, b) for n, a, b in self.host if n == WINDOW]
        self.window: Optional[Interval] = (
            (min(a for a, _ in wins), max(b for _, b in wins)) if wins else None)
        self.units = sorted((a, b) for n, a, b in self.host if n == UNIT)

    def window_s(self) -> float:
        if self.window is None:
            return 0.0
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, intervals: Optional[Sequence[Interval]] = None) -> float:
        """Device-busy seconds inside `intervals` (default: the window),
        averaged over the devices that ran anything."""
        if intervals is None:
            intervals = [self.window] if self.window else []
        if not self.busy:
            return 0.0
        per = [sum(covered(m, a, b) for a, b in intervals)
               for m in self.busy.values()]
        return sum(per) / len(per) / 1e9

    def top_ops(self, k: int = 10) -> List[list]:
        """Programs by total device seconds in the window, averaged over
        devices."""
        lo, hi = self.window or (0, 1 << 62)
        tot: Dict[str, float] = {}
        for evs in self.devices.values():
            for name, a, b in evs:
                if b > lo and a < hi:
                    tot[name] = tot.get(name, 0.0) + (min(b, hi) - max(a, lo))
        n = max(len(self.devices), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, v / n / 1e9] for name, v in ranked]

    def idle_gaps(self, names=None, k: int = 10) -> List[list]:
        """The k longest idle gaps of the first device inside the window,
        each named after the shortest host span that covers its middle
        (the most specific one open at the time), among the host events
        named in `names` (the program's span names; default: all)."""
        if not self.busy or self.window is None:
            return []
        lo, hi = self.window
        merged = self.busy[sorted(self.busy)[0]]
        gaps, t = [], lo
        for a, b in merged:
            if b <= lo:
                continue
            if a >= hi:
                break
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) // 2
            open_spans = [(e - s, n) for n, s, e in self.host
                          if s <= mid < e and n not in (WINDOW, UNIT)
                          and (names is None or n in names)]
            name = min(open_spans)[1] if open_spans else "(no span)"
            out.append([name, (b - a) / 1e9])
        return out


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return Reduced(ProfileData.from_file(path))


def summary(r: Reduced, names=None) -> dict:
    return {
        "window_s": r.window_s(),
        "busy_s": r.busy_s(),
        "unit_busy_s": r.busy_s(r.units),
        "units": len(r.units),
        "devices": sorted(r.devices),
        "device_ops": r.top_ops(),
        "idle_gaps": r.idle_gaps(names),
    }


if __name__ == "__main__":
    # optional further arguments: the span names gaps may be named after
    print(json.dumps(summary(load(sys.argv[1]), set(sys.argv[2:]) or None), indent=1))
