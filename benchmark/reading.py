"""What a per-layer metric reader is handed: the spans, counter deltas
and device trace of the measured window, and the interval of each unit
of work in it (an answer, or a query).

A reader is a file `benchmark/metrics/<metric name>.py` with one
function `read(r: Reading)` that returns a number, or None when the
window holds nothing it can read (the harness then leaves the metric out
of the result line).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from .trace_reduce import Reduced, covered, union


class Reading:
    def __init__(self, units: List[Tuple[int, int]], spans: List[tuple],
                 counters: Dict[str, object], trace: Optional[Reduced],
                 answered: int):
        self.units = units  # (start_us, end_us) on the span clock
        self.spans = spans  # (name, ts_us, dur_us, tid, depth, attrs)
        self.counters = counters  # registry delta over the window
        self.trace = trace
        self.answered = answered  # units that completed with an answer

    def per_unit_union(self, names: Sequence[str]) -> Optional[float]:
        """Seconds per unit in which any span of `names` was open (the
        union over threads, clipped to each unit)."""
        if not self.units:
            return None
        merged = union([(ts, ts + dur) for n, ts, dur, *_ in self.spans
                        if n in names])
        if not merged:
            return None
        total = sum(covered(merged, a, b) for a, b in self.units)
        return total / 1e6 / len(self.units)

    def per_unit_count(self, names: Sequence[str]) -> Optional[float]:
        """Spans of `names` that start inside a unit, per unit."""
        if not self.units:
            return None
        starts = sorted(ts for n, ts, *_ in self.spans if n in names)
        n = sum(1 for ts in starts for a, b in self.units if a <= ts < b)
        return n / len(self.units)

    def per_unit_counter(self, name: str) -> Optional[float]:
        if not self.units or name not in self.counters:
            return None
        return float(self.counters[name]) / len(self.units)

    def device_busy_per_unit(self) -> Optional[float]:
        if self.trace is None or not self.trace.units or not self.trace.busy:
            return None
        return self.trace.busy_s(self.trace.units) / len(self.trace.units)
