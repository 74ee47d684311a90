"""Every second of an answer, attributed: the self time of each span on
the answer's thread, and the program's spans on the device trace's clock.

A span's self time is the time in which it is the innermost span open on
its thread. It is found by a sweep over the span boundaries: at each
instant the innermost open span is the one that started last (the deeper
one on a tie), so spans recorded after the fact (the program's `jit.*`
compile events) take their time from the span they interrupted and no
instant counts twice. Spans on other threads (the AOT pool's compiles)
run beside the answer and are not subtracted. Time covered by no span,
or by the root `apply` span alone, is `unattributed`: per answer, the
self times and `unattributed` sum to the answer's wall.

The ring's clock maps onto the device trace through the program's
`obs.clock` anchors, (ring ns, wall ns) pairs. A `jax.profiler` trace
stores the wall clock less its start time; that one constant is read
from the benchmark's own `bench.unit` annotations, each paired with the
unit's interval on the ring.

A program without span ids or anchors gives None wherever they are
needed, never an error.
"""

from __future__ import annotations

import heapq
import statistics
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .trace_reduce import covered

ROOT = "apply"
CLOCK = "obs.clock"


def _root_thread(spans, a: int, b: int) -> Optional[int]:
    """The thread of the root `apply` span that starts inside [a, b)."""
    roots = [(ts, e[3]) for e in spans
             if e[0] == ROOT and a <= (ts := e[1]) < b and e[2] > 0]
    return min(roots)[1] if roots else None


def segments(spans, a: int, b: int, tid: int) -> List[Tuple[int, int, Optional[str]]]:
    """[a, b) cut where the innermost open span on thread `tid` changes:
    (start, end, its name), None where no span is open."""
    items = sorted((max(e[1], a), min(e[1] + e[2], b), e[1], e[4], i, e[0])
                   for i, e in enumerate(spans)
                   if e[3] == tid and e[2] > 0 and e[1] < b and e[1] + e[2] > a)
    points = sorted({a, b, *(s for s, *_ in items), *(t for _s, t, *_ in items)})
    out: List[Tuple[int, int, Optional[str]]] = []
    heap: list = []
    k = 0
    for t0, t1 in zip(points, points[1:]):
        while k < len(items) and items[k][0] <= t0:
            s, t, start, depth, i, name = items[k]
            heapq.heappush(heap, (-start, -depth, -i, t, name))
            k += 1
        while heap and heap[0][3] <= t0:
            heapq.heappop(heap)
        name = heap[0][4] if heap else None
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def partition(r) -> Optional[List[dict]]:
    """Per unit: its wall (us), the self time (us) of each span name on
    the answer's thread, and the unattributed time. None when a unit has
    no root `apply` span to find its thread by."""
    if not r.units:
        return None
    out = []
    for a, b in r.units:
        tid = _root_thread(r.spans, a, b)
        if tid is None:
            return None
        own: Dict[str, int] = {}
        loose = 0
        for t0, t1, name in segments(r.spans, a, b, tid):
            if name is None or name == ROOT:
                loose += t1 - t0
            else:
                own[name] = own.get(name, 0) + t1 - t0
        out.append({"wall": b - a, "self": own, "unattributed": loose})
    return out


def self_per_unit(r, names: Sequence[str]) -> Optional[float]:
    """Seconds per unit of self time in the spans named `names`; None
    when the program records none of them."""
    parts = partition(r)
    if parts is None:
        return None
    if not any(n in p["self"] for p in parts for n in names):
        return None
    return sum(p["self"].get(n, 0) for p in parts for n in names) / 1e6 / len(parts)


def unattributed_per_unit(r) -> Optional[float]:
    parts = partition(r)
    if parts is None:
        return None
    return sum(p["unattributed"] for p in parts) / 1e6 / len(parts)


def wall_clock(spans) -> Optional[Callable[[float], int]]:
    """ring ns -> wall ns through the `obs.clock` anchors: linear between
    the two around a time, at the nearest one's offset outside them."""
    pts = sorted((e[5]["ts_ns"], e[5]["wall_ns"]) for e in spans
                 if e[0] == CLOCK and isinstance(e[5], dict) and "wall_ns" in e[5])
    if not pts:
        return None

    def to_wall(t: float) -> int:
        t = round(t)  # integers: wall ns overflow a float's mantissa
        if t <= pts[0][0]:
            return pts[0][1] + t - pts[0][0]
        if t >= pts[-1][0]:
            return pts[-1][1] + t - pts[-1][0]
        lo, hi = 0, len(pts) - 1
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if pts[mid][0] <= t:
                lo = mid
            else:
                hi = mid
        (a0, w0), (a1, w1) = pts[lo], pts[hi]
        return w0 + (t - a0) * (w1 - w0) // (a1 - a0)

    return to_wall


def device_clock(r) -> Optional[Callable[[float], float]]:
    """ring us -> the device trace's ns: the anchors' wall clock less the
    trace's start, the median over the units of the wall time of a unit's
    start on the ring less its `bench.unit` start in the trace."""
    to_wall = wall_clock(r.spans)
    trace = r.trace
    if to_wall is None or trace is None or len(trace.units) != len(r.units) or not r.units:
        return None
    start = statistics.median(to_wall(a * 1000) - ta
                              for (a, _b), (ta, _tb) in zip(r.units, trace.units))
    return lambda ts_us: to_wall(ts_us * 1000) - start


def fetch_idle_per_unit(r, name: str = "fetch.get") -> Optional[float]:
    """Device-idle seconds per unit inside the spans named `name`,
    averaged over the devices that ran anything."""
    to_dev = device_clock(r)
    if to_dev is None or not r.trace.busy:
        return None
    idle = 0.0
    for a, b in r.units:
        for e in r.spans:
            if e[0] == name and a <= e[1] < b and e[2] > 0:
                s, t = to_dev(e[1]), to_dev(e[1] + e[2])
                busy = [covered(m, s, t) for m in r.trace.busy.values()]
                idle += (t - s) - sum(busy) / len(busy)
    return idle / 1e9 / len(r.units)


def idle_by_span(r) -> Optional[Dict[str, float]]:
    """Device-idle seconds per unit, split by the innermost span open on
    the answer's thread (first device; `(none)` where no span is open)."""
    to_dev = device_clock(r)
    if to_dev is None or not r.trace.busy:
        return None
    merged = r.trace.busy[sorted(r.trace.busy)[0]]
    out: Dict[str, float] = {}
    for a, b in r.units:
        tid = _root_thread(r.spans, a, b)
        if tid is None:
            return None
        for t0, t1, name in segments(r.spans, a, b, tid):
            s, t = to_dev(t0), to_dev(t1)
            key = name or "(none)"
            out[key] = out.get(key, 0.0) + ((t - s) - covered(merged, s, t)) / 1e9
    return {k: v / len(r.units) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def clock_errors(r, start_ns: Optional[int] = None) -> Optional[List[float]]:
    """For each span bridged into the trace as a `TraceAnnotation`, its
    ring start mapped through the anchors less the annotation's start
    (ns). With `start_ns` (the trace's `profile_start_time`) the wall
    clock is compared directly; without it, through `device_clock`.
    Spans are paired by name, in order, where both sides count alike."""
    if start_ns is not None:
        to_wall = wall_clock(r.spans)
        to_dev = None if to_wall is None else (lambda us: to_wall(us * 1000) - start_ns)
    else:
        to_dev = device_clock(r)
    if to_dev is None or r.trace is None:
        return None
    ring: Dict[str, List[int]] = {}
    for e in r.spans:
        if e[2] > 0:
            ring.setdefault(e[0], []).append(e[1])
    host: Dict[str, List[int]] = {}
    for n, s, _e in r.trace.host:
        if n in ring:
            host.setdefault(n, []).append(s)
    errs: List[float] = []
    for n, starts in host.items():
        if len(starts) == len(ring[n]):
            errs += [to_dev(a) - s for a, s in zip(sorted(ring[n]), sorted(starts))]
    return errs
