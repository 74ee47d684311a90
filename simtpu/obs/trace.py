"""Low-overhead span tracer with Chrome-trace-event (Perfetto) export.

The tracer answers the question the phase timings cannot: WHERE inside a
phase the wall-clock went — which chunk dispatches, which AOT compiles on
which pool threads, which fault-sweep blocks, which checkpoint writes.
Every layer of the engine opens spans through the one context manager
here:

    from simtpu.obs import span
    with span("scan.chunk", pods=int(b - a)):
        ...

Design constraints (measured by `make bench-obs`):
- **Disabled = free.** `span()` returns one shared no-op singleton when
  tracing is off — no span object, no event, no lock; the only cost is
  the enabled-flag check (and the caller's kwargs, which are empty on
  the hot paths that matter).  The bench pins ~0% overhead off and <3%
  on, against a warm bulk placement.
- **Bounded memory.** Events land in a fixed-capacity ring buffer
  (default 65536); a long run overwrites its oldest spans instead of
  growing without bound.  The flight recorder (obs/flight.py) snapshots
  the last N on failure for exactly this reason.
- **Thread-safe.** The AOT precompile pool opens compile spans from
  worker threads concurrently with the dispatch loop's chunk spans; the
  ring index is bumped under one lock at span EXIT only (one lock
  acquisition per completed span; on entry only a root span takes it,
  for its clock anchor).

Identity and causality: every span gets an integer id (`sid`), the id of
the span that encloses it on its thread (`parent`, None for a root) and
the id of its outermost span (`root`: one request or answer).  Work
handed to another thread keeps its cause: capture `current()` where the
work is submitted and run it under `adopt(ctx)`, so the worker's spans
take the submitting span as parent and root (engine/precompile.py does
this for the AOT pool).  `complete()` records a span after the fact from
a measured duration (the `jit.*` compile events of obs/profile.py).

Clock anchors: the ring runs on `time.perf_counter_ns()`.  `enable()` and
the start of every root span record an `obs.clock` instant holding a
(ring ns, `time.time_ns()`) pair taken back to back; `profiler_ns()` maps
a ring timestamp through the nearest anchors onto the wall clock, the
host clock of `jax.profiler` traces, which store it less their
`profile_start_time`.

Export is the Chrome trace-event JSON object format — `{"traceEvents":
[...]}` with complete ("ph": "X") events — loadable directly in Perfetto
(https://ui.perfetto.dev) or chrome://tracing.  Timestamps are
microseconds from an arbitrary per-process origin, durations are
microseconds, `tid` is the Python thread ident (named via metadata
events); `args` carries `depth`, `parent` and `root`, and `otherData`
the clock anchors.  `simtpu apply --trace FILE` writes one; SIMTPU_TRACE=1 arms
in-memory tracing (SIMTPU_TRACE=<path> also exports at process exit —
the hook tools/run_tests.py uses for its slowest-spans summary).
"""

from __future__ import annotations

import bisect
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

DEFAULT_CAPACITY = 65536

_LOCK = threading.Lock()
_ENABLED = False
_RING: List[Optional[tuple]] = []
_COUNT = 0  # total events ever recorded (ring index = _COUNT % capacity)
_DROPPED = 0  # events overwritten after wraparound
_T0 = time.perf_counter_ns()  # per-process trace origin
#: per-thread state: `stack`, the (sid, root) of each open span, and
#: `inherit`, the (parent, root) a worker thread adopted from its submitter
_TLS = threading.local()
_IDS = itertools.count(1)  # span ids; next() is atomic under the GIL
#: (ring ns since _T0, time.time_ns()) pairs, oldest first
_ANCHORS: List[Tuple[int, int]] = []
ANCHOR_CAP = 4096  # a long traced serve run keeps its newest anchors

#: set by obs/profile.py while a jax.profiler capture is live: a callable
#: name -> context manager (jax.profiler.TraceAnnotation) entered by every
#: span so the device profile and the span trace share one vocabulary
_ANNOTATION_FACTORY = None


class _NoopSpan:
    """The shared disabled-path span: one instance for the whole process,
    allocation-free to enter/exit (the zero-overhead contract)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):  # noqa: ARG002 - signature parity with _Span
        return self


_NOOP = _NoopSpan()


class _Span:
    """One live span: records (name, start, duration, thread, depth,
    attrs, sid, parent, root) into the ring on exit."""

    __slots__ = ("name", "attrs", "_t0", "_depth", "_ann", "_sid", "_parent", "_root")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        """Attach/override attributes mid-span (e.g. bytes fetched, known
        only after the body ran)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def __enter__(self):
        sid = self._sid = next(_IDS)
        self._depth, self._parent, root = _context()
        self._root = sid if root is None else root
        _stack().append((sid, self._root))
        if root is None:
            anchor()  # one clock anchor per root: per request or answer
        ann = None
        factory = _ANNOTATION_FACTORY
        if factory is not None:
            try:
                ann = factory(self.name)
                ann.__enter__()
            except Exception:  # noqa: BLE001 - profiling must never break the run
                ann = None
        self._ann = ann
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # noqa: BLE001
                pass
        # truncate rather than pop: a span leaked inside this one must not
        # corrupt the thread's nesting for every later span
        del _stack()[self._depth:]
        _record((
            self.name,
            (self._t0 - _T0) // 1000,  # ts, us
            max((t1 - self._t0) // 1000, 1),  # dur, us (Perfetto drops 0)
            threading.get_ident(),
            self._depth,
            self.attrs,
            self._sid,
            self._parent,
            self._root,
        ))
        return False


def _stack() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _context() -> tuple:
    """(depth, parent, root) for an event recorded now on this thread."""
    stack = _stack()
    if stack:
        return (len(stack),) + stack[-1]
    inherited = getattr(_TLS, "inherit", None)
    return (0,) + (inherited or (None, None))


def _record(event: tuple) -> None:
    global _COUNT, _DROPPED
    with _LOCK:
        if _ENABLED:  # disabled mid-span: drop, buffers already cleared
            cap = len(_RING)
            if _COUNT >= cap:
                _DROPPED += 1
            _RING[_COUNT % cap] = event
            _COUNT += 1


def span(name: str, **attrs):
    """Open a span named `name` (a context manager).  With tracing off
    this is the shared no-op singleton — callers never pay for tracing
    they didn't enable.  Attributes must be JSON-serializable; hot-path
    callers should pass cheap scalars (pod counts, byte totals)."""
    if not _ENABLED:
        return _NOOP
    return _Span(name, attrs or None)


def _point(name: str, ts_us: int, dur_us: int, attrs: Optional[dict]) -> None:
    """Record an event that never sat on the span stack, under the span
    open on this thread (its own root when none is)."""
    depth, parent, root = _context()
    sid = next(_IDS)
    _record((
        name, ts_us, dur_us, threading.get_ident(), depth, attrs, sid,
        parent, sid if root is None else root,
    ))


def instant(name: str, **attrs) -> None:
    """Record a zero-duration point event (e.g. a wavefront rollback)."""
    if not _ENABLED:
        return
    _point(name, (time.perf_counter_ns() - _T0) // 1000, 0, attrs or None)


def complete(name: str, seconds: float, **attrs) -> None:
    """Record a span that ends now and lasted `seconds`, measured by
    someone else (a JAX compile event): it nests under the span open on
    this thread, like a span opened and closed around the same work."""
    if not _ENABLED:
        return
    t1 = time.perf_counter_ns()
    dur = int(seconds * 1e9)
    _point(name, (t1 - dur - _T0) // 1000, max(dur // 1000, 1), attrs or None)


def anchor() -> None:
    """Record an `obs.clock` instant: the ring clock and `time.time_ns()`
    read back to back, so ring timestamps map onto the wall clock (and
    from there onto a `jax.profiler` trace) through `profiler_ns()`."""
    if not _ENABLED:
        return
    ts_ns = time.perf_counter_ns() - _T0
    wall_ns = time.time_ns()
    with _LOCK:
        if len(_ANCHORS) >= ANCHOR_CAP:
            del _ANCHORS[: ANCHOR_CAP // 2]
        _ANCHORS.append((ts_ns, wall_ns))
    _point("obs.clock", ts_ns // 1000, 0, {"ts_ns": ts_ns, "wall_ns": wall_ns})


def profiler_ns(ts_us: float) -> Optional[int]:
    """Map a ring timestamp (us, the `ts` of an event) onto the wall
    clock in ns: linearly between the two anchors around it, at the rate
    of the nearest one outside them.  Monotone in `ts_us` while the wall
    clock does not step back.  None before any anchor."""
    with _LOCK:
        anchors = _ANCHORS
        if not anchors:
            return None
        t = round(ts_us * 1000)  # integers: wall ns overflow a float's mantissa
        i = bisect.bisect_right(anchors, (t, 1 << 63))
        if i == 0 or i == len(anchors):
            a, w = anchors[0] if i == 0 else anchors[-1]
            return w + t - a
        (a0, w0), (a1, w1) = anchors[i - 1], anchors[i]
    return w0 + (t - a0) * (w1 - w0) // (a1 - a0)


def current() -> Optional[tuple]:
    """The (span id, root id) that work submitted from here should run
    under — pass it to `adopt()` on the worker thread.  None when tracing
    is off or no span is open."""
    if not _ENABLED:
        return None
    stack = _stack()
    return stack[-1] if stack else getattr(_TLS, "inherit", None)


class _Adopt:
    __slots__ = ("ctx", "prev")

    def __init__(self, ctx: tuple):
        self.ctx = ctx

    def __enter__(self):
        self.prev = getattr(_TLS, "inherit", None)
        _TLS.inherit = self.ctx
        return self

    def __exit__(self, *exc):
        _TLS.inherit = self.prev
        return False


def adopt(ctx: Optional[tuple]):
    """Run a worker's spans under `ctx`, a `current()` captured where the
    work was submitted: they take its span as parent and its root as
    root.  `adopt(None)` is the shared no-op."""
    if ctx is None:
        return _NOOP
    return _Adopt(ctx)


def enabled() -> bool:
    return _ENABLED


def enable(capacity: int = DEFAULT_CAPACITY) -> None:
    """Arm the tracer with a fresh ring of `capacity` events (re-enabling
    clears prior events — one trace per arming)."""
    global _ENABLED, _RING, _COUNT, _DROPPED
    if capacity < 1:
        raise ValueError(f"trace capacity must be >= 1, got {capacity}")
    with _LOCK:
        _RING = [None] * capacity
        _COUNT = 0
        _DROPPED = 0
        _ANCHORS.clear()
        _ENABLED = True
    anchor()


def disable() -> None:
    """Disarm and drop the buffered events."""
    global _ENABLED, _RING, _COUNT, _DROPPED
    with _LOCK:
        _ENABLED = False
        _RING = []
        _COUNT = 0
        _DROPPED = 0
        _ANCHORS.clear()


def events() -> List[tuple]:
    """Chronological snapshot of the buffered events — oldest surviving
    first (wraparound drops the oldest).  Tuples of (name, ts_us, dur_us,
    tid, depth, attrs, sid, parent, root)."""
    with _LOCK:
        if not _RING:
            return []
        cap = len(_RING)
        if _COUNT <= cap:
            return [e for e in _RING[:_COUNT] if e is not None]
        head = _COUNT % cap
        return [e for e in _RING[head:] + _RING[:head] if e is not None]


def dropped() -> int:
    """Events overwritten by ring wraparound since enable()."""
    return _DROPPED


def to_chrome_trace(last: Optional[int] = None) -> Dict[str, object]:
    """The buffered spans as a Chrome trace-event JSON object (Perfetto
    loads it directly).  `last` keeps only the newest N events (the
    flight-recorder view)."""
    evs = events()
    if last is not None:
        evs = evs[-last:]
    with _LOCK:
        anchors = [list(a) for a in _ANCHORS]
    pid = os.getpid()
    trace_events: List[dict] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": "simtpu"},
        }
    ]
    tids = []
    for name, ts, dur, tid, depth, attrs, _sid, parent, root in evs:
        args = {"depth": depth, "parent": parent, "root": root}
        if attrs:
            args.update(attrs)
        if dur == 0:
            trace_events.append(
                {
                    "name": name,
                    "ph": "i",
                    "s": "t",
                    "ts": ts,
                    "pid": pid,
                    "tid": tid,
                    "cat": "simtpu",
                    "args": args,
                }
            )
        else:
            trace_events.append(
                {
                    "name": name,
                    "ph": "X",
                    "ts": ts,
                    "dur": dur,
                    "pid": pid,
                    "tid": tid,
                    "cat": "simtpu",
                    "args": args,
                }
            )
        if tid not in tids:
            tids.append(tid)
    for i, tid in enumerate(tids):
        trace_events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": "main" if i == 0 else f"thread-{i}"},
            }
        )
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "otherData": {
            "dropped_events": _DROPPED,
            # [ring ns, time.time_ns()] pairs: `ts` * 1000 - ring ns + wall
            # ns is the wall clock a jax.profiler trace runs on
            "clock_anchors": anchors,
        },
    }


def export_trace(path: str, last: Optional[int] = None) -> str:
    """Write the Chrome trace JSON to `path` (parent dirs created) and
    return the path."""
    doc = to_chrome_trace(last=last)
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def span_summary(top: int = 10) -> List[dict]:
    """Top-N span names by total wall-clock: [{"name", "count",
    "total_s", "max_s"}] — the run_tests / flight-recorder digest."""
    agg: Dict[str, List[float]] = {}
    for name, _, dur, *_ in events():
        row = agg.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += dur / 1e6
        row[2] = max(row[2], dur / 1e6)
    rows = [
        {
            "name": name,
            "count": int(c),
            "total_s": round(tot, 6),
            "max_s": round(mx, 6),
        }
        for name, (c, tot, mx) in agg.items()
    ]
    rows.sort(key=lambda r: -r["total_s"])
    return rows[:top]


def init_from_env() -> None:
    """SIMTPU_TRACE activation, read once at `import simtpu`:

    - unset / "0"  — tracing stays off (the default; spans are no-ops)
    - "1"          — in-memory tracing on (consumers export explicitly)
    - anything else — treated as an output PATH: tracing on, and the
      buffered trace exports there at interpreter exit (atexit) — the
      hook tools/run_tests.py uses to collect per-module traces

    Capacity override: SIMTPU_TRACE_CAPACITY (events, default 65536)."""
    raw = os.environ.get("SIMTPU_TRACE", "")
    if raw in ("", "0"):
        return
    cap = int(os.environ.get("SIMTPU_TRACE_CAPACITY", DEFAULT_CAPACITY))
    enable(capacity=cap)
    if raw != "1":
        import atexit

        atexit.register(export_trace, raw)
