"""One metrics registry for every simtpu counter family (ISSUE 8).

Before this module, telemetry lived in five ad-hoc module-global dicts —
`engine/scan.py`'s TRACE/FETCH/WAVE counters, `engine/state.py`'s carried
state gauge, `durable/backoff.py`'s OOM counters — each with its own
snapshot function, naming style, and consumer wiring (bench poked the
globals, the CLI assembled the `--json` engine block by hand).  The
registry gives them ONE home with stable dotted names, typed instruments,
and a uniform snapshot/delta protocol the CLI's `metrics` block and
bench's JSON line both read.

The legacy snapshot functions (`fetch_counts()`, `trace_counts()`,
`wave_counts()`, `backoff_counts()`, `state_gauge()`) were kept for one
release as alias views and are now REMOVED (ISSUE 13): the registry is
the only read surface — `REGISTRY.value(name)`, `snapshot(prefix)`, or
the `family(prefix, keys)` helper below for the flat short-key shape the
old functions returned.

Instruments:
- `Counter`  — monotone int, `inc(n)`; thread-safe (bumped from the AOT
  pool threads and the dispatch loop concurrently).
- `Gauge`    — last-write-wins value of any JSON-serializable type
  (ints, bools, per-plane byte dicts).
- `Histogram` — count/total/min/max summary of observed samples (span
  wall-clocks, byte sizes); no buckets — the Perfetto trace is the
  distribution view, the histogram is the cheap always-on summary.

Naming: `<family>.<field>`, lowercase, dots as the only separator —
`fetch.get`, `fetch.bytes`, `compile.scan`, `wavefront.rollback_pods`,
`backoff.events`, `state.carried_bytes`, `audit.total_violations`,
`jit.compile_s`.  The full table lives in docs/observability.md.
"""

from __future__ import annotations

import threading
from typing import Dict

#: bump when the `--json` metrics block (or any stable name in it)
#: changes layout — downstream consumers pin on this, not on key probing
#: (`simtpu version --json` reports it next to the package version).
#: 2 = ISSUE 13: the versioned `explain` block joins the --json document,
#: `explain.*`/`compile.explain` instruments join the registry, and the
#: one-release legacy alias views are gone
SCHEMA_VERSION = 2


class Counter:
    """Monotone integer counter."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._value = 0
        self._lock = lock

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins value (any JSON-serializable type)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._value = 0

    def set(self, value) -> None:
        self._value = value

    @property
    def value(self):
        return self._value


class Histogram:
    """count/total/min/max summary of observed samples."""

    __slots__ = ("name", "count", "total", "min", "max", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._lock = lock

    def observe(self, sample: float) -> None:
        with self._lock:
            self.count += 1
            self.total += sample
            if self.min is None or sample < self.min:
                self.min = sample
            if self.max is None or sample > self.max:
                self.max = sample

    @property
    def value(self) -> Dict[str, object]:
        return {
            "count": self.count,
            "total": self.total,
            "min": self.min,
            "max": self.max,
        }


class MetricsRegistry:
    """Process-wide instrument registry.

    Instruments are created on first use and live for the process (the
    same lifetime the legacy module globals had — counters are monotone
    over a run; consumers wanting per-phase numbers snapshot before and
    `delta_since` after, which is exactly how the CLI's `metrics` block
    and the Applier's engine aliases are built, guaranteeing the two are
    bit-equal)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[str, object] = {}

    def _get(self, name: str, cls):
        inst = self._instruments.get(name)
        if inst is None:
            with self._lock:
                inst = self._instruments.get(name)
                if inst is None:
                    inst = cls(name, self._lock)
                    self._instruments[name] = inst
        if not isinstance(inst, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(inst).__name__}, not {cls.__name__}"
            )
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    # -- read side ---------------------------------------------------------

    def value(self, name: str, default=0):
        """Current value of one instrument (counters default to 0 when
        never bumped — reading must not create instruments)."""
        inst = self._instruments.get(name)
        return default if inst is None else inst.value

    def snapshot(self, prefix: str = "") -> Dict[str, object]:
        """Flat name → value dict of every registered instrument (dict
        values are copied — the snapshot never aliases live state)."""
        out = {}
        for name, inst in sorted(self._instruments.items()):
            if prefix and not name.startswith(prefix):
                continue
            v = inst.value
            out[name] = dict(v) if isinstance(v, dict) else v
        return out

    def delta_since(self, before: Dict[str, object]) -> Dict[str, object]:
        """Snapshot minus `before`: counters and histogram count/total
        subtract, gauges report their CURRENT value (a gauge is a level,
        not a flow — `state.carried_bytes` after a plan is the carry's
        size, not a difference), instruments absent from `before` report
        verbatim."""
        now = self.snapshot()
        out = {}
        for name, v in now.items():
            inst = self._instruments.get(name)
            b = before.get(name)
            if isinstance(inst, Counter) and isinstance(b, int):
                out[name] = v - b
            elif isinstance(inst, Histogram) and isinstance(b, dict):
                out[name] = {
                    "count": v["count"] - b.get("count", 0),
                    "total": v["total"] - b.get("total", 0.0),
                    "min": v["min"],
                    "max": v["max"],
                }
            else:
                out[name] = v
        return out

    def reset(self) -> None:
        """Drop every instrument — TEST-ONLY (production counters are
        process-monotone by contract; resetting under a live dispatch
        loop would skew every open snapshot delta)."""
        with self._lock:
            self._instruments = {}


#: the process-wide registry every simtpu counter family lives in
REGISTRY = MetricsRegistry()


def family(prefix: str, keys) -> Dict[str, object]:
    """Read `<prefix>.<key>` for each key as one flat short-key dict —
    the shape the removed pre-registry snapshot functions exposed
    (e.g. `family("fetch", ("get", "bytes"))`); never-bumped counters
    read 0 rather than registering."""
    return {k: REGISTRY.value(f"{prefix}.{k}") for k in keys}
