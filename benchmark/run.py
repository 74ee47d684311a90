#!/usr/bin/env python3
"""simtpu's benchmark: one cell per run, driven by `BENCHMARK.json`.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (`benchmark/configs/<config>.json`, built by
`benchmark/gen/<generator>.py`) and a traffic mix
(`benchmark/traffic/<traffic>.json`, run by
`benchmark/drivers/<driver>.py`); each per-layer metric is read by
`benchmark/metrics/<metric>.py`. Adding any of them is adding a file.

One process: set-up (generate the input from the seed, warm up on the
cell's own shapes), the measured window, the reference check of every
answer the window produced, then the result as the last line of stdout.
With `--trace 1` the window runs under the span tracer and the JAX
profiler and the line carries the per-layer metrics instead of the
end-to-end ones. Without a TPU (or with fewer chips than the cell asks)
it exits 2 and prints no result; `--cpu-rehearsal` lets the CPU through
for the builder's rehearsal and for benchmark/tests, and marks the line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Tuple  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache", "jax")
TRACE_CAPACITY = 1 << 21


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def import_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a driver is handed: the cell, its input parameters and the
    window's bookkeeping."""

    workload: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    workdir: str
    control: str = ""  # a name under the config's `controls`
    units: List[Tuple[int, int]] = field(default_factory=list)  # perf ns

    @contextlib.contextmanager
    def unit(self):
        """Bracket one answer or query: its interval on the host clock,
        and in a traced run a `bench.unit` annotation in the profile."""
        ann = contextlib.nullcontext()
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation("bench.unit")
        t0 = time.perf_counter_ns()
        with ann:
            yield
        self.units.append((t0, time.perf_counter_ns()))

    def note(self, msg: str) -> None:
        say(msg)


def resolve(args) -> Tuple[str, dict, dict, dict, int]:
    """(workload name, its entry, config, traffic, chips) from
    BENCHMARK.json, or from --config/--traffic for a rehearsal."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.config or args.traffic:
        if not (args.config and args.traffic and args.cpu_rehearsal):
            raise SystemExit("--config and --traffic go together, with --cpu-rehearsal")
        entry = {"name": args.workload or f"{args.config}.{args.traffic}",
                 "config": args.config, "traffic": args.traffic, "chips": 1}
    else:
        entry = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
        if entry is None:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    cfg = load_json(os.path.join(BENCH, "configs", entry["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH, "traffic", entry["traffic"] + ".json"))
    return entry["name"], bench, cfg, traffic, int(entry["chips"])


def metrics_for(bench: dict, workload: str, reported: List[str], trace: bool):
    """The metrics this run prints: end-to-end ones (trace 0) or per-layer
    ones (trace 1) that belong to the cell. A rehearsal cell that
    BENCHMARK.json does not list gets every metric of what it reports."""
    known = {w["name"] for w in bench["workloads"]}
    out = []
    for m in bench["per_layer"] if trace else bench["end_to_end"]:
        cells = m.get("workloads") if workload in known else None
        if cells is not None and workload in cells:
            out.append(m)
        elif cells is None and (m.get("moves") in reported if trace
                                else m["name"] in reported):
            out.append(m)
    return out


def check_devices(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chip(s); JAX found {len(devices)}")
    return devices[:chips]


@contextlib.contextmanager
def traced(ctx: Context, outdir: str):
    """Span tracer + JAX profiler around the window, with the program's
    span -> TraceAnnotation bridge (as `simtpu.obs.profile.profile_capture`
    installs it, but with the Python tracer off: it would record every
    Python call of the host path)."""
    if not ctx.trace:
        yield
        return
    import jax

    from simtpu.obs import trace as obs_trace

    obs_trace.enable(TRACE_CAPACITY)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(outdir, profiler_options=opts)
    obs_trace._ANNOTATION_FACTORY = jax.profiler.TraceAnnotation
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        obs_trace._ANNOTATION_FACTORY = None
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def per_layer(ctx: Context, bench: dict, reported: List[str], profile_dir: str,
              spans, counters, win: dict, device: dict):
    """The cell's per-layer metrics from the traced window, and the
    device's busy time and breakdown from the profile."""
    from benchmark.reading import Reading
    from benchmark.trace_reduce import load
    from simtpu.obs import trace as obs_trace

    t_read = time.monotonic()
    pbs = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    reduced = load(pbs[0]) if pbs else None
    t0 = obs_trace._T0
    units = [((a - t0) // 1000, (b - t0) // 1000) for a, b in ctx.units]
    reading = Reading(units, spans, counters, reduced, win["answered"])
    metrics = {}
    for m in metrics_for(bench, ctx.workload, reported, True):
        reader = import_file(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        breakdown = {"device_ops": reduced.top_ops(),
                     "idle_gaps": reduced.idle_gaps({e[0] for e in spans})}
        ctx.note(f"trace: units={len(reduced.units)} busy_s={device['busy_s']} "
                 f"window_s={device['window_s']} bytes={os.path.getsize(pbs[0])} "
                 f"read_s={time.monotonic() - t_read}")
    return metrics, breakdown


def run(args) -> dict:
    """One whole run; returns the result line (a dict)."""
    workload, bench, cfg, traffic, chips = resolve(args)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    devices = check_devices(chips, args.cpu_rehearsal)

    from simtpu.cache import enable_compilation_cache
    from simtpu.obs import trace as obs_trace
    from simtpu.obs.metrics import REGISTRY

    say(f"compilation cache: {enable_compilation_cache()}")
    driver = import_file(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
                         f"bench_driver_{traffic['driver']}")
    workdir = tempfile.mkdtemp(prefix="simtpu-bench-")
    ctx = Context(workload=workload, cfg=cfg, traffic=traffic, seed=args.seed,
                  seconds=float(args.seconds), trace=bool(args.trace),
                  workdir=workdir, control=args.control)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    try:
        state = driver.setup(ctx)
        # start the window from a collected heap: set-up leaves millions of
        # young objects, whose first full collection otherwise lands in the
        # window as a stall of a second or two on every thread
        gc.collect()
        setup_s = time.monotonic() - T_START
        before = REGISTRY.snapshot()
        profile_dir = os.path.join(workdir, "profile")
        with traced(ctx, profile_dir):
            win = driver.window(ctx, state)
        counters = REGISTRY.delta_since(before)
        spans = obs_trace.events() if ctx.trace else []
        if ctx.trace:
            obs_trace.disable()
        compiles = {k: v for k, v in counters.items()
                    if k.startswith("compile.") and v}
        ctx.note(f"window: units={len(ctx.units)} attempted={win['attempted']} "
                 f"failed={win['failed']} "
                 f"compiles={json.dumps(compiles, sort_keys=True)}")
        device["memory_peak_bytes"] = memory_peak(devices)
        reported = list(win["e2e"]) + ["setup_s"]
        metrics: Dict[str, dict] = {}
        breakdown = None
        if ctx.trace:
            metrics, breakdown = per_layer(ctx, bench, reported, profile_dir,
                                           spans, counters, win, device)
        else:
            values = dict(win["e2e"], setup_s=setup_s)
            for m in metrics_for(bench, workload, reported, False):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
            if args.cpu_rehearsal:
                for k in set(values) - set(metrics):
                    metrics[k] = {"value": values[k], "unit": "s"}
        t_check = time.monotonic()
        checks = driver.check(ctx, state)
        ctx.note(f"reference check: {time.monotonic() - t_check} s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    correct = all(v <= lim for _n, v, lim in checks)
    line = {"correct": correct, "attempted": win["attempted"], "failed": win["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if args.cpu_rehearsal:
        line["rehearsal"] = "cpu"
    for name, value, limit in checks:
        say(f"check {name} = {value} (limit {limit})")
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--config", default="", help="rehearsal: a config by file name")
    ap.add_argument("--traffic", default="", help="rehearsal: a traffic by file name")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="let the CPU through (builder rehearsal and tests)")
    ap.add_argument("--control", default="",
                    help="check the answers of this control (a name under the "
                    "config's `controls`) in place of the program's")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run(args)
    except NoChip as exc:
        say(f"benchmark: {exc}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
