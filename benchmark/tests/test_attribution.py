"""`benchmark/attribution.py`: the self-time partition of an answer, run
by hand on the CPU (not part of the repo's tier-1 tests):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_attribution.py -q

Synthetic spans check the partition exactly; a traced CPU rehearsal of
`tiny-cpu` checks it on the program's own spans. Run as a script, this
file is that traced run: `benchmark/run.py` with its arguments, plus a
JSON report of each answer's self times, the device-idle split by span
and the anchors' clock agreement, written to `--out`:

    python benchmark/tests/test_attribution.py --out <file.json> \\
        --workload <cell> --seed <n> --seconds <s> --trace 1
"""

from __future__ import annotations

import json
import os
import re
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import attribution as at  # noqa: E402
from benchmark.reading import Reading  # noqa: E402


def ev(name, ts, dur, tid=1, depth=0, attrs=None, sid=None, parent=None, root=None):
    return (name, ts, dur, tid, depth, attrs, sid, parent, root)


def reading(spans, units):
    return Reading(units, spans, {}, None, len(units))


def test_partition_closes_exactly():
    spans = [
        ev("apply", 10, 980, depth=0, sid=1, root=1),
        ev("ingest", 20, 300, depth=1, sid=2, parent=1, root=1),
        ev("ingest.decode", 30, 200, depth=2, sid=3, parent=2, root=1),
        ev("plan", 400, 500, depth=1, sid=4, parent=1, root=1),
        ev("plan.materialize", 700, 150, depth=2, sid=5, parent=4, root=1),
        # recorded after the fact, inside plan.materialize and past a child
        ev("jit.compile", 720, 60, depth=3, sid=6, parent=5, root=1),
        ev("jit.cache_load", 730, 20, depth=4, sid=7, parent=5, root=1),
    ]
    (part,) = at.partition(reading(spans, [(0, 1000)]))
    own = part["self"]
    assert own == {"ingest": 100, "ingest.decode": 200, "plan": 350,
                   "plan.materialize": 90, "jit.compile": 40, "jit.cache_load": 20}
    # outside apply (10 + 10) and apply's own time (10 + 80 + 90)
    assert part["unattributed"] == 200
    assert sum(own.values()) + part["unattributed"] == part["wall"] == 1000


def test_other_threads_are_not_subtracted():
    spans = [
        ev("apply", 0, 1000, tid=1, sid=1, root=1),
        ev("plan.candidate", 100, 400, tid=1, depth=1, sid=2, parent=1, root=1),
        # the pool compile the candidate caused: same root, another thread
        ev("aot.compile", 150, 700, tid=2, depth=0, sid=3, parent=2, root=1),
        ev("jit.compile", 200, 600, tid=2, depth=1, sid=4, parent=3, root=1),
    ]
    (part,) = at.partition(reading(spans, [(0, 1000)]))
    assert part["self"] == {"plan.candidate": 400}
    assert part["unattributed"] == 600
    assert at.self_per_unit(reading(spans, [(0, 1000)]), ("aot.compile",)) is None


def test_per_unit_readers():
    spans = []
    for k in range(2):
        o = k * 2000
        spans += [ev("apply", o, 1000, sid=10 * k + 1, root=10 * k + 1),
                  ev("expand", o + 100, 300, depth=1, sid=10 * k + 2),
                  ev("tensorize", o + 400, 100, depth=1, sid=10 * k + 3)]
    r = reading(spans, [(0, 1000), (2000, 3000)])
    assert at.self_per_unit(r, ("expand",)) == 300 / 1e6
    assert at.self_per_unit(r, ("tensorize", "plan.tensorize")) == 100 / 1e6
    assert at.unattributed_per_unit(r) == 600 / 1e6


def test_program_without_ids_or_anchors_reads_none():
    """The parent program's 6-field events: no root, no anchors, so the
    readers that need them give None and raise nothing."""
    old = [("ingest", 0, 500, 1, 0, None), ("expand", 10, 100, 1, 1, None)]
    r = reading(old, [(0, 1000)])
    assert at.partition(r) is None
    assert at.self_per_unit(r, ("expand",)) is None
    assert at.unattributed_per_unit(r) is None
    assert at.wall_clock(old) is None
    assert at.fetch_idle_per_unit(r) is None


def test_wall_clock_through_anchors():
    wall0 = 1_700_000_000_000_000_000
    spans = [ev("obs.clock", 1, 0, attrs={"ts_ns": 1_000, "wall_ns": wall0}),
             ev("obs.clock", 1001, 0, attrs={"ts_ns": 1_001_000, "wall_ns": wall0 + 1_000_100})]
    to_wall = at.wall_clock(spans)
    assert to_wall(1_000) == wall0
    assert to_wall(1_001_000) == wall0 + 1_000_100
    assert to_wall(501_000) == wall0 + 500_050
    assert to_wall(0) == wall0 - 1_000
    vals = [to_wall(t) for t in range(-5_000, 1_010_000, 997)]
    assert vals == sorted(vals)


def run_traced(out, *args):
    """This file as a script: a traced run, its report and its stderr."""
    cmd = [sys.executable, os.path.abspath(__file__), "--out", out, *args]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(out) as f:
        return json.load(f), proc.stderr


def test_cpu_rehearsal_partition_closes(tmp_path):
    report, err = run_traced(str(tmp_path / "attribution.json"),
                             "--config", "tiny-cpu", "--traffic", "apply-default",
                             "--cpu-rehearsal", "--seed", "4294967311",
                             "--seconds", "3", "--trace", "1")
    walls = json.loads(re.search(r"walls_s=(\[.*\])", err).group(1))
    units = report["units"]
    assert len(units) == len(walls) >= 3
    for u, wall in zip(units, walls):
        total = sum(u["self_s"].values()) + u["unattributed_s"]
        assert abs(total - u["wall_s"]) < 1e-9
        assert abs(total - wall) <= 0.01 * wall
        for name in ("ingest.decode", "ingest.objects", "expand", "tensorize",
                     "plan.materialize", "report"):
            assert name in u["self_s"], name
    for name in ("decode_s.answer", "expand_s.answer", "tensorize_s.answer",
                 "materialize_s.answer", "compile_s.answer", "compile_s.setup",
                 "unattributed_s.answer"):
        assert name in report["metrics"], name


def jit_by_fun(spans, units) -> dict:
    """Seconds per unit of each `jit.*` event inside the units, by
    function and thread ("answer" or "pool"), largest first."""
    answer = {at._root_thread(spans, a, b) for a, b in units}
    out: dict = {}
    for e in spans:
        if e[0].startswith("jit.") and any(a <= e[1] < b for a, b in units):
            fun = (e[5] or {}).get("fun", "")
            key = f"{e[0]} {fun} ({'answer' if e[3] in answer else 'pool'})"
            out[key] = out.get(key, 0.0) + e[2] / 1e6 / len(units)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    """A `benchmark/run.py` run that also writes the attribution report."""
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--out")
    out = argv[i + 1]
    del argv[i:i + 2]
    from benchmark import run, trace_reduce

    seen: dict = {}

    def load(path):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        env = pd.find_plane_with_name("Task Environment")
        seen["start_ns"] = dict(env.stats).get("profile_start_time") if env else None
        seen["trace"] = trace_reduce.Reduced(pd)
        return seen["trace"]

    per_layer = run.per_layer

    def report(ctx, bench, reported, profile_dir, spans, counters, win, device):
        metrics, breakdown = per_layer(ctx, bench, reported, profile_dir, spans,
                                       counters, win, device)
        from simtpu.obs import trace as obs_trace

        t0 = obs_trace._T0
        units = [((a - t0) // 1000, (b - t0) // 1000) for a, b in ctx.units]
        r = Reading(units, spans, counters, seen.get("trace"), win["answered"])
        parts = at.partition(r) or []
        names = {e[6]: e[0] for e in spans if len(e) > 6}
        children: dict = {}
        for e in spans:
            if len(e) > 7 and e[7] in names:
                children.setdefault(names[e[7]], set()).add(e[0])
        doc = {
            "metrics": {k: v["value"] for k, v in metrics.items()},
            "units": [{"wall_s": p["wall"] / 1e6,
                       "unattributed_s": p["unattributed"] / 1e6,
                       "self_s": {k: v / 1e6 for k, v in
                                  sorted(p["self"].items(), key=lambda kv: -kv[1])}}
                      for p in parts],
            "children": {k: sorted(v) for k, v in sorted(children.items())},
            "jit_s": jit_by_fun(spans, units),
            "idle_by_span_s": at.idle_by_span(r),
        }
        for key, start in (("clock_us", seen.get("start_ns")), ("clock_us_by_units", None)):
            errs = at.clock_errors(r, start) if r.trace is not None else None
            if errs:
                mags = [abs(x) / 1e3 for x in errs]
                doc[key] = {"n": len(mags), "median": statistics.median(mags),
                            "max": max(mags), "mean_signed": statistics.mean(errs) / 1e3}
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
        return metrics, breakdown

    trace_reduce.load = load
    run.per_layer = report
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
