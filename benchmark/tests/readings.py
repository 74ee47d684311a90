"""The readings a limit is set from: the program's answer on many seeds,
and each control on a few, in one process (set-up and compiles paid
once), at the cell's own size. Each answer is the one a run's set-up
makes and its window repeats (`simtpu apply` through the CLI), checked
by the run's own check:

    python benchmark/tests/readings.py --workload <cell> --seeds 1,2 \\
        [--control <name>:3,4,5] [--out <file.jsonl>]

(or `--config <c> --traffic <t> --cpu-rehearsal` for a CPU rehearsal).

Prints one JSON line per answer: the seed, the control (or none), the
answer's wall and nodes_added, and every number the check compares.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="")
    ap.add_argument("--config", default="", help="rehearsal, as run.py's")
    ap.add_argument("--traffic", default="", help="rehearsal, as run.py's")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run

    cell = argparse.Namespace(workload=args.workload, config=args.config,
                              traffic=args.traffic, cpu_rehearsal=args.cpu_rehearsal)
    workload, _bench, cfg, traffic, chips = run.resolve(cell)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", run.CACHE)
    try:
        run.check_devices(chips, args.cpu_rehearsal)
    except run.NoChip as exc:
        run.say(f"readings: {exc}")
        return 2
    from simtpu.cache import enable_compilation_cache

    enable_compilation_cache()
    driver = run.import_file(os.path.join(run.BENCH, "drivers", traffic["driver"] + ".py"),
                             f"bench_driver_{traffic['driver']}")
    jobs = [("", int(s)) for s in args.seeds.split(",") if s]
    for spec in args.control:
        name, seeds = spec.split(":")
        jobs += [(name, int(s)) for s in seeds.split(",") if s]
    for control, seed in jobs:
        workdir = tempfile.mkdtemp(prefix="simtpu-readings-")
        ctx = run.Context(workload=workload, cfg=cfg, traffic=traffic, seed=seed,
                          seconds=0.0, trace=False, workdir=workdir, control=control)
        t0 = time.monotonic()
        try:
            state = driver.setup(ctx)
            checks = driver.check(ctx, state)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        answers = state.answers
        line = {"workload": workload, "seed": seed, "control": control or None,
                "wall_s": answers[0].wall_s if answers else None,
                "nodes_added": answers[0].doc.get("nodes_added") if answers else None,
                "probes": answers[0].doc.get("probes") if answers else None,
                "seconds": time.monotonic() - t0,
                "correct": all(v <= lim for _n, v, lim in checks),
                "checks": {n: [v, lim] for n, v, lim in checks}}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del state, answers
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
