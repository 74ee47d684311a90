"""The benchmark's own check, shown to fail (run by hand on the CPU; not
part of the repo's tier-1 tests):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Each case runs `benchmark/run.py` in a child process on the tiny
rehearsal configurations, past the harness's look for a chip, with one
fault planted in the timed path (`faults.py`), or with the control in
the program's place, and reads `correct` from the result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: (config, traffic) of each rehearsal cell, and the controls its config names
CELLS = {"plan": ("tiny-cpu", "apply-default"),
         "exact": ("tiny-sched-perf", "apply-exact")}
CONTROLS = [("plan", "ignore_pod_cap"), ("plan", "padded"),
            ("exact", "ignore_spread"), ("exact", "ignore_scores")]


def run_cell(cell: str, fault: str, *extra: str) -> dict:
    config, traffic = CELLS[cell]
    cmd = [sys.executable, os.path.join(HERE, "faults.py"), fault,
           "--config", config, "--traffic", traffic, "--cpu-rehearsal",
           "--seed", "4294967311", "--seconds", "3", *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    assert run_cell(cell, "none")["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_dropped", "answer_altered"])
def test_fault_is_caught(cell, fault):
    line = run_cell(cell, fault)
    assert line["correct"] is False, line["checks"]


def test_dropped_scores_are_caught():
    line = run_cell("exact", "scores_dropped")
    assert line["checks"]["crowded"]["value"] > 0, line["checks"]


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_control_is_caught(cell, control):
    line = run_cell(cell, "none", "--control", control)
    assert line["correct"] is False, line["checks"]
