"""The preemption cell's check, shown to fail (run by hand on the CPU; not
part of the repo's tier-1 tests):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_preempt_faults.py -q

Each case runs `benchmark/run.py` in a child process on the tiny
rehearsal configuration `tiny-preempt` (sched-perf-preempt-5k's
generator at 24 nodes), through `faults.py`: the sound program must read
`correct`, each planted fault and each control must not, and a traced
run must read the preemption layer's metrics.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NODES = 24  # tiny-preempt's


def run_cell(fault: str, *extra: str) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "faults.py"), fault,
           "--config", "tiny-preempt", "--traffic", "apply-preempt", "--cpu-rehearsal",
           "--seed", "4294967311", "--seconds", "3", *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    line = run_cell("none")
    assert line["correct"] is True, line["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_dropped", "answer_altered"])
def test_fault_is_caught(fault):
    line = run_cell(fault)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("control,number,reading", [
    # the parent's answer: no evictions, one template clone per preemptor
    ("no_preempt", "victims_wrong", 3 * NODES),
    # every low pod on a preemptor's node evicted: one per node fits back
    ("evict_all", "reprievable", NODES),
])
def test_control_is_caught(control, number, reading):
    line = run_cell("none", "--control", control)
    assert line["correct"] is False, line["checks"]
    assert line["checks"][number]["value"] == reading, line["checks"]


def test_traced_run_reads_the_preemption_layer():
    line = run_cell("none", "--trace", "1")
    assert line["correct"] is True, line["checks"]
    metrics = line["metrics"]
    assert metrics["preempt_waves.answer"]["value"] >= 1
    assert 0 < metrics["propose_s.answer"]["value"] <= metrics["preempt_s.answer"]["value"]
