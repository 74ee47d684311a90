"""Plant one fault in the program's timed path, then run the benchmark:

    python benchmark/tests/faults.py <fault> <run.py arguments...>

Faults (each must turn `correct` false):
- none: nothing planted (the sound run, for comparison);
- state_unchanged: every scheduling step returns the state it was given
  (the serial scan's `schedule_step`, the wavefront's `wavefront_scan`
  and the bulk rounds' `_round_core`);
- half_dropped: half of the pods of the answer are left out of it;
- answer_altered: the answer is altered where it is produced (a batch
  answer's `nodes_added` plus one);
- scores_dropped: the scan scores every node alike (the default score
  weights zeroed), so a pod takes the first node its filters pass.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def state_unchanged() -> None:
    from simtpu.engine import rounds, scan

    step, wave, core = scan.schedule_step, scan.wavefront_scan, rounds._round_core

    def frozen(fn):
        def call(statics, state, *a, **kw):
            return (state,) + tuple(fn(statics, state, *a, **kw)[1:])
        return call

    frozen_step, frozen_wave, frozen_core = frozen(step), frozen(wave), frozen(core)

    scan.schedule_step = frozen_step
    scan.wavefront_scan = frozen_wave
    rounds._round_core = frozen_core


def half_dropped() -> None:
    from simtpu.plan import capacity

    run = capacity.Applier.run

    def dropped(self, *a, **kw):
        plan = run(self, *a, **kw)
        if plan.result is not None:
            for status in plan.result.node_status:
                del status.pods[: len(status.pods) // 2]
        return plan

    capacity.Applier.run = dropped


def answer_altered() -> None:
    from simtpu import cli

    plan_json = cli._plan_json

    def altered(plan, *a, **kw):
        plan.nodes_added += 1
        try:
            return plan_json(plan, *a, **kw)
        finally:
            plan.nodes_added -= 1

    cli._plan_json = altered


def scores_dropped() -> None:
    from simtpu import schedconfig

    schedconfig.DEFAULT_WEIGHTS[:] = 0.0


FAULTS = {"none": lambda: None, "state_unchanged": state_unchanged,
          "half_dropped": half_dropped, "answer_altered": answer_altered,
          "scores_dropped": scores_dropped}


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    FAULTS[sys.argv[1]]()
    from benchmark import run

    sys.exit(run.main(sys.argv[2:]))
