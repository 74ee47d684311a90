"""Closed loop of batch answers: `simtpu apply -f <config> --json` run
in-process through `simtpu.cli.main`, one answer at a time, on the
cell's seeded input.

Traffic parameters: `apply_args` (extra CLI flags), `min_answers` (the
window runs at least this many; it starts another only while the mean
answer so far still fits before the window closes).

End-to-end metric: `answer_s`, the summed walls of the window's answers
over their count. Every answer (the warm-up's too) is reduced to counts
and checked by the reference once the window has closed.

The CLI prints only a summary of its answer; the placement behind it is
taken from the `PlanResult` that `capacity.Applier.run` returns, the one
place it leaves the program (no public output carries it yet). An answer
whose placement was not caught this way reads `uncaptured`, so a program
change that moves it fails loudly instead of checking less.

A control (`--control <name>`, a config's `controls` entry) answers in
the program's place: `{"reference": <broken guarantee>}` is the
reference's own first fit (`reference/control.py`); `{"apply_args":
[...]}` runs the program with other flags, its own looser path;
`{"pad_clones": n}` runs the program on the cluster with n template
clones already in it, an answer padded to n added nodes, as a search
that stopped at its upper bracket would give.
"""

from __future__ import annotations

import contextlib
import copy
import hashlib
import io
import json
import os
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import List

from benchmark.gen.problem import group_of
from benchmark.reference import check as ref
from benchmark.reference.control import control_groups, first_fit

#: pod-name stream seed for every answer: the program names generated
#: pods from a process-wide random stream; pinning it makes two answers
#: of one input name their pods alike (placements do not depend on it)
NAME_SEED = 7


@dataclass
class Answer:
    wall_s: float
    rc: int
    doc: dict
    placed: dict  # node name -> Counter(group -> pods)
    unscheduled: Counter
    clones: int  # nodes of the answer that are not in the cluster
    digest: str


@dataclass
class State:
    problem: object
    argv: List[str]
    plans: list
    pad: int = 0  # template clones already in the cluster (a control)
    answers: List[Answer] = field(default_factory=list)


_SINK: dict = {"box": None}


def _capture(box: list):
    """Keep the PlanResult each `Applier.run` returns (the CLI prints
    only its summary): the placement behind the answer. Installed once
    per process; `box` takes the plans from now on."""
    from simtpu.plan import capacity

    _SINK["box"] = box
    if getattr(capacity.Applier.run, "bench_capture", False):
        return
    orig = capacity.Applier.run

    def run(self, *a, **kw):
        plan = orig(self, *a, **kw)
        _SINK["box"].append(plan)
        return plan

    run.bench_capture = True
    capacity.Applier.run = run


def reduce(plan, known_nodes) -> tuple:
    placed, clones = {}, 0
    result = plan.result
    if result is None:
        return placed, Counter(), 0
    for status in result.node_status:
        name = status.node["metadata"]["name"]
        placed[name] = Counter(group_of(p) for p in status.pods)
        clones += name not in known_nodes
    unscheduled = Counter(group_of(u.pod) for u in result.unscheduled_pods)
    return placed, unscheduled, clones


def answer(ctx, state: State) -> Answer:
    from simtpu import cli
    from simtpu.workloads.expand import seed_name_hashes

    seed_name_hashes(NAME_SEED)
    out, err = io.StringIO(), io.StringIO()
    with ctx.unit():
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = cli.main(state.argv)
        wall = time.perf_counter() - t0
    text = out.getvalue().strip().splitlines()
    try:
        doc = json.loads(text[-1]) if text else {}
    except json.JSONDecodeError:
        doc = {}
    if rc not in (0, 1) or not doc:
        sys.stderr.write(err.getvalue()[-4000:])
    plan = state.plans.pop() if state.plans else None
    state.plans.clear()
    if state.pad and "nodes_added" in doc:
        doc["nodes_added"] += state.pad
    known = {n.name for n in state.problem.node_specs}
    placed, unscheduled, clones = reduce(plan, known) if plan else ({}, Counter(), -1)
    h = hashlib.sha256(json.dumps(
        [sorted((k, sorted(v.items())) for k, v in placed.items()),
         sorted(unscheduled.items()), doc.get("nodes_added")]).encode())
    return Answer(wall, rc, doc, placed, unscheduled, clones, h.hexdigest())


def setup(ctx) -> State:
    from benchmark.gen import build

    problem = build(ctx.cfg, ctx.seed)
    config = problem.write(ctx.workdir)
    argv = ["apply", "-f", config, "--json"]
    if problem.storage:
        argv += ["-e", "open-local"]
    control = ctx.cfg["controls"][ctx.control] if ctx.control else {}
    if "reference" in control:  # the reference answers in the program's place
        return State(problem=problem, argv=argv, plans=[])
    argv += list(control.get("apply_args", ctx.traffic.get("apply_args", [])))
    state = State(problem=problem, argv=argv, plans=[])
    if control.get("pad_clones"):
        state.pad = int(control["pad_clones"])
        config = pad(problem, state.pad, ctx.workdir)
        argv[argv.index("-f") + 1] = config
    _capture(state.plans)
    warm = answer(ctx, state)  # the warm-up: every shape of the window
    ctx.units.clear()
    state.answers.append(warm)
    ctx.note(f"warm-up answer: wall_s={warm.wall_s} rc={warm.rc} "
             f"nodes_added={warm.doc.get('nodes_added')} "
             f"unscheduled={warm.doc.get('unscheduled')}")
    return state


def pad(problem, n: int, workdir: str) -> str:
    """Write the problem again with n template clones in the cluster."""
    clones = []
    for i in range(n):
        node = copy.deepcopy(problem.template)
        name = f"{node['metadata']['name']}-pad-{i:03d}"
        node["metadata"]["name"] = name
        node["metadata"]["labels"] = {
            k: (name if v == problem.template["metadata"]["name"] else v)
            for k, v in node["metadata"]["labels"].items()}
        clones.append(node)
    nodes = problem.nodes
    problem.nodes = nodes + clones
    try:
        return problem.write(os.path.join(workdir, "padded"))
    finally:
        problem.nodes = nodes


def window(ctx, state: State):
    if ctx.control:
        return {"e2e": {"answer_s": 0.0}, "attempted": 0, "failed": 0, "answered": 0}
    t_end = time.perf_counter() + ctx.seconds
    walls: List[float] = []
    need = int(ctx.traffic.get("min_answers", 3))
    while True:
        mean = sum(walls) / len(walls) if walls else 0.0
        if len(walls) >= need and time.perf_counter() + mean > t_end:
            break
        a = answer(ctx, state)
        walls.append(a.wall_s)
        state.answers.append(a)
    failed = sum(1 for a in state.answers[1:] if a.rc != 0)
    ctx.note(f"answers: {len(walls)} walls_s={walls}")
    return {"e2e": {"answer_s": sum(walls) / len(walls)},
            "attempted": len(walls), "failed": failed,
            "answered": len(walls) - failed}


def check(ctx, state: State):
    """The reference over every answer (warm-up included); each number is
    the worst answer's."""
    p = state.problem
    answers = state.answers
    control = ctx.cfg["controls"][ctx.control] if ctx.control else {}
    extra = ctx.cfg.get("checks", [])
    limits = ctx.cfg.get("limits", {})
    if "reference" in control:
        placed, unscheduled = first_fit(p.node_specs, control_groups(p.groups),
                                        control["reference"])
        doc = {"nodes_added": 0, "unscheduled": sum(unscheduled.values()),
               "success": True, "engine": {"audit": {"ok": True}}}
        answers = [Answer(0.0, 0, doc, placed, unscheduled, 0, "control")]
    worst = Counter()
    for a in answers:
        doc = a.doc
        nums = ref.check(p.node_specs, p.template_spec,
                         max(int(doc.get("nodes_added") or 0), 0), p.groups,
                         a.placed, a.unscheduled, extra=extra)
        nums["uncaptured"] = int(a.clones < 0)
        unsched = sum(a.unscheduled.values())
        nums["unscheduled"] = unsched
        nums["answer_mismatch"] = (
            abs(int(doc.get("nodes_added", -1)) - a.clones)
            + abs(int(doc.get("unscheduled", -1)) - unsched)
            + int(bool(doc.get("success")) != (unsched == 0)))
        audit = (doc.get("engine") or {}).get("audit") or {}
        nums["audit_failed"] = int(audit.get("ok") is not True)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    worst["answers_differ"] = sum(1 for a in answers if a.digest != answers[0].digest)
    ctx.note(f"checked {len(answers)} answers: "
             f"nodes_added={[a.doc.get('nodes_added') for a in answers]}")
    names = list(ref.NUMBERS) + ["unscheduled", "answer_mismatch",
                                 "audit_failed", "answers_differ", "uncaptured"]
    names += [ref.EXTRA[c] for c in extra]
    return [(n, int(worst[n]), limits.get(n, 0)) for n in names]
