"""Closed loop of batch answers on a spec whose pods preempt: the
`batch_answer` loop (its capture, its answer and its window, by import)
with the victims of each answer kept beside its placement, and a check
by the preemption reference (`reference/preempt.py`) in place of the
shared one, which would count every evicted bound pod as lost.

Traffic parameters are `batch_answer`'s: `apply_args`, `min_answers`.
End-to-end metric: `answer_s`, as there.

Each answer's victims come from the same captured `PlanResult`
(`plan.result.preempted_pods`), reduced to counts: (node, victim group,
preemptor group, whether the preemptor landed on the victim's node).

Controls (a config's `controls` entry): `{"apply_args": [...]}` runs the
program with other flags; `{"reference": "evict_all"}` puts the
reference's own answer, every lower-priority pod on the chosen node
evicted and none reprieved, in the program's place.
"""

from __future__ import annotations

from collections import Counter

from benchmark.drivers import batch_answer as ba
from benchmark.gen.problem import group_of
from benchmark.reference import preempt as ref

window = ba.window


class _Plans(list):
    """The plans `batch_answer`'s capture appends; keeps the one its
    answer takes, for the victims."""

    last = None

    def pop(self, *a):
        self.last = super().pop(*a)
        return self.last


class _Answers(list):
    """The answers of a run: each one appended takes the victims of the
    plan its answer took (None where none was captured)."""

    def __init__(self, plans: _Plans):
        super().__init__()
        self.plans = plans

    def append(self, a) -> None:
        plan, self.plans.last = self.plans.last, None
        a.victims = reduce_victims(plan) if plan is not None else None
        super().append(a)


def reduce_victims(plan) -> Counter:
    """The victims of a plan's result, as counts."""
    result = plan.result
    out: Counter = Counter()
    if result is None:
        return out
    where = {}
    for status in result.node_status:
        name = status.node["metadata"]["name"]
        for p in status.pods:
            meta = p.get("metadata") or {}
            where[f"{meta.get('namespace', 'default')}/{meta.get('name', '')}"] = name
    for pre in result.preempted_pods:
        node = pre.node or ((pre.pod.get("spec") or {}).get("nodeName") or "")
        by = pre.preempted_by
        out[(node, group_of(pre.pod), by.split("/", 1)[-1].rsplit("-", 1)[0],
             where.get(by) == node)] += 1
    return out


def _num(doc: dict, key: str) -> int:
    """A count of the `--json` answer; -1 where it is missing or null."""
    v = doc.get(key)
    return -1 if v is None else int(v)


def setup(ctx) -> ba.State:
    from benchmark.gen import build

    problem = build(ctx.cfg, ctx.seed)
    config = problem.write(ctx.workdir)
    plans = _Plans()
    state = ba.State(problem=problem, argv=["apply", "-f", config, "--json"],
                     plans=plans)
    state.answers = _Answers(plans)
    control = ctx.cfg["controls"][ctx.control] if ctx.control else {}
    if "reference" in control:  # the reference answers in the program's place
        return state
    state.argv += list(control.get("apply_args", ctx.traffic.get("apply_args", [])))
    ba._capture(plans)
    warm = ba.answer(ctx, state)  # the warm-up: every shape of the window
    ctx.units.clear()
    state.answers.append(warm)
    if "preempted" not in warm.doc:
        raise SystemExit("benchmark: the answer reports no `preempted` count, so "
                         "this program cannot answer the cell's preemption question")
    ctx.note(f"warm-up answer: wall_s={warm.wall_s} rc={warm.rc} "
             f"nodes_added={warm.doc.get('nodes_added')} "
             f"unscheduled={warm.doc.get('unscheduled')} "
             f"preempted={warm.doc.get('preempted')}")
    return state


def check(ctx, state: ba.State):
    """The preemption reference over every answer (warm-up included);
    each number is the worst answer's."""
    p = state.problem
    answers = list(state.answers)
    control = ctx.cfg["controls"][ctx.control] if ctx.control else {}
    limits = ctx.cfg.get("limits", {})
    _, _, want = ref.expected(p.node_specs, p.groups, p.priority)
    if "reference" in control:
        if control["reference"] != "evict_all":
            raise ValueError(f"unknown control {control['reference']!r}")
        placed, unscheduled, gone = ref.expected(p.node_specs, p.groups, p.priority,
                                                 evict_all=True)
        by = next(g.key for g in p.groups if not g.bound)
        victims = Counter({(node, key, by, True): k for (node, key), k in gone.items()})
        doc = {"nodes_added": 0, "unscheduled": sum(unscheduled.values()),
               "preempted": sum(victims.values()), "success": not unscheduled,
               "engine": {"audit": {"ok": True}}}
        a = ba.Answer(0.0, 0, doc, placed, unscheduled, 0, "control")
        a.victims = victims
        answers = [a]
    measured = sum(g.count for g in p.groups if not g.bound)
    worst = Counter()
    for a in answers:
        doc = a.doc
        victims = a.victims if a.victims is not None else Counter()
        nums = ref.check(p.node_specs, p.template_spec,
                         max(int(doc.get("nodes_added") or 0), 0), p.groups,
                         p.priority, a.placed, a.unscheduled, victims, want)
        nums["uncaptured"] = int(a.clones < 0 or a.victims is None)
        unsched = sum(a.unscheduled.values())
        if doc.get("unscheduled") is None:
            # a failed search ships no placement: read its best probe
            nums["unscheduled"] = min(
                (int(v) for v in (doc.get("probes") or {}).values()), default=measured)
        nums["answer_mismatch"] = (
            abs(_num(doc, "nodes_added") - a.clones)
            + abs(_num(doc, "unscheduled") - unsched)
            + abs(_num(doc, "preempted") - sum(victims.values()))
            + int(bool(doc.get("success")) != (unsched == 0)))
        audit = (doc.get("engine") or {}).get("audit") or {}
        nums["audit_failed"] = int(audit.get("ok") is not True)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    worst["answers_differ"] = sum(1 for a in answers if a.digest != answers[0].digest)
    ctx.note(f"checked {len(answers)} answers: "
             f"nodes_added={[a.doc.get('nodes_added') for a in answers]} "
             f"preempted={[a.doc.get('preempted') for a in answers]}")
    names = list(ref.NUMBERS) + ["answer_mismatch", "audit_failed",
                                 "answers_differ", "uncaptured"]
    return [(n, int(worst[n]), limits.get(n, 0)) for n in names]
