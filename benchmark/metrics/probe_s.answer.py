"""Seconds per answer inside the planner's candidate placements: the
union of the `plan.base` and `plan.candidate` spans (base, probes,
verify) of each answer."""


def read(r):
    return r.per_unit_union(("plan.base", "plan.candidate"))
