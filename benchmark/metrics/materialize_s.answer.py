"""Host seconds per answer building the result's node statuses and the
report: the self time of the `plan.materialize` and `report` spans of
each answer."""

from benchmark.attribution import self_per_unit


def read(r):
    return self_per_unit(r, ("plan.materialize", "report"))
