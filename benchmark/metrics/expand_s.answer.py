"""Host seconds per answer expanding workloads into pods: the self time
of the `expand` spans of each answer."""

from benchmark.attribution import self_per_unit


def read(r):
    return self_per_unit(r, ("expand",))
