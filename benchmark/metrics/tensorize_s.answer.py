"""Host seconds per answer building the tensors: the self time of the
`tensorize` and `plan.tensorize` spans of each answer."""

from benchmark.attribution import self_per_unit


def read(r):
    return self_per_unit(r, ("tensorize", "plan.tensorize"))
