"""Host seconds per answer proposing victims: the self time of the
`preempt.propose` spans of each answer (the wave model and the victim
search of every preemptor)."""

from benchmark.attribution import self_per_unit


def read(r):
    return self_per_unit(r, ("preempt.propose",))
