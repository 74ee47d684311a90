"""Seconds per answer in the preemption waves: the union of the
`preempt.wave` spans of each answer (victim proposals, evictions, the
batched verify placement, restores and commits)."""


def read(r):
    return r.per_unit_union(("preempt.wave",))
