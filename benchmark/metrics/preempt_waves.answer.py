"""Preemption waves per answer: the `preempt.waves` counter over the
window, divided by the answers in it."""


def read(r):
    return r.per_unit_counter("preempt.waves")
