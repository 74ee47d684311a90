"""Seconds per answer in the independent auditor: the union of the
`audit.pass` spans of each answer."""


def read(r):
    return r.per_unit_union(("audit.pass",))
