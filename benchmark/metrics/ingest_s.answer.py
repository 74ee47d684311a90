"""Host seconds per answer in ingest, workload expansion and tensorize:
the union of the `ingest`, `expand`, `tensorize` and `plan.tensorize`
spans inside each answer."""


def read(r):
    return r.per_unit_union(("ingest", "expand", "tensorize", "plan.tensorize"))
