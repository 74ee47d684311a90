"""Device dispatches per answer: the `rounds.chunk`, `scan.chunk` and
`scan.wave` spans that start inside each answer. Their counts are exact;
their durations are enqueue time only, so only the count is read."""


def read(r):
    return r.per_unit_count(("rounds.chunk", "scan.chunk", "scan.wave"))
