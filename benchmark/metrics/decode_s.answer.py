"""Host seconds per answer decoding YAML/JSON text into dicts: the self
time of the `ingest.decode` spans of each answer."""

from benchmark.attribution import self_per_unit


def read(r):
    return self_per_unit(r, ("ingest.decode",))
