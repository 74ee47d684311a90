"""Device-idle seconds per answer inside the blocking `fetch.get` spans,
mapped onto the device trace through the program's `obs.clock` anchors:
what the fetches cost beyond the device's own compute."""

from benchmark.attribution import fetch_idle_per_unit


def read(r):
    return fetch_idle_per_unit(r)
