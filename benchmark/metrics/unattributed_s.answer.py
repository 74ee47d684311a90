"""Seconds per answer on the answer's thread inside no span but the
root `apply`: what the spans do not yet attribute to a layer."""

from benchmark.attribution import unattributed_per_unit


def read(r):
    return unattributed_per_unit(r)
