"""Seeded input generators; a configuration names its module under `generator`."""

import importlib


def build(cfg: dict, seed: int):
    """The configuration's problem for one run's seed."""
    return importlib.import_module(f"benchmark.gen.{cfg['generator']}").build(cfg, seed)
