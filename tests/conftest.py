"""Test configuration.

Tests run on CPU with a virtual 8-device topology so multi-chip sharding
(`simtpu.parallel`) is exercised without TPU hardware, per the driver contract.
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never claim an accelerator
# Speculative wavefront dispatch is OFF for the general suite: placements
# are bit-identical either way (that IS the pinned contract), but every
# tiny test problem would otherwise compile its own wavefront executables
# on top of the scan/round bodies — a suite-wide compile tax that pushed
# the fast tier against its wall-clock budget.  tests/test_wavefront.py
# (and anything else that wants the dispatcher) sets Engine.speculate
# explicitly, which overrides this default.
os.environ.setdefault("SIMTPU_WAVEFRONT", "0")
# Flight-recorder bundles (obs/flight.py) default to the CWD when no
# checkpoint dir is involved — under pytest that is the repo root, which
# the exit-3/exit-4 CLI tests would litter with simtpu-flight-*.json.
# Point the default at a per-session temp dir; tests that assert on
# bundles override SIMTPU_FLIGHT_DIR themselves (monkeypatch wins).
import tempfile  # noqa: E402

os.environ.setdefault(
    "SIMTPU_FLIGHT_DIR", tempfile.mkdtemp(prefix="simtpu-flight-tests-")
)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# NOTE: the persistent compilation cache is deliberately NOT enabled here:
# tests run on the CPU backend, whose cached-executable loader can segfault
# on this host (see simtpu/cache.py) — enable_compilation_cache() itself
# refuses CPU backends for the same reason.

import pytest  # noqa: E402

REFERENCE_EXAMPLES = "/root/reference/example"


@pytest.fixture(scope="session")
def example_dir():
    if not os.path.isdir(REFERENCE_EXAMPLES):
        pytest.skip("reference example fixtures not available")
    return REFERENCE_EXAMPLES


@pytest.fixture(scope="module", autouse=True)
def _drop_xla_executables():
    """Release each module's compiled XLA:CPU executables.

    A single long pytest process accumulates hundreds of loaded CPU
    executables; past ~190 tests the host's XLA:CPU
    `backend_compile_and_load` starts segfaulting (the same toolchain
    fault class simtpu/cache.py works around).  Dropping the jit caches
    between modules keeps the resident-executable count bounded at the
    cost of cross-module recompiles.  `tools/run_tests.py` goes further
    (one subprocess per module) and is the canonical full-suite entry."""
    yield
    jax.clear_caches()
