"""The main path's device programs compile for a TPU v5e at chip_smoke.py's
real size (5,000 nodes x 150,000 pods), with no chip attached.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and the worker that runs this file keeps
it until it exits.  The programs are the ones the engines themselves
enumerate for AOT precompilation (`engine/precompile.py`), so the shapes
are the dispatched ones.  The persistent compilation cache is off around
these compiles: an entry written for a described chip cannot be read back
without one.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def phase_b():
    """chip_smoke.py's phase-b batch, tensorized: (tensorizer, batch)."""
    import chip_smoke
    from simtpu import constants as C
    from simtpu.core.objects import set_label
    from simtpu.core.tensorize import Tensorizer
    from simtpu.workloads.expand import get_valid_pods_exclude_daemonset

    cluster, apps, _ = chip_smoke.phase_b_problem()
    pods = []
    for app in apps:
        expanded = get_valid_pods_exclude_daemonset(app.resource)
        for pod in expanded:
            set_label(pod, C.LABEL_APP_NAME, app.name)
        pods.extend(expanded)
    assert len(cluster.nodes) == 5_000 and len(pods) == 150_000
    tensorizer = Tensorizer(
        cluster.nodes,
        extra_resources=("open-local",),
        storage_classes=list(cluster.storage_classes),
    )
    return tensorizer, tensorizer.add_pods(pods)


class _Recorder:
    """Stands in for the AOT pipeline: keeps each enumerated program."""

    def __init__(self):
        self.jobs = []

    def submit(self, name, static_tail, fn, args_sds):
        self.jobs.append((name, static_tail, fn, args_sds))
        return True


def _first_program(engine, batch, kind):
    from simtpu.engine.precompile import precompile_place

    rec = _Recorder()
    precompile_place(engine, batch, rec)
    jobs = [j for j in rec.jobs if kind in str(j[0])]
    assert jobs, f"no {kind!r} program among {[str(j[0]) for j in rec.jobs]}"
    return jobs[0]


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def test_scan_step_compiles(phase_b, one_chip):
    from simtpu.engine.scan import Engine

    tensorizer, batch = phase_b
    engine = Engine(tensorizer)
    engine.speculate = False  # the plain serial scan, not its wavefronts
    _, tail, fn, args = _first_program(engine, batch, "scan")
    fn.lower(*_on(args, one_chip), *tail).compile()


def test_bulk_rounds_chunk_compiles(phase_b, one_chip):
    from simtpu.engine.rounds import RoundsEngine

    tensorizer, batch = phase_b
    _, tail, fn, args = _first_program(RoundsEngine(tensorizer), batch, "rounds")
    fn.lower(*_on(args, one_chip), *tail).compile()


def test_node_sharded_rounds_compiles_on_four_chips(phase_b, topo):
    from simtpu.parallel import ShardedRoundsEngine, make_mesh

    tensorizer, batch = phase_b
    mesh = make_mesh(list(topo.devices))
    assert mesh.devices.size == 4
    engine = ShardedRoundsEngine(tensorizer, mesh)
    _, tail, fn, args = _first_program(engine, batch, "sharded_rounds")
    compiled = fn.lower(*args, *tail).compile()
    assert "all-reduce" in compiled.as_text()  # the node axis really split


def test_solver_relaxation_compiles(phase_b, one_chip):
    """The planner's bucket: every candidate clone count 0..128 of the
    template over the 5,000 nodes, one vmapped dispatch."""
    from simtpu.solve.relax import _pow2, _relax_kernel, build_relax_problem, solver_iters

    tensorizer, batch = phase_b
    tensors = tensorizer.freeze()
    prob = build_relax_problem(tensors, batch)
    s, n = 129, len(tensors.alloc) + 128
    cp, np_, rp = _pow2(len(prob.cnt)), _pow2(n), _pow2(prob.cap.shape[1])
    sds = [
        ((cp, np_), bool), ((cp, rp), np.float32), ((cp,), np.float32),
        ((np_, rp), np.float32), ((np_, rp), np.float32),
        ((_pow2(s), np_), bool), ((), np.float32),
    ]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip) for sh, dt in sds]
    _relax_kernel.lower(solver_iters(), *args).compile()


def test_audit_bulk_jit_compiles_for_the_host_in_f64(phase_b):
    """The auditor's f64 prefix algebra over every pod of the batch.  The
    v5e compiler crashes (SIGSEGV) on this program past ~16k placed pods,
    so the audit runs on the host CPU backend (`audit/checker.py`
    `_bulk_flags_jax`); this compiles it there at the real width."""
    from simtpu.audit.checker import (
        _bulk_jit_args, _entries_from_batch, _get_bulk_jit, _host_cpu,
    )

    tensorizer, batch = phase_b
    tensors = tensorizer.freeze()
    n = len(tensors.alloc)
    nodes = np.arange(len(batch.group)) % n
    entries = _entries_from_batch(tensors, batch, nodes, None)
    host = SingleDeviceSharding(_host_cpu())
    with jax.enable_x64(True):
        args = _bulk_jit_args(tensors, entries, np.ones(n, bool))
        sds = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=host) for a in args]
        text = _get_bulk_jit().lower(*sds).compile().as_text()
    assert "f64" in text


def test_audit_jit_path_runs_under_installed_jax():
    """The auditor's jit path imports and runs on the CPU (jax 0.9 dropped
    `jax.experimental.enable_x64`, which once broke every certified path)."""
    from simtpu.api import simulate
    from simtpu.synth import synth_apps, synth_cluster

    result = simulate(
        synth_cluster(8, seed=1, zones=2),
        synth_apps(40, seed=2, zones=2, pods_per_deployment=10),
        audit=True,
    )
    assert result.audit.mode == "jit"
    assert result.audit.ok
