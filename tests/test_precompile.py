"""Cold-start pipeline tests (`simtpu/engine/precompile.py`): parallel AOT
precompilation races, bit-identical placements with the pipeline on/off,
loud fallback, and the stretch-group fetch coalescing of the bulk dispatch.
"""

from __future__ import annotations

import numpy as np

from simtpu.core.objects import ResourceTypes, set_label
from simtpu.core.tensorize import Tensorizer
from simtpu.obs.metrics import REGISTRY
from simtpu import constants as C
from simtpu.synth import make_deployment, make_node
from simtpu.workloads.expand import get_valid_pods_exclude_daemonset


def _mixed_pods():
    """A pod list whose runs alternate bulk KINDS: plain threshold rounds,
    matrix rounds (multi-GPU), and domain-quota rounds (DoNotSchedule
    spread) — at least three kind-stretches in one dispatch."""
    res = ResourceTypes()
    res.deployments = [
        make_deployment("plain-a", 24, 100, 128),
        make_deployment("gpu-multi", 24, 100, 128, gpu_mem_mib=1000, gpu_count=2),
        make_deployment("plain-b", 24, 100, 128),
        make_deployment(
            "spread", 24, 100, 128,
            spread_topo="topology.kubernetes.io/zone", spread_hard=True,
        ),
    ]
    pods = get_valid_pods_exclude_daemonset(res)
    for pod in pods:
        set_label(pod, C.LABEL_APP_NAME, "mix")
    return pods


def _nodes(n=8):
    return [
        make_node(
            f"node-{i:03d}", 8000, 32,
            {
                "kubernetes.io/hostname": f"node-{i:03d}",
                "topology.kubernetes.io/zone": f"zone-{i % 4}",
            },
            gpu=(4, 16000),
        )
        for i in range(n)
    ]


def _place(pods, precompile: bool, engine_cls=None, wait_first: bool = False):
    from simtpu.engine.rounds import RoundsEngine

    tz = Tensorizer(_nodes())
    batch = tz.add_pods(pods)
    eng = (engine_cls or RoundsEngine)(tz)
    pipe = None
    if precompile:
        from simtpu.engine.precompile import precompile_place

        pipe = precompile_place(eng, batch)
        if wait_first:
            pipe.wait_all()
    nodes, reasons, _ = eng.place(batch)
    return np.asarray(nodes), np.asarray(reasons), pipe


def test_bulk_placements_bit_identical_with_pipeline():
    """Acceptance pin: the pipeline changes when/where compilation happens,
    never what executes — nodes and reasons byte-equal on/off."""
    pods = _mixed_pods()
    n_off, r_off, _ = _place(pods, precompile=False)
    n_on, r_on, pipe = _place(pods, precompile=True)
    assert np.array_equal(n_off, n_on)
    assert np.array_equal(r_off, r_on)
    pipe.wait_all()
    s = pipe.stats()
    assert s["submitted"] > 0
    assert s["failures"] == 0, s
    assert s["hits"] > 0, s


def test_concurrent_precompile_one_executable_per_signature():
    """The race pin: place() starts while the background compiles are still
    in flight; every dispatch whose signature is enumerated must WAIT on
    the in-flight compile rather than compiling its own copy — observable
    as exactly one jit trace per distinct executable (trace counters bump
    once per trace, shared by the AOT lowering and the jit path)."""
    import jax

    from simtpu.engine.scan import COMPILE_COUNT_KINDS
    from simtpu.obs.metrics import family as metrics_family

    def trace_counts():
        return metrics_family("compile", COMPILE_COUNT_KINDS)

    jax.clear_caches()  # compile accounting must start cold
    pods = _mixed_pods()
    c0 = trace_counts()
    # eager dispatch against in-flight compiles (wait_first=False)
    n_on, r_on, pipe = _place(pods, precompile=True, wait_first=False)
    pipe.wait_all()
    s = pipe.stats()
    delta = {k: trace_counts()[k] - c0.get(k, 0) for k in trace_counts()}
    # one executable per signature: had a dispatch compiled its own copy
    # next to the background one, the trace count would exceed the number
    # of distinct submitted + missed signatures
    assert s["failures"] == 0, s
    assert s["misses"] == 0, s  # full-capacity scenario: no leftover probes
    assert delta["rounds"] + delta["scan"] == s["submitted"], (delta, s)
    # and the results are the no-pipeline results
    n_off, r_off, _ = _place(pods, precompile=False)
    assert np.array_equal(n_off, n_on)
    assert np.array_equal(r_off, r_on)


def test_serial_engine_pipeline_identical():
    from simtpu.engine.scan import Engine

    pods = _mixed_pods()[:200]
    n_off, r_off, _ = _place(pods, precompile=False, engine_cls=Engine)
    n_on, r_on, pipe = _place(pods, precompile=True, engine_cls=Engine)
    assert np.array_equal(n_off, n_on)
    assert np.array_equal(r_off, r_on)
    pipe.wait_all()
    assert pipe.stats()["failures"] == 0


def test_stretch_group_fetch_coalescing():
    """Consecutive bulk stretches of DIFFERENT kinds must share ONE
    blocking device→host fetch (the stretch-group coalescing): the mixed
    batch has >= 3 kind-stretches and no scan segments or leftovers, so
    the whole placement pays exactly one fetch."""
    from simtpu.engine.rounds import RoundsEngine
    from simtpu.obs.metrics import family as metrics_family

    from simtpu.engine.scan import FETCH_KEYS

    def fetch_counts():
        return metrics_family("fetch", FETCH_KEYS)

    pods = _mixed_pods()
    tz = Tensorizer(_nodes())
    batch = tz.add_pods(pods)
    eng = RoundsEngine(tz)
    segments = eng._segments(batch, tz.freeze())
    kinds = [k for k, _, _ in segments]
    assert "scan" not in kinds
    assert len(set(kinds)) >= 3, kinds  # distinct bulk kinds interleave
    f0 = fetch_counts()["get"]
    nodes, _, _ = eng.place(batch)
    assert fetch_counts()["get"] - f0 == 1
    assert (np.asarray(nodes) >= 0).all()  # no leftovers in this scenario


def test_failed_compile_falls_back_loud(caplog):
    """A background compile failure must fall back to the jit path AND
    warn — never silently."""
    import logging

    from simtpu.engine.precompile import AotPipeline, _sds

    class _Boom:
        def lower(self, *args, **kwargs):
            raise RuntimeError("AOT lowering unsupported here")

    pipe = AotPipeline(workers=1)
    arg = np.zeros(3, np.float32)
    pipe.submit("boom", (), _Boom(), (_sds((3,), np.float32),))
    pipe.wait_all()
    before = REGISTRY.value("aot.failures")
    with caplog.at_level(logging.WARNING, logger="simtpu.precompile"):
        out = pipe.call("boom", (), (arg,), lambda: "fell-back")
    assert out == "fell-back"
    assert pipe.stats()["failures"] == 1
    # the process-wide counter chip_smoke.py asserts on
    assert REGISTRY.value("aot.failures") == before + 1
    assert any("AOT precompile" in rec.message for rec in caplog.records)
    # second call falls back again but does not re-warn (loud once)
    n_warn = len(caplog.records)
    out = pipe.call("boom", (), (arg,), lambda: "fell-back-2")
    assert out == "fell-back-2"
    assert len(caplog.records) == n_warn
    pipe.shutdown()


def test_unknown_signature_misses_to_jit_path():
    from simtpu.engine.precompile import AotPipeline

    pipe = AotPipeline(workers=1)
    out = pipe.call("never-submitted", (), (np.zeros(2, np.float32),), lambda: 7)
    assert out == 7
    assert pipe.stats()["misses"] == 1
    pipe.shutdown()


def test_incremental_plan_precompile_identical():
    """plan_capacity_incremental(precompile=True) answers exactly what the
    un-pipelined plan answers (shared-registry probe/verify engines
    included)."""
    from simtpu.plan.incremental import plan_capacity_incremental
    from simtpu.workloads.expand import seed_name_hashes
    from simtpu.core.objects import AppResource

    cluster = ResourceTypes()
    cluster.nodes = [
        make_node(
            f"node-{i:03d}", 8000, 32, {"kubernetes.io/hostname": f"node-{i:03d}"}
        )
        for i in range(4)
    ]
    res = ResourceTypes()
    res.deployments = [make_deployment(f"dep-{j}", 30, 1000, 512) for j in range(2)]
    apps = [AppResource(name="a", resource=res)]
    template = make_node("tmpl", 16000, 64, {"kubernetes.io/hostname": "tmpl"})

    seed_name_hashes(5)
    base = plan_capacity_incremental(
        cluster, apps, template, max_new_nodes=40, precompile=False
    )
    seed_name_hashes(5)
    piped = plan_capacity_incremental(
        cluster, apps, template, max_new_nodes=40, precompile=True
    )
    assert base.success and piped.success
    assert piped.nodes_added == base.nodes_added
    assert "compile_wall" in piped.timings
    assert "compile_wall" not in base.timings


def test_fault_sweep_signature_failed_aot_falls_back_loud(caplog, monkeypatch):
    """The scenario-batched fault-sweep signature gets the same loud
    warn-and-fallback contract as the scan/bulk signatures (ISSUE 6
    satellite): a failed background compile of the "fault_sweep"
    executable warns ONCE, every chunk falls back to the plain jit, and
    the sweep's outcome is identical to the un-pipelined run."""
    import logging

    import simtpu.faults.sweep as sweep_mod
    from simtpu.engine.precompile import AotPipeline
    from simtpu.faults import generate_scenarios, place_cluster, sweep_scenarios
    from simtpu.synth import synth_apps, synth_cluster

    cluster = synth_cluster(8, seed=13, zones=2)
    apps = synth_apps(24, seed=14, zones=2, pods_per_deployment=8)
    pc = place_cluster(cluster, apps)
    scen = generate_scenarios(cluster.nodes, "k=1")
    base = sweep_scenarios(pc, scen, s_chunk=4)

    class _NoLower:
        """The compiled sweep entry point with AOT lowering broken: the
        background compile fails, the jit fallback still works."""

        def __init__(self, real):
            self.real = real

        def lower(self, *args, **kwargs):
            raise RuntimeError("AOT lowering rejected (injected)")

        def __call__(self, *args, **kwargs):
            return self.real(*args, **kwargs)

    monkeypatch.setattr(
        sweep_mod, "_fault_sweep", _NoLower(sweep_mod._fault_sweep)
    )
    pipe = AotPipeline(workers=1)
    try:
        with caplog.at_level(logging.WARNING, logger="simtpu.precompile"):
            out = sweep_scenarios(pc, scen, s_chunk=4, pipeline=pipe)
        assert pipe.stats()["failures"] >= 1
        warned = [
            rec for rec in caplog.records if "fault_sweep" in rec.message
        ]
        assert len(warned) == 1  # loud once, not per chunk
        assert np.array_equal(out.requeue_rows, base.requeue_rows)
        assert np.array_equal(out.requeue_nodes, base.requeue_nodes)
        assert np.array_equal(out.requeue_reasons, base.requeue_reasons)
    finally:
        pipe.shutdown()
