"""Speculative wavefront scan (engine/scan.py, docs/speculation.md): the
batched verify-and-rollback dispatcher must place BIT-IDENTICALLY to the
pod-at-a-time scan on every constraint mix — including the quota (hard
spread/anti), matrix (multi-GPU/multi-LVM), and preemption-free priority
variants — and under GSPMD node sharding; the accept/rollback telemetry must
account for every wavefront pod; and a forced conflict must roll back and
still reproduce the serial answer.
"""

from __future__ import annotations

import numpy as np
import pytest

from simtpu import constants as C
from simtpu.core.objects import set_label
from simtpu.core.tensorize import Tensorizer
from simtpu.engine.scan import WAVE_KEYS, Engine
from simtpu.obs.metrics import family as metrics_family


def wave_counts():
    # registry-backed speculation counters (the alias view is gone)
    return metrics_family("wavefront", WAVE_KEYS)
from simtpu.synth import make_deployment, make_node, synth_apps, synth_cluster
from simtpu.workloads.expand import (
    get_valid_pods_exclude_daemonset,
    seed_name_hashes,
)


def _expand(apps):
    pods = []
    for app in apps:
        expanded = get_valid_pods_exclude_daemonset(app.resource)
        for pod in expanded:
            set_label(pod, C.LABEL_APP_NAME, app.name)
        pods.extend(expanded)
    return pods


def _mix_problem(mix: str, seed: int):
    """A small problem whose pod sequence is dominated by same-group runs
    (24-replica deployments) under the named constraint mix."""
    hard = mix == "hard"
    matrix = mix == "matrix"
    cluster = synth_cluster(
        24, seed=seed, zones=3, taint_frac=0.1,
        storage_frac=0.4, gpu_frac=0.5 if matrix else 0.0,
    )
    apps = synth_apps(
        240,
        seed=seed + 1,
        zones=3,
        pods_per_deployment=24,
        selector_frac=0.2,
        toleration_frac=0.1,
        anti_affinity_frac=0.25,
        anti_affinity_hard_frac=0.4 if hard else 0.0,
        spread_frac=0.3,
        spread_hard_frac=0.5 if hard else 0.0,
        gpu_frac=0.25 if matrix else 0.0,
        gpu_multi_frac=0.5 if matrix else 0.0,
        storage_frac=0.25,
        storage_device_frac=0.0 if matrix else 0.3,
        lvm_multi_frac=0.5 if matrix else 0.0,
        affinity_frac=0.15 if matrix else 0.0,
    )
    if mix == "priority":
        # preemption-free priority spread: distinct priorities per
        # deployment, ample capacity (nothing is ever evicted — priority
        # only orders the queue)
        for i, app in enumerate(apps):
            for dep in app.resource.deployments:
                dep["spec"]["template"]["spec"]["priority"] = (i % 4) * 100
    return cluster, apps


def _place(cluster, apps, speculate, engine_cls=Engine, **engine_kw):
    seed_name_hashes(0)
    pods = _expand(apps)
    tz = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
    batch = tz.add_pods(pods)
    eng = engine_cls(tz, **engine_kw)
    eng.speculate = speculate
    nodes, reasons, extras = eng.place(batch)
    return nodes, reasons, extras


def _assert_identical(a, b):
    nodes_a, reasons_a, extras_a = a
    nodes_b, reasons_b, extras_b = b
    assert np.array_equal(nodes_a, nodes_b)
    assert np.array_equal(reasons_a, reasons_b)
    for key in extras_a:
        assert np.array_equal(
            np.asarray(extras_a[key]), np.asarray(extras_b[key])
        ), key


MIXES = ("north", "hard", "matrix", "priority")


class TestWavefrontBitIdentity:
    # the matrix mix is the heavyweight cell; CI's fuzz-smoke matrix
    # covers wavefront bit-identity on every push, so it rides the
    # slow tier to keep tier-1 inside its wall budget
    @pytest.mark.parametrize(
        "mix",
        [pytest.param(m, marks=pytest.mark.slow) if m == "matrix" else m
         for m in MIXES])
    def test_identical_to_pod_at_a_time(self, mix):
        """The headline guarantee: wavefront placements (nodes, reasons,
        extended-resource allocations) are bit-identical to the serial
        scan on every mix, and the wavefront path actually engaged."""
        cluster, apps = _mix_problem(mix, seed=7)
        base = _place(cluster, apps, speculate=False)
        before = wave_counts()
        wave = _place(cluster, apps, speculate=True)
        after = wave_counts()
        _assert_identical(base, wave)
        assert after["pods"] > before["pods"], "no wavefront engaged"

    @pytest.mark.slow
    @pytest.mark.parametrize("mix", MIXES)
    @pytest.mark.parametrize("seed", [21, 33])
    def test_identical_more_seeds(self, mix, seed):
        cluster, apps = _mix_problem(mix, seed=seed)
        base = _place(cluster, apps, speculate=False)
        wave = _place(cluster, apps, speculate=True)
        _assert_identical(base, wave)

    @pytest.mark.slow
    def test_identical_under_sliced_chunk_contexts(self):
        """Forced tiny chunk/row budgets exercise the group- and term-row-
        sliced statics contexts the wavefront dispatch composes with."""
        from simtpu.engine.scan import (
            build_pod_arrays,
            default_wave_call,
            flags_from,
            run_scan_chunked,
            statics_from,
        )
        from simtpu.engine.state import build_state

        cluster, apps = _mix_problem("north", seed=11)
        seed_name_hashes(0)
        pods = _expand(apps)
        tz = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
        batch = tz.add_pods(pods)
        tensors = tz.freeze()
        statics = statics_from(tensors)
        flags = flags_from(tensors, batch.ext)
        r = tensors.alloc.shape[1]
        _, pod_arrays = build_pod_arrays(batch, r)
        groups = np.asarray(batch.group)

        def fresh():
            return build_state(
                tensors, np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros((0, r), np.float32), None,
            )

        _, base = run_scan_chunked(
            statics, fresh(), pod_arrays, flags, tensors, groups,
            chunk=32, row_budget=4,
        )
        _, wave = run_scan_chunked(
            statics, fresh(), pod_arrays, flags, tensors, groups,
            chunk=32, row_budget=4, wave_call=default_wave_call,
        )
        for a, b in zip(base, wave):
            assert np.array_equal(a, b)


class TestWavefrontSharded:
    def test_identical_under_gspmd(self):
        """--shard equivalence: the mesh-compiled wavefront must place
        identically to the unsharded serial scan (dead-node padding plus
        the sharded reduced carries)."""
        from simtpu.parallel import ShardedEngine, make_mesh

        cluster, apps = _mix_problem("north", seed=9)
        base = _place(cluster, apps, speculate=False)
        mesh = make_mesh(sweep=1)
        before = wave_counts()
        sharded = _place(
            cluster, apps, speculate=True,
            engine_cls=ShardedEngine, mesh=mesh,
        )
        after = wave_counts()
        _assert_identical(base, sharded)
        assert after["pods"] > before["pods"], "sharded wavefronts not engaged"


class TestWavefrontRollback:
    def _conflict_problem(self):
        """Three identical nodes and one 12-replica run sized so the
        speculative wavefront-start answer (every pod on the argmax node)
        diverges immediately — the serial engine spreads — and nodes fill
        up mid-run, flipping the fit mask (the lean verifier's rollback
        trigger)."""
        from simtpu.core.objects import AppResource, ResourceTypes

        cluster = ResourceTypes()
        cluster.nodes = [
            make_node(
                f"n-{i}", 4000, 8,
                {"kubernetes.io/hostname": f"n-{i}",
                 "topology.kubernetes.io/zone": "z0"},
            )
            for i in range(3)
        ]
        res = ResourceTypes()
        res.deployments.append(make_deployment("burst", 12, 1000, 1024))
        return cluster, [AppResource(name="burst", resource=res)]

    def test_forced_conflict_rolls_back_to_serial_answer(self):
        cluster, apps = self._conflict_problem()
        base = _place(cluster, apps, speculate=False)
        before = wave_counts()
        wave = _place(cluster, apps, speculate=True)
        after = wave_counts()
        diff = {k: after[k] - before[k] for k in after}
        # 12 pods on 4-slot nodes: serial spreads while speculation drafts
        # one node — divergences must be detected and the rolled-back pods
        # replayed to the exact serial answer
        assert diff["pods"] == 12
        assert diff["rollbacks"] >= 1
        assert diff["rollback_pods"] >= 1
        _assert_identical(base, wave)
        # capacity is exactly 12 pods; everything must have placed
        assert int((wave[0] >= 0).sum()) == 12

    def test_overflow_tail_reasons_exact(self):
        """A run that exhausts the cluster mid-wavefront: the unplaced
        tail's failure reasons must match the serial scan exactly (the
        verifier's fail-code cascade)."""
        from simtpu.core.objects import AppResource, ResourceTypes

        cluster = ResourceTypes()
        cluster.nodes = [
            make_node(
                f"n-{i}", 4000, 8,
                {"kubernetes.io/hostname": f"n-{i}",
                 "topology.kubernetes.io/zone": "z0"},
            )
            for i in range(2)
        ]
        res = ResourceTypes()
        res.deployments.append(make_deployment("over", 12, 1000, 1024))
        apps = [AppResource(name="over", resource=res)]
        base = _place(cluster, apps, speculate=False)
        wave = _place(cluster, apps, speculate=True)
        _assert_identical(base, wave)
        assert int((wave[0] < 0).sum()) == 4  # 8 slots, 12 pods
        from simtpu.engine.scan import FAIL_RESOURCES

        assert set(np.asarray(wave[1])[np.asarray(wave[0]) < 0]) == {
            FAIL_RESOURCES
        }

    def test_interpod_blocked_tail_reason_exact(self):
        """A lean run emptied by EXISTING pods' required anti-affinity
        (sym_violated — the run owns no terms of its own) must report the
        serial scan's FAIL_INTERPOD, not a later cascade stage: the lean
        verifier's fail cascade keeps the interpod mask out of the spread
        stage (regression — it used to fold m_nofit in and report
        FAIL_SPREAD)."""
        from simtpu.core.objects import AppResource, ResourceTypes

        cluster = ResourceTypes()
        cluster.nodes = [
            make_node(
                f"n-{i}", 64000, 64,
                {"kubernetes.io/hostname": f"n-{i}",
                 "topology.kubernetes.io/zone": "z0"},
            )
            for i in range(4)
        ]
        # a placed group owning required anti-affinity that selects the
        # lean run's label — every node's domain then rejects the run
        blocker = make_deployment("blk", 4, 250, 1)
        blocker["spec"]["template"]["spec"]["affinity"] = {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "labelSelector": {"matchLabels": {"app": "web"}},
                        "topologyKey": "kubernetes.io/hostname",
                    }
                ]
            }
        }
        res_b = ResourceTypes()
        res_b.deployments.append(blocker)
        res_w = ResourceTypes()
        res_w.deployments.append(make_deployment("web", 8, 250, 1))
        apps = [
            AppResource(name="blk", resource=res_b),
            AppResource(name="web", resource=res_w),
        ]
        base = _place(cluster, apps, speculate=False)
        wave = _place(cluster, apps, speculate=True)
        _assert_identical(base, wave)
        from simtpu.engine.scan import FAIL_INTERPOD

        unplaced = np.asarray(wave[0]) < 0
        assert unplaced.sum() == 8  # the whole web run is blocked
        assert set(np.asarray(wave[1])[unplaced]) == {FAIL_INTERPOD}

    def test_counters_account_for_every_wavefront_pod(self):
        cluster, apps = _mix_problem("north", seed=13)
        before = wave_counts()
        _place(cluster, apps, speculate=True)
        after = wave_counts()
        diff = {k: after[k] - before[k] for k in after}
        assert diff["pods"] > 0
        assert diff["accepted"] + diff["rollback_pods"] == diff["pods"]
        assert diff["rollbacks"] <= diff["wavefronts"]


class TestWavefrontPrecompile:
    def test_aot_registry_serves_wavefronts(self):
        """precompile_place must enumerate the wavefront signatures so the
        first dispatch finds them in the registry (hits > 0) — with
        placements identical to the plain-jit path."""
        from simtpu.engine.precompile import precompile_place

        cluster, apps = _mix_problem("north", seed=17)
        base = _place(cluster, apps, speculate=True)

        seed_name_hashes(0)
        pods = _expand(apps)
        tz = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
        batch = tz.add_pods(pods)
        eng = Engine(tz)
        eng.speculate = True
        pipe = precompile_place(eng, batch)
        try:
            nodes, reasons, extras = eng.place(batch)
            pipe.wait_all()
            stats = pipe.stats()
        finally:
            pipe.shutdown()
        _assert_identical(base, (nodes, reasons, extras))
        assert stats["hits"] > 0
        assert stats["failures"] == 0


@pytest.mark.slow
class TestFullScaleSpotCheck:
    def test_north_star_stretch_exact_vs_bulk(self):
        """VERDICT r5 next-round #5: one sampled ~10k-pod stretch of the
        north-star mix at 100k nodes through the (wavefront) exact scan,
        cross-checked against the bulk engine within the documented
        divergence classes (placed-count band — the bulk round's
        round-boundary packing may strand or save a sliver relative to
        the serial order; see tests/test_fuzz.py)."""
        import os

        n_nodes = int(os.environ.get("SIMTPU_SPOTCHECK_NODES", 100_000))
        n_pods = int(os.environ.get("SIMTPU_SPOTCHECK_PODS", 10_000))
        cluster = synth_cluster(
            n_nodes, seed=3, zones=16, taint_frac=0.1, storage_frac=0.3
        )
        apps = synth_apps(
            n_pods,
            seed=4,
            zones=16,
            pods_per_deployment=1000,
            selector_frac=0.2,
            toleration_frac=0.1,
            anti_affinity_frac=0.2,
            spread_frac=0.3,
            storage_frac=0.2,
            storage_device_frac=0.3,
        )
        from simtpu.engine.rounds import RoundsEngine

        seed_name_hashes(0)
        pods = _expand(apps)
        tz = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
        batch = tz.add_pods(pods)

        eng = Engine(tz)
        eng.speculate = True
        before = wave_counts()
        nodes_exact, reasons_exact, _ = eng.place(batch)
        after = wave_counts()
        assert after["pods"] - before["pods"] > n_pods // 2, (
            "the stretch should be wavefront-dominated"
        )

        seed_name_hashes(0)
        tz2 = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
        batch2 = tz2.add_pods(pods)
        bulk = RoundsEngine(tz2)
        nodes_bulk, reasons_bulk, _ = bulk.place(batch2)

        placed_exact = int((nodes_exact >= 0).sum())
        placed_bulk = int((nodes_bulk >= 0).sum())
        tol = max(1, placed_exact // 100)  # the fuzz suite's 1% band
        assert abs(placed_exact - placed_bulk) <= tol, (
            placed_exact, placed_bulk,
        )
        # node-capacity feasibility of the exact placement: no node
        # oversubscribed (placements respect the serial fit semantics)
        tensors = tz.freeze()
        r = tensors.alloc.shape[1]
        used = np.zeros_like(tensors.alloc, dtype=np.float64)
        req = np.asarray(batch.req, np.float64)
        if req.shape[1] < r:
            req = np.pad(req, ((0, 0), (0, r - req.shape[1])))
        ok = nodes_exact >= 0
        np.add.at(used, nodes_exact[ok], req[ok, :r])
        assert (used <= tensors.alloc * (1 + 1e-5) + 1e-6).all()


class TestHeavyDrafting:
    """ISSUE 16 tentpole: storage/GPU/ports/volume pods — excluded from
    drafting entirely by the pre-round-16 lean rule — ride the HARD
    verifier with the extra resource stages (ports conflicts, LVM/device
    allocation, GPU-share fitting) recomputed inside the verify scan.
    Placements stay bit-identical to the serial scan, the accept rate on
    those previously-skipped pods is > 0, and the result audits clean."""

    def _all_heavy_problem(self):
        """Every pod carries a heavy feature (LVM, exclusive device, GPU
        share, or hostPort): the lean rule drafts NOTHING here, so every
        drafted pod must ride the hard verifier."""
        from simtpu.core.objects import AppResource, ResourceTypes

        cluster = synth_cluster(
            24, seed=17, zones=3, taint_frac=0.0,
            gpu_frac=0.6, storage_frac=0.6,
        )
        res = ResourceTypes()
        res.deployments = [
            make_deployment("lvmy", 24, 500, 256, lvm_gib=5),
            make_deployment("gpuey", 24, 500, 256, gpu_mem_mib=1024),
            make_deployment("devy", 12, 300, 256, device_gib=10),
            make_deployment("porty", 16, 100, 128, host_port=8080),
        ]
        return cluster, [AppResource(name="heavy", resource=res)]

    def test_all_heavy_mix_accepts_where_legacy_skips(self):
        cluster, apps = self._all_heavy_problem()
        serial = _place(cluster, apps, speculate=False)
        before = wave_counts()
        wave = _place(cluster, apps, speculate=True)
        after = wave_counts()
        _assert_identical(serial, wave)
        drafted = after["pods"] - before["pods"]
        accepted = after["accepted"] - before["accepted"]
        hard = after["draft_hard"] - before["draft_hard"]
        assert drafted > 0, "no heavy pod was drafted"
        assert hard > 0, "heavy pods must ride the hard verifier"
        # no pod of this mix is LEAN, so no wavefront may take the lean
        # verifier: every drafted pod is a hard-verifier pod
        assert hard == drafted, (
            f"{drafted - hard} of {drafted} all-heavy pods drafted as lean"
        )
        assert accepted > 0, (
            f"wavefront_accept_rate is 0 on the all-heavy mix "
            f"({drafted} drafted)"
        )
        rate = accepted / drafted
        assert 0 < rate <= 1

    # one gnarly seed stays in tier-1; the other two ride the slow tier
    # (fuzz-smoke sweeps the full seeded corpus in CI regardless)
    @pytest.mark.parametrize(
        "seed",
        [5,
         pytest.param(7, marks=pytest.mark.slow),
         pytest.param(12, marks=pytest.mark.slow)])
    def test_fuzz_gnarly_mixes_identical_and_audit_clean(self, seed):
        """Seeded gnarly storage/GPU/ports mixes (audit/fuzz.gen_case —
        seed 7 draws all three): wavefront == serial bit-identically, the
        hard-drafting path engages, and the placement audits clean."""
        from simtpu.audit.checker import audit_placement, extras_from_log
        from simtpu.audit.fuzz import gen_case
        from simtpu.faults import place_cluster

        cluster, apps, mix = gen_case(seed, n_nodes=16, n_pods=96)
        assert mix["gpu_frac"] or mix["storage_frac"] or mix["ports"]
        serial = place_cluster(cluster, apps, bulk=False, speculate=False)
        before = wave_counts()
        wave = place_cluster(cluster, apps, bulk=False, speculate=True)
        after = wave_counts()
        assert np.array_equal(serial.nodes, wave.nodes)
        assert after["pods"] > before["pods"], "no wavefront engaged"
        assert after["draft_hard"] > before["draft_hard"], (
            "gnarly mix never engaged the hard verifier"
        )
        rep = audit_placement(
            wave.tensors, wave.batch, wave.nodes, extras_from_log(wave)
        )
        assert rep.ok, rep
