"""Tests for the DefaultPreemption analog (`simtpu/api.py _try_preempt`,
mirroring `vendor/.../plugins/defaultpreemption/default_preemption.go`).
"""

from __future__ import annotations

import os

import pytest

from simtpu.api import simulate

# wall-clock envelopes only fire on dedicated perf runs (advisor low, round
# 4): explicit opt-in, anything else keeps them off
_PERF_ASSERT = os.environ.get("SIMTPU_PERF_ASSERT", "").lower() in ("1", "true", "yes", "on")
from simtpu.core.objects import ResourceTypes  # noqa: E402

from .fixtures import (  # noqa: E402
    make_fake_node,
    make_fake_pod,
    with_node_labels,
    with_pod_affinity,
    with_pod_labels,
)


def _prio(pod, p):
    pod["spec"]["priority"] = p
    return pod


def _placements(result):
    out = {}
    for status in result.node_status:
        for pod in status.pods:
            out[pod["metadata"]["name"]] = status.node["metadata"]["name"]
    return out


def test_high_priority_pod_preempts_lower():
    node = make_fake_node("n0", "10", "16Gi")
    fillers = [
        _prio(make_fake_pod(f"low{i}", "default", "4", "1Gi"), 0) for i in range(2)
    ]
    vip = _prio(make_fake_pod("vip", "default", "6", "1Gi"), 1000)
    result = simulate(ResourceTypes(nodes=[node], pods=fillers + [vip]))
    placed = _placements(result)
    assert "vip" in placed
    assert len(result.preempted_pods) == 1
    assert result.preempted_pods[0].pod["metadata"]["name"].startswith("low")
    assert result.preempted_pods[0].preempted_by == "default/vip"
    assert result.preempted_pods[0].node == "n0"
    # one low pod survives: 4 + 6 = 10 cpu
    assert sum(1 for name in placed if name.startswith("low")) == 1
    assert not result.unscheduled_pods


def test_equal_priority_does_not_preempt():
    node = make_fake_node("n0", "10", "16Gi")
    fillers = [
        _prio(make_fake_pod(f"low{i}", "default", "4", "1Gi"), 10) for i in range(2)
    ]
    pod = _prio(make_fake_pod("late", "default", "6", "1Gi"), 10)
    result = simulate(ResourceTypes(nodes=[node], pods=fillers + [pod]))
    assert not result.preempted_pods
    assert len(result.unscheduled_pods) == 1
    assert result.unscheduled_pods[0].pod["metadata"]["name"] == "late"


def test_picks_node_with_lowest_victim_priority():
    # n0 carries a prio-50 pod, n1 a prio-5 pod; preemptor (prio 100) must
    # evict from n1 (lowest max victim priority)
    n0 = make_fake_node("n0", "4", "16Gi")
    n1 = make_fake_node("n1", "4", "16Gi")
    p0 = _prio(make_fake_pod("mid", "default", "4", "1Gi"), 50)
    p0["spec"]["nodeName"] = "n0"
    p1 = _prio(make_fake_pod("small", "default", "4", "1Gi"), 5)
    p1["spec"]["nodeName"] = "n1"
    vip = _prio(make_fake_pod("vip", "default", "3", "1Gi"), 100)
    result = simulate(ResourceTypes(nodes=[n0, n1], pods=[p0, p1, vip]))
    placed = _placements(result)
    assert placed.get("vip") == "n1"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["small"]


def test_minimal_victim_set():
    # evicting ONE 2-cpu victim suffices for the 2-cpu preemptor; both lows
    # must not be evicted
    node = make_fake_node("n0", "8", "16Gi")
    fillers = [
        _prio(make_fake_pod(f"low{i}", "default", "2", "1Gi"), 0) for i in range(4)
    ]
    vip = _prio(make_fake_pod("vip", "default", "2", "1Gi"), 9)
    result = simulate(ResourceTypes(nodes=[node], pods=fillers + [vip]))
    assert len(result.preempted_pods) == 1
    assert not result.unscheduled_pods


def test_mid_batch_failure_keeps_bookkeeping_aligned():
    # the failing pod is NOT last in its batch: a pod placed after it in the
    # same batch must not skew the engine-log ↔ simulator bookkeeping
    node = make_fake_node("n0", "10", "16Gi")
    pods = [
        _prio(make_fake_pod("low0", "default", "4", "1Gi"), 0),
        _prio(make_fake_pod("low1", "default", "4", "1Gi"), 0),
        _prio(make_fake_pod("vip", "default", "6", "1Gi"), 1000),
        _prio(make_fake_pod("tiny", "default", "1", "1Gi"), 0),
    ]
    result = simulate(ResourceTypes(nodes=[node], pods=pods))
    placed = _placements(result)
    # low0+low1+tiny place first (9 cpu); vip preempts the minimal victim
    # set {tiny, low1} (latest lowest-priority placements) and lands
    assert "vip" in placed
    assert not result.unscheduled_pods
    names = {p.pod["metadata"]["name"] for p in result.preempted_pods}
    assert names == {"tiny", "low1"}
    assert set(placed) == {"low0", "vip"}


def test_wave_commit_never_rides_restored_victims():
    """Advisor finding (round 4): in a preemption wave, a pod committed
    before the first verify failure f may have verify-landed on a node that
    only had room because of f's evictions (the batched placement applies
    ALL wave evictions).  Restoring f's victims under it silently
    overcommits the node — impossible in the serial evict/retry/undo flow.

    Construction: preemptors A (10 cpu) and B (20 cpu) both fail and wave
    together.  A's proposal evicts fA on nA (lowest victim priority), B's
    evicts fB on nB.  With both evictions applied, the score pipeline sends
    A to the roomier nB; B then cannot fit and fails verify.  The buggy
    flow committed A on nB and restored fB beside it (30 cpu on a 20-cpu
    node).  The fixed flow demotes A, lets B's authoritative retry land on
    nB, and re-verifies A — converging to the serial-exact placement."""
    nA = make_fake_node("nA", "10", "16Gi")
    nB = make_fake_node("nB", "20", "32Gi")
    fA = _prio(make_fake_pod("fa", "default", "10", "1Gi"), 0)
    fA["spec"]["nodeName"] = "nA"
    fB = _prio(make_fake_pod("fb", "default", "20", "2Gi"), 1)
    fB["spec"]["nodeName"] = "nB"
    a = _prio(make_fake_pod("a", "default", "10", "1Gi"), 100)
    b = _prio(make_fake_pod("b", "default", "20", "2Gi"), 100)
    result = simulate(ResourceTypes(nodes=[nA, nB], pods=[fA, fB, a, b]))
    placed = _placements(result)
    # the serial flow places both preemptors, evicting both fillers
    assert placed.get("a") == "nA"
    assert placed.get("b") == "nB"
    assert not result.unscheduled_pods
    assert {p.pod["metadata"]["name"] for p in result.preempted_pods} == {"fa", "fb"}
    # the no-overcommit invariant the buggy flow violated: per-node summed
    # cpu requests within allocatable
    cap = {"nA": 10.0, "nB": 20.0}
    used: dict = {}
    for status in result.node_status:
        name = status.node["metadata"]["name"]
        for pod in status.pods:
            cpu = pod["spec"]["containers"][0]["resources"]["requests"]["cpu"]
            used[name] = used.get(name, 0.0) + float(cpu)
    for name, total in used.items():
        assert total <= cap[name] + 1e-9, (name, total)


def test_affinity_dependent_head_not_finalized():
    """ADVICE r5 #3 regression: a retried head whose verify success depends
    on another wave pod BEING placed (required positive affinity to it)
    must not be finalized by retry finality — the head verifies FIRST in
    its wave, so its fresh attempt never sees the anchor pod placed.

    Construction: both nodes are full of prio-0 fillers.  X (needs
    colocation with app=anchor on a hostname domain) and D (carries
    app=anchor) both fail on resources and wave together, X first.  X's
    verify keeps failing on inter-pod affinity until D lands; the old
    finality rule recorded X unscheduled on its second fresh failure.  With
    the exemption, X re-queues BEHIND D, D places, and X colocates."""
    n0 = make_fake_node(
        "n0", "10", "16Gi", with_node_labels({"kubernetes.io/hostname": "n0"})
    )
    n1 = make_fake_node(
        "n1", "10", "16Gi", with_node_labels({"kubernetes.io/hostname": "n1"})
    )
    f0 = _prio(make_fake_pod("f0", "default", "10", "1Gi"), 0)
    f0["spec"]["nodeName"] = "n0"
    f1 = _prio(make_fake_pod("f1", "default", "10", "1Gi"), 0)
    f1["spec"]["nodeName"] = "n1"
    x = _prio(
        make_fake_pod(
            "x", "default", "5", "1Gi",
            with_pod_affinity({
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "anchor"}},
                            "topologyKey": "kubernetes.io/hostname",
                        }
                    ]
                }
            }),
        ),
        100,
    )
    d = _prio(
        make_fake_pod(
            "d", "default", "5", "1Gi", with_pod_labels({"app": "anchor"})
        ),
        100,
    )
    result = simulate(ResourceTypes(nodes=[n0, n1], pods=[f0, f1, x, d]))
    placed = _placements(result)
    assert not result.unscheduled_pods, [
        u.reason for u in result.unscheduled_pods
    ]
    # the affinity actually binds: x shares d's node
    assert placed.get("x") == placed.get("d")


def test_preempts_port_holder():
    import copy

    node = make_fake_node("n0", "32", "64Gi")
    low = _prio(make_fake_pod("low", "default", "1", "1Gi"), 0)
    low["spec"]["containers"][0]["ports"] = [
        {"containerPort": 80, "hostPort": 80, "protocol": "TCP"}
    ]
    vip = _prio(copy.deepcopy(low), 100)
    vip["metadata"]["name"] = "vip"
    result = simulate(ResourceTypes(nodes=[node], pods=[low, vip]))
    placed = _placements(result)
    assert "vip" in placed
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["low"]
    assert not result.unscheduled_pods


def test_static_failures_never_preempt():
    node = make_fake_node("n0", "10", "16Gi")
    filler = _prio(make_fake_pod("low", "default", "9", "1Gi"), 0)
    vip = _prio(make_fake_pod("vip", "default", "1", "1Gi"), 1000)
    vip["spec"]["nodeSelector"] = {"nonexistent": "label"}
    result = simulate(ResourceTypes(nodes=[node], pods=[filler, vip]))
    assert not result.preempted_pods
    assert len(result.unscheduled_pods) == 1


def _with_labels(pod, labels):
    pod["metadata"]["labels"] = dict(labels)
    return pod


def _pdb(name, ns, match_labels, allowed=0):
    return {
        "apiVersion": "policy/v1beta1",
        "kind": "PodDisruptionBudget",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"selector": {"matchLabels": dict(match_labels)}},
        "status": {"disruptionsAllowed": allowed},
    }


def test_pdb_flips_the_chosen_victim_node():
    """pickOneNode criterion 1: a node whose victim set violates no PDB wins
    over an otherwise-identical node whose victim is PDB-covered
    (`default_preemption.go` pickOneNodeForPreemption + 
    filterPodsWithPDBViolation)."""
    n0 = make_fake_node("n0", "4", "16Gi")
    n1 = make_fake_node("n1", "4", "16Gi")
    covered = _with_labels(
        _prio(make_fake_pod("covered", "default", "4", "1Gi"), 0),
        {"app": "critical-db"},
    )
    covered["spec"]["nodeName"] = "n0"
    free = _prio(make_fake_pod("free", "default", "4", "1Gi"), 0)
    free["spec"]["nodeName"] = "n1"
    vip = _prio(make_fake_pod("vip", "default", "3", "1Gi"), 100)
    pdb = _pdb("db-pdb", "default", {"app": "critical-db"}, allowed=0)
    cluster = ResourceTypes(nodes=[n0, n1], pods=[covered, free, vip])
    cluster.pod_disruption_budgets = [pdb]
    result = simulate(cluster)
    placed = _placements(result)
    # without the PDB, the tie-break key is identical for both nodes and the
    # lowest node index (n0) would win; the PDB flips the choice to n1
    assert placed.get("vip") == "n1"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["free"]
    assert placed.get("covered") == "n0"


def test_pdb_budget_permits_disruption():
    """A PDB with disruptionsAllowed >= victims does not penalize the node."""
    n0 = make_fake_node("n0", "4", "16Gi")
    n1 = make_fake_node("n1", "4", "16Gi")
    covered = _with_labels(
        _prio(make_fake_pod("covered", "default", "4", "1Gi"), 0),
        {"app": "web"},
    )
    covered["spec"]["nodeName"] = "n0"
    # n1's victim has HIGHER priority, so n0 wins on criterion 2 once its
    # budgeted PDB contributes zero violations
    pricey = _prio(make_fake_pod("pricey", "default", "4", "1Gi"), 50)
    pricey["spec"]["nodeName"] = "n1"
    vip = _prio(make_fake_pod("vip", "default", "3", "1Gi"), 100)
    cluster = ResourceTypes(nodes=[n0, n1], pods=[covered, pricey, vip])
    cluster.pod_disruption_budgets = [_pdb("web-pdb", "default", {"app": "web"}, allowed=1)]
    result = simulate(cluster)
    placed = _placements(result)
    assert placed.get("vip") == "n0"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["covered"]


def test_pdb_prefers_uncovered_victim_within_node():
    """Victim greed keeps PDB-covered pods placed when an uncovered victim
    suffices (the reference reprieves violating victims preferentially)."""
    node = make_fake_node("n0", "6", "16Gi")
    covered = _with_labels(
        _prio(make_fake_pod("covered", "default", "2", "1Gi"), 0),
        {"app": "db"},
    )
    free = _prio(make_fake_pod("free", "default", "2", "1Gi"), 0)
    vip = _prio(make_fake_pod("vip", "default", "4", "1Gi"), 100)
    cluster = ResourceTypes(nodes=[node], pods=[covered, free, vip])
    cluster.pod_disruption_budgets = [_pdb("db-pdb", "default", {"app": "db"}, allowed=0)]
    result = simulate(cluster)
    placed = _placements(result)
    assert placed.get("vip") == "n0"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["free"]
    assert placed.get("covered") == "n0"


def test_empty_pdb_selector_matches_nothing():
    """filterPodsWithPDBViolation: a PDB with a nil or empty selector
    matches nothing (unlike the general LabelSelector empty-matches-all)."""
    n0 = make_fake_node("n0", "4", "16Gi")
    n1 = make_fake_node("n1", "4", "16Gi")
    a = _with_labels(_prio(make_fake_pod("a", "default", "4", "1Gi"), 0), {"x": "1"})
    a["spec"]["nodeName"] = "n0"
    b = _with_labels(_prio(make_fake_pod("b", "default", "4", "1Gi"), 0), {"x": "2"})
    b["spec"]["nodeName"] = "n1"
    vip = _prio(make_fake_pod("vip", "default", "3", "1Gi"), 100)
    cluster = ResourceTypes(nodes=[n0, n1], pods=[a, b, vip])
    empty = {
        "apiVersion": "policy/v1beta1",
        "kind": "PodDisruptionBudget",
        "metadata": {"name": "catch-all", "namespace": "default"},
        "spec": {"selector": {}},
        "status": {"disruptionsAllowed": 0},
    }
    cluster.pod_disruption_budgets = [empty]
    result = simulate(cluster)
    placed = _placements(result)
    # no PDB matches: plain tie-break picks the lowest node index
    assert placed.get("vip") == "n0"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["a"]


def test_pdb_with_budget_does_not_penalize_covered_victim():
    """Budget-aware reprieve split: a victim whose PDB still absorbs the
    eviction (disruptionsAllowed=1) is NON-violating and ranks purely by
    priority — the priority-0 covered pod is evicted, not the priority-50
    uncovered one."""
    node = make_fake_node("n0", "6", "16Gi")
    covered = _with_labels(
        _prio(make_fake_pod("covered", "default", "2", "1Gi"), 0),
        {"app": "web"},
    )
    pricey = _prio(make_fake_pod("pricey", "default", "2", "1Gi"), 50)
    vip = _prio(make_fake_pod("vip", "default", "4", "1Gi"), 100)
    cluster = ResourceTypes(nodes=[node], pods=[covered, pricey, vip])
    cluster.pod_disruption_budgets = [_pdb("web-pdb", "default", {"app": "web"}, allowed=1)]
    result = simulate(cluster)
    placed = _placements(result)
    assert placed.get("vip") == "n0"
    assert [p.pod["metadata"]["name"] for p in result.preempted_pods] == ["covered"]
    assert placed.get("pricey") == "n0"


@pytest.mark.slow
def test_preemption_at_100k_scale():
    """VERDICT r3 task 2: preemption at the scale round 2 actually asked for
    — a placement log of 100,000 pods and >= 1,000 forced preemptions.
    The wave machinery (api.py _preempt_failed_batch) makes this a handful
    of device dispatches: host-side victim proposals against one shared
    whole-log model, one batched eviction delta, one batched verify
    placement (the 1,100 preemptors are one run, so the verify itself is a
    bulk round). Semantics pinned exactly: every high-priority pod lands,
    each evicting precisely the two 1-cpu victims its 2-cpu request needs.

    Measured 2026-07-31 (CPU, shared host): 113 s end-to-end including the
    100k-pod initial bulk placement and jit compiles; docs/status.md keeps
    the number. The envelope below is deliberately loose for slow CI."""
    import time

    from simtpu.core.objects import AppResource, ResourceTypes
    from simtpu.synth import make_deployment, make_node

    n = 6250
    cluster = ResourceTypes()
    cluster.nodes = [
        make_node(
            f"node-{i:06d}",
            16000,
            64,
            {
                "topology.kubernetes.io/zone": f"zone-{i % 8}",
                "kubernetes.io/hostname": f"node-{i:06d}",
            },
        )
        for i in range(n)
    ]
    low = make_deployment("low", n * 16, 1000, 512)
    low["spec"]["template"]["spec"]["priority"] = 10
    high = make_deployment("high", 1100, 2000, 1024)
    high["spec"]["template"]["spec"]["priority"] = 1000
    res_low = ResourceTypes()
    res_low.deployments = [low]
    res_high = ResourceTypes()
    res_high.deployments = [high]
    apps = [
        AppResource(name="low", resource=res_low),
        AppResource(name="high", resource=res_high),
    ]
    from simtpu.workloads.expand import seed_name_hashes

    seed_name_hashes(1)
    t0 = time.perf_counter()
    out = simulate(cluster, apps, bulk=True)
    wall = time.perf_counter() - t0
    placed = sum(len(s.pods) for s in out.node_status)
    assert len(out.unscheduled_pods) == 0
    assert len(out.preempted_pods) == 2 * 1100
    assert placed == n * 16 - 2 * 1100 + 1100
    # wall-clock envelope only on dedicated perf runs (advisor low, round
    # 4): a loaded shared CI host can exceed it without anything being
    # wrong; functional runs still pin placement/preemption counts above
    if _PERF_ASSERT:
        assert wall < 420, f"100k-scale preemption too slow: {wall:.1f}s"


def test_preemption_at_scale():
    """VERDICT r2 task 5: hundreds of preemptions against a placement log of
    thousands of entries must run in seconds — the victim search is
    vectorized over the whole log (api.py) and evictions update the carried
    device state incrementally instead of rebuilding it (engine/scan.py).
    Semantics pinned: every high-priority pod lands, every eviction is
    recorded, and the displaced capacity matches exactly."""
    import time

    from simtpu.core.objects import AppResource, ResourceTypes
    from simtpu.synth import make_deployment, make_node

    n = 300
    cluster = ResourceTypes()
    cluster.nodes = [
        make_node(
            f"node-{i:06d}",
            4000,
            16,
            {
                "topology.kubernetes.io/zone": f"zone-{i % 4}",
                "kubernetes.io/hostname": f"node-{i:06d}",
            },
        )
        for i in range(n)
    ]
    low = make_deployment("low", n * 4, 1000, 512)
    low["spec"]["template"]["spec"]["priority"] = 10
    high = make_deployment("high", 250, 2000, 1024)
    high["spec"]["template"]["spec"]["priority"] = 1000
    res_low = ResourceTypes()
    res_low.deployments = [low]
    res_high = ResourceTypes()
    res_high.deployments = [high]
    apps = [
        AppResource(name="low", resource=res_low),
        AppResource(name="high", resource=res_high),
    ]
    from simtpu.workloads.expand import seed_name_hashes

    seed_name_hashes(1)
    t0 = time.perf_counter()
    out = simulate(cluster, apps, bulk=True)
    wall = time.perf_counter() - t0
    placed = sum(len(s.pods) for s in out.node_status)
    # every high-prio pod fits by evicting exactly two 1-cpu victims
    assert len(out.unscheduled_pods) == 0
    assert len(out.preempted_pods) == 2 * 250
    assert placed == n * 4 - 2 * 250 + 250
    # generous envelope: the pre-vectorization search alone took minutes
    if _PERF_ASSERT:
        assert wall < 120, f"preemption path too slow: {wall:.1f}s"


def test_wave_cap_abort_tags_failures_distinctly(caplog):
    """ADVICE r5 (`api.py` waves_left): when the termination cap trips, the
    still-pending preemptors are finalized with their ORIGINAL (stale)
    failure reason — the report must distinguish a cap abort from a genuine
    verify failure, and a warning must carry the remaining-pod count."""
    import logging

    from simtpu.api import PREEMPT_WAVE_CAP_NOTE, Simulator

    node = make_fake_node("n0", "10", "16Gi")
    fillers = [
        _prio(make_fake_pod(f"low{i}", "default", "4", "1Gi"), 0) for i in range(2)
    ]
    vip = _prio(make_fake_pod("vip", "default", "6", "1Gi"), 1000)

    sim = Simulator()
    sim.WAVE_CAP_SLACK = -100  # trip the cap on the first wave
    with caplog.at_level(logging.WARNING, logger="simtpu.api"):
        result = sim.run_cluster(
            ResourceTypes(nodes=[node], pods=fillers + [vip])
        )
    # the vip WOULD have preempted (test_high_priority_pod_preempts_lower);
    # the forced cap abort records it unscheduled with the distinct tag
    assert len(result.unscheduled_pods) == 1
    reason = result.unscheduled_pods[0].reason
    assert PREEMPT_WAVE_CAP_NOTE in reason
    assert "1 pod(s) unresolved" in reason
    assert any(
        "preemption wave cap exhausted with 1 pod(s)" in rec.getMessage()
        for rec in caplog.records
    )
    # the untagged path stays untagged
    sim2 = Simulator()
    result2 = sim2.run_cluster(
        ResourceTypes(nodes=[node], pods=fillers + [vip])
    )
    assert not result2.unscheduled_pods


def test_preemption_under_compact_rides_direct_delta(monkeypatch):
    """ISSUE 16 tentpole: with a compact carry, the batched eviction delta
    and every restore of a rejected wave ride the DIRECT compact apply —
    the expand -> apply -> recompress round trip never runs on the hot
    path (state.delta_direct > 0), and the full simulation outcome
    (placements, evictions, unscheduled set) is identical to a dense-carry
    run (SIMTPU_COMPACT=0), whose deltas take the dense apply."""
    from simtpu.core.objects import AppResource
    from simtpu.obs.metrics import REGISTRY
    from simtpu.synth import make_deployment, make_node
    from simtpu.workloads.expand import seed_name_hashes

    def run():
        n = 24
        cluster = ResourceTypes()
        cluster.nodes = [
            make_node(
                f"node-{i:06d}",
                4000,
                16,
                {
                    "topology.kubernetes.io/zone": f"zone-{i % 4}",
                    "kubernetes.io/hostname": f"node-{i:06d}",
                },
            )
            for i in range(n)
        ]
        # zone spread gives the problem tabular topology terms, so the
        # carry compresses; the capacity squeeze forces real preemptions
        low = make_deployment(
            "low", n * 4, 1000, 512, priority=10,
            spread_topo="topology.kubernetes.io/zone",
        )
        high = make_deployment(
            "high", 16, 2000, 1024, priority=1000,
            spread_topo="topology.kubernetes.io/zone",
        )
        res_low = ResourceTypes()
        res_low.deployments = [low]
        res_high = ResourceTypes()
        res_high.deployments = [high]
        apps = [
            AppResource(name="low", resource=res_low),
            AppResource(name="high", resource=res_high),
        ]
        seed_name_hashes(3)
        before = REGISTRY.snapshot()
        out = simulate(cluster, apps, bulk=True)
        after = REGISTRY.snapshot()
        delta = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in ("state.delta_direct", "state.expand", "state.compress")
        }
        placements = tuple(sorted(_placements(out).items()))
        evicted = tuple(
            sorted(p.pod["metadata"]["name"] for p in out.preempted_pods)
        )
        unsched = tuple(
            sorted(p["metadata"]["name"] for p in out.unscheduled_pods)
        )
        return delta, placements, evicted, unsched

    d_direct, p_direct, e_direct, u_direct = run()
    assert e_direct, "scenario produced no preemptions — not exercising the path"
    assert d_direct["state.delta_direct"] > 0, d_direct

    monkeypatch.setenv("SIMTPU_COMPACT", "0")
    d_dense, p_dense, e_dense, u_dense = run()
    # a dense carry is never compressed, so no delta can take the direct
    # compact route and nothing expands
    assert d_dense == {
        "state.delta_direct": 0, "state.expand": 0, "state.compress": 0
    }, d_dense
    assert p_direct == p_dense
    assert e_direct == e_dense
    assert u_direct == u_dense


# -- kube-scheduler's PreemptionBasic through `simtpu apply`'s default path --
#
# The benchmark's generator (benchmark/gen/sched_perf_preempt.py) writes the
# shape at a few nodes; the answer, reduced to counts, is checked by the
# plain reference (benchmark/reference/preempt.py), which imports nothing of
# simtpu. AUTO_ENGINE_NODES is lowered below the node count so that
# auto-selection takes its large-problem branch, as at 5,000 nodes.

_LOW = {"cpu_m": 900, "mem_mib": 500}
_HIGH = {"name": "pod-high-priority", "cpu_m": 3000, "mem_mib": 500}


def _preempt_basic_cfg(nodes: int, tiers=(1,), high_priority: int = 10) -> dict:
    per_tier = 4 * nodes // len(tiers)
    low = [dict(_LOW, name=f"pod-low-priority{'' if len(tiers) == 1 else p}",
                pods=per_tier, priority=p) for p in tiers]
    return {"nodes": nodes, "node_template": {"cpu": 4, "mem_gib": 32, "pods": 110},
            "low": low, "measure": dict(_HIGH, pods=nodes, priority=high_priority)}


def _apply_preempt_basic(tmp_path, monkeypatch, capsys, cfg, *flags):
    """(problem, --json document, stderr, captured PlanResult) of one
    `simtpu apply -f <problem> --json` answer."""
    import json

    from benchmark.gen import sched_perf_preempt
    from simtpu import cli
    from simtpu.plan import capacity

    problem = sched_perf_preempt.build(cfg, 4294967311)
    config = problem.write(str(tmp_path))
    monkeypatch.setattr(capacity, "AUTO_ENGINE_NODES", cfg["nodes"] // 2)
    plans = []
    run = capacity.Applier.run

    def capture(self, *a, **kw):
        plans.append(run(self, *a, **kw))
        return plans[-1]

    monkeypatch.setattr(capacity.Applier, "run", capture)
    capsys.readouterr()
    rc = cli.main(["apply", "-f", config, "--json", *flags])
    out, err = capsys.readouterr()
    doc = json.loads(out.strip().splitlines()[-1])
    assert rc == (0 if doc["success"] else 1)
    return problem, doc, err, plans[-1]


def _reference_numbers(problem, doc, plan) -> dict:
    from benchmark.drivers.batch_answer import reduce
    from benchmark.drivers.preempt_answer import reduce_victims
    from benchmark.reference import preempt as ref

    placed, unscheduled, clones = reduce(plan, {n.name for n in problem.node_specs})
    victims = reduce_victims(plan)
    _, _, want = ref.expected(problem.node_specs, problem.groups, problem.priority)
    nums = ref.check(problem.node_specs, problem.template_spec, doc["nodes_added"],
                     problem.groups, problem.priority, placed, unscheduled,
                     victims, want)
    nums["answer_mismatch"] = (
        abs(doc["nodes_added"] - clones)
        + abs(doc["unscheduled"] - sum(unscheduled.values()))
        + abs(doc["preempted"] - sum(victims.values())))
    return nums


@pytest.mark.parametrize("nodes,tiers", [(8, (1,)), (32, (1,)), (64, (1,)), (32, (1, 5))],
                         ids=["8", "32", "64", "32-two-tiers"])
def test_preemption_basic_default_apply_matches_reference(
        tmp_path, monkeypatch, capsys, nodes, tiers):
    """Every high pod evicts exactly three low pods on its own node (the
    lowest tier first), nothing is left unscheduled and no node is added:
    the reference's every number reads 0, and `preempted` is 3 x nodes."""
    problem, doc, err, plan = _apply_preempt_basic(
        tmp_path, monkeypatch, capsys, _preempt_basic_cfg(nodes, tiers))
    assert doc["engine"]["search"] == "binary" and doc["engine"]["bulk"] is True
    assert "pods can preempt" in err and "IGNORED" not in err
    assert doc["success"] is True and doc["nodes_added"] == 0
    assert doc["unscheduled"] == 0
    assert doc["preempted"] == 3 * nodes
    assert doc["engine"]["audit"]["ok"] is True
    assert _reference_numbers(problem, doc, plan) == dict.fromkeys(
        ("overcommit", "lost_pods", "unscheduled", "victim_priority",
         "reprievable", "victims_wrong", "answer_mismatch"), 0)


def test_preemption_basic_explicit_incremental_ignores_preemption(
        tmp_path, monkeypatch, capsys):
    """`--search incremental` keeps its old answer: no evictions, clones up
    to the search's cap of 100, the rest unscheduled, and the notice."""
    problem, doc, err, plan = _apply_preempt_basic(
        tmp_path, monkeypatch, capsys, _preempt_basic_cfg(128), "--search", "incremental")
    assert doc["engine"]["search"] == "incremental"
    assert doc["engine"]["preemption_ignored"] is True
    assert "IGNORED" in err
    assert doc["success"] is False and doc["preempted"] is None
    assert min(doc["probes"].values()) > 0


def test_preemption_basic_uniform_priority_keeps_incremental(
        tmp_path, monkeypatch, capsys):
    """Where every pod has the same priority nothing can preempt: the large
    problem keeps the incremental search, with no IGNORED notice."""
    _, doc, err, _ = _apply_preempt_basic(
        tmp_path, monkeypatch, capsys, _preempt_basic_cfg(8, (7,), high_priority=7))
    assert doc["engine"]["search"] == "incremental"
    assert doc["engine"]["preemption_ignored"] is False
    assert "IGNORED" not in err and "pods can preempt" not in err
    assert doc["success"] is True and doc["nodes_added"] == 8
    assert doc["preempted"] == 0


@pytest.mark.parametrize("tiers", [(1,), (1, 5)], ids=["32", "32-two-tiers"])
def test_preemption_basic_victim_tables(tmp_path, monkeypatch, capsys, tiers):
    """The high pods are one class: each wave searches the whole log once
    (`preempt.tables`) and then only the node each proposal debited
    (`preempt.refreshes`), with the reference still reading 0 everywhere."""
    from simtpu.obs.metrics import REGISTRY

    before = REGISTRY.snapshot()
    problem, doc, _, plan = _apply_preempt_basic(
        tmp_path, monkeypatch, capsys, _preempt_basic_cfg(32, tiers))
    after = REGISTRY.snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("preempt.waves", "preempt.preemptors", "preempt.tables",
                       "preempt.refreshes")}
    assert delta["preempt.waves"] >= 1
    assert delta["preempt.tables"] == delta["preempt.waves"]
    assert delta["preempt.refreshes"] >= delta["preempt.preemptors"] - 1
    assert doc["preempted"] == 3 * 32 and doc["unscheduled"] == 0
    assert _reference_numbers(problem, doc, plan) == dict.fromkeys(
        ("overcommit", "lost_pods", "unscheduled", "victim_priority",
         "reprievable", "victims_wrong", "answer_mismatch"), 0)
