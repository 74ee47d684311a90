"""The preemption wave's victim tables (`simtpu/api.py` `_VictimTable`):
a (pod class, reason) searches the whole placement log once per wave and
then only the nodes that proposals debited. The same proposal sequence is
run twice, once through the reused tables and once with a fresh whole-log
search per proposal; victims and the wave model must agree after every
step.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from simtpu.api import Simulator
from simtpu.core.objects import ResourceTypes
from simtpu.engine.scan import (
    FAIL_ATTACH,
    FAIL_GPU,
    FAIL_INTERPOD,
    FAIL_PORTS,
    FAIL_RESOURCES,
    FAIL_SPREAD,
    FAIL_STORAGE,
    FAIL_VOLUME,
)
from simtpu.obs.metrics import REGISTRY
from simtpu.synth import make_node

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
MIB = 1 << 20


def _pod(name, cpu_m, mem_b, prio, node=None, labels=None):
    container = {
        "name": "c",
        "image": "app",
        "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": str(int(mem_b))}},
    }
    meta = {"name": name, "namespace": "default", "labels": dict(labels or {})}
    spec = {"containers": [container], "priority": int(prio)}
    if node:
        spec["nodeName"] = node
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta, "spec": spec}


def _gpu(pod, mib):
    pod["metadata"].setdefault("annotations", {}).update(
        {"alibabacloud.com/gpu-mem": f"{mib}Mi", "alibabacloud.com/gpu-count": "1"}
    )
    return pod


def _lvm(pod, gib):
    pod["metadata"].setdefault("annotations", {})["simon/pod-local-storage"] = json.dumps(
        {"volumes": [{"kind": "LVM", "scName": "open-local-lvm", "size": gib << 30}]}
    )
    return pod


def _port(pod):
    pod["spec"]["containers"][0]["ports"] = [{"containerPort": 8080, "hostPort": 8080}]
    return pod


def _volume(pod, vol):
    pod["spec"].setdefault("volumes", []).append(vol)
    return pod


def _pdb(name, labels, allowed):
    return {
        "apiVersion": "policy/v1",
        "kind": "PodDisruptionBudget",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"selector": {"matchLabels": labels}},
        "status": {"disruptionsAllowed": allowed},
    }


def _preemptors(n_nodes, rng):
    """(pod, reason) per class, two pods each: every preemptible reason,
    a pinned pod, and a class of lower priority than some victims."""
    web = {"matchLabels": {"app": "web"}}
    pin = f"n{int(rng.integers(n_nodes)):02d}"
    out = []
    for k in range(2):
        res = _pod(f"res-{k}", 2000, 3 << 30, 10)
        low = _pod(f"low-{k}", 1500, 2 << 30, 3)
        pinned = _pod(f"pin-{k}", 1000, 2 << 30, 10)
        pinned["spec"]["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {"nodeSelectorTerms": [
                {"matchFields": [{"key": "metadata.name", "operator": "In",
                                  "values": [pin]}]}]}}}
        anti = _pod(f"anti-{k}", 100, MIB, 10)
        anti["spec"]["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": web, "topologyKey": HOST}]}}
        spread = _pod(f"spread-{k}", 100, MIB, 10, labels={"app": "web"})
        spread["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": web}]
        out += [
            (res, FAIL_RESOURCES),
            (low, FAIL_RESOURCES),
            (pinned, FAIL_RESOURCES),
            (_port(_pod(f"port-{k}", 100, MIB, 10)), FAIL_PORTS),
            (anti, FAIL_INTERPOD),
            (spread, FAIL_SPREAD),
            (_volume(_pod(f"disk-{k}", 100, MIB, 10),
                     {"name": "d", "gcePersistentDisk": {"pdName": "disk-a"}}), FAIL_VOLUME),
            (_volume(_pod(f"ebs-{k}", 100, MIB, 10),
                     {"name": "e", "awsElasticBlockStore": {"volumeID": "vol-new"}}),
             FAIL_ATTACH),
            (_gpu(_pod(f"gpu-{k}", 100, MIB, 10), 12000), FAIL_GPU),
            (_lvm(_pod(f"lvm-{k}", 100, MIB, 10), 150), FAIL_STORAGE),
        ]
    return out


def _random_world(seed):
    """A simulator whose log holds seeded low-priority pods of every kind
    the search tells apart, bound to 16 nodes, with PDBs of mixed budgets."""
    rng = np.random.default_rng(seed)
    n = 16
    nodes = []
    for i in range(n):
        labels = {ZONE: f"zone-{i % 4}", HOST: f"n{i:02d}"}
        nodes.append(make_node(
            f"n{i:02d}", 8000, 16, labels,
            gpu=(2, 16000) if i % 4 == 0 else None,
            storage_gib=(200,) if i % 4 == 1 else (),
        ))
    pods = []
    for k in range(7 * n):
        # two pods on every node first: GPU and LVM users where nodes have them
        i = k % n if k < 2 * n else int(rng.integers(n))
        kind = rng.choice(["plain", "web", "port", "disk", "ebs"])
        if k < 2 * n and i % 4 < 2:
            kind = "gpu" if i % 4 == 0 else "lvm"
        # memory in bytes, not a round number of MiB
        mem = int(rng.integers(200, 1500)) * MIB + int(rng.integers(1, 4096)) * 512
        labels = {"app": str(kind), "tier": str(rng.choice(["a", "b"]))}
        pod = _pod(f"v{k:03d}", int(rng.integers(100, 1200)), mem,
                   int(rng.integers(1, 6)), node=f"n{i:02d}", labels=labels)
        if kind == "port":
            _port(pod)
        elif kind == "disk":
            _volume(pod, {"name": "d", "gcePersistentDisk": {"pdName": "disk-a"}})
        elif kind == "ebs":
            _volume(pod, {"name": "e", "awsElasticBlockStore": {"volumeID": f"vol-{k}"}})
        elif kind == "gpu":
            _gpu(pod, int(rng.integers(2000, 9000)))
        elif kind == "lvm":
            _lvm(pod, int(rng.integers(20, 80)))
        pods.append(pod)
    pdbs = [
        _pdb("web-a", {"app": "web", "tier": "a"}, int(rng.integers(0, 3))),
        _pdb("disk", {"app": "disk"}, int(rng.integers(0, 2))),
        _pdb("plain-b", {"app": "plain", "tier": "b"}, int(rng.integers(0, 3))),
    ]
    sim = Simulator()
    sim.run_cluster(ResourceTypes(nodes=nodes, pods=pods, pod_disruption_budgets=pdbs))
    assert len(sim._scheduled) == len(pods)
    return sim, rng


def _classes(sim, pods):
    batch = sim._tensorizer.add_pods(pods)
    return sim._victim_classes(batch, range(len(pods)))


def _replay(sim, steps):
    """Propose `steps` ((class, reason) each) against two models of the same
    wave: `reused` keeps its tables, `fresh` searches the whole log for every
    proposal. Returns the victims per step and the reused model. The search
    reads each class off its batch rows: it tensorizes nothing itself."""
    reused, fresh = sim._build_preempt_model(), sim._build_preempt_model()
    answers = []
    tz = sim._tensorizer
    tz.add_pods = None  # any call fails
    for cls, reason in steps:
        fresh["tables"].clear()
        got = sim._propose_victims(cls, reason, reused)
        want = sim._propose_victims(cls, reason, fresh)
        assert got == want
        for k, v in reused.items():
            if isinstance(v, np.ndarray):
                assert np.array_equal(v, fresh[k]), k
        answers.append(got)
    del tz.add_pods
    return answers, reused


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reused_tables_match_whole_log_search(seed):
    sim, rng = _random_world(seed)
    if seed % 2:
        # fault-masked nodes are no landing sites
        valid = np.ones(len(sim._nodes), bool)
        valid[rng.choice(len(sim._nodes), 3, replace=False)] = False
        sim._engine.node_valid = valid
    model = sim._build_preempt_model()
    assert (model["gpu_use_log"] > 0).any() and (model["vg_use_log"] > 0).any()
    pods = _preemptors(len(sim._nodes), rng)
    classes = _classes(sim, [p for p, _ in pods])
    steps = [(classes[j], pods[j][1]) for j in rng.integers(len(pods), size=80)]
    before = REGISTRY.snapshot()
    answers, reused = _replay(sim, steps)
    after = REGISTRY.snapshot()
    # every reason found victims, and the tables were reused
    assert {r for (_, r), a in zip(steps, answers) if a} == {r for _, r in pods}
    assert len(reused["tables"]) <= len({(r, c.key) for c, r in steps})
    assert after["preempt.refreshes"] > before.get("preempt.refreshes", 0)
    # two pods of a class are one class
    assert classes[0] is classes[len(pods) // 2]


def test_segment_sums_do_not_round_with_log_position():
    """A 10^4-entry log of memory-sized float32 requests: the node searched
    last fits the preemptor after exactly one eviction, which within-
    segment float64 sums see; the old whole-log float32 cumsum less the
    segment base rounds that victim's memory down, 10^13 bytes into the
    log, and would have needed a second victim."""
    n, per = 1000, 10
    v = (1 << 32) + 512  # a float32 memory size, not a multiple of the log's ulp
    nodes = [make_node(f"n{i:04d}", 64000, 48, {HOST: f"n{i:04d}"}) for i in range(n)]
    # the last node: two pods of v and exactly 1 GiB free
    nodes[-1]["status"]["allocatable"]["memory"] = str(2 * v + (1 << 30))
    nodes[-1]["status"]["capacity"]["memory"] = str(2 * v + (1 << 30))
    rng = np.random.default_rng(7)
    pods = []
    for i in range(n - 1):
        mems = (1 << 32) + rng.integers(1, 1 << 20, size=per) * 512
        nodes[i]["status"]["allocatable"]["memory"] = str(int(mems.sum()))
        nodes[i]["status"]["capacity"]["memory"] = str(int(mems.sum()))
        pods += [_pod(f"p{i}-{k}", 100, int(m), 1, node=f"n{i:04d}")
                 for k, m in enumerate(mems)]
    pods += [_pod(f"t-{k}", 100, v, 1, node=f"n{n - 1:04d}") for k in range(2)]
    sim = Simulator()
    sim.run_cluster(ResourceTypes(nodes=nodes, pods=pods))
    assert len(sim._scheduled) == (n - 1) * per + 2  # about 10^4

    (cls,) = _classes(sim, [_pod("hi", 100, (1 << 30) + v, 10)])
    answers, reused = _replay(sim, [(cls, FAIL_RESOURCES)] * 6)
    assert answers[0] is not None and len(answers[0]) == 1
    assert int(reused["placed_nodes"][answers[0][0]]) == n - 1
    # then two victims on the first node, whose freed room takes one more
    # preemptor per victim after that
    assert [len(a) for a in answers] == [1, 2, 1, 1, 1, 1]

    # the old arithmetic on the same first search would have rounded
    model = sim._build_preempt_model()
    cand = np.flatnonzero(model["prios"] < cls.prio)
    s = sim._victim_segments(model, cls, FAIL_RESOURCES, cand)
    mem = sim._tensorizer.resources.get("memory")
    vals = model["placed_req"][s.rows, mem]
    cum = np.cumsum(vals)
    seg_id = np.repeat(np.arange(len(s.first)), np.diff(np.append(s.first, len(vals))))
    old = cum - (cum - vals)[s.first][seg_id]
    last = s.first[-1]
    assert s.node[-1] == n - 1 and vals[last] == v
    assert old[last] < v
