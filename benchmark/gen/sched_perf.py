"""The 5,000-node cases of the upstream kube-scheduler benchmark
(kubernetes/test/integration/scheduler_perf, config/performance-config.yaml):
`node-default` nodes labelled over three zones, initial `pod-default` pods
already bound, then the measured pods: `pod-default` pods (SchedulingBasic)
followed by pods of the TopologySpreading template.

The run's `--seed` draws the zone of each node (the TopologySpreading
case's labelNodePrepareStrategy picks one of its values per node) and the
node each initial pod is bound to; the sizes never change.
"""

from __future__ import annotations

import numpy as np

from . import synth
from .problem import Problem
from .synth import GroupSpec


def _node(name: str, zone: str, t: dict) -> dict:
    alloc = {"cpu": str(t["cpu"]), "memory": f"{t['mem_gib']}Gi",
             "pods": str(t["pods"])}
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name,
                     "labels": {synth.HOST_KEY: name, synth.ZONE_KEY: zone}},
        "spec": {},
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def _pod(name: str, t: dict, labels=None, node=None, spread=None) -> dict:
    res = {"cpu": f"{t['cpu_m']}m", "memory": f"{t['mem_mib']}Mi"}
    spec = {"containers": [{
        "name": "pause", "image": "registry.k8s.io/pause:3.10",
        "ports": [{"containerPort": 80}],
        "resources": {"limits": dict(res), "requests": dict(res)},
    }]}
    if node:
        spec["nodeName"] = node
    if spread:
        spec["topologySpreadConstraints"] = [{
            "maxSkew": spread["max_skew"], "topologyKey": spread["key"],
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": dict(labels)},
        }]
    meta = {"name": name, "namespace": "default"}
    if labels:
        meta["labels"] = dict(labels)
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta, "spec": spec}


def _group(key: str, count: int, t: dict, spread=None) -> GroupSpec:
    return GroupSpec(key=key, count=count, cpu_m=t["cpu_m"],
                     mem_b=t["mem_mib"] * synth.MIB, spread=spread)


def build(cfg: dict, seed: int) -> Problem:
    nt, pt = cfg["node_template"], cfg["pod_template"]
    zones = cfg["zones"]
    rng = np.random.default_rng(seed)
    zone_of = rng.integers(len(zones), size=cfg["nodes"])
    nodes, specs = [], []
    for i in range(cfg["nodes"]):
        name = f"scheduler-perf-{i:05d}"
        nodes.append(_node(name, zones[int(zone_of[i])], nt))
        specs.append(synth.NodeSpec(
            name=name, cpu_m=int(nt["cpu"]) * 1000,
            mem_b=nt["mem_gib"] * synth.GIB, pods=nt["pods"],
            zone=zones[int(zone_of[i])]))
    init = cfg["init_pods"]
    bound_to = rng.integers(cfg["nodes"], size=init)
    bound, groups = [], []
    g = _group("init-pod", init, pt)
    for i in range(init):
        node = specs[int(bound_to[i])].name
        bound.append(_pod(f"init-pod-{i:05d}", pt, node=node))
        g.bound.append(node)
    groups.append(g)
    workloads = []
    for phase in cfg["measure"]:
        spread = phase.get("spread")
        labels = phase.get("labels")
        for i in range(phase["pods"]):
            workloads.append(_pod(f"{phase['name']}-{i:05d}", pt,
                                  labels=labels, spread=spread))
        groups.append(_group(phase["name"], phase["pods"], pt, spread))
    tmpl = _node("node-default", zones[0], nt)
    tmpl_spec = synth.NodeSpec(
        name="node-default", cpu_m=int(nt["cpu"]) * 1000,
        mem_b=nt["mem_gib"] * synth.GIB, pods=nt["pods"], zone=zones[0])
    return Problem(nodes=nodes, node_specs=specs, workloads=workloads,
                   groups=groups, template=tmpl, template_spec=tmpl_spec,
                   bound_pods=bound)
