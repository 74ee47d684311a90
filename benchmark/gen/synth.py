"""Seeded manifest generators, copied from `simtpu/synth.py` (make_node,
make_deployment, synth_cluster, synth_apps) so that a later change to the
program's own generator cannot move the benchmark's yardstick.

Kept as in the original: every random draw, in the same order, for the
options the benchmark uses. Changed: a node's `pods` allocatable is a
parameter, 110 by default, the per-node limit of Kubernetes' documented
large-cluster envelope (the original hard-codes 256). Left out: the GPU
share, exclusive-device, host-port, priority and self-affinity options no
configuration here uses (their draws are kept where the original draws
them unconditionally, so the random streams are the original's).

Beside each manifest, the generator records the same numbers in plain
form (`NodeSpec`, `GroupSpec`): the reference check in
`benchmark/reference/` reads those, never the program's parsed objects.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

GIB = 1 << 30
MIB = 1 << 20
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


@dataclass
class NodeSpec:
    name: str
    cpu_m: int
    mem_b: int
    pods: int
    zone: str
    tainted: bool = False
    vgs: Tuple[int, ...] = ()  # VG capacities in bytes


@dataclass
class GroupSpec:
    """Pods that share one spec: a Deployment's replicas, or bare pods
    from one template (scheduler_perf's createPods)."""

    key: str
    count: int
    cpu_m: int
    mem_b: int
    tolerates: bool = False
    lvm_b: Tuple[int, ...] = ()
    spread: Optional[dict] = None  # {"key", "max_skew", "hard"}
    anti_host: Optional[str] = None  # "soft" | "hard"
    bound: List[str] = field(default_factory=list)  # nodeName per bound pod


def make_node(name, cpu_milli, mem_gib, labels=None, taints=None,
              storage_gib=(), pods=110):
    alloc = {"cpu": f"{cpu_milli}m", "memory": f"{mem_gib}Gi", "pods": str(pods)}
    annotations = {}
    if storage_gib:
        annotations["simon/node-local-storage"] = json.dumps({
            "vgs": [
                {"name": f"vg{j}", "capacity": g * GIB, "requested": 0}
                for j, g in enumerate(storage_gib)
            ],
            "devices": [],
        })
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name, "labels": dict(labels or {}),
                     "annotations": annotations},
        "spec": ({"taints": taints} if taints else {}),
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def node_spec(node: dict, cpu_m: int, mem_gib: int, pods: int,
              storage_gib=()) -> NodeSpec:
    labels = node["metadata"]["labels"]
    return NodeSpec(
        name=node["metadata"]["name"], cpu_m=cpu_m, mem_b=mem_gib * GIB,
        pods=pods, zone=labels.get(ZONE_KEY, ""),
        tainted=bool(node["spec"].get("taints")),
        vgs=tuple(g * GIB for g in storage_gib),
    )


def make_deployment(name, replicas, cpu_milli, mem_mib, namespace="bench",
                    tolerations=None, anti_affinity_topo=None,
                    anti_affinity_required=False, spread_topo=None,
                    spread_hard=False, lvm_gib=0):
    labels = {"app": name}
    requests = {"cpu": f"{cpu_milli}m", "memory": f"{mem_mib}Mi"}
    spec = {"containers": [{"name": "c", "image": "app",
                            "resources": {"requests": requests}}]}
    if tolerations:
        spec["tolerations"] = list(tolerations)
    if anti_affinity_topo:
        term = {"labelSelector": {"matchLabels": labels},
                "topologyKey": anti_affinity_topo}
        if anti_affinity_required:
            anti = {"requiredDuringSchedulingIgnoredDuringExecution": [term]}
        else:
            anti = {"preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": 100, "podAffinityTerm": term}]}
        spec["affinity"] = {"podAntiAffinity": anti}
    if spread_topo:
        spec["topologySpreadConstraints"] = [{
            "maxSkew": 1,
            "topologyKey": spread_topo,
            "whenUnsatisfiable": "DoNotSchedule" if spread_hard else "ScheduleAnyway",
            "labelSelector": {"matchLabels": labels},
        }]
    annotations = {}
    claims = (lvm_gib,) if isinstance(lvm_gib, int) else tuple(lvm_gib)
    volumes = [{"kind": "LVM", "scName": "open-local-lvm", "size": g * GIB}
               for g in claims if g]
    if volumes:
        annotations["simon/pod-local-storage"] = json.dumps({"volumes": volumes})
    meta = {"name": name, "namespace": namespace, "labels": dict(labels)}
    if annotations:
        meta["annotations"] = annotations
    dep = {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": meta,
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": labels},
            "template": {"metadata": {"labels": dict(labels)}, "spec": spec},
        },
    }
    group = GroupSpec(
        key=name, count=replicas, cpu_m=cpu_milli, mem_b=mem_mib * MIB,
        tolerates=bool(tolerations),
        lvm_b=tuple(g * GIB for g in claims if g),
        spread=({"key": spread_topo, "max_skew": 1, "hard": bool(spread_hard)}
                if spread_topo else None),
        anti_host=(("hard" if anti_affinity_required else "soft")
                   if anti_affinity_topo else None),
    )
    return dep, group


def synth_cluster(n_nodes, seed, zones, taint_frac, storage_frac,
                  racks_per_zone=4, pods=110, names=None):
    """`simtpu.synth.synth_cluster` with gpu_frac=0: the same draws.
    `names` (optional) names the nodes in order."""
    rng = np.random.default_rng(seed)
    nodes, specs = [], []
    for i in range(n_nodes):
        name = names[i] if names is not None else f"node-{i:06d}"
        labels = {ZONE_KEY: f"zone-{i % zones}", HOST_KEY: name}
        taints = None
        if rng.random() < taint_frac:
            taints = [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]
        rng.random()  # the original's gpu_frac draw
        storage = ()
        if rng.random() < storage_frac:
            storage = tuple(int(rng.integers(200, 1000))
                            for _ in range(int(rng.integers(1, 3))))
            if rng.random() < 0.5:
                # the original's exclusive-device draws, kept for the stream
                for _ in range(int(rng.integers(1, 4))):
                    rng.integers(100, 500)
        cpu = int(rng.choice([16000, 32000, 64000, 96000]))
        mem = int(rng.choice([64, 128, 256, 384]))
        node = make_node(name, cpu, mem, labels, taints, storage, pods)
        nodes.append(node)
        specs.append(node_spec(node, cpu, mem, pods, storage))
    if racks_per_zone > 0:
        rack_of = rng.integers(racks_per_zone, size=n_nodes)
        for i, node in enumerate(nodes):
            node["metadata"]["labels"]["simtpu.io/rack"] = (
                f"zone-{i % zones}-rack-{int(rack_of[i])}")
    return nodes, specs


def synth_apps(n_pods, seed, zones, pods_per_deployment, selector_frac,
               toleration_frac, anti_affinity_frac, spread_frac,
               storage_frac, storage_device_frac, names=None):
    """`simtpu.synth.synth_apps` with gpu_frac=0 and no device claims
    (storage_device_frac must be 0): the same draws. `names` (optional)
    names the deployments in order."""
    if storage_device_frac or selector_frac:
        raise ValueError("device claims and node selectors are not copied")
    rng = np.random.default_rng(seed)
    deps, groups = [], []
    made = d = 0
    while made < n_pods:
        replicas = min(pods_per_deployment, n_pods - made)
        kw = {}
        roll = rng.random()
        if roll < storage_frac:
            rng.random()  # the original's storage_device_frac draw
            kw["lvm_gib"] = int(rng.integers(5, 40))
        rng.random()  # the original's selector_frac draw
        if rng.random() < toleration_frac:
            kw["tolerations"] = [
                {"key": "dedicated", "operator": "Exists", "effect": "NoSchedule"}]
        if rng.random() < anti_affinity_frac:
            kw["anti_affinity_topo"] = HOST_KEY
        if spread_frac and rng.random() < spread_frac:
            kw["spread_topo"] = ZONE_KEY
        dep, group = make_deployment(
            names[d] if names is not None else f"dep-{d:05d}", replicas,
            int(rng.choice([250, 500, 1000, 2000])),
            int(rng.choice([256, 512, 1024, 4096])), **kw)
        deps.append(dep)
        groups.append(group)
        made += replicas
        d += 1
    return deps, groups
