"""kube-scheduler's preemption benchmark at 5,000 nodes (kubernetes/
test/integration/scheduler_perf, config/performance-config.yaml,
PreemptionBasic 5000Nodes): `node-default` nodes, `pod-low-priority` pods
already scheduled, then the measured `pod-high-priority` pods, each of
which fits only by evicting low-priority pods.

The low pods are bound by `spec.nodeName`, the same number on every node:
900m of a node's 4 CPU lets four fit and not a fifth, so the source's
scheduler leaves exactly four on each node, and binding them gives the
state it reaches before the measured pods. A configuration may split
them into tiers of different priority (`low` is a list); the seed then
deals the tiers' pods out over the nodes.

The run's `--seed` draws which low pods sit on which node, the order of
the high pods and the names (a tag in every node and pod name). The
sizes never change: every seed asks the same work, one instance under
many names.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

import numpy as np

from . import synth
from .problem import Problem
from .sched_perf import _node, _pod
from .synth import GroupSpec


@dataclass
class PreemptProblem(Problem):
    priority: Dict[str, int] = field(default_factory=dict)  # group -> priority


def _plain_node(name: str, nt: dict) -> dict:
    node = _node(name, "", nt)
    # node-default carries no zone label in this case (no labelNodePrepareStrategy)
    del node["metadata"]["labels"][synth.ZONE_KEY]
    return node


def _prio_pod(name: str, t: dict, node=None) -> dict:
    pod = _pod(name, t, node=node)
    pod["spec"]["priority"] = int(t["priority"])
    return pod


def _group(t: dict, count: int) -> GroupSpec:
    return GroupSpec(key=t["name"], count=count, cpu_m=t["cpu_m"],
                     mem_b=t["mem_mib"] * synth.MIB)


def build(cfg: dict, seed: int) -> PreemptProblem:
    nt = cfg["node_template"]
    n = cfg["nodes"]
    rng = np.random.default_rng(seed)
    tag = f"{int(rng.integers(1 << 32)):08x}"
    nodes, specs = [], []
    for i in range(n):
        name = f"scheduler-perf-{tag}-{i:05d}"
        nodes.append(_plain_node(name, nt))
        specs.append(synth.NodeSpec(name=name, cpu_m=int(nt["cpu"]) * 1000,
                                    mem_b=nt["mem_gib"] * synth.GIB,
                                    pods=nt["pods"], zone=""))
    low = cfg["low"]
    total = sum(t["pods"] for t in low)
    if total % n:
        raise ValueError(f"{total} low-priority pods do not bind evenly to {n} nodes")
    per_node = total // n
    # slot k of the shuffled low pods is bound to node k // per_node
    tier_of = np.repeat(np.arange(len(low)), [t["pods"] for t in low])
    slots = rng.permutation(total)
    groups = [_group(t, t["pods"]) for t in low]
    seen = [0] * len(low)
    bound = []
    for k, j in enumerate(slots):
        ti = int(tier_of[j])
        t = low[ti]
        node = specs[k // per_node].name
        bound.append(_prio_pod(f"{t['name']}-{tag}{seen[ti]:05d}", t, node=node))
        groups[ti].bound.append(node)
        seen[ti] += 1
    hi = cfg["measure"]
    order = rng.permutation(hi["pods"])
    workloads = [_prio_pod(f"{hi['name']}-{tag}{int(i):05d}", hi) for i in order]
    groups.append(_group(hi, hi["pods"]))
    priority = {t["name"]: int(t["priority"]) for t in (*low, hi)}
    tmpl = _plain_node("node-default", nt)
    tmpl_spec = synth.NodeSpec(name="node-default", cpu_m=int(nt["cpu"]) * 1000,
                               mem_b=nt["mem_gib"] * synth.GIB, pods=nt["pods"],
                               zone="")
    return PreemptProblem(nodes=nodes, node_specs=specs, workloads=workloads,
                          groups=groups, template=tmpl, template_spec=tmpl_spec,
                          bound_pods=bound, priority=priority)
