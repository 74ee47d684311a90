"""Kubernetes' documented large-cluster envelope with the plan mix of
`chip_smoke.phase_b_problem` (copied): a seeded cluster, its app list,
the template node, and the fit-query stream of the served cell.

The cell's `--seed` does not change what is generated, only its names:
the nodes and the deployments are drawn, in order, from the
configuration's own seeds, and the run's seed deals out their names
(node-NNNNNN, dep-NNNNN) by a permutation. Every seed asks the same work.
The order is kept because it decides the answer: permuting the lists by
the seed moved the pods left without LVM room between 3,974 and 7,302
(my CPU runs, PR 22), and the answer's work with them; renaming moved
nothing (6,944 on three seeds).
"""

from __future__ import annotations

import numpy as np

from . import synth
from .problem import Problem


def build(cfg: dict, seed: int) -> Problem:
    c, a, t = cfg["cluster"], cfg["apps"], cfg["template"]
    rng = np.random.default_rng(seed)
    n_deps = -(-a["pods"] // a["pods_per_deployment"])
    nodes, specs = synth.synth_cluster(
        c["nodes"], seed=c["seed"], zones=c["zones"],
        taint_frac=c["taint_frac"], storage_frac=c["storage_frac"],
        pods=c["pods_per_node"],
        names=[f"node-{i:06d}" for i in rng.permutation(c["nodes"])])
    deps, groups = synth.synth_apps(
        a["pods"], seed=a["seed"], zones=c["zones"],
        pods_per_deployment=a["pods_per_deployment"], selector_frac=0.0,
        toleration_frac=a["toleration_frac"],
        anti_affinity_frac=a["anti_affinity_frac"],
        spread_frac=a["spread_frac"], storage_frac=a["storage_frac"],
        storage_device_frac=0.0,
        names=[f"dep-{i:05d}" for i in rng.permutation(n_deps)])
    tmpl = synth.make_node(
        t["name"], t["cpu_m"], t["mem_gib"],
        {synth.HOST_KEY: t["name"], synth.ZONE_KEY: t["zone"]},
        storage_gib=tuple(t["vg_gib"]), pods=c["pods_per_node"])
    tmpl_spec = synth.node_spec(tmpl, t["cpu_m"], t["mem_gib"],
                                c["pods_per_node"], tuple(t["vg_gib"]))
    return Problem(nodes=nodes, node_specs=specs, workloads=deps,
                   groups=groups, template=tmpl, template_spec=tmpl_spec,
                   storage=True)


def fit_queries(cfg: dict, traffic: dict, seed: int):
    """The served cell's query pool: `pool` deployments drawn once from
    the app mix's distributions (replicas log-uniform over the traffic's
    range) under the traffic's own seed, in a fixed order; the run's seed
    deals out their names (fit-NNNN), as it does the cluster's. Returns
    (payloads, groups)."""
    a, q = cfg["apps"], traffic["queries"]
    rng = np.random.default_rng(q["seed"])
    names = np.random.default_rng(seed).permutation(q["pool"])
    lo, hi = q["replicas"]
    payloads, groups = [], []
    for i in range(q["pool"]):
        replicas = int(np.floor(np.exp(rng.uniform(np.log(lo), np.log(hi + 1)))))
        replicas = min(max(replicas, lo), hi)
        kw = {}
        if rng.random() < a["storage_frac"]:
            kw["lvm_gib"] = int(rng.integers(5, 40))
        if rng.random() < a["toleration_frac"]:
            kw["tolerations"] = [
                {"key": "dedicated", "operator": "Exists", "effect": "NoSchedule"}]
        if rng.random() < a["anti_affinity_frac"]:
            kw["anti_affinity_topo"] = synth.HOST_KEY
        if rng.random() < a["spread_frac"]:
            kw["spread_topo"] = synth.ZONE_KEY
        dep, group = synth.make_deployment(
            f"fit-{names[i]:04d}", replicas,
            int(rng.choice([250, 500, 1000, 2000])),
            int(rng.choice([256, 512, 1024, 4096])), **kw)
        payloads.append({"name": dep["metadata"]["name"], "workloads": [dep]})
        groups.append(group)
    return payloads, groups
