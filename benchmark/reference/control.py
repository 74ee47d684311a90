"""The reference's controls: a plain first-fit placement that breaks one
guarantee the configuration states, put in the program's place (a
config's `controls` entry `{"reference": <name>}`). The check has to read
it as not correct.

- `ignore_pod_cap`: a node takes pods past its `pods` allocatable (the
  110-pod limit of the large-cluster envelope).
- `ignore_spread`: DoNotSchedule topology spread is not enforced.
- `ignore_scores`: every filter is kept and no node is scored: each pod
  takes the first node with room, as a scan with its scoring dropped
  would.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List

import numpy as np

from .check import Ledger


def first_fit(nodes, groups, broken: str) -> (Dict[str, Counter], Counter):
    """Place every group's pods, in order, on the first node with room.
    Bound pods stay on their nodes. Returns (placed, unscheduled)."""
    if broken not in ("ignore_pod_cap", "ignore_spread", "ignore_scores"):
        raise ValueError(f"unknown control {broken!r}")
    ledger = Ledger(nodes)
    m = ledger.n_spec
    placed: Dict[str, Counter] = {}
    unscheduled: Counter = Counter()
    zones = sorted({z for z in ledger.zone[:m] if z})
    zone_idx = np.array([zones.index(z) if z else -1 for z in ledger.zone[:m]])
    cap_pods = broken != "ignore_pod_cap"
    for g in groups:
        lvm = sum(g.lvm_b)
        if g.bound:
            for name in g.bound:
                i = ledger.index[name]
                placed.setdefault(name, Counter())[g.key] += 1
                ledger.used_cpu[i] += g.cpu_m
                ledger.used_mem[i] += g.mem_b
                ledger.used_pods[i] += 1
            continue
        zcount = np.zeros(len(zones), np.int64)
        hard = bool(g.spread and g.spread["hard"]) and broken != "ignore_spread"
        for _ in range(g.count):
            room = ((ledger.cpu[:m] - ledger.used_cpu[:m] >= g.cpu_m)
                    & (ledger.mem[:m] - ledger.used_mem[:m] >= g.mem_b))
            room &= g.tolerates | ~ledger.tainted[:m]
            if cap_pods:
                room &= ledger.pods[:m] - ledger.used_pods[:m] >= 1
            if lvm:
                room &= ((ledger.vg_sum[:m] - ledger.used_lvm[:m] >= lvm)
                         & (ledger.vg_max[:m] >= max(g.lvm_b)))
            if hard:
                ok_zone = zcount + 1 - zcount.min() <= g.spread["max_skew"]
                room &= (zone_idx >= 0) & ok_zone[np.maximum(zone_idx, 0)]
            hit = np.flatnonzero(room)
            if not len(hit):
                unscheduled[g.key] += 1
                continue
            i = int(hit[0])
            placed.setdefault(ledger.names[i], Counter())[g.key] += 1
            ledger.used_cpu[i] += g.cpu_m
            ledger.used_mem[i] += g.mem_b
            ledger.used_pods[i] += 1
            ledger.used_lvm[i] += lvm
            if zone_idx[i] >= 0:
                zcount[zone_idx[i]] += 1
    return placed, unscheduled


def control_groups(groups: List) -> List:
    """Groups in the order the control places them: bound pods first."""
    return sorted(groups, key=lambda g: not g.bound)
