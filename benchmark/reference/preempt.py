"""The plain reference for preemption: a serial, count-level
DefaultPreemption, and the check of a finished answer against it.

It imports nothing of the program. It reads the generator's plain
numbers (nodes, groups, each group's priority) and each answer reduced
to counts: how many pods of each group sit on each node name, how many
are unscheduled, and the victims as (node, victim group, preemptor
group, whether the preemptor landed on the victim's node) counts.
Integers throughout: a fit is exact or it is not. No configuration it
checks has a PodDisruptionBudget, so every PDB violation count is 0.

`expected` is the reference's own answer: each measured pod, in arrival
order, takes the first node with room; otherwise the node that
pickOneNodeForPreemption's order picks (fewest PDB violations, lowest
highest-victim priority, smallest priority sum, fewest victims, then
the lowest node index), on which it evicts every lower-priority pod and
reprieves them in descending priority while the preemptor still fits
(selectVictimsOnNode). Pods of one group are identical, so reprieving a
group's pods as many at a time as fit is reprieving them one by one.

`check` returns, each compared with the limit 0:

- overcommit: (node, resource) pairs over allocatable (cpu, memory,
  pod count);
- lost_pods: a bound pod that is not a victim off its node (or a victim
  that was never bound there), a measured pod placed twice or missing,
  a pod of no group, a pod on a node that is neither in the cluster nor
  one of the answer's template clones;
- unscheduled: measured pods the answer left unscheduled;
- victim_priority: victims whose priority is not below their
  preemptor's, or whose preemptor is not on the victim's node;
- reprievable: victims that fit back on their node in the final state
  (reprieved in descending priority, as selectVictimsOnNode does);
- victims_wrong: the sum over nodes and groups of |victims - the
  reference's victims|.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, Tuple

import numpy as np

from .check import Ledger

NUMBERS = ("overcommit", "lost_pods", "unscheduled", "victim_priority",
           "reprievable", "victims_wrong")

def _bind(ledger: Ledger, groups) -> Dict[str, np.ndarray]:
    """Put the bound pods on the ledger; returns group -> pods per node."""
    held = {g.key: np.zeros(len(ledger.cpu), np.int64) for g in groups}
    for g in groups:
        for name in g.bound:
            i = ledger.index[name]
            held[g.key][i] += 1
            ledger.used_cpu[i] += g.cpu_m
            ledger.used_mem[i] += g.mem_b
            ledger.used_pods[i] += 1
    return held


def _reprieve(ledger: Ledger, groups, priority, cand: Dict[str, np.ndarray],
              free_cpu, free_mem, free_pods) -> Dict[str, np.ndarray]:
    """Per node, how many of each candidate group's pods fit back into
    the free room, highest priority first."""
    back = {}
    free_cpu, free_mem, free_pods = free_cpu.copy(), free_mem.copy(), free_pods.copy()
    for g in sorted(groups, key=lambda g: -priority[g.key]):
        if g.key not in cand:
            continue
        k = np.minimum(cand[g.key], np.maximum(free_pods, 0))
        k = np.minimum(k, np.maximum(free_cpu, 0) // max(g.cpu_m, 1))
        k = np.minimum(k, np.maximum(free_mem, 0) // max(g.mem_b, 1))
        back[g.key] = k
        free_cpu -= k * g.cpu_m
        free_mem -= k * g.mem_b
        free_pods -= k
    return back


def expected(nodes, groups, priority: Dict[str, int], evict_all: bool = False
             ) -> Tuple[Dict[str, Counter], Counter, Counter]:
    """The reference's own answer: (placed: node -> Counter(group -> pods),
    unscheduled: Counter(group -> pods), victims: Counter((node, group)
    -> pods)). `evict_all` drops the reprieve: every lower-priority pod
    on the chosen node is evicted (the `evict_all` control)."""
    ledger = Ledger(nodes)
    held = _bind(ledger, groups)
    victims: Counter = Counter()
    unscheduled: Counter = Counter()
    n = len(ledger.cpu)
    idx = np.arange(n)
    for g in groups:
        if g.bound:
            continue
        p = priority[g.key]
        lower = [h for h in groups if priority[h.key] < p]
        for _ in range(g.count):
            room = ((ledger.cpu - ledger.used_cpu >= g.cpu_m)
                    & (ledger.mem - ledger.used_mem >= g.mem_b)
                    & (ledger.pods - ledger.used_pods >= 1))
            hit = np.flatnonzero(room)
            if len(hit):
                i = int(hit[0])
            else:
                cand = {h.key: held[h.key] for h in lower}
                # room with every lower-priority pod gone, less the preemptor
                fc = ledger.cpu - ledger.used_cpu - g.cpu_m
                fm = ledger.mem - ledger.used_mem - g.mem_b
                fp = ledger.pods - ledger.used_pods - 1
                for h in lower:
                    fc = fc + cand[h.key] * h.cpu_m
                    fm = fm + cand[h.key] * h.mem_b
                    fp = fp + cand[h.key]
                fits = (fc >= 0) & (fm >= 0) & (fp >= 0)
                if not fits.any():
                    unscheduled[g.key] += 1
                    continue
                back = ({h.key: np.zeros(n, np.int64) for h in lower} if evict_all
                        else _reprieve(ledger, lower, priority, cand, fc, fm, fp))
                out = {h.key: cand[h.key] - back[h.key] for h in lower}
                n_v = sum(out.values())
                top = np.full(n, -(1 << 62), np.int64)
                psum = np.zeros(n, np.int64)
                for h in lower:
                    top = np.where(out[h.key] > 0, np.maximum(top, priority[h.key]), top)
                    psum += out[h.key] * priority[h.key]
                key = np.lexsort((idx, n_v, psum, top, ~fits))
                i = int(key[0])
                for h in lower:
                    k = int(out[h.key][i])
                    if k:
                        victims[(ledger.names[i], h.key)] += k
                        held[h.key][i] -= k
                        ledger.used_cpu[i] -= k * h.cpu_m
                        ledger.used_mem[i] -= k * h.mem_b
                        ledger.used_pods[i] -= k
            held[g.key][i] += 1
            ledger.used_cpu[i] += g.cpu_m
            ledger.used_mem[i] += g.mem_b
            ledger.used_pods[i] += 1
    placed: Dict[str, Counter] = {}
    for key, arr in held.items():
        for i in np.flatnonzero(arr):
            placed.setdefault(ledger.names[int(i)], Counter())[key] += int(arr[i])
    return placed, unscheduled, victims


def check(nodes, template, max_clones: int, groups, priority: Dict[str, int],
          placed: Dict[str, Counter], unscheduled: Counter, victims: Counter,
          reference: Counter) -> Dict[str, int]:
    """One whole answer against the guarantees. `victims` counts the
    answer's victims by (node name, victim group, preemptor group,
    preemptor on the victim's node); `reference` is `expected(...)[2]`,
    the reference's victims per (node, group)."""
    out = {k: 0 for k in NUMBERS}
    ledger = Ledger(nodes, template, max_clones)
    by_key = {g.key: g for g in groups}
    held: Dict[str, Counter] = {}  # group -> Counter(node index -> pods)
    for name, counts in placed.items():
        i = ledger.node(name)
        for key, k in counts.items():
            g = by_key.get(key)
            if g is None or i is None:
                out["lost_pods"] += k
                continue
            held.setdefault(key, Counter())[i] += k
            ledger.used_cpu[i] += k * g.cpu_m
            ledger.used_mem[i] += k * g.mem_b
            ledger.used_pods[i] += k
    out["overcommit"] = int((ledger.used_cpu > ledger.cpu).sum()
                            + (ledger.used_mem > ledger.mem).sum()
                            + (ledger.used_pods > ledger.pods).sum())
    # victims per (node index, group); those off every known node are lost
    gone: Dict[str, Counter] = {}
    per_node: Counter = Counter()  # (node name, group) -> victims
    for (name, vkey, pkey, same), k in victims.items():
        per_node[(name, vkey)] += k
        if not same or vkey not in priority or pkey not in priority \
                or priority[vkey] >= priority[pkey]:
            out["victim_priority"] += k
        i = ledger.index.get(name)
        if i is None or vkey not in by_key:
            out["lost_pods"] += k
            continue
        gone.setdefault(vkey, Counter())[i] += k
    for key in unscheduled:
        if key not in by_key:
            out["lost_pods"] += unscheduled[key]
    for g in groups:
        here = held.get(g.key, Counter())
        if g.bound:
            want = Counter(ledger.index[b] for b in g.bound)
            want.subtract(gone.get(g.key, Counter()))
            out["lost_pods"] += sum(abs(here.get(i, 0) - c) for i, c in want.items())
            out["lost_pods"] += sum(c for i, c in here.items() if i not in want)
            out["lost_pods"] += abs(unscheduled.get(g.key, 0))
        else:
            got = sum(here.values()) + unscheduled.get(g.key, 0)
            out["lost_pods"] += abs(got - g.count)
            out["unscheduled"] += unscheduled.get(g.key, 0)
    # reprievable: each node's victims put back, highest priority first
    free_cpu = ledger.cpu - ledger.used_cpu
    free_mem = ledger.mem - ledger.used_mem
    free_pods = ledger.pods - ledger.used_pods
    cand = {}
    for vkey, c in gone.items():
        arr = np.zeros(len(ledger.cpu), np.int64)
        for i, k in c.items():
            arr[i] = k
        cand[vkey] = arr
    back = _reprieve(ledger, [by_key[k] for k in cand], priority, cand,
                     free_cpu, free_mem, free_pods)
    out["reprievable"] = int(sum(int(a.sum()) for a in back.values()))
    keys = set(per_node) | set(reference)
    out["victims_wrong"] = int(sum(abs(per_node.get(k, 0) - reference.get(k, 0))
                                   for k in keys))
    return out
