"""The plain reference: checks a finished placement against the
configuration's guarantees, from the generator's plain numbers alone.

It imports nothing of the program and takes nothing the program made
except the answer under test, reduced to counts: how many pods of each
group sit on each node name, how many of each group are unscheduled, and
how many pods could not be attributed to any group. Integers throughout
(milli-cpu, bytes, pod counts): a fit is exact or it is not.

Every number it returns counts violations, so each is compared with the
limit 0:

- lost_pods: pods missing from, duplicated in, or foreign to the answer,
  and bound pods found off their bound node;
- bad_node: pods on a node that is neither in the cluster nor a clone of
  the template the configuration allows adding;
- overcommit: (node, resource) pairs over allocatable: cpu, memory, pod
  count, and the LVM claims against the node's volume groups (their sum,
  and each claim against the largest group);
- taint: pods on a NoSchedule-tainted node they do not tolerate;
- anti_host: extra pods of a group with required hostname anti-affinity
  sharing a node;
- spread_skew: how far each group with a DoNotSchedule constraint
  exceeds its maxSkew across zones (final counts: every placement kept
  skew within maxSkew when it was made, and counts only grow, so the
  final skew is within it too);
- fit_left_out: unscheduled pods for which a node with room existed in
  the final state (resources only grow in use, so the node had room when
  the pod was tried). Not judged for groups with a DoNotSchedule spread,
  or where only a node with several volume groups could take the claim,
  whose packing the counts cannot settle.

Two more, each where the configuration's `checks` names it:

- crowded (`fewest_in_zone`): for identical pods on identical nodes,
  kube-scheduler's LeastAllocated and BalancedAllocation scores both fall
  with every pod a node holds, and zone spread scores every node of one
  zone alike, so each pod goes to a node with the fewest pods in its zone
  at that moment. A node's last pod then found it at its final count
  less one, at most the final count of every node of its zone. The
  number sums, over the nodes that took a measured pod, how far their
  final count exceeds the zone's fewest by more than one;
- spare_clones (`spare_clones`): template clones of the answer that the
  reference empties, one at a time and fewest pods first, by moving
  their pods into room left on the other nodes (first fit, every hard
  constraint kept; on a node with k volume groups a claim of s bytes
  counts floor(free / s) - k places, which holds however the used bytes
  lie). The search's answer is the fewest clones its placement needs;
  one padded towards its upper bracket leaves whole clones to spare.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional

import numpy as np

NUMBERS = ("lost_pods", "bad_node", "overcommit", "taint", "anti_host",
           "spread_skew", "fit_left_out")
#: a configuration's `checks` -> the number each adds
EXTRA = {"fewest_in_zone": "crowded", "spare_clones": "spare_clones"}


class Ledger:
    """Per-node use of the cluster (and of any template clones)."""

    def __init__(self, nodes, template=None, max_clones: int = 0):
        self.template = template
        self.names: List[str] = [n.name for n in nodes]
        self.index = {name: i for i, name in enumerate(self.names)}
        self.n_spec = len(nodes)
        specs = list(nodes)
        if template is not None:
            specs += [template] * max_clones
        self.cpu = np.array([n.cpu_m for n in specs], np.int64)
        self.mem = np.array([n.mem_b for n in specs], np.int64)
        self.pods = np.array([n.pods for n in specs], np.int64)
        self.vg_sum = np.array([sum(n.vgs) for n in specs], np.int64)
        self.vg_max = np.array([max(n.vgs, default=0) for n in specs], np.int64)
        self.vg_n = np.array([len(n.vgs) for n in specs], np.int64)
        self.tainted = np.array([n.tainted for n in specs], bool)
        self.zone = [n.zone for n in specs]
        self.clones = 0
        self.max_clones = max_clones if template is not None else 0
        self.used_cpu = np.zeros(len(specs), np.int64)
        self.used_mem = np.zeros(len(specs), np.int64)
        self.used_pods = np.zeros(len(specs), np.int64)
        self.used_lvm = np.zeros(len(specs), np.int64)

    def node(self, name: str) -> Optional[int]:
        """Index of a node name; an unknown name becomes the next template
        clone while the configuration allows one, else None."""
        i = self.index.get(name)
        if i is None and self.clones < self.max_clones:
            i = self.n_spec + self.clones
            self.clones += 1
            self.index[name] = i
            self.names.append(name)
        return i


def _zero() -> Dict[str, int]:
    return {k: 0 for k in NUMBERS}


def add_groups(ledger: Ledger, groups, placed: Dict[str, Counter],
               unscheduled: Counter, unknown: int = 0) -> Dict[str, int]:
    """Put `placed` (node name -> Counter of group key -> pods) on the
    ledger and check the groups it holds. Returns the violation counts of
    everything but overcommit and fit_left_out (see `finish`)."""
    out = _zero()
    out["lost_pods"] += int(unknown)
    by_key = {g.key: g for g in groups}
    seen = Counter()
    per_group_nodes: Dict[str, Dict[int, int]] = {}
    for name, counts in placed.items():
        i = ledger.node(name)
        for key, n in counts.items():
            g = by_key.get(key)
            if g is None:
                out["lost_pods"] += n
                continue
            if i is None:
                out["bad_node"] += n
                continue
            seen[key] += n
            ledger.used_cpu[i] += n * g.cpu_m
            ledger.used_mem[i] += n * g.mem_b
            ledger.used_pods[i] += n
            ledger.used_lvm[i] += n * sum(g.lvm_b)
            if g.lvm_b and max(g.lvm_b) > ledger.vg_max[i]:
                out["overcommit"] += n
            if ledger.tainted[i] and not g.tolerates:
                out["taint"] += n
            per_group_nodes.setdefault(key, {})[i] = n
    for key, n in unscheduled.items():
        if key not in by_key:
            out["lost_pods"] += n
    for g in groups:
        got = seen[g.key] + unscheduled.get(g.key, 0)
        out["lost_pods"] += abs(got - g.count)
        nodes = per_group_nodes.get(g.key, {})
        if g.bound:
            want = Counter(ledger.index.get(b) for b in g.bound)
            out["lost_pods"] += sum(abs(nodes.get(i, 0) - c) for i, c in want.items())
            out["lost_pods"] += sum(c for i, c in nodes.items() if i not in want)
        if g.anti_host == "hard":
            out["anti_host"] += sum(c - 1 for c in nodes.values() if c > 1)
        if g.spread and g.spread["hard"]:
            zones = Counter()
            for i, c in nodes.items():
                zones[ledger.zone[i]] += c
            domains = {z for z in ledger.zone[: ledger.n_spec] if z}
            counts = [zones.get(z, 0) for z in domains]
            if counts:
                out["spread_skew"] += max(0, max(counts) - min(counts)
                                          - g.spread["max_skew"])
    return out


def finish(ledger: Ledger, groups, unscheduled: Counter,
           out: Dict[str, int]) -> Dict[str, int]:
    """Overcommit over the whole ledger, and unscheduled pods that had
    room."""
    out["overcommit"] += int(
        (ledger.used_cpu > ledger.cpu).sum()
        + (ledger.used_mem > ledger.mem).sum()
        + (ledger.used_pods > ledger.pods).sum()
        + (ledger.used_lvm > ledger.vg_sum).sum())
    by_key = {g.key: g for g in groups}
    m = ledger.n_spec + ledger.clones
    for key, n in unscheduled.items():
        g = by_key.get(key)
        if g is None or n <= 0 or (g.spread and g.spread["hard"]):
            continue
        room = ((ledger.cpu[:m] - ledger.used_cpu[:m] >= g.cpu_m)
                & (ledger.mem[:m] - ledger.used_mem[:m] >= g.mem_b)
                & (ledger.pods[:m] - ledger.used_pods[:m] >= 1)
                & (g.tolerates | ~ledger.tainted[:m]))
        if g.lvm_b:
            room &= (ledger.vg_n[:m] == 1) & (
                ledger.vg_sum[:m] - ledger.used_lvm[:m] >= sum(g.lvm_b))
        if room.any():
            out["fit_left_out"] += int(n)
    return out


def crowded(ledger: Ledger, groups, placed: Dict[str, Counter]) -> int:
    """The `fewest_in_zone` number (see the module's docstring)."""
    m = ledger.n_spec
    if len({(g.cpu_m, g.mem_b, g.lvm_b, g.tolerates, g.anti_host) for g in groups}) > 1 \
            or len({(int(a), int(b), int(c)) for a, b, c in
                    zip(ledger.cpu[:m], ledger.mem[:m], ledger.pods[:m])}) > 1 \
            or ledger.tainted[:m].any():
        raise ValueError("fewest_in_zone needs identical pods on identical nodes")
    measured = {g.key for g in groups if not g.bound}
    took = sorted({ledger.index[name] for name, counts in placed.items()
                   if name in ledger.index and ledger.index[name] < m
                   and any(counts.get(k) for k in measured)})
    fewest: Dict[str, int] = {}
    for i in range(m):
        z = ledger.zone[i]
        fewest[z] = min(fewest.get(z, 1 << 62), int(ledger.used_pods[i]))
    return sum(max(0, int(ledger.used_pods[i]) - 1 - fewest[ledger.zone[i]])
               for i in took)


def _places(ledger: Ledger, g, free: np.ndarray, holding: np.ndarray) -> np.ndarray:
    """How many pods of group `g` each node can still take."""
    big = np.int64(1) << 40
    k = np.minimum(ledger.pods - ledger.used_pods, big)
    k = np.minimum(k, (ledger.cpu - ledger.used_cpu) // max(g.cpu_m, 1))
    k = np.minimum(k, (ledger.mem - ledger.used_mem) // max(g.mem_b, 1))
    if g.lvm_b:
        s = max(g.lvm_b)
        vg = (ledger.vg_sum - ledger.used_lvm) // s - ledger.vg_n
        k = np.minimum(k, np.where(ledger.vg_max >= s, vg // len(g.lvm_b), 0))
    if not g.tolerates:
        k = np.where(ledger.tainted, 0, k)
    if g.anti_host == "hard":
        k = np.where(holding > 0, 0, np.minimum(k, 1))
    return np.where(free, np.maximum(k, 0), 0)


def spare_clones(ledger: Ledger, groups, placed: Dict[str, Counter]) -> int:
    """The `spare_clones` number (see the module's docstring). Changes
    the ledger's use: call it last."""
    by_key = {g.key: g for g in groups}
    clones = [i for i in range(ledger.n_spec, ledger.n_spec + ledger.clones)]
    counts = {ledger.index[name]: c for name, c in placed.items()
              if name in ledger.index}
    holding: Dict[str, np.ndarray] = {}
    for i, c in counts.items():
        for key, n in c.items():
            holding.setdefault(key, np.zeros(len(ledger.cpu), np.int64))[i] += n
    free = np.ones(len(ledger.cpu), bool)
    free[ledger.n_spec + ledger.clones:] = False
    spare = 0
    for c in sorted(clones, key=lambda i: (int(ledger.used_pods[i]), i)):
        pods = counts.get(c, Counter())
        if any(k not in by_key or (by_key[k].spread and by_key[k].spread["hard"])
               for k in pods):
            continue  # a hard spread's zones are not the reference's to move
        saved = [a.copy() for a in (ledger.used_cpu, ledger.used_mem,
                                    ledger.used_pods, ledger.used_lvm)]
        free[c] = False
        moved, takes = True, []
        for key, n in sorted(pods.items(), key=lambda kv: (-sum(by_key[kv[0]].lvm_b),
                                                           -by_key[kv[0]].cpu_m, kv[0])):
            g = by_key[key]
            hold = holding.get(key, np.zeros(len(ledger.cpu), np.int64))
            k = _places(ledger, g, free, hold)
            if k.sum() < n:
                moved = False
                break
            take = np.minimum(k, np.maximum(n - (np.cumsum(k) - k), 0))
            takes.append((key, take))
            for arr, per in ((ledger.used_cpu, g.cpu_m), (ledger.used_mem, g.mem_b),
                             (ledger.used_pods, 1), (ledger.used_lvm, sum(g.lvm_b))):
                arr += take * per
        if moved:
            spare += 1
            for key, take in takes:
                holding.setdefault(key, np.zeros(len(ledger.cpu), np.int64))[:] += take
        else:
            free[c] = True
            (ledger.used_cpu, ledger.used_mem,
             ledger.used_pods, ledger.used_lvm) = saved
    return spare


def check(nodes, template, max_clones: int, groups, placed, unscheduled,
          unknown: int = 0, extra=()) -> Dict[str, int]:
    """One whole placement: the numbers above, and those of `extra`
    (`fewest_in_zone`, `spare_clones`) by the names `crowded` and
    `spare_clones`."""
    ledger = Ledger(nodes, template, max_clones)
    out = add_groups(ledger, groups, placed, unscheduled, unknown)
    out = finish(ledger, groups, unscheduled, out)
    if "fewest_in_zone" in extra:
        out[EXTRA["fewest_in_zone"]] = crowded(ledger, groups, placed)
    if "spare_clones" in extra:
        out[EXTRA["spare_clones"]] = spare_clones(ledger, groups, placed)
    return out
