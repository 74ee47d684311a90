"""Filter kernels: each returns a boolean feasibility mask over all nodes.

One kernel per vendored filter-plugin family (the checklist in SURVEY.md §2.2,
`vendor/.../scheduler/algorithmprovider/registry.go:75-145`). The reference
evaluates these per (pod, node) with 16 goroutines
(`core/generic_scheduler.go:271-341`); here the node axis is a vector lane and
one call covers every node at once.

Stateless filters (NodeUnschedulable, TaintToleration, NodeAffinity/selector,
NodeName pinning) are precomputed per pod-group in core/tensorize.py; the
kernels here are the ones that depend on mutable scan state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Relative slack for float32 resource comparisons; the reference compares exact
# integer milli-quantities, so allow only rounding-level drift.
_RES_EPS = 1e-5


def resources_fit(free: jnp.ndarray, req: jnp.ndarray) -> jnp.ndarray:
    """NodeResourcesFit: every requested resource fits in the node's free
    allocatable (incl. the synthetic `pods` count resource).

    free: [N, R], req: [R] → mask [N].
    Mirrors `plugins/noderesources/fit.go` fitsRequest.
    """
    slack = _RES_EPS * jnp.maximum(jnp.abs(free), 1.0)
    return jnp.all(free + slack >= req, axis=-1)


def ports_conflict_free(ports_used: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    """NodePorts: no requested (protocol, hostPort) pair already in use on the
    node (`plugins/nodeports/node_ports.go` Filter).

    ports_used: [N, P] in-use counts, want: [P] bool → mask [N].
    """
    return ~jnp.any(want[None, :] & (ports_used > 0), axis=-1)


def volume_conflict_free(
    vols_any: jnp.ndarray,  # [N, W] users (rw or ro) of exclusive volume w
    vols_rw: jnp.ndarray,  # [N, W] read-write users of volume w
    want_rw: jnp.ndarray,  # [W] bool — pod mounts volume w read-write
    want_ro: jnp.ndarray,  # [W] bool — pod mounts volume w read-only
) -> jnp.ndarray:
    """VolumeRestrictions (`plugins/volumerestrictions/volume_restrictions.go`):
    a read-write mount conflicts with any existing user of the same volume; a
    read-only mount conflicts with an existing read-write user. Returns [N].
    """
    rw_conflict = jnp.any(want_rw[None, :] & (vols_any > 0), axis=-1)
    ro_conflict = jnp.any(want_ro[None, :] & (vols_rw > 0), axis=-1)
    return ~(rw_conflict | ro_conflict)


def attach_limits_ok(
    vols_any: jnp.ndarray,  # [N, W] users of volume w on node n
    want_att: jnp.ndarray,  # [W] bool — pod attaches volume w
    class_mask: jnp.ndarray,  # [C, W] bool — volume w belongs to attach class c
    limits: jnp.ndarray,  # [N, C] per-node attach limits
) -> jnp.ndarray:
    """NodeVolumeLimits (`plugins/nodevolumelimits/non_csi.go`): per class,
    unique volumes already attached to the node plus the pod's volumes not yet
    on the node must stay within the node's limit. A class the pod adds
    nothing to never filters — upstream returns early on zero new volumes, so
    an already-over-limit node (e.g. from forced `spec.nodeName` placements)
    still accepts volume-less pods. Returns [N].
    """
    present = (vols_any > 0).astype(jnp.float32)  # [N, W]
    cm = class_mask.astype(jnp.float32)  # [C, W]
    hp = jax.lax.Precision.HIGHEST  # volume counts must stay exact
    used = jnp.matmul(present, cm.T, precision=hp)  # [N, C] unique volumes per class
    new = jnp.matmul(
        (1.0 - present) * want_att.astype(jnp.float32)[None, :], cm.T, precision=hp
    )
    return jnp.all((new == 0) | (used + new <= limits), axis=-1)


def topology_spread_filter(
    cnt_at: jnp.ndarray,  # [T, N] placed pods matching term t at node n's domain
    valid: jnp.ndarray,  # [T, N] node carries term t's topology key
    max_skew: jnp.ndarray,  # [T] maxSkew of the pod's DoNotSchedule constraints (0 = inactive)
    elig_nodes: jnp.ndarray,  # [N] nodes eligible for the pod (static mask ∩ valid)
) -> jnp.ndarray:
    """PodTopologySpread hard filter (`plugins/podtopologyspread/filtering.go`):
    placing on node n must keep `count(domain of n) + 1 - min count over
    eligible domains <= maxSkew` for every DoNotSchedule constraint; nodes
    missing the topology key are infeasible for that constraint.

    The eligible-domain minimum is taken over domains containing ≥1 node that
    passes the pod's static filters (upstream restricts to nodes passing
    nodeSelector/nodeAffinity; our static mask folds taints in as well — a
    strictly tighter, usually identical set); since every eligible domain
    surfaces its count at its eligible nodes, the per-node masked minimum of
    `cnt_at` equals the per-domain minimum. Counts are cluster-wide per
    domain rather than restricted to eligible nodes.
    """
    t_count, n = cnt_at.shape
    active = max_skew > 0
    if t_count == 0:
        return jnp.ones(n, bool)
    inf = jnp.float32(3.4e38)
    elig = valid & elig_nodes[None, :]
    min_cnt = jnp.min(jnp.where(elig, cnt_at, inf), axis=1)  # [T]
    min_cnt = jnp.where(min_cnt >= inf, 0.0, min_cnt)
    ok_tn = (~active[:, None]) | (
        valid & (cnt_at + 1.0 - min_cnt[:, None] <= max_skew[:, None])
    )
    return jnp.all(ok_tn, axis=0)


def interpod_filter(
    cnt_at: jnp.ndarray,  # [T, N] placed pods matching term t at node n's domain
    own_anti_at: jnp.ndarray,  # [T, N] placed owners of required anti term t
    valid: jnp.ndarray,  # [T, N] node carries term t's topology key
    cnt_total: jnp.ndarray,  # [T] cluster-wide matching count per term
    s_match: jnp.ndarray,  # [T] incoming pod matches term selector+ns
    a_aff: jnp.ndarray,  # [T] incoming pod requires affinity term t
    a_anti: jnp.ndarray,  # [T] incoming pod requires anti-affinity term t
) -> jnp.ndarray:
    """InterPodAffinity filter over all nodes.

    Mirrors `plugins/interpodaffinity/filtering.go`:
    - satisfyPodAffinity: every required affinity term must have ≥1 matching
      placed pod in the node's domain (node must carry the topology key); if no
      matching pod exists cluster-wide for any term and the pod matches its own
      terms, it may pass anywhere.
    - satisfyPodAntiAffinity: no required anti-affinity term of the incoming
      pod may have a matching placed pod in the node's domain.
    - satisfyExistingPodsAntiAffinity: no placed pod owning a required
      anti-affinity term that matches the incoming pod may share its domain.
    The [T, N] inputs are the engine's per-node count state. Returns mask [N].
    """
    t_count, n = cnt_at.shape
    if t_count == 0:
        return jnp.ones(n, bool)

    # anti-affinity: incoming pod's terms
    anti_violated = jnp.any(a_anti[:, None] & (cnt_at > 0), axis=0)  # [N]
    # symmetry: existing pods' anti terms that select the incoming pod
    sym_violated = jnp.any(s_match[:, None] & (own_anti_at > 0), axis=0)

    # affinity: every required term satisfied in-domain (key must exist)
    aff_term_ok = (~a_aff[:, None]) | (valid & (cnt_at > 0))  # [T, N]
    aff_ok = jnp.all(aff_term_ok, axis=0)
    # first-pod-in-series escape: no matching pod anywhere for any required
    # term AND the pod matches all its own terms AND node has all topo keys
    total_match = jnp.sum(jnp.where(a_aff, cnt_total, 0.0))
    self_ok = (
        (total_match == 0)
        & jnp.all(jnp.where(a_aff, s_match, True))
        & jnp.all((~a_aff[:, None]) | valid, axis=0)
    )
    aff_ok = aff_ok | (jnp.any(a_aff) & self_ok)

    return aff_ok & ~anti_violated & ~sym_violated
