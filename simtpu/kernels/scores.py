"""Score kernels: each returns a float score vector over all nodes.

One kernel per score plugin active in the reference's profile — the default
algorithm provider (`vendor/.../algorithmprovider/registry.go:101-145`) plus
the Simon plugin (`pkg/simulator/plugin/simon.go:44-100`). Weights follow the
registry: LeastAllocated 1, BalancedAllocation 1, NodeAffinity 1,
TaintToleration 1, InterPodAffinity 1, Simon 1 (extension scores).

Normalization mirrors each plugin's NormalizeScore; scores are computed over
the full node axis but normalized over the feasible mask only, exactly like
`prioritizeNodes` running on the filtered list (`core/generic_scheduler.go:470`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensorize import RES_CPU, RES_MEMORY

# pod counts are integers in f32: contract them exactly (the TPU's default
# precision rounds operands to bf16, exact only up to 256)
_HP = jax.lax.Precision.HIGHEST

MAX_NODE_SCORE = 100.0


def minmax_normalize(score: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Min-max to [0, 100] over feasible nodes (SimonPlugin.NormalizeScore,
    `plugin/simon.go:76-100`; same default for NodeAffinity)."""
    big = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(mask, score, big))
    hi = jnp.max(jnp.where(mask, score, -big))
    rng = hi - lo
    return jnp.where(rng > 0, (score - lo) * MAX_NODE_SCORE / jnp.maximum(rng, 1e-30), 0.0)


def maxabs_normalize(score: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Scale by max |score| to [-100, 100] (InterPodAffinity NormalizeScore)."""
    m = jnp.max(jnp.where(mask, jnp.abs(score), 0.0))
    return jnp.where(m > 0, score * MAX_NODE_SCORE / jnp.maximum(m, 1e-30), 0.0)


def least_allocated(
    free: jnp.ndarray, alloc: jnp.ndarray, req: jnp.ndarray
) -> jnp.ndarray:
    """NodeResourcesLeastAllocated over cpu+memory
    (`plugins/noderesources/least_allocated.go`): mean of free-fraction × 100
    after placing the pod."""
    cols = jnp.array([RES_CPU, RES_MEMORY])
    fa = free[:, cols] - req[cols]  # [N, 2] free after placement
    al = alloc[:, cols]
    frac = jnp.where(al > 0, jnp.clip(fa, 0.0) / jnp.maximum(al, 1e-30), 0.0)
    return jnp.mean(frac, axis=-1) * MAX_NODE_SCORE


def balanced_allocation(
    free: jnp.ndarray, alloc: jnp.ndarray, req: jnp.ndarray
) -> jnp.ndarray:
    """NodeResourcesBalancedAllocation (`plugins/noderesources/
    balanced_allocation.go`, two-resource form): 100 - |cpuFrac - memFrac|·100."""
    cols = jnp.array([RES_CPU, RES_MEMORY])
    used_after = alloc[:, cols] - free[:, cols] + req[cols]
    frac = jnp.where(
        alloc[:, cols] > 0, used_after / jnp.maximum(alloc[:, cols], 1e-30), 1.0
    )
    return (1.0 - jnp.abs(frac[:, 0] - frac[:, 1])) * MAX_NODE_SCORE


def simon_share(alloc: jnp.ndarray, req: jnp.ndarray) -> jnp.ndarray:
    """Simon plugin raw score (`plugin/simon.go:44-67`): dominant share of the
    pod request against (static allocatable − request), per node, ×100.

    Uses the *static* allocatable, not remaining free — the fake-client node
    object never shrinks as pods bind, and the plugin reads it directly.
    """
    denom = alloc - req[None, :]  # [N, R]
    share = jnp.where(
        denom == 0,
        jnp.where(req[None, :] == 0, 0.0, 1.0),
        req[None, :] / jnp.where(denom == 0, 1.0, denom),
    )
    # only resources the node allocates participate; Go's `share > res` fold
    # starts at 0 so negatives never win
    share = jnp.where(alloc > 0, share, 0.0)
    return jnp.clip(jnp.max(share, axis=-1), 0.0) * MAX_NODE_SCORE


def taint_toleration_score(intolerable_cnt: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """TaintToleration score (`plugins/tainttoleration`): fewer intolerable
    PreferNoSchedule taints → higher, reverse-normalized to [0, 100]."""
    hi = jnp.max(jnp.where(mask, intolerable_cnt, 0.0))
    return jnp.where(
        hi > 0,
        MAX_NODE_SCORE * (1.0 - intolerable_cnt / jnp.maximum(hi, 1e-30)),
        MAX_NODE_SCORE,
    )


def spread_score_from_raw(raw: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """The inverse-min-max of `topology_spread_score` applied to an already
    summed [N] raw count vector — the single formula source shared by the
    [T, N] kernel and the wavefront verifier's incrementally carried raw."""
    big = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(mask, raw, big))
    hi = jnp.max(jnp.where(mask, raw, -big))
    rng = hi - lo
    return jnp.where(
        rng > 0, MAX_NODE_SCORE * (hi - raw) / jnp.maximum(rng, 1e-30), MAX_NODE_SCORE
    )


def topology_spread_score(
    cnt_at: jnp.ndarray,  # [T, N] matching placed pods at each node's domain
    soft_w: jnp.ndarray,  # [T] ScheduleAnyway constraint multiplicity
    mask: jnp.ndarray,  # [N] feasible nodes
) -> jnp.ndarray:
    """PodTopologySpread score (`plugins/podtopologyspread/scoring.go`,
    registry weight 2 applied by the caller): lower matching count in the
    node's domains → higher score, inverse-min-max to [0, 100]; nodes missing
    a topology key count 0 for that constraint."""
    return spread_score_from_raw(jnp.matmul(soft_w, cnt_at, precision=_HP), mask)


def selector_spread_compose(
    cnt_host: jnp.ndarray,  # [N] matching placed pods on each node
    cnt_zone: jnp.ndarray,  # [N] matching placed pods in each node's zone
    max_host,  # scalar — max of cnt_host over feasible nodes (0-floored)
    max_zone,  # scalar — max of cnt_zone over feasible nodes (0-floored)
    any_zone_terms,  # bool scalar — the pod has zone-key counting terms
) -> jnp.ndarray:
    """`selector_spread_score`'s normalization with the masked maxima
    precomputed — the wavefront verifier carries them as incrementally
    maintained scalars (max is order-free, so the carried value is
    bit-identical to the reduction)."""
    node_score = jnp.where(
        max_host > 0,
        MAX_NODE_SCORE * (max_host - cnt_host) / jnp.maximum(max_host, 1e-30),
        MAX_NODE_SCORE,
    )
    zone_score = jnp.where(
        max_zone > 0,
        MAX_NODE_SCORE * (max_zone - cnt_zone) / jnp.maximum(max_zone, 1e-30),
        MAX_NODE_SCORE,
    )
    have_zones = any_zone_terms & (max_zone > 0)
    zw = jnp.float32(2.0 / 3.0)
    return jnp.where(
        have_zones, (1.0 - zw) * node_score + zw * zone_score, node_score
    )


def selector_spread_from_counts(
    cnt_host: jnp.ndarray,  # [N] matching placed pods on each node
    cnt_zone: jnp.ndarray,  # [N] matching placed pods in each node's zone
    any_zone_terms,  # bool scalar — the pod has zone-key counting terms
    mask: jnp.ndarray,  # [N]
) -> jnp.ndarray:
    """`selector_spread_score`'s normalization on already summed host/zone
    count vectors (shared with the wavefront verifier's carried raws)."""
    return selector_spread_compose(
        cnt_host,
        cnt_zone,
        jnp.max(jnp.where(mask, cnt_host, 0.0)),
        jnp.max(jnp.where(mask, cnt_zone, 0.0)),
        any_zone_terms,
    )


def selector_spread_score(
    cnt_at: jnp.ndarray,  # [T, N] matching placed pods at each node's domain
    ss_host: jnp.ndarray,  # [T] hostname-key counting terms of the pod
    ss_zone: jnp.ndarray,  # [T] zone-key counting terms
    mask: jnp.ndarray,  # [N]
) -> jnp.ndarray:
    """SelectorSpread score (`plugins/selectorspread/selector_spread.go`):
    spread pods of the same service/controller across nodes, then zones with
    zoneWeighting=2/3 when zones exist."""
    return selector_spread_from_counts(
        jnp.matmul(ss_host.astype(jnp.float32), cnt_at, precision=_HP),
        jnp.matmul(ss_zone.astype(jnp.float32), cnt_at, precision=_HP),
        jnp.any(ss_zone),
        mask,
    )


def interpod_score(
    cnt_at: jnp.ndarray,  # [T, N] matching placed pods at each node's domain
    own_aff_at: jnp.ndarray,  # [T, N] placed owners of required affinity terms
    w_own_aff_at: jnp.ndarray,  # [T, N] summed preferred-affinity owner weights
    w_own_anti_at: jnp.ndarray,  # [T, N]
    s_match: jnp.ndarray,  # [T] incoming pod matches term
    w_aff_pref: jnp.ndarray,  # [T] incoming pod's preferred affinity weights
    w_anti_pref: jnp.ndarray,  # [T]
    hard_pod_affinity_weight: float = 1.0,
) -> jnp.ndarray:
    """InterPodAffinity score (`plugins/interpodaffinity/scoring.go`):

    + weight × matching placed pods in domain, for the incoming pod's
      preferred (anti-)affinity terms, and symmetrically
    + placed pods' preferred terms (and required affinity terms, scaled by
      HardPodAffinityWeight=1) that select the incoming pod.
    The [T, N] inputs are the engine's per-node count state (SchedState).
    Raw, un-normalized; caller applies maxabs_normalize.
    """
    incoming = (w_aff_pref - w_anti_pref) @ cnt_at
    symmetric = s_match.astype(jnp.float32) @ (
        w_own_aff_at - w_own_anti_at + hard_pod_affinity_weight * own_aff_at
    )
    return incoming + symmetric
