"""Batched capacity-planning sweep: all candidate cluster sizes at once.

The reference finds the minimum node-add count with up to 101 *serial* full
re-simulations, building a fresh simulator per candidate
(`pkg/apply/apply.go:183-233`, `pkg/type/const.go:51`). Here the candidate
axis becomes a tensor dimension: tensorize ONE cluster containing the base
nodes plus `max_new` template clones, mark per-candidate membership with a
`node_valid [S, N]` mask, and `vmap` the placement scan over S. One XLA
compilation evaluates every candidate; on a mesh the S axis shards over
"sweep" (DCN/ICI data parallelism) and the node axis over "nodes".

DaemonSet semantics: clone nodes get their DaemonSet pods expanded like real
nodes, so candidate i must ignore failures of pods pinned to clones >= i
(those pods don't exist in candidate i's cluster — the reference equivalently
only ever creates DS pods for nodes present in that iteration,
`pkg/simulator/core.go:72-82`).
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants as C
from ..core.objects import AppResource, ResourceTypes, set_label
from ..core.tensorize import Tensorizer
from ..engine.scan import (
    StaticArrays,
    StepFlags,
    build_pod_arrays,
    flags_from,
    schedule_step,
    statics_from,
)
from ..engine.state import build_state
from ..obs.trace import span
from ..workloads.expand import (
    get_valid_pods_exclude_daemonset,
    make_valid_pods_by_daemonset,
)
from .mesh import NODE_AXIS, SWEEP_AXIS
from .sharded import pad_state, pad_statics, state_sharding, statics_sharding


@partial(jax.jit, static_argnums=(4,))
def _sweep_scan(
    statics: StaticArrays,
    valid_s: jnp.ndarray,
    state,
    pods,
    flags: StepFlags = StepFlags(),
):
    """vmap the scan over the candidate axis; only node_valid varies.

    Deliberately NOT donated (donation audit, docs/memory.md): donation
    only enables input→output aliasing, and no input here can alias an
    output — the [N]-shaped base carry and [S]-masks come out vmapped to
    [S, N] — so donate_argnums would buy nothing and emit the
    donated-buffers-unusable warning on every sweep.  XLA frees the
    inputs at last use regardless."""

    def one(valid):
        st = statics._replace(node_valid=statics.node_valid & valid)
        return jax.lax.scan(partial(schedule_step, st, flags=flags), state, pods)

    return jax.vmap(one)(valid_s)


def assemble_planning_problem(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    max_new: int,
    extended_resources: Sequence[str] = (),
):
    """One tensorization covering the base cluster plus `max_new` template
    clones, with the ordered pod sequence exactly as simulate() submits it
    (cluster pods + DaemonSet expansion over ALL nodes incl. clones, then
    each app's sorted pods). Shared by the batched sweep and the
    incremental planner — candidate membership is expressed afterwards via
    `node_valid` masks, never by re-tensorizing.

    Returns (tensorizer, all_nodes, n_base, ordered_pods).
    """
    from ..plan.capacity import new_fake_nodes

    base_nodes = list(cluster.nodes)
    n_base = len(base_nodes)
    all_nodes = base_nodes + new_fake_nodes(new_node, max_new)

    from ..api import _sort_app_pods

    ordered: List[dict] = []
    with span("expand") as sp:
        work = ResourceTypes(**{k: list(v) for k, v in vars(cluster).items()})
        work.nodes = all_nodes
        cluster_pods = get_valid_pods_exclude_daemonset(work)
        for ds in work.daemon_sets:
            cluster_pods.extend(make_valid_pods_by_daemonset(ds, all_nodes))
        ordered.extend(cluster_pods)
        for app in apps:
            pods = get_valid_pods_exclude_daemonset(app.resource)
            for ds in app.resource.daemon_sets:
                pods.extend(make_valid_pods_by_daemonset(ds, all_nodes))
            for pod in pods:
                set_label(pod, C.LABEL_APP_NAME, app.name)
            ordered.extend(_sort_app_pods(pods))
        sp.set(pods=len(ordered))

    with span("tensorize", nodes=len(all_nodes)):
        tensorizer = Tensorizer(
            all_nodes,
            extended_resources,
            storage_classes=list(cluster.storage_classes),
            services=list(cluster.services),
            pvcs=list(cluster.persistent_volume_claims),
            pvs=list(cluster.persistent_volumes),
        )
    return tensorizer, all_nodes, n_base, ordered


def sweep_feasibility(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    candidates: Sequence[int],
    extended_resources: Sequence[str] = (),
    mesh=None,
    sched_config=None,
):
    """Run every candidate clone-count in one batched placement.

    Returns (failures [S] int array — unscheduled-pod count per candidate,
    n_base, pods) where `pods` is the concatenated ordered pod list.
    """
    candidates = np.asarray(list(candidates), np.int32)
    max_new = int(candidates.max()) if len(candidates) else 0
    tensorizer, all_nodes, n_base, ordered = assemble_planning_problem(
        cluster, apps, new_node, max_new, extended_resources
    )
    batch = tensorizer.add_pods(ordered)
    tensors = tensorizer.freeze()
    statics = statics_from(tensors, sched_config)
    r = tensors.alloc.shape[1]
    _, pods_arrays = build_pod_arrays(batch, r)
    state = build_state(
        tensors,
        np.zeros(0, np.int32),
        np.zeros(0, np.int32),
        np.zeros((0, r), np.float32),
        None,
    )

    n_total = len(all_nodes)
    # valid_s[s, j]: base nodes always; clone j-n_base iff < candidates[s]
    clone_idx = np.arange(n_total) - n_base
    valid_s = (clone_idx[None, :] < candidates[:, None]) | (clone_idx[None, :] < 0)

    n_cand = len(candidates)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        shards = mesh.shape[NODE_AXIS]
        statics, pad = pad_statics(statics, shards)
        state = pad_state(state, pad)
        if pad:
            valid_s = np.pad(valid_s, ((0, 0), (0, pad)))
        # the candidate axis must also divide its mesh axis: replicate the
        # last candidate row as padding and drop those rows from the output
        s_pad = (-n_cand) % mesh.shape[SWEEP_AXIS]
        if s_pad:
            valid_s = np.concatenate(
                [valid_s, np.repeat(valid_s[-1:], s_pad, axis=0)]
            )
        statics = jax.device_put(statics, statics_sharding(mesh))
        state = jax.device_put(state, state_sharding(mesh))
        valid_arr = jax.device_put(
            jnp.asarray(valid_s), NamedSharding(mesh, P(SWEEP_AXIS, NODE_AXIS))
        )
        pods_arrays = jax.device_put(pods_arrays, NamedSharding(mesh, P()))
    else:
        valid_arr = jnp.asarray(valid_s)

    _, outs = _sweep_scan(
        statics, valid_arr, state, pods_arrays, flags_from(tensors, batch.ext)
    )
    nodes_sp = np.asarray(outs[0])[:n_cand]  # [S, P] chosen node (-1 = failed)

    # per-candidate failure count, ignoring pods that only exist on clones
    # beyond the candidate's size (pins into invalid clone rows)
    pin = np.asarray(batch.pin)
    failures = np.zeros(len(candidates), np.int64)
    for s, cand in enumerate(candidates):
        phantom = (pin >= 0) & (pin - n_base >= cand)
        failures[s] = int(((nodes_sp[s] < 0) & ~phantom).sum())
    return failures, n_base, ordered


def plan_capacity_batched(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    max_new_nodes: int = C.MAX_NUM_NEW_NODE,
    extended_resources: Sequence[str] = (),
    mesh=None,
    progress=None,
    sched_config=None,
    corrected_ds_overhead: bool = False,
):
    """Batched replacement for the serial min-node-add search.

    Evaluates all candidate counts 0..max_new_nodes in one compiled sweep,
    then re-runs the precise serial simulation at the winning count to
    produce the full report-grade `SimulateResult` (the sweep's phantom-pod
    bookkeeping makes its placements candidate-exact, but reports want node
    annotations built for exactly the winning cluster).
    """
    from ..plan.capacity import PlanResult, plan_capacity, satisfy_resource_setting
    from ..api import simulate

    say = progress or (lambda s: None)
    # parity with the serial planner: the largest candidate ever simulated is
    # max_new_nodes-1 (the reference's `for i := 0; i < MaxNumNewNode` walk,
    # apply.go:183; see plan_capacity)
    candidates = list(range(max_new_nodes))
    say(f"sweeping {len(candidates)} candidate sizes in one batch")
    failures, _, _ = sweep_feasibility(
        cluster, apps, new_node, candidates, extended_resources, mesh, sched_config
    )
    feasible = np.flatnonzero(failures == 0)
    probes = {int(c): int(f) for c, f in zip(candidates, failures)}
    if len(feasible) == 0:
        # fall back to the serial planner for its rich infeasibility
        # diagnostics (apply.go:213-231 semantics)
        return plan_capacity(
            cluster,
            apps,
            new_node,
            max_new_nodes,
            extended_resources,
            search="binary",
            progress=progress,
            sched_config=sched_config,
            corrected_ds_overhead=corrected_ds_overhead,
        )
    from ..plan.capacity import new_fake_nodes

    # occupancy caps (MaxCPU/MaxMemory/MaxVG) are part of feasibility and
    # monotone in node count — the reference keeps adding nodes on a cap
    # miss (`apply.go:199-207`), so walk the schedulable candidates upward
    result, reason = None, ""
    for best in (int(c) for c in feasible):
        say(f"candidate add = {best} node(s); re-simulating exactly")
        trial = ResourceTypes(**{k: list(v) for k, v in vars(cluster).items()})
        trial.nodes = list(cluster.nodes) + new_fake_nodes(new_node, best)
        result = simulate(
            trial, apps, extended_resources=extended_resources, sched_config=sched_config
        )
        ok, reason = satisfy_resource_setting(result)
        if ok:
            return PlanResult(True, best, result, "Success!", probes)
        say(reason.rstrip("\n"))
    return PlanResult(False, int(feasible[-1]), result, reason, probes)
