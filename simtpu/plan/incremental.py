"""Incremental min-node-add capacity planning: one tensorization, one base
placement, cheap completion probes.

The reference re-simulates the ENTIRE cluster from scratch for every
candidate clone count (`pkg/apply/apply.go:183-233` builds a fresh simulator
per iteration) — at planning scale that re-pays workload expansion,
tensorization, compilation, and a full placement per probe. This module
exploits two structural facts:

1. Candidate clusters differ only in how many template clones are VALID.
   Tensorizing base + max clones ONCE and flipping a `node_valid` mask per
   candidate (`StaticArrays.node_valid`, the same lever the batched sweep
   vmaps over) reuses the frozen tensors, memoized device statics, and every
   compiled executable across all probes.

2. Feasibility probes only need to answer "do the pods that failed on the
   base cluster fit once i clones exist?". The base run's final engine state
   is snapshotted on device; probe(i) resumes from the snapshot, places the
   clone-pinned DaemonSet pods for clones < i plus the base failures in
   their original order, and checks nothing is left behind. This is the
   retry semantics of a REAL cluster — kube-scheduler moves unschedulable
   pods back through the queue when node-add events arrive; it re-places
   only them, never the whole cluster — while the reference's fresh-restart
   is an artifact of its simulator design.

Because greedy placement is order-path-dependent, a fresh run at the chosen
count can in principle differ from base+completion. `verify=True` (default)
re-runs the winning candidate as one fresh full placement over the same
tensorization/compiled code (reference-faithful semantics, one extra
placement of wall-clock); if the fresh run disagrees, the search continues
upward with fresh runs — correctness never rests on the incremental oracle.

Engine-level throughout: probes bypass the Simulator facade (no per-pod
Python bookkeeping) and the final SimulateResult materializes once at the
end. Preemption does not run inside probes — capacity planning asks whether
everything fits, and evicting lower-priority pods does not change cluster
capacity (the serial planner inherits preemption from `simulate()`; use it
when priority-eviction semantics matter).

Two cross-candidate performance levers ride on top (the ISSUE-1 tentpole):

- MESH SHARDING: with `mesh=`, base placement, completion probes, and the
  verify re-runs all execute with the node axis sharded over the mesh
  (`MaskedShardedRoundsEngine`) — the candidate mask composes with the
  sharding's dead-node pad mask and placements stay bit-identical to the
  single-device path.  The compiled mesh executables live in a mesh-wide
  cache (`parallel.sharded._SHARDED_JITS`), so the fresh engine each
  candidate gets does NOT re-jit.
- SHAPE BUCKETING: every engine of one plan shares a bulk-chunk shape
  registry; probe chunks snap UP into (segment count, round capacity,
  carried term rows) buckets the base run already compiled
  (`RoundsEngine.snap_shapes`), so the whole linear/binary probe sweep and
  the verify run reuse warm round-body executables instead of
  shape-specializing per candidate — and the shapes stay deterministic
  across processes, which is what lets the persistent compilation cache
  (`simtpu/cache.py`) collapse the cold path on accelerator backends.
  `PlanResult.compiles` records the per-phase jit-trace counts.

Serial-engine dispatches inside the plan (the rounds engines' serial
fallback segments — tiny runs, matrix leftovers) additionally ride the
speculative wavefront dispatcher (engine/scan.py, docs/speculation.md):
eligible same-group lean runs place through the batched
verify-and-rollback executable instead of the pod-at-a-time scan, with
bit-identical placements.  `speculate=` (None = the SIMTPU_WAVEFRONT
default) forces it per plan for A/B measurement.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import constants as C
from ..core.objects import (
    AppResource,
    NodeStatus,
    ResourceTypes,
    SimulateResult,
    UnscheduledPod,
    deep_copy,
    name_of,
    namespace_of,
)
from ..core.tensorize import slice_batch
from ..durable.deadline import PlanInterrupted
from ..engine.rounds import RoundsEngine
from ..engine.scan import REASON_TEXT
from ..engine.state import CompactState
from ..obs.trace import span
from .capacity import PlanResult, _env_cap, meet_resource_requests


class MaskedRoundsEngine(RoundsEngine):
    """Bulk rounds engine restricted to a candidate cluster: `node_valid`
    masks out clone nodes beyond the candidate's size (dead rows no pod can
    select, exactly like the sweep's vmapped membership masks).  The
    mesh-sharded counterpart is `parallel.sharded.MaskedShardedRoundsEngine`
    (same mask, composed before the shard padding)."""

    def __init__(self, tensorizer, node_valid: np.ndarray):
        super().__init__(tensorizer)
        self.node_valid = np.asarray(node_valid, bool)

    def _dispatch(self, statics, state, pods, flags):
        import jax.numpy as jnp

        statics = statics._replace(
            node_valid=statics.node_valid & jnp.asarray(self.node_valid)
        )
        return super()._dispatch(statics, state, pods, flags)


_state_copier = None


def _copy_state(state):
    """One-dispatch on-device copy of the scan carry (the engines donate
    their input state, so each probe consumes a copy of the snapshot).
    The jitted copier is module-cached — a fresh lambda per call would
    retrace every probe."""
    global _state_copier
    if _state_copier is None:
        import jax
        import jax.numpy as jnp

        _state_copier = jax.jit(
            lambda s: jax.tree_util.tree_map(jnp.copy, s)
        )
    return _state_copier(state)


def _vocab_of(tensors) -> tuple:
    """Engine.place's state-reuse key, for snapshot injection."""
    from ..engine.scan import Engine

    return Engine.state_vocab(tensors)


def _caps_satisfied(
    tensors, placed_req_sum: np.ndarray, node_valid: np.ndarray, vg_extra: float
) -> tuple:
    """MaxCPU/MaxMemory/MaxVG occupancy caps (`apply.go:580-666`), computed
    from the dense arrays instead of walking a million result pods. All caps
    at their default 100 are trivially satisfied (rates cannot exceed 100
    without overcommit, which the engines never do)."""
    max_cpu = _env_cap(C.ENV_MAX_CPU)
    max_mem = _env_cap(C.ENV_MAX_MEMORY)
    max_vg = _env_cap(C.ENV_MAX_VG)
    if max_cpu == 100 and max_mem == 100 and max_vg == 100:
        return True, ""
    from ..core.tensorize import RES_CPU, RES_MEMORY

    alloc = tensors.alloc[node_valid]
    total_cpu = float(alloc[:, RES_CPU].sum())
    total_mem = float(alloc[:, RES_MEMORY].sum())
    cpu_rate = int(placed_req_sum[RES_CPU] / total_cpu * 100) if total_cpu else 0
    mem_rate = int(placed_req_sum[RES_MEMORY] / total_mem * 100) if total_mem else 0
    if cpu_rate > max_cpu:
        return False, (
            f"the average occupancy rate({cpu_rate}%) of cpu goes beyond "
            f"the env setting({max_cpu}%)\n"
        )
    if mem_rate > max_mem:
        return False, (
            f"the average occupancy rate({mem_rate}%) of memory goes beyond "
            f"the env setting({max_mem}%)\n"
        )
    ext = tensors.ext
    vg_cap = float(ext.vg_cap[node_valid].sum())
    if vg_cap:
        vg_req = float(ext.vg_req0[node_valid].sum()) + vg_extra
        vg_rate = int(vg_req / vg_cap * 100)
        if vg_rate > max_vg:
            return False, (
                f"the average occupancy rate({vg_rate}%) of vg goes beyond "
                f"the env setting({max_vg}%)\n"
            )
    return True, ""


def plan_capacity_incremental(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    max_new_nodes: int = C.MAX_NUM_NEW_NODE,
    extended_resources: Sequence[str] = (),
    progress=None,
    sched_config=None,
    corrected_ds_overhead: bool = False,
    verify: bool = True,
    materialize: bool = True,
    mesh=None,
    precompile: bool = False,
    pipeline=None,
    speculate=None,
    checkpoint=None,
    control=None,
    audit: Optional[bool] = None,
    explain: bool = False,
    solver: Optional[bool] = None,
) -> PlanResult:
    """Minimum clone count of `new_node` deploying everything, via the
    incremental probe strategy described in the module docstring.

    `solver` (None = the SIMTPU_SOLVER default, off) consults the global
    solve backend (simtpu/solve, docs/solver.md) right after the shared
    tensorization: one vmapped convex relaxation over every candidate
    count.  An audit-certified solver answer ships directly (no base
    placement, no probes); a rejected one floors the resource lower
    bound with the solver's certified LP bound and the probe search runs
    as usual — always advisory, the auditor disposes.

    `explain` (off by default; the off path adds zero device dispatches)
    attaches the decision-observability block (simtpu/explain) to
    terminal failure results: the per-stage breakdown of the failing
    candidate's unplaced pods against its carried state, plus the
    binding-constraint bottleneck with the template verdict — the plan
    then reports *what to buy*, not just *how many*.

    `audit` (None = the SIMTPU_AUDIT default, on) runs the independent
    placement auditor (simtpu/audit) over the accepted candidate's fresh
    verify placement.  On audit failure the plan is NOT shipped: the
    candidate re-places through the serial exact scan (wavefront off,
    dense carry), re-audits, and the result carries a divergence
    diagnostic under `PlanResult.audit` — graceful degradation instead of
    a silently wrong answer (docs/robustness.md).  Audit requires the
    default `verify=True` path (the unverified fast path is explicitly
    uncertified).

    Matches `plan_capacity`'s contract (candidates 0..max_new_nodes-1,
    occupancy caps, can-never-help diagnostics, PlanResult shape); the
    per-candidate oracle differs as documented. `PlanResult.timings` carries
    the phase breakdown (tensorize / base / probes / verify / materialize)
    and `PlanResult.compiles` the per-phase jit-trace counts (the shape-
    bucketed probe sweep is expected to trace the round body at most twice
    across every candidate size).

    With `mesh` (a jax.sharding.Mesh), every placement — base, completion
    probes, and the fresh verify re-runs — executes node-sharded over the
    mesh's "nodes" axis (`MaskedShardedRoundsEngine`); the candidate
    node_valid mask composes with the sharding's dead-node pad mask, so
    placements are bit-identical to the single-device path.

    With `precompile`, one shared AOT pipeline (engine/precompile.py)
    background-compiles every executable the base run will need as soon as
    tensorization fixes the shape buckets — and each probe/verify engine
    re-enumerates against its own batch, deduplicating through the shared
    registry (probe chunks snap into base buckets, so they mostly find the
    base executables).  Placements are bit-identical either way; the
    per-phase `compiles` counts then attribute background traces to
    whatever phase is active when they run (timings gain
    compile_wall/compile_serial).  An internally-created pipeline is shut
    down on EVERY exit (cancelling enumerated-but-undispatched compiles —
    a raised plan must not leave the process lingering at exit finishing
    unused work); pass `pipeline=` (an AotPipeline, implies precompile) to
    share one registry across several plans — the caller then owns its
    lifecycle.
    """
    own_pipeline = None
    if pipeline is None and precompile:
        from ..engine.precompile import AotPipeline

        pipeline = own_pipeline = AotPipeline()
    try:
        return _plan_capacity_incremental(
            cluster, apps, new_node, max_new_nodes, extended_resources,
            progress, sched_config, corrected_ds_overhead, verify,
            materialize, mesh, pipeline, speculate, checkpoint, control,
            audit, explain, solver,
        )
    except PlanInterrupted as exc:
        # deadline / SIGINT between candidates (docs/robustness.md): the
        # structured partial result — every completed candidate is
        # already checkpointed, so a later --resume loses nothing
        from ..durable.deadline import partial_message

        best = getattr(exc, "best_candidate", None)
        out = PlanResult(
            False,
            -1 if best is None else best,
            None,
            partial_message(exc.reason, best, checkpoint),
            getattr(exc, "probes", {}),
            partial=True,
        )
        out.timings = getattr(exc, "timings", {})
        out.compiles = getattr(exc, "compiles", {})
        return out
    finally:
        if own_pipeline is not None:
            own_pipeline.shutdown()


def _plan_capacity_incremental(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    max_new_nodes: int,
    extended_resources: Sequence[str],
    progress,
    sched_config,
    corrected_ds_overhead: bool,
    verify: bool,
    materialize: bool,
    mesh,
    pipeline,
    speculate,
    checkpoint,
    control,
    audit=None,
    explain=False,
    solver=None,
) -> PlanResult:
    from ..audit.checker import audit_enabled
    from ..engine.scan import COMPILE_COUNT_KINDS, statics_from
    from ..obs.metrics import family as metrics_family
    from ..parallel.sweep import assemble_planning_problem
    from ..solve import solver_enabled

    def trace_counts() -> Dict[str, int]:
        # per-kind jit-trace counters off the obs registry (the ISSUE-8
        # alias views are gone; this is the direct read)
        return metrics_family("compile", COMPILE_COUNT_KINDS)

    # the auditor certifies the ACCEPTED candidate's fresh verify
    # placement; the explicitly-unverified verify=False path stays
    # uncertified by design
    audit_on = (audit_enabled() if audit is None else bool(audit)) and verify

    say = progress or (lambda s: None)
    timings: Dict[str, float] = {}
    compiles: Dict[str, Dict[str, int]] = {}
    probes: Dict[int, int] = {}
    # the global-solver consult's record + the priority-ignored flag,
    # attached to EVERY result this plan returns (finalize)
    solve_doc: Dict[str, object] = {}
    preempt_flag = [False]
    fail_msg = f"we have added {max_new_nodes} nodes but it still failed!!"
    # the best candidate any probe/verify found feasible so far — what an
    # interrupted plan reports as its partial answer
    best_candidate: List[Optional[int]] = [None]

    def check() -> None:
        """Deadline/SIGINT poll at the candidate boundary; the raised
        PlanInterrupted carries the search progress so the wrapper can
        assemble the partial PlanResult."""
        if control is None:
            return
        try:
            control.check()
        except PlanInterrupted as exc:
            exc.probes = dict(probes)
            exc.timings = dict(timings)
            exc.compiles = dict(compiles)
            exc.best_candidate = best_candidate[0]
            raise

    def mark_compiles(phase: str, before: dict) -> None:
        after = trace_counts()
        prev = compiles.get(phase, {})
        compiles[phase] = {
            k: prev.get(k, 0) + after.get(k, 0) - before.get(k, 0)
            for k in after
        }

    def finalize(out: PlanResult) -> PlanResult:
        if pipeline is not None:
            s = pipeline.stats()
            timings["compile_wall"] = s["compile_wall_s"]
            timings["compile_serial"] = s["compile_serial_s"]
        out.timings = timings
        out.compiles = compiles
        if solve_doc and not out.solve:
            out.solve = dict(solve_doc)
        out.preemption_ignored = preempt_flag[0]
        return out

    t0 = time.perf_counter()
    max_new = max(max_new_nodes - 1, 0)  # reference walks i in [0, max)
    if checkpoint is not None:
        # pin the pod-name suffix stream to the problem fingerprint: the
        # ONE expansion below then produces identical pods (names
        # included) in the interrupted and the resuming process, which is
        # what makes the recorded placement vectors replayable across
        # processes (durable.checkpoint.name_seed)
        from ..durable.checkpoint import name_seed
        from ..workloads.expand import seed_name_hashes

        seed_name_hashes(name_seed(checkpoint.fingerprint))
    with span("plan.tensorize"):
        tz, all_nodes, n_base, ordered = assemble_planning_problem(
            cluster, apps, new_node, max_new, extended_resources
        )
        with span("tensorize", pods=len(ordered)):
            batch = tz.add_pods(ordered)
            tensors = tz.freeze()
        statics_from(tensors, sched_config)  # transfer device statics once
        vocab = _vocab_of(tensors)
        pin = np.asarray(batch.pin)
        clone_of = pin - n_base  # >= 0 for clone-pinned (DaemonSet) pods
    timings["tensorize"] = time.perf_counter() - t0

    # -- loud no-preemption notice (docs/status.md): probes never evict.
    # Capacity planning asks whether everything FITS — where pods could
    # preempt (a pod to schedule outranks another), their eviction
    # semantics are ignored, and that must be visible at runtime, not
    # only in the docs.  Uniform priorities can never preempt.
    from ..core.objects import can_preempt, pod_priority, pod_spec

    prios = [pod_priority(p) for p in ordered]
    if can_preempt(
        (q for p, q in zip(ordered, prios) if not pod_spec(p).get("nodeName")),
        prios,
    ):
        import sys

        preempt_flag[0] = True
        notice = (
            "simtpu: pod priorities differ, but the incremental "
            "planner never runs preemption — priority/eviction semantics "
            "are IGNORED (use --search binary/linear for simulate()'s "
            "preemption path)"
        )
        print(notice, file=sys.stderr)
        say(notice)

    # -- global-solver consult (simtpu/solve, docs/solver.md): one
    # vmapped relaxation over every candidate count, on the SAME
    # tensorization the probes would use.  Accepted => the plan ships
    # here (no base placement, no probes); rejected => its certified LP
    # bound floors the resource lower bound below.  Checkpointed runs
    # skip it — solver answers are not candidate records.
    lb_solve = 0
    solver_on = solver_enabled() if solver is None else bool(solver)
    if solver_on and checkpoint is None:
        from ..solve import attempt_solve

        check()
        c0 = trace_counts()
        t_s = time.perf_counter()
        with span("solve"):
            att = attempt_solve(
                tz, tensors, batch, all_nodes, n_base, max_new,
                sched_config, say,
            )
        timings["solve"] = time.perf_counter() - t_s
        mark_compiles("solve", c0)
        solve_doc.update(att.doc)
        if att.accepted:
            probes[att.k] = 0
            best_candidate[0] = att.k
            result = None
            if materialize:
                t1 = time.perf_counter()
                result = _materialize(
                    tz, all_nodes, n_base + att.k, batch, att.nodes_arr,
                    att.reasons, clone_of, att.k, att.ext_log, att.gpu_arr,
                )
                timings["materialize"] = time.perf_counter() - t1
            out = PlanResult(True, att.k, result, "Success!", probes)
            out.audit = att.audit_doc
            return finalize(out)
        if att.certified:
            lb_solve = att.lower_bound
            if lb_solve > 0:
                say(
                    f"solver: certified lower bound {lb_solve} — flooring "
                    "the probe search"
                )

    # one shape-bucket registry for every engine of this plan: probes snap
    # their bulk chunks into buckets the base run (or an earlier probe)
    # already compiled, so the whole candidate sweep stays on warm
    # executables (engine/rounds.py `_bulk_chunk`)
    shape_registry: Dict = {}
    # ... and one AOT pipeline (when the wrapper created or was handed
    # one): every engine enumerates its batch's executables into the same
    # background-compile registry, so the base run's compiles start before
    # its first dispatch and the probe/verify engines find them finished
    # (engine/precompile.py)

    def make_engine(node_valid: np.ndarray, plan_batch=None):
        if mesh is not None:
            from ..parallel.sharded import MaskedShardedRoundsEngine

            eng = MaskedShardedRoundsEngine(tz, mesh, node_valid)
        else:
            eng = MaskedRoundsEngine(tz, node_valid)
        eng.sched_config = sched_config
        eng.bulk_shapes = shape_registry
        eng.snap_shapes = True
        if speculate is not None:
            eng.speculate = bool(speculate)
        if pipeline is not None and plan_batch is not None:
            from ..engine.precompile import precompile_place

            precompile_place(eng, plan_batch, pipeline)
        return eng

    def valid_mask(i: int) -> np.ndarray:
        m = np.ones(len(all_nodes), bool)
        m[n_base + i :] = False
        return m

    def _fallback_engine(i: int):
        """The serial exact referee the audit falls back to: pod-at-a-time
        scan, wavefront off, dense carry (docs/robustness.md)."""
        from ..engine.scan import Engine

        fb = Engine(tz)
        fb.node_valid = valid_mask(i)
        fb.speculate = False
        fb.compact = False
        fb.sched_config = sched_config
        return fb

    def _plane_diff(a_eng, b_eng):
        """Which carried-state planes the two engines' logs disagree on —
        the divergence diagnostic's state witness (engine/state.py
        diff_state_planes; audit-readable from-log views, no carries
        touched)."""
        from ..engine.state import build_state, diff_state_planes

        def dense(e):
            return build_state(
                tensors,
                np.asarray(e.placed_group, np.int32),
                np.asarray(e.placed_node, np.int32),
                e.log_req_matrix(r_res),
                e.ext_log,
            )

        return diff_state_planes(dense(a_eng), dense(b_eng))

    def mk_explain(eng, ebatch, erows, enodes, ereasons, i, base_nodes=None):
        """Decision-observability block for a failing candidate
        (simtpu/explain): per-stage breakdown against the engine's
        carried state + the bottleneck analysis with the template
        verdict.  {} when --explain was not requested (the off path
        dispatches nothing).  A checkpoint-replayed candidate has no
        carried state — it explains with the bottleneck block alone, its
        free capacity rebuilt from EVERY visible placement: probe call
        sites hand in `base_nodes` because their `enodes`/`ebatch` cover
        only the unplaced-from-base slice, and free derived from that
        slice alone would overstate capacity and misname the binding
        resource."""
        if not explain or not len(erows):
            return {}
        from ..explain import build_explain_doc

        all_ds = list(cluster.daemon_sets)
        for app in apps:
            all_ds += app.resource.daemon_sets
        try:
            state = eng.carried_state()
        except ValueError:
            state = None
        free = None
        if state is None:
            used = np.zeros(tensors.alloc.shape, np.float32)
            enodes_np = np.asarray(enodes)
            ereq = np.asarray(ebatch.req, np.float32)
            if ereq.shape[1] < r_res:
                ereq = np.pad(ereq, ((0, 0), (0, r_res - ereq.shape[1])))
            placed = np.flatnonzero(enodes_np >= 0)
            np.add.at(used, enodes_np[placed], ereq[placed])
            if base_nodes is not None:
                base_np = np.asarray(base_nodes)
                bplaced = np.flatnonzero(base_np >= 0)
                np.add.at(used, base_np[bplaced], req_pad[bplaced])
            free = tensors.alloc - used
        return build_explain_doc(
            tensors, ebatch, erows, state, np.asarray(enodes),
            np.asarray(ereasons), node_valid=valid_mask(i),
            sched_config=sched_config, new_node=new_node,
            daemon_sets=all_ds, corrected_ds_overhead=corrected_ds_overhead,
            free=free,
        )

    r_res = tensors.alloc.shape[1]
    req_pad = batch.req
    if req_pad.shape[1] < r_res:
        req_pad = np.pad(req_pad, ((0, 0), (0, r_res - req_pad.shape[1])))

    def replay_engine(i, rows, nodes_arr, lvm, dev, gpu, with_state):
        """An engine equivalent to one that just completed the recorded
        run (checkpoint resume): placement log + ext_log rebuilt from the
        record's placement vectors, and — when the caller needs the carry
        (the base candidate, whose snapshot seeds every probe) — the
        carried state rebuilt from that log, which is bit-identical to
        the dispatched carry (the donated-state reuse guard's pinned
        contract).  `rows` maps record positions to batch rows (None =
        identity: a full fresh run)."""
        from ..engine.state import build_state

        eng = make_engine(valid_mask(i))
        ok = np.flatnonzero(nodes_arr >= 0)
        rows_ok = ok if rows is None else np.asarray(rows)[ok]
        eng.placed_group = np.asarray(batch.group)[rows_ok].tolist()
        eng.placed_node = nodes_arr[ok].tolist()
        eng.placed_req = list(req_pad[rows_ok])
        eng.ext_log = {
            "node": nodes_arr[ok].tolist(),
            "vg_alloc": list(lvm[ok]),
            "sdev_take": list(dev[ok]),
            "gpu_shares": list(gpu[ok]),
            "gpu_mem": np.asarray(batch.ext["gpu_mem"])[rows_ok].tolist(),
        }
        if with_state:
            dense = build_state(
                tensors,
                np.asarray(eng.placed_group, np.int32),
                np.asarray(eng.placed_node, np.int32),
                eng.log_req_matrix(r_res),
                eng.ext_log,
            )
            eng.last_state = eng._store_state(tensors, dense)
            eng._last_vocab = vocab
            eng._state_dirty = False
        return eng

    def fresh_run(i: int, phase: str = "verify"):
        """Full placement of every pod against base + i clones (the
        reference's per-candidate semantics, minus re-tensorization).
        With a checkpoint, a completed record for (phase, i) replays
        instead of dispatching — the resume path."""
        rec = checkpoint.get(phase, i) if checkpoint is not None else None
        phantom = clone_of >= i
        if rec is not None:
            nodes = np.asarray(rec["nodes"])
            reasons = np.asarray(rec["reasons"])
            lvm, dev, gpu = (
                np.asarray(rec["lvm"]),
                np.asarray(rec["dev"]),
                np.asarray(rec["gpu"]),
            )
            eng = replay_engine(
                i, None, nodes, lvm, dev, gpu, with_state=(phase == "base")
            )
            failed = (nodes < 0) & ~phantom
            probes[i] = int(failed.sum())
            return eng, nodes, reasons, failed, {
                "lvm_alloc": lvm, "dev_take": dev, "gpu_shares": gpu,
            }
        check()
        c0 = trace_counts()
        with span("plan.candidate", count=int(i), phase=phase):
            eng = make_engine(valid_mask(i), plan_batch=batch)
            nodes, reasons, extras = eng.place(batch)
        failed = (nodes < 0) & ~phantom
        probes[i] = int(failed.sum())
        mark_compiles(phase, c0)
        if checkpoint is not None:
            checkpoint.put(
                phase, i,
                nodes=nodes, reasons=reasons, lvm=extras["lvm_alloc"],
                dev=extras["dev_take"], gpu=extras["gpu_shares"],
            )
        return eng, nodes, reasons, failed, extras

    # -- base candidate: i = 0 -------------------------------------------
    t0 = time.perf_counter()
    say("add 0 node(s)")
    with span("plan.base"):
        base_eng, base_nodes_arr, base_reasons, base_failed, base_extras = (
            fresh_run(0, phase="base")
        )
    timings["base"] = time.perf_counter() - t0

    def finish(i, eng, nodes_arr, reasons, extras):
        ok, reason = _caps_satisfied(
            tensors,
            batch.req[nodes_arr >= 0].sum(axis=0),
            valid_mask(i),
            vg_extra=float(
                np.asarray(eng.ext_log["vg_alloc"]).sum()
                if len(eng.ext_log["vg_alloc"])
                else 0.0
            ),
        )
        if not ok:
            say(reason.rstrip("\n"))
            return None
        nodes_arr = np.asarray(nodes_arr)
        reasons = np.asarray(reasons)
        ext_log = eng.ext_log
        gpu_arr = extras["gpu_shares"]
        audit_doc: Dict[str, object] = {}
        if audit_on:
            from ..audit.checker import (
                audit_placement,
                divergence_diagnostic,
                inject_divergence,
                inject_divergence_enabled,
            )

            phantom = clone_of >= i
            nodes_aud = nodes_arr
            if inject_divergence_enabled():
                nodes_aud = inject_divergence(tensors, batch, nodes_arr)
            rep = audit_placement(
                tensors, batch, nodes_aud, extras,
                node_valid=valid_mask(i), require_all=True,
                expect_mask=~phantom,
            )
            audit_doc = rep.counters()
            if not rep.ok:
                # divergence-safe fallback (docs/robustness.md): do NOT
                # ship the uncertified plan — re-place through the serial
                # exact scan, re-audit, and report the divergence
                say(
                    f"audit FAILED on the accepted candidate "
                    f"({rep.summary()}) — re-placing through the serial "
                    "exact scan"
                )
                fb = _fallback_engine(i)
                nodes_f, reasons_f, extras_f = fb.place(batch)
                nodes_f = np.asarray(nodes_f)
                rep_f = audit_placement(
                    tensors, batch, nodes_f, extras_f,
                    node_valid=valid_mask(i), require_all=True,
                    expect_mask=~phantom,
                )
                audit_doc = {
                    **rep.counters(),
                    "fallback": True,
                    "fallback_audit": rep_f.counters(),
                    "divergence": divergence_diagnostic(
                        tensors, batch, nodes_aud, nodes_f, rep,
                        planes=_plane_diff(eng, fb),
                    ),
                }
                if not rep_f.ok:
                    out = PlanResult(
                        False, i, None,
                        "audit failure: the accepted candidate violates "
                        "its claimed constraints and the serial-exact "
                        f"fallback did not certify either ({rep_f.summary()})",
                        probes,
                    )
                    out.audit = audit_doc
                    return finalize(out)
                audit_doc["ok"] = True
                nodes_arr, reasons = nodes_f, np.asarray(reasons_f)
                ext_log, gpu_arr = fb.ext_log, extras_f["gpu_shares"]
        result = None
        if materialize:
            t1 = time.perf_counter()
            result = _materialize(
                tz, all_nodes, n_base + i, batch, nodes_arr, reasons,
                clone_of, i, ext_log, gpu_arr,
            )
            timings["materialize"] = time.perf_counter() - t1
        out = PlanResult(True, i, result, "Success!", probes)
        out.audit = audit_doc
        return finalize(out)

    if probes[0] == 0:
        best_candidate[0] = 0
        done = finish(0, base_eng, base_nodes_arr, base_reasons, base_extras)
        if done is not None:
            return done
        # caps failed at 0: more nodes lower the average rate — keep searching
    u0 = np.flatnonzero(base_failed)

    def diagnose(failed_idx) -> Optional[str]:
        """Adding template nodes can never help (`apply.go:213-231`)."""
        from ..core.match import node_should_run_pod

        all_ds = list(cluster.daemon_sets)
        for app in apps:
            all_ds += app.resource.daemon_sets
        with span("plan.diagnose"):
            for j in failed_idx[:64]:  # a handful suffices for the message
                pod = ordered[int(j)]
                if not node_should_run_pod(new_node, pod):
                    return (
                        f"failed to schedule pod {namespace_of(pod)}/{name_of(pod)}: "
                        "the pod cannot be scheduled successfully by adding node: "
                        "pod does not fit new node affinity or taints"
                    )
                if not meet_resource_requests(
                    new_node, pod, all_ds, corrected=corrected_ds_overhead
                ):
                    return (
                        f"failed to schedule pod {namespace_of(pod)}/{name_of(pod)}: "
                        "new node cannot meet resource requests of pod: the total "
                        "requested resource of daemonset pods in new node is too large"
                    )
        return None

    msg = diagnose(u0)
    if msg:
        out = PlanResult(False, 0, None, msg, probes)
        out.explain = mk_explain(
            base_eng, batch, u0, base_nodes_arr, base_reasons, 0
        )
        return finalize(out)
    if max_new == 0:
        # no candidate beyond 0 exists (max_new_nodes <= 1, apply.go's
        # exclusive upper bound) — the base failure is terminal
        out = PlanResult(False, max_new_nodes, None, fail_msg, probes)
        out.explain = mk_explain(
            base_eng, batch, u0, base_nodes_arr, base_reasons, 0
        )
        return finalize(out)

    # -- snapshot + cheap probes ------------------------------------------
    t0 = time.perf_counter()
    # the snapshot is the base engine's carry AS STORED — under the compact
    # layout (engine/state.py CompactState) that is the domain-tabular
    # form, and the probes inject it VERBATIM: place()'s reuse branch
    # expands a compact carry without donating or mutating it and then
    # stores a fresh carry, so the shared snapshot stays intact across
    # probes.  A dense snapshot must be copied per probe — the reuse
    # branch hands it straight to a donating dispatch.
    snapshot = base_eng.last_state
    copy_snapshot = (
        (lambda: snapshot)
        if isinstance(snapshot, CompactState)
        else (lambda: _copy_state(snapshot))
    )

    def probe(i: int) -> tuple:
        """Completion probe: from the base snapshot, place the clone
        DaemonSet pods for clones < i plus every base failure, in original
        order. Feasible iff all of them place.  With a checkpoint, a
        completed record for ("probe", i) replays instead of dispatching
        (idx is deterministic given the — itself checkpointed — base)."""
        idx = np.flatnonzero(base_failed | ((clone_of >= 0) & (clone_of < i)))
        rec = checkpoint.get("probe", i) if checkpoint is not None else None
        if rec is not None:
            nodes = np.asarray(rec["nodes"])
            reasons = np.asarray(rec["reasons"])
            lvm, dev, gpu = (
                np.asarray(rec["lvm"]),
                np.asarray(rec["dev"]),
                np.asarray(rec["gpu"]),
            )
            eng = replay_engine(i, idx, nodes, lvm, dev, gpu, with_state=False)
            failed = nodes < 0
            probes[i] = int(failed.sum())
            return eng, idx, nodes, reasons, failed, gpu
        check()
        say(f"add {i} node(s)")
        c0 = trace_counts()
        with span("plan.candidate", count=int(i), phase="probes"):
            probe_batch = slice_batch(batch, idx)
            eng = make_engine(valid_mask(i), plan_batch=probe_batch)
            eng.last_state = copy_snapshot()
            eng._last_vocab = vocab
            eng._state_dirty = False
            nodes, reasons, extras = eng.place(probe_batch)
        failed = nodes < 0
        probes[i] = int(failed.sum())
        mark_compiles("probes", c0)
        if checkpoint is not None:
            checkpoint.put(
                "probe", i,
                nodes=nodes, reasons=reasons, lvm=extras["lvm_alloc"],
                dev=extras["dev_take"], gpu=extras["gpu_shares"],
            )
        return eng, idx, nodes, reasons, failed, extras["gpu_shares"]

    # resource lower bound: the base failures must at least FIT the added
    # template capacity, DS overhead aside — probes below it cannot succeed
    lb = 1
    if len(u0):
        demand = batch.req[u0].sum(axis=0)
        cap = tensors.alloc[n_base]
        with np.errstate(divide="ignore", invalid="ignore"):
            need = np.where(demand > 0, demand / np.maximum(cap, 1e-30), 0.0)
        need_max = float(need.max())
        if not math.isfinite(need_max) or need_max >= max_new_nodes:
            # a demanded resource the template lacks, or a bound beyond the
            # cap: a single terminal probe decides (and diagnoses) failure
            lb = max_new
        else:
            lb = max(1, int(math.ceil(need_max - 1e-9)))
    # the solver's certified LP bound floors the resource bound — LP
    # feasibility is necessary for ANY placement, so probes below it are
    # wasted dispatches (simtpu/solve, docs/solver.md)
    lb = max(lb, lb_solve)
    # doubling from the bound, then bisection on the open interval; when the
    # very first probe (the resource lower bound) is feasible, try bound-1
    # next — the bound is usually tight, making the whole search 2 probes
    hi = None
    first_cand = cand = min(max(lb, 1), max_new)
    lo = 0  # 0 is known infeasible (or cap-failed)
    while True:
        if cand <= lo:
            break
        eng_i, idx_i, nodes_i, reasons_i, failed_i, gpu_i = probe(cand)
        if probes[cand] == 0:
            hi, hi_run = cand, (eng_i, idx_i, nodes_i, gpu_i)
            if best_candidate[0] is None or cand < best_candidate[0]:
                best_candidate[0] = cand
        else:
            lo = max(lo, cand)
            msg = diagnose(idx_i[failed_i])
            if msg:
                out = PlanResult(False, cand, None, msg, probes)
                out.explain = mk_explain(
                    eng_i, slice_batch(batch, idx_i),
                    np.flatnonzero(failed_i), nodes_i, reasons_i, cand,
                    base_nodes=base_nodes_arr,
                )
                return finalize(out)
        if hi is None:
            if cand >= max_new:
                out = PlanResult(False, max_new_nodes, None, fail_msg, probes)
                out.explain = mk_explain(
                    eng_i, slice_batch(batch, idx_i),
                    np.flatnonzero(np.asarray(failed_i)), nodes_i,
                    reasons_i, cand, base_nodes=base_nodes_arr,
                )
                return finalize(out)
            cand = min(cand * 2, max_new)
        elif hi == first_cand and lo == 0 and hi - 1 > lo:
            cand = hi - 1  # tight-bound fast path
        elif hi - lo > 1:
            cand = (lo + hi) // 2
        else:
            break
    timings["probes"] = time.perf_counter() - t0

    # -- reference-faithful confirmation ----------------------------------
    if verify:
        t0 = time.perf_counter()
        i = hi
        while i < max_new_nodes:
            say(f"verify {i} node(s) with a fresh placement")
            eng_v, nodes_v, reasons_v, failed_v, extras_v = fresh_run(i)
            if probes[i] == 0:
                if best_candidate[0] is None or i < best_candidate[0]:
                    best_candidate[0] = i
                timings["verify"] = time.perf_counter() - t0
                done = finish(i, eng_v, nodes_v, reasons_v, extras_v)
                if done is not None:
                    return done
                i += 1  # caps failed: monotone in node count, walk upward
                continue
            msg = diagnose(np.flatnonzero(failed_v))
            if msg:
                out = PlanResult(False, i, None, msg, probes)
                out.explain = mk_explain(
                    eng_v, batch, np.flatnonzero(failed_v), nodes_v,
                    reasons_v, i,
                )
                return finalize(out)
            i += 1
        out = PlanResult(False, max_new_nodes, None, fail_msg, probes)
        out.explain = mk_explain(
            eng_v, batch, np.flatnonzero(failed_v), nodes_v, reasons_v,
            max_new_nodes - 1,
        )
        return finalize(out)

    # -- incremental result: base placements + winning probe -------------
    eng_w, idx_w, nodes_w, gpu_w = hi_run
    nodes_all = base_nodes_arr.copy()
    nodes_all[idx_w] = nodes_w
    gpu_all = np.asarray(base_extras["gpu_shares"]).copy()
    if len(idx_w):
        gpu_all[idx_w] = gpu_w
    reasons_all = base_reasons.copy()
    ext_log = {
        k: list(base_eng.ext_log[k]) + list(eng_w.ext_log[k])
        for k in base_eng.ext_log
    }
    ok, reason = _caps_satisfied(
        tensors,
        batch.req[nodes_all >= 0].sum(axis=0),
        valid_mask(hi),
        vg_extra=float(
            np.asarray(ext_log["vg_alloc"]).sum() if len(ext_log["vg_alloc"]) else 0.0
        ),
    )
    if not ok:
        # rare unverified path with caps configured: fall back to fresh
        # upward walk for exact reference cap semantics
        say(reason.rstrip("\n"))
        i = hi + 1
        while i < max_new_nodes:
            eng_v, nodes_v, reasons_v, failed_v, extras_v = fresh_run(i)
            if probes[i] == 0:
                done = finish(i, eng_v, nodes_v, reasons_v, extras_v)
                if done is not None:
                    return done
            i += 1
        return finalize(PlanResult(False, max_new_nodes, None, fail_msg, probes))
    result = None
    if materialize:
        t1 = time.perf_counter()
        result = _materialize(
            tz, all_nodes, n_base + hi, batch, nodes_all, reasons_all,
            clone_of, hi, ext_log, gpu_all,
        )
        timings["materialize"] = time.perf_counter() - t1
    return finalize(PlanResult(True, hi, result, "Success!", probes))


def _materialize(
    tz,
    all_nodes: List[dict],
    n_nodes: int,
    batch: PodBatch,
    nodes_arr: np.ndarray,
    reasons: np.ndarray,
    clone_of: np.ndarray,
    n_clones: int,
    ext_log: dict,
    gpu_shares_arr,
) -> SimulateResult:
    """Assemble the SimulateResult for the winning candidate from the
    engine-level placement vector (one pass, no per-probe Python cost)."""
    from ..api import record_placed_pod, write_extended_annotations

    with span("plan.materialize", nodes=int(n_nodes)):
        node_objs = [deep_copy(n) for n in all_nodes[:n_nodes]]
        write_extended_annotations(tz.ext, ext_log, node_objs)
        names = [name_of(n) for n in node_objs]
        by_node: List[List[dict]] = [[] for _ in range(n_nodes)]
        unscheduled: List[UnscheduledPod] = []
        gpu_shares_arr = np.asarray(gpu_shares_arr)
        phantom = clone_of >= n_clones
        for j in np.flatnonzero((nodes_arr >= 0) & ~phantom):
            pod = batch.pods[int(j)]
            by_node[int(nodes_arr[j])].append(
                record_placed_pod(pod, names[int(nodes_arr[j])], gpu_shares_arr[j])
            )
        for j in np.flatnonzero((nodes_arr < 0) & ~phantom):
            pod = batch.pods[int(j)]
            msg = REASON_TEXT.get(int(reasons[j]), "unschedulable")
            unscheduled.append(
                UnscheduledPod(
                    pod=pod,
                    reason=(
                        f"failed to schedule pod ({namespace_of(pod)}/{name_of(pod)}): "
                        f"Unschedulable: 0/{n_nodes} nodes are available: {msg}"
                    ),
                )
            )
        statuses = [
            NodeStatus(node=n, pods=by_node[i]) for i, n in enumerate(node_objs)
        ]
    return SimulateResult(
        unscheduled_pods=unscheduled, node_status=statuses, preempted_pods=[]
    )
