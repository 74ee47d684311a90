"""Capacity planner: minimum node additions for a full deployment.

Mirrors `Applier.Run` (`pkg/apply/apply.go:88-245`): load apps + cluster +
new-node template, then find the smallest number of template-node clones that
lets every pod schedule, subject to the MaxCPU/MaxMemory/MaxVG average
utilization caps (`apply.go:580-666`), with "adding nodes can never help"
diagnostics (`apply.go:213-231` → `utils.NodeShouldRunPod`,
`utils.MeetResourceRequests`).

Search strategy: the reference walks i = 0,1,2,…,100 re-simulating from
scratch each time (`apply.go:183`, `MaxNumNewNode=100`). Feasibility is
monotone in the clone count (clones only add capacity), so the default here is
a doubling probe + binary search — O(log N) full simulations instead of O(N) —
with `search="linear"` available for reference-exact behavior.

Non-monotone caveat (pinned by tests/test_plan.py): SCHEDULABILITY is
monotone, but the MaxCPU/MaxMemory/MaxVG occupancy-cap verdict need not be —
with DaemonSet overhead, every clone adds `u` usage against `A` capacity, so
the average rate tends toward u/A and RISES with the clone count whenever it
starts below that ratio.  A feasible window like {k0..k1} can then be jumped
over by the doubling probe, where the reference's linear walk would land
inside it.  The binary search therefore falls back LOUDLY to the
reference-exact linear scan the moment any probe is rejected by the caps
alone (everything scheduled, rate over the cap); probes already known
unschedulable are skipped in the fallback (schedulability stays monotone).
With the caps at their default 100 the fallback can never trigger.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .. import constants as C
from ..api import simulate
from ..config import SimonConfig, validate_config
from ..core.match import node_should_run_pod
from ..core.objects import (
    AppResource,
    ResourceTypes,
    SimulateResult,
    can_preempt,
    name_of,
    namespace_of,
    pod_priority,
    pod_requests,
    pod_spec,
    set_label,
)
from ..core.quantity import parse_quantity
from ..durable.deadline import PlanInterrupted
from ..io.cluster import (
    create_cluster_resource_from_client,
    create_cluster_resource_from_cluster_config,
    match_and_set_local_storage_annotation_on_node,
)
from ..io.yaml_loader import get_objects_from_yaml_content, get_yaml_content_from_directory
from ..obs.metrics import REGISTRY, SCHEMA_VERSION
from ..obs.trace import span
from ..workloads.expand import make_valid_node_by_node, new_daemon_pod


@dataclass
class PlanResult:
    success: bool
    nodes_added: int
    result: Optional[SimulateResult]
    message: str = ""
    # per-candidate-count unscheduled totals, for transparency
    probes: Dict[int, int] = field(default_factory=dict)
    # per-phase wall-clock seconds (ingest, plan), the observability the
    # reference lacks (SURVEY.md §5: vendored metrics exist but are never
    # exported)
    timings: Dict[str, float] = field(default_factory=dict)
    # the engines that actually ran (search strategy, bulk placement,
    # node-shard count, and whether the choice was automatic): auto engine
    # selection can change results vs the reference-exact path (bulk
    # tie-breaks, incremental's no-preemption semantics), and a stderr-only
    # notice is invisible to scripted/CI consumers — this rides the result
    # and the CLI's --json output
    engine: Dict[str, object] = field(default_factory=dict)
    # per-phase jit-trace counts from the incremental planner (base /
    # probes / verify, each {"rounds": n, "scan": m}) — the compile
    # observability behind the shape-bucketed probe sweep and bench.py's
    # cold-path tracking
    compiles: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # True when the plan was interrupted (deadline / SIGINT) and this
    # result reports only the best candidate verified BEFORE the
    # interrupt (nodes_added = that candidate, or -1 when none) — the
    # structured partial-result contract (docs/robustness.md); rides the
    # CLI's --json as "partial"
    partial: bool = False
    # the independent placement audit of the shipped candidate
    # (simtpu/audit, docs/robustness.md): AuditReport.counters() plus —
    # when the primary engine's answer failed its audit and the
    # serial-exact fallback shipped instead — "fallback": true and a
    # "divergence" diagnostic (first divergent pod, differing state
    # planes).  {} = audit not run (--no-audit / SIMTPU_AUDIT=0);
    # rides --json under engine.audit and decides the audit exit code
    audit: Dict[str, object] = field(default_factory=dict)
    # the unified metrics block (ISSUE 8, obs/metrics.py): one flat
    # name → value dict of every counter family's delta over this plan
    # (gauges report their end-of-plan level).  The legacy engine-block
    # fields above are aliases built FROM these values — bit-equal by
    # construction, kept for one release; rides --json as "metrics"
    metrics: Dict[str, object] = field(default_factory=dict)
    # layout stamp for --json consumers (obs.metrics.SCHEMA_VERSION):
    # bumped whenever the metrics block or any stable field changes
    # shape — pin on this, not on key probing
    schema_version: int = SCHEMA_VERSION
    # decision-observability block (simtpu/explain, `--explain`): the
    # per-stage failure breakdown of the reported candidate's unplaced
    # pods + the binding-constraint bottleneck analysis ("what to buy").
    # {} = not requested (the zero-cost default); carries its own
    # "version" stamp (explain.EXPLAIN_VERSION); rides --json as
    # "explain" and the flight recorder's exit-3/4 bundles
    explain: Dict[str, object] = field(default_factory=dict)
    # the global-solver backend's record (simtpu/solve, docs/solver.md):
    # status (accepted / accepted_fallback / rejected / infeasible /
    # ineligible), the certified lower bound it handed the exact search,
    # and the audit/fallback trail when its answer shipped.  {} = solver
    # not consulted (--no-solver / SIMTPU_SOLVER unset); rides --json
    # under engine.solve
    solve: Dict[str, object] = field(default_factory=dict)
    # True when the incremental planner received specs whose pods can
    # preempt: probes never run preemption (capacity planning asks
    # whether everything fits), so priority semantics were IGNORED — the
    # loud runtime counterpart of the docs/status.md note; rides --json
    # under engine.preemption_ignored
    preemption_ignored: bool = False


def new_fake_nodes(template: dict, count: int) -> List[dict]:
    """Clone the template node `count` times as simon-%02d with the new-node
    label (`pkg/apply/apply.go:286-303`)."""
    nodes = []
    for i in range(count):
        hostname = f"{C.NEW_NODE_NAME_PREFIX}-{i:02d}"
        node = make_valid_node_by_node(template, hostname)
        set_label(node, C.LABEL_NEW_NODE, "")
        nodes.append(node)
    return nodes


def _env_cap(name: str) -> int:
    """0-100 percentage cap from env; out-of-range falls back to 100
    (`apply.go:580-610`)."""
    raw = os.environ.get(name, "")
    if not raw:
        return 100
    val = int(raw)
    return 100 if (val > 100 or val < 0) else val


def satisfy_resource_setting(result: SimulateResult) -> (bool, str):
    """Average cluster occupancy caps MaxCPU/MaxMemory/MaxVG
    (`apply.go:580-666`)."""
    import json

    max_cpu = _env_cap(C.ENV_MAX_CPU)
    max_mem = _env_cap(C.ENV_MAX_MEMORY)
    max_vg = _env_cap(C.ENV_MAX_VG)

    total = {"cpu": 0.0, "memory": 0.0}
    used = {"cpu": 0.0, "memory": 0.0}
    vg_cap = vg_req = 0.0
    for status in result.node_status:
        alloc = ((status.node.get("status") or {}).get("allocatable")) or {}
        total["cpu"] += parse_quantity(alloc.get("cpu"))
        total["memory"] += parse_quantity(alloc.get("memory"))
        for pod in status.pods:
            req = pod_requests(pod)
            used["cpu"] += req.get("cpu", 0.0)
            used["memory"] += req.get("memory", 0.0)
        anno = (status.node.get("metadata") or {}).get("annotations") or {}
        raw = anno.get(C.ANNO_NODE_LOCAL_STORAGE)
        if raw:
            storage = json.loads(raw)
            for vg in storage.get("vgs") or []:
                vg_cap += parse_quantity(vg.get("capacity"))
                vg_req += parse_quantity(vg.get("requested"))

    cpu_rate = int(used["cpu"] / total["cpu"] * 100) if total["cpu"] else 0
    mem_rate = int(used["memory"] / total["memory"] * 100) if total["memory"] else 0
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
    if vg_cap:
        vg_rate = int(vg_req / vg_cap * 100)
        if vg_rate > max_vg:
            return False, (
                f"the average occupancy rate({vg_rate}%) of vg goes beyond "
                f"the env setting({max_vg}%)\n"
            )
    return True, ""


def meet_resource_requests(
    node: dict, pod: dict, daemon_sets: Sequence[dict], corrected: bool = False
) -> bool:
    """Could the new-node template EVER hold this pod, once its daemonsets are
    accounted for? (`pkg/utils/utils.go:768-818`).

    Reference quirk preserved by default: the probe daemon pod is pinned to a
    node named `simon` (`utils.go:777` passes NewNodeNamePrefix as the node
    name), so unless the template node is literally named "simon" the
    matchFields pin fails NodeShouldRunPod and daemonset overhead contributes
    nothing — a DS-heavy cluster under-provisions exactly like the reference.
    `corrected=True` pins the probe pod to the template node's own name so
    the overhead is actually accounted (opt-in via `--corrected-ds-overhead`).
    """
    import json

    probe_name = name_of(node) if corrected else C.NEW_NODE_NAME_PREFIX
    total_cpu = total_mem = 0.0
    for ds in daemon_sets:
        daemon_pod = new_daemon_pod(ds, probe_name)
        if node_should_run_pod(node, daemon_pod):
            req = pod_requests(daemon_pod)
            total_cpu += req.get("cpu", 0.0)
            total_mem += req.get("memory", 0.0)
    req = pod_requests(pod)
    total_cpu += req.get("cpu", 0.0)
    total_mem += req.get("memory", 0.0)
    alloc = ((node.get("status") or {}).get("allocatable")) or {}
    if total_cpu > parse_quantity(alloc.get("cpu")) or total_mem > parse_quantity(
        alloc.get("memory")
    ):
        return False
    # local storage: sum of LVM claims must fit the largest VG
    anno = (node.get("metadata") or {}).get("annotations") or {}
    raw = anno.get(C.ANNO_NODE_LOCAL_STORAGE)
    if not raw:
        return True
    storage = json.loads(raw)
    vg_max = max(
        [parse_quantity(vg.get("capacity")) for vg in storage.get("vgs") or []] or [0.0]
    )
    pod_anno = (pod.get("metadata") or {}).get("annotations") or {}
    pvc_raw = pod_anno.get(C.ANNO_POD_LOCAL_STORAGE)
    pvc_sum = 0.0
    if pvc_raw:
        for vol in (json.loads(pvc_raw) or {}).get("volumes") or []:
            if vol.get("kind") == "LVM":
                pvc_sum += parse_quantity(vol.get("size"))
    return pvc_sum <= vg_max


def plan_capacity(
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
    new_node: dict,
    max_new_nodes: int = C.MAX_NUM_NEW_NODE,
    extended_resources: Sequence[str] = (),
    search: str = "binary",
    progress: Optional[Callable[[str], None]] = None,
    bulk: bool = False,
    sched_config=None,
    corrected_ds_overhead: bool = False,
    precompile: bool = False,
    checkpoint=None,
    control=None,
    audit: Optional[bool] = None,
    explain: bool = False,
    solver: Optional[bool] = None,
) -> PlanResult:
    """Find the minimum clone count of `new_node` that deploys everything.

    `solver` (None = the SIMTPU_SOLVER default, off) consults the global
    solve backend (simtpu/solve, docs/solver.md) FIRST: one vmapped
    convex relaxation over every candidate count replaces the whole
    doubling+bisection when its rounded answer is audit-certified at a
    count whose predecessor carries an infeasibility proof.  Advisory
    mode throughout — a rejected/uncertified solve falls through to the
    exact search below, warm-started with the solver's certified lower
    bound when one exists; the answer is then bit-identical to the
    solver-off run.

    `explain` (off by default — the off path adds zero device
    dispatches) attaches the decision-observability block
    (simtpu/explain) to the result: every live candidate simulation
    computes the failure breakdown + bottleneck analysis of its
    unplaced pods, and the reported candidate's block rides
    `PlanResult.explain` — so an infeasible plan reports *what to buy*
    (binding resource, template-node hint), not just *how many*.
    Deliberate cost shape: any candidate can turn out terminal (the
    diagnose failures return straight from the probe that hit them) and
    the Simulator closes inside simulate(), so each failing candidate
    pays its own explain pass — one vmapped dispatch per 64 unplaced
    pods, small against the full simulation it rides; fully-placed
    candidates pay nothing.

    `audit` (None = the SIMTPU_AUDIT default, on) runs the independent
    placement auditor (simtpu/audit) inside every candidate simulation
    and gates the WINNER on its verdict: an audit-dirty winner is never
    shipped — the candidate re-simulates through the serial exact engines
    (bulk off, wavefront off, dense carry), re-audits, and the result
    carries the divergence diagnostic under `PlanResult.audit`
    (docs/robustness.md).

    Durable execution (docs/robustness.md): with `checkpoint` (a
    `durable.checkpoint.PlanCheckpoint`) every completed candidate's
    verdict persists, and a resumed plan replays recorded candidates
    instead of re-simulating them (the winning candidate re-simulates
    once to materialize its SimulateResult — deterministic, so the
    PlanResult is bit-identical to the uninterrupted run).  With
    `control` (a `durable.deadline.RunControl`) the deadline/SIGINT check
    runs before each candidate; an interrupt yields a partial PlanResult
    (`partial=True`) instead of a traceback."""
    from ..audit.checker import audit_enabled, inject_divergence_enabled
    from ..solve import solver_enabled

    say = progress or (lambda s: None)
    probes: Dict[int, int] = {}
    # -- global-solver consult (simtpu/solve): solver proposes, auditor
    # disposes.  An accepted attempt IS the plan (no simulate() at all);
    # anything else warm-starts the exact search below.  Checkpointed
    # runs skip the solver — its answers are not candidate records.
    solve_doc: Dict[str, object] = {}
    lb_hint = 0
    solver_on = solver_enabled() if solver is None else bool(solver)
    if solver_on and checkpoint is None:
        from ..solve import solve_capacity_plan

        with span("solve"):
            plan_s, att = solve_capacity_plan(
                cluster, apps, new_node, max_new_nodes,
                extended_resources, progress=say, sched_config=sched_config,
            )
        if plan_s is not None:
            return plan_s
        solve_doc = att.doc
        if att.certified and att.lower_bound > 0:
            lb_hint = min(att.lower_bound, max_new_nodes - 1)
            say(
                f"solver: certified lower bound {att.lower_bound} — "
                "warm-starting the exact search"
            )
    all_daemon_sets = list(cluster.daemon_sets)
    for app in apps:
        all_daemon_sets += app.resource.daemon_sets
    best_candidate: list = [None]  # lowest candidate found feasible
    last_result: list = [None]  # most recent live SimulateResult
    audit_on = audit_enabled() if audit is None else bool(audit)
    # decision observability (simtpu/explain): the template context folds
    # the can-another-node-ever-help verdict into the bottleneck block
    explain_opts = (
        {
            "new_node": new_node,
            "daemon_sets": all_daemon_sets,
            "corrected": corrected_ds_overhead,
        }
        if explain
        else False
    )

    def with_explain(out: PlanResult, result) -> PlanResult:
        out.explain = getattr(result, "explain", None) or {}
        return out

    def run(i: int, serial_exact: bool = False) -> SimulateResult:
        say(f"add {i} node(s)")
        with span("plan.candidate", count=int(i), serial_exact=serial_exact):
            return _run_candidate(i, serial_exact)

    def _run_candidate(i: int, serial_exact: bool) -> SimulateResult:
        trial = ResourceTypes(**{k: list(v) for k, v in vars(cluster).items()})
        trial.nodes = list(cluster.nodes) + new_fake_nodes(new_node, i)
        if serial_exact:
            # the divergence-safe fallback's engines: pod-at-a-time scan,
            # wavefront off, dense carry (docs/robustness.md) — never the
            # engine config whose answer just failed its audit
            from ..engine.scan import Engine

            def factory(tz):
                eng = Engine(tz)
                eng.speculate = False
                eng.compact = False
                return eng

            return simulate(
                trial,
                apps,
                extended_resources=extended_resources,
                engine_factory=factory,
                sched_config=sched_config,
                audit=True,
                explain=explain_opts,
            )
        result = simulate(
            trial,
            apps,
            extended_resources=extended_resources,
            bulk=bulk,
            sched_config=sched_config,
            precompile=precompile,
            audit=audit_on,
            explain=explain_opts,
            _audit_inject=audit_on and inject_divergence_enabled(),
        )
        probes[i] = len(result.unscheduled_pods)
        last_result[0] = result
        return result

    def diagnose(result: SimulateResult) -> Optional[str]:
        """Return a message when adding template nodes can never help
        (`apply.go:213-231`)."""
        for unsched in result.unscheduled_pods:
            pod = unsched.pod
            if not node_should_run_pod(new_node, pod):
                return (
                    f"failed to schedule pod {namespace_of(pod)}/{name_of(pod)}: "
                    "the pod cannot be scheduled successfully by adding node: "
                    "pod does not fit new node affinity or taints"
                )
            if not meet_resource_requests(
                new_node, pod, all_daemon_sets, corrected=corrected_ds_overhead
            ):
                return (
                    f"failed to schedule pod {namespace_of(pod)}/{name_of(pod)}: "
                    "new node cannot meet resource requests of pod: the total "
                    "requested resource of daemonset pods in new node is too large"
                )
        return None

    cap_rejected = False  # a probe scheduled everything but missed a cap

    def feasible(result: SimulateResult) -> Tuple[bool, str]:
        """Candidate acceptance = everything scheduled AND occupancy caps
        hold. The reference treats a cap miss like infeasibility
        (`apply.go:199-207`); schedulability is monotone in the clone
        count, but the cap verdict need NOT be (DaemonSet overhead — see
        the module docstring), so a cap rejection is flagged and aborts
        the O(log N) search in favor of the reference's linear walk."""
        nonlocal cap_rejected
        if result.unscheduled_pods:
            return False, ""
        ok, reason = satisfy_resource_setting(result)
        if not ok:
            cap_rejected = True
            say(reason.rstrip("\n"))
        return ok, reason

    def evaluate(i: int, need_result: bool = False):
        """(feasible, unscheduled, diagnosis, result) for candidate i —
        replayed from the checkpoint record when one exists (resume
        path; result is then None), else one live simulation, recorded
        afterwards.  `need_result` forces the live run: the winning
        candidate materializes its SimulateResult, and determinism makes
        the re-run bit-identical to the recorded verdict's run."""
        nonlocal cap_rejected
        rec = None if checkpoint is None else checkpoint.get("cand", i)
        if rec is not None and not need_result:
            probes[i] = int(rec["unscheduled"])
            if bool(rec["cap_rejected"]):
                cap_rejected = True
            ok = bool(rec["feasible"])
            msg = str(rec["message"]) or None
            if ok and (best_candidate[0] is None or i < best_candidate[0]):
                best_candidate[0] = i
            return ok, probes[i], msg, None
        if control is not None:
            control.check()
        if checkpoint is not None:
            # pin the pod-name suffix stream per candidate so a resumed
            # run's live evaluations expand the exact pods the
            # uninterrupted run's would — including the replayed winner's
            # re-materialization (durable.checkpoint.name_seed)
            from ..durable.checkpoint import name_seed
            from ..workloads.expand import seed_name_hashes

            seed_name_hashes(name_seed(checkpoint.fingerprint, i))
        result = run(i)
        ok, _ = feasible(result)
        msg = diagnose(result) if result.unscheduled_pods else None
        if checkpoint is not None:
            # a cap rejection is per-candidate (fully scheduled, cap
            # missed) — exactly the records whose replay must re-trigger
            # the linear fallback on resume
            checkpoint.put(
                "cand", i,
                unscheduled=probes[i], feasible=ok,
                cap_rejected=(not ok) and not result.unscheduled_pods,
                message=msg or "",
            )
        if ok and (best_candidate[0] is None or i < best_candidate[0]):
            best_candidate[0] = i
        return ok, probes[i], msg, result

    def final_success(i: int, result) -> PlanResult:
        if result is None:  # checkpoint-replayed winner: materialize live
            _, _, _, result = evaluate(i, need_result=True)
        out = with_explain(PlanResult(True, i, result, "Success!", probes), result)
        rep = getattr(result, "audit", None)
        if not audit_on or rep is None:
            return out
        out.audit = rep.counters()
        if rep.ok:
            return out
        # divergence-safe fallback: the winner's audit failed — do NOT
        # ship it; re-simulate through the serial exact engines and
        # re-audit (docs/robustness.md)
        say(
            f"audit FAILED on the winning candidate ({rep.summary()}) — "
            "re-simulating through the serial exact engines"
        )
        fb = run(i, serial_exact=True)
        rep_f = fb.audit
        audit_doc = {
            **rep.counters(),
            "fallback": True,
            "fallback_audit": rep_f.counters(),
            "divergence": _result_divergence(result, fb, rep),
        }
        if not rep_f.ok or fb.unscheduled_pods:
            out = with_explain(
                PlanResult(
                    False, i, fb,
                    "audit failure: the winning candidate violates its claimed "
                    "constraints and the serial-exact fallback did not certify "
                    f"either ({rep_f.summary()})",
                    probes,
                ),
                fb,
            )
            out.audit = audit_doc
            return out
        audit_doc["ok"] = True
        out.result = fb
        out.explain = getattr(fb, "explain", None) or {}
        out.audit = audit_doc
        return out

    def _result_divergence(primary, fallback, report) -> Dict[str, object]:
        """Divergence record for two SimulateResults.  Pod-name suffixes
        are process-random across separate simulations, so the diagnostic
        compares per-node pod counts rather than names."""

        def by_node(res):
            return {name_of(s.node): len(s.pods) for s in res.node_status}

        pa, fb = by_node(primary), by_node(fallback)
        changed = sorted(n for n in pa if pa.get(n) != fb.get(n))
        return {
            "violations": dict(report.by_class),
            "nodes_changed": len(changed),
            "first_changed_node": changed[0] if changed else "",
        }

    def linear_from(start: int) -> PlanResult:
        """The reference-exact linear walk over [start, max_new_nodes);
        candidates already probed and found UNSCHEDULABLE are skipped
        (schedulability is monotone — more clones cannot unschedule
        them... fewer cannot schedule them), cap-rejected ones re-run."""
        for i in range(start, max_new_nodes):
            if i in probes and probes[i] > 0:
                continue  # known unschedulable
            ok, unsched, msg, result = evaluate(i)
            if ok:
                return final_success(i, result)
            if unsched and msg:
                res = result or last_result[0]
                return with_explain(
                    PlanResult(False, i, res, msg, probes), res
                )
        return with_explain(
            PlanResult(False, max_new_nodes, last_result[0], fail_msg, probes),
            last_result[0],
        )

    fail_msg = f"we have added {max_new_nodes} nodes but it still failed!!"

    def search_candidates() -> PlanResult:
        nonlocal cap_rejected
        if lb_hint < 1:
            ok, unsched, msg, result = evaluate(0)
            if ok:
                return final_success(0, result)
            if unsched and msg:
                res = result or last_result[0]
                return with_explain(PlanResult(False, 0, res, msg, probes), res)
        # else: the solver PROVED candidate 0 (and everything below
        # lb_hint) infeasible — skip straight to the bound

        # the reference's loop is `for i := 0; i < MaxNumNewNode; i++`
        # (apply.go:183) — the largest candidate ever tried is
        # max_new_nodes-1
        if search == "linear":
            return linear_from(max(1, lb_hint))

        def cap_fallback() -> PlanResult:
            """A cap rejection makes feasibility potentially non-monotone —
            bisection could skip the window the reference's walk would
            find.  Fall back loudly to the linear scan (pinned by
            tests/test_plan.py's DaemonSet-overhead adversary)."""
            import sys

            msg = (
                "simtpu: an occupancy cap rejected a fully-scheduled "
                "candidate; cap feasibility can be non-monotone in the "
                "clone count (DaemonSet overhead) — falling back to the "
                "reference's linear scan"
            )
            print(msg, file=sys.stderr)
            say(msg)
            return linear_from(1)

        # doubling probe then binary search (feasibility monotone in
        # clone count); a certified solver lower bound starts the
        # doubling at the bound instead of 1
        hi, hi_result = None, None
        probe = max(1, lb_hint)
        while probe < max_new_nodes:
            ok, unsched, msg, result = evaluate(probe)
            if cap_rejected:
                return cap_fallback()
            if ok:
                hi, hi_result = probe, result
                break
            if unsched and msg:
                res = result or last_result[0]
                return with_explain(
                    PlanResult(False, probe, res, msg, probes), res
                )
            probe *= 2
        if hi is None:
            probe = max_new_nodes - 1
            if probe in probes:  # already tried as the last doubling step
                return with_explain(
                    PlanResult(
                        False, max_new_nodes, last_result[0], fail_msg, probes
                    ),
                    last_result[0],
                )
            ok, unsched, msg, result = evaluate(probe)
            if cap_rejected:
                return cap_fallback()
            if not ok:
                res = result or last_result[0]
                return with_explain(
                    PlanResult(False, max_new_nodes, res, fail_msg, probes),
                    res,
                )
            hi, hi_result = probe, result
        # lowest infeasible known is hi//2 (probed by the doubling, or 0)
        # — unless the solver certified everything below lb_hint
        lo = max(hi // 2, lb_hint - 1)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            ok, _, _, result = evaluate(mid)
            if cap_rejected:
                return cap_fallback()
            if ok:
                hi, hi_result = mid, result
            else:
                lo = mid
        return final_success(hi, hi_result)

    def _with_solve(out: PlanResult) -> PlanResult:
        # a rejected/uncertified solver consult still rides the result —
        # --json consumers see WHY the exact search answered
        if solve_doc and not out.solve:
            out.solve = dict(solve_doc)
        return out

    try:
        return _with_solve(search_candidates())
    except PlanInterrupted as exc:
        # deadline / SIGINT between candidates: the structured partial
        # result — every completed candidate is already checkpointed
        from ..durable.deadline import partial_message

        best = best_candidate[0]
        return _with_solve(
            PlanResult(
                False,
                -1 if best is None else best,
                None,
                partial_message(exc.reason, best, checkpoint),
                probes,
                partial=True,
            )
        )


@dataclass
class ApplierOptions:
    """CLI options (`pkg/apply/apply.go:32-38`).

    `search` / `bulk` default to None = scale-aware auto: the reference's
    `simon apply` is ONE command that is always its fastest
    (`pkg/apply/apply.go:88,183`), so `simtpu apply` picks the engines
    itself — serial scan + binary search at conformance scale, bulk rounds
    + incremental search once the problem is large enough that the serial
    floor would dominate (see `_resolve_engines`)."""

    simon_config: str = ""
    default_scheduler_config: str = ""
    use_greed: bool = False
    interactive: bool = False
    extended_resources: Sequence[str] = ()
    search: Optional[str] = None  # None = auto; binary | linear | incremental
    bulk: Optional[bool] = None  # None = auto; place replica runs bulk
    # None = auto: shard the incremental planner's node axis over the device
    # mesh when more than one accelerator device is visible (placements are
    # bit-identical to the single-device path; CPU backends stay unsharded
    # unless forced — virtual CPU "devices" share one host's FLOPs)
    shard: Optional[bool] = None
    # None = auto: AOT-precompile each run's jit executables on a
    # background thread pool as soon as the shapes are known, so the cold
    # `simtpu apply` path overlaps compilation with host work instead of
    # serializing compiles at first dispatch (engine/precompile.py).  Auto
    # is ON for accelerator backends only — on CPU the "device" computes on
    # the same host cores the compiles need, so backgrounding them is pure
    # contention (measured slower), the same reasoning as the persistent
    # cache's CPU gating.  Placements are bit-identical either way;
    # --precompile forces it anywhere, --no-precompile disables.
    precompile: Optional[bool] = None
    # account daemonset overhead on the template node in the can-ever-fit
    # diagnostic (off = faithful to the reference's NewNodeNamePrefix quirk)
    corrected_ds_overhead: bool = False
    # durable execution (docs/robustness.md): checkpoint directory for
    # per-candidate plan records ("" = no checkpointing), `resume` replays
    # a prior run's records from it (fingerprint-guarded), `deadline`
    # bounds the plan's wall-clock in seconds (None = none), and
    # `install_sigint` makes the first ^C a graceful interrupt (partial
    # result + flushed checkpoint) — the CLI sets it; library callers
    # keep their own signal handling
    checkpoint: str = ""
    resume: bool = False
    deadline: Optional[float] = None
    install_sigint: bool = False
    # None = auto (the SIMTPU_AUDIT default, on): run the independent
    # placement auditor over the accepted candidate and fall back to the
    # serial exact engines on failure; False = --no-audit
    audit: Optional[bool] = None
    # None = the SIMTPU_SOLVER default (off): consult the global solve
    # backend (simtpu/solve) before the exact search — advisory mode,
    # the auditor gates everything it proposes; --solver forces it on,
    # --no-solver off (docs/solver.md)
    solver: Optional[bool] = None
    # decision observability (simtpu/explain, --explain): attach failure
    # breakdowns + the bottleneck analysis to the plan.  Off = zero cost
    # (no explain import, no extra device dispatch)
    explain: bool = False
    # observability (ISSUE 8, docs/observability.md): `trace` = output
    # path for a Perfetto-loadable Chrome trace of the run's spans
    # ("" = no trace file; arming leaves the process tracer on so a
    # later flight-recorder dump still sees the spans); `profile` = log
    # dir for a jax.profiler capture of the plan phase with span-named
    # TraceAnnotations ("" = SIMTPU_PROFILE env, else off)
    trace: str = ""
    profile: str = ""


# Auto-engine thresholds: below both, the serial scan keeps its per-pod
# reference-exact tie-breaks and compiles fastest; above either, the bulk
# rounds engine (~600x the serial rate at 100k nodes, BENCH_r04) and the
# incremental planner win by minutes.  Declared pods, not expanded: the
# estimate runs before workload expansion.
AUTO_ENGINE_NODES = 1024
AUTO_ENGINE_PODS = 16384


def _declared_pod_estimate(cluster: ResourceTypes, apps: Sequence[AppResource]) -> int:
    """Cheap upper-ish estimate of the expanded pod count: declared replica
    counts plus one DaemonSet pod per node, without running expansion."""

    def one(res: ResourceTypes, n_nodes: int) -> int:
        total = len(res.pods)
        for w in res.deployments + res.replica_sets + res.replication_controllers + res.stateful_sets:
            spec = w.get("spec") or {}
            total += int(spec.get("replicas") or 1)
        for j in res.jobs:
            spec = j.get("spec") or {}
            total += int(spec.get("completions") or spec.get("parallelism") or 1)
        for cj in res.cron_jobs:
            total += 1
        total += len(res.daemon_sets) * n_nodes
        return total

    n = len(cluster.nodes)
    return one(cluster, n) + sum(one(a.resource, n) for a in apps)


def _declared_preemption(cluster: ResourceTypes, apps: Sequence[AppResource]) -> bool:
    """Whether the declared pods and workload templates can preempt, read
    without expansion: a pod still to be scheduled (an unbound pod, or any
    workload's template) outranks another declared pod."""
    pending: List[float] = []
    bound: List[float] = []
    for res in [cluster, *(a.resource for a in apps)]:
        for p in res.pods:
            (bound if pod_spec(p).get("nodeName") else pending).append(pod_priority(p))
        for w in (res.deployments + res.replica_sets + res.replication_controllers
                  + res.stateful_sets + res.jobs + res.daemon_sets):
            pending.append(pod_priority((w.get("spec") or {}).get("template") or {}))
        for cj in res.cron_jobs:
            job = ((cj.get("spec") or {}).get("jobTemplate") or {}).get("spec") or {}
            pending.append(pod_priority(job.get("template") or {}))
    return can_preempt(pending, bound)


def _resolve_engines(
    opts: ApplierOptions,
    cluster: ResourceTypes,
    apps: Sequence[AppResource],
) -> Tuple[str, bool, Optional[object]]:
    """Fill in auto (None) search/bulk/shard choices from the problem size
    (and device topology) and say so loudly on stderr — the user should
    never need to know the flags to get the fast path, but must be able to
    see (and override) what was picked.  Returns (search, bulk, mesh) where
    mesh is a node-sharding device mesh for the incremental planner or
    None."""
    import sys

    n_nodes = len(cluster.nodes)
    est_pods = _declared_pod_estimate(cluster, apps)
    large = n_nodes >= AUTO_ENGINE_NODES or est_pods >= AUTO_ENGINE_PODS
    # the incremental planner never preempts: where pods can, only the
    # searches through simulate() give their answer, at any size
    preempt = large and opts.search is None and _declared_preemption(cluster, apps)
    search = opts.search if opts.search is not None else (
        "incremental" if large and not preempt else "binary")
    bulk = opts.bulk if opts.bulk is not None else large
    if large and (opts.search is None or opts.bulk is None):
        why = (
            "; pods can preempt (their priorities differ), so the search "
            "runs simulate()'s preemption — pass --search incremental to "
            "plan without it, or --no-bulk for the serial reference-exact "
            "engines"
            if preempt
            else "; pass --search binary/linear or --no-bulk for the serial "
            "reference-exact engines"
        )
        print(
            f"simtpu: large problem ({n_nodes} nodes, ~{est_pods} declared "
            f"pods) — auto-selected {'bulk' if bulk else 'serial'} placement"
            f" + {search} search{why}",
            file=sys.stderr,
        )
    mesh = None
    if search == "incremental" and opts.shard is not False:
        import jax

        devices = jax.devices()
        # auto: only real accelerator meshes (virtual CPU devices split one
        # host's FLOPs — sharding there is a test vehicle, not a speedup)
        want = opts.shard is True or (
            opts.shard is None
            and len(devices) > 1
            and jax.default_backend() != "cpu"
        )
        if want:
            from ..parallel.mesh import planner_mesh

            mesh = planner_mesh()  # None on single-device topologies
            if mesh is not None and opts.shard is None:
                print(
                    f"simtpu: sharding the incremental plan's node axis over "
                    f"{len(devices)} devices; pass --no-shard for "
                    "single-device execution",
                    file=sys.stderr,
                )
    if opts.shard is True and mesh is None:
        # an explicit --shard that cannot be honored must be LOUD — a CI
        # job forcing the sharded path would otherwise silently validate
        # the unsharded one (same contract as the auto-engine notice)
        why = (
            "the search strategy is not 'incremental'"
            if search != "incremental"
            else "only one device is visible"
        )
        print(
            f"simtpu: --shard ignored ({why}); the plan runs unsharded",
            file=sys.stderr,
        )
    return search, bulk, mesh


class Applier:
    """End-to-end capacity-planning run (`pkg/apply/apply.go:55-245`)."""

    def __init__(self, opts: ApplierOptions):
        self.opts = opts
        self.config = SimonConfig.from_file(opts.simon_config)
        validate_config(self.config, opts.default_scheduler_config)

    def load_apps(self) -> List[AppResource]:
        apps = []
        for info in self.config.app_list:
            if info.chart:
                from .. import chart as chart_mod

                content = chart_mod.process_chart(info.name, info.path)
            else:
                content = get_yaml_content_from_directory(info.path)
            apps.append(
                AppResource(name=info.name, resource=get_objects_from_yaml_content(content))
            )
        return apps

    def load_cluster(self) -> ResourceTypes:
        if self.config.cluster.kube_config:
            return create_cluster_resource_from_client(self.config.cluster.kube_config)
        return create_cluster_resource_from_cluster_config(self.config.cluster.custom_config)

    def _sched_config(self):
        """Parse --default-scheduler-config when given
        (`pkg/simulator/utils.go:281` loads the file the same way)."""
        if not self.opts.default_scheduler_config:
            return None
        from ..schedconfig import SchedulerConfig

        return SchedulerConfig.from_file(self.opts.default_scheduler_config)

    def load_new_node(self) -> dict:
        content = get_yaml_content_from_directory(self.config.new_node)
        resources = get_objects_from_yaml_content(content)
        if not resources.nodes:
            raise ValueError(f"the new node directory({self.config.new_node}) has no nodes")
        match_and_set_local_storage_annotation_on_node(resources.nodes, self.config.new_node)
        return resources.nodes[0]

    def run(
        self,
        select_apps: Optional[Callable[[List[str]], List[str]]] = None,
        progress: Optional[Callable[[str], None]] = None,
    ) -> PlanResult:
        import contextlib
        import os
        import time as _time

        from ..obs import trace as obs_trace
        from ..obs.profile import profile_capture

        # --trace FILE arms the span tracer for this run (a tracer armed
        # earlier — SIMTPU_TRACE — keeps its buffer; the export below
        # only adds this run's output file).  Deliberately NOT disabled
        # afterwards: a failing exit's flight recorder (obs/flight.py)
        # reads the same buffer after run() returns.
        if self.opts.trace and not obs_trace.enabled():
            obs_trace.enable()

        timings: Dict[str, float] = {}
        t0 = _time.perf_counter()
        # the ingest span brackets exactly the wall the "ingest" timing
        # reports (spans and --json phase timings must reconcile); the
        # interactive selection's human think-time sits between two spans
        # just as it sits outside both timed regions
        sp_ingest = span("ingest")
        sp_ingest.__enter__()
        try:
            apps = self.load_apps()
            if select_apps is not None:
                # human think-time must not count toward the ingest phase
                timings["ingest"] = _time.perf_counter() - t0
                sp_ingest.__exit__(None, None, None)
                chosen = set(select_apps([a.name for a in apps]))
                apps = [a for a in apps if a.name in chosen]
                t0 = _time.perf_counter()
                sp_ingest = span("ingest")
                sp_ingest.__enter__()
            cluster = self.load_cluster()
            new_node = self.load_new_node()
            timings["ingest"] = (
                timings.get("ingest", 0.0) + _time.perf_counter() - t0
            )
        finally:
            # a load failure must still close the span: a leaked span is
            # never recorded AND corrupts the thread's nesting depth for
            # every later span — exactly on the failing runs a trace or
            # flight bundle is read to explain
            sp_ingest.__exit__(None, None, None)

        import jax

        # --profile DIR (or SIMTPU_PROFILE=DIR) captures a jax.profiler
        # trace of the plan phase, with TraceAnnotations named after the
        # spans (obs/profile.py).  Note: before ISSUE 8 the profiler dir
        # rode SIMTPU_TRACE — that name now arms the span tracer instead.
        profile_dir = self.opts.profile or os.environ.get("SIMTPU_PROFILE", "")
        ctx = profile_capture(profile_dir) if profile_dir else contextlib.nullcontext()
        from ..engine.scan import wave_enabled

        search, bulk, mesh = _resolve_engines(self.opts, cluster, apps)
        metrics_before = REGISTRY.snapshot()

        # durable execution (docs/robustness.md): per-candidate checkpoint
        # records under --checkpoint DIR, fingerprint-guarded resume, and
        # a deadline/SIGINT control polled at candidate boundaries
        checkpoint = None
        control = None
        if self.opts.checkpoint:
            from ..durable.checkpoint import (
                PlanCheckpoint,
                file_digest,
                plan_fingerprint,
            )

            fingerprint = plan_fingerprint(
                cluster, apps, new_node,
                extra={
                    "search": search,
                    "bulk": bool(bulk),
                    "extended_resources": list(self.opts.extended_resources),
                    "corrected_ds_overhead": self.opts.corrected_ds_overhead,
                    # CONTENT digest: editing the sched-config between a
                    # kill and a --resume must refuse, same path or not
                    "sched_config": file_digest(
                        self.opts.default_scheduler_config
                    ),
                    "caps": [
                        _env_cap(C.ENV_MAX_CPU),
                        _env_cap(C.ENV_MAX_MEMORY),
                        _env_cap(C.ENV_MAX_VG),
                    ],
                },
            )
            checkpoint = PlanCheckpoint(
                self.opts.checkpoint, kind=search, fingerprint=fingerprint,
                resume=self.opts.resume,
            )
        elif self.opts.resume:
            raise ValueError("--resume requires --checkpoint DIR")
        if self.opts.deadline is not None or self.opts.install_sigint:
            from ..durable.deadline import RunControl

            control = RunControl(deadline=self.opts.deadline)
        # auto-ON for apply on accelerator backends: the one-shot CLI user
        # always pays the cold path, which is exactly what the background
        # AOT pipeline attacks.  CPU backends stay off under auto (the
        # compiles would contend with the placement compute for the same
        # host cores; ApplierOptions.precompile documents the measurement)
        # — an explicit --precompile forces it anywhere.
        precompile = self.opts.precompile is True or (
            self.opts.precompile is None and jax.default_backend() != "cpu"
        )
        t0 = _time.perf_counter()
        sig_ctx = (
            control.sigint()
            if control is not None and self.opts.install_sigint
            else contextlib.nullcontext()
        )
        with ctx, sig_ctx, span("plan", search=search):
            if search == "incremental":
                from .incremental import plan_capacity_incremental

                plan = plan_capacity_incremental(
                    cluster,
                    apps,
                    new_node,
                    extended_resources=self.opts.extended_resources,
                    progress=progress,
                    sched_config=self._sched_config(),
                    corrected_ds_overhead=self.opts.corrected_ds_overhead,
                    mesh=mesh,
                    precompile=precompile,
                    checkpoint=checkpoint,
                    control=control,
                    audit=self.opts.audit,
                    explain=self.opts.explain,
                    solver=self.opts.solver,
                )
            else:
                plan = plan_capacity(
                    cluster,
                    apps,
                    new_node,
                    extended_resources=self.opts.extended_resources,
                    search=search,
                    progress=progress,
                    bulk=bulk,
                    sched_config=self._sched_config(),
                    corrected_ds_overhead=self.opts.corrected_ds_overhead,
                    precompile=precompile,
                    checkpoint=checkpoint,
                    control=control,
                    audit=self.opts.audit,
                    explain=self.opts.explain,
                    solver=self.opts.solver,
                )
        timings["plan"] = _time.perf_counter() - t0
        plan.timings = timings
        # machine-readable record of what actually ran (ADVICE r5: the
        # stderr notice alone is invisible to scripted consumers —
        # "search"/"bulk" distinguish the non-reference-exact fast path)
        from ..parallel.mesh import NODE_AXIS

        # the unified metrics block (ISSUE 8): one registry delta over
        # the plan — counters subtract, gauges report their end-of-plan
        # level — plus the shipped candidate's audit verdict under the
        # audit.* names (the registry's audit counters aggregate EVERY
        # candidate's pass; the block reports the one that shipped, the
        # same record engine.audit carries)
        metrics = REGISTRY.delta_since(metrics_before)
        if plan.audit:
            for k in ("ok", "checked", "violations", "wall_s", "mode"):
                if k in plan.audit:
                    metrics[f"audit.{k}"] = plan.audit[k]
        plan.metrics = metrics
        # the legacy engine-block families below are ALIAS VIEWS of the
        # metrics block — same numbers re-grouped, bit-equal by
        # construction; kept for one release (pin on schema_version)
        plan.engine = {
            "search": search,
            "bulk": bool(bulk) if search != "incremental" else True,
            "shards": int(mesh.shape[NODE_AXIS]) if mesh is not None else 0,
            "precompile": precompile,
            "auto_search": self.opts.search is None,
            "auto_bulk": self.opts.bulk is None,
            "reference_exact": search == "linear" and not bulk,
            # the speculative wavefront dispatcher's telemetry over this
            # plan's serial-engine dispatches (docs/speculation.md):
            # placements are bit-identical with it on or off, so this is
            # pure observability — acceptance rate and rollback volume
            "speculate": wave_enabled(),
            # compact-carry delta applies that took the direct scatter, and
            # the full-plane expand/compress passes the run still paid
            "delta_direct": {
                "applied": metrics.get("state.delta_direct", 0),
                "expand": metrics.get("state.expand", 0),
                "compress": metrics.get("state.compress", 0),
            },
            "wavefront": {
                k: metrics.get(f"wavefront.{k}", 0)
                for k in (
                    "wavefronts", "pods", "accepted", "rollbacks",
                    "rollback_pods", "draft_hard",
                )
            },
            # transfer + carried-state byte telemetry (ISSUE 5): blocking
            # device→host round-trips and bytes this plan paid, plus the
            # final engine carry's per-plane byte breakdown under the
            # active layout (compact = the domain-tabular carry,
            # SIMTPU_COMPACT A/B — placements are identical either way)
            "fetch": {
                "get": metrics.get("fetch.get", 0),
                "bytes": metrics.get("fetch.bytes", 0),
            },
            # OOM-backoff telemetry (docs/robustness.md): caught
            # RESOURCE_EXHAUSTED events, the sub-dispatches their halving
            # replays created, and the smallest chunk any replay
            # re-dispatched at ("chunk_min" is a process-lifetime floor,
            # not a delta — 0 = no backoff this process)
            "backoff": {
                "events": metrics.get("backoff.events", 0),
                "splits": metrics.get("backoff.splits", 0),
                "chunk_min": metrics.get("backoff.chunk_min", 0),
            },
            # `compact` is the gauge's own record of what the final carry
            # actually was — NOT the SIMTPU_COMPACT default, which an
            # engine attribute or a spec with no tabular keys can override
            # (kept out of `state_bytes` so the byte breakdown holds only
            # the carried/dense/per-plane numbers, not a duplicate flag)
            "compact": metrics.get("state.compact", False),
            "state_bytes": {
                "carried_bytes": metrics.get("state.carried_bytes", 0),
                "dense_bytes": metrics.get("state.dense_bytes", 0),
                "planes": metrics.get("state.planes", {}),
            },
            # the independent placement audit of the shipped candidate
            # (simtpu/audit): counters, plus fallback/divergence records
            # when the primary engine's answer failed certification.
            # {"enabled": False} = --no-audit / SIMTPU_AUDIT=0
            "audit": plan.audit if plan.audit else {"enabled": False},
            # the global-solver backend's record (simtpu/solve): which
            # engine ANSWERED — an accepted status means the vmapped
            # relaxation produced the shipped plan; rejected/ineligible
            # means the exact search did (with the solver's certified
            # lower bound when one existed).  {"enabled": False} =
            # solver not consulted (--no-solver / SIMTPU_SOLVER unset)
            "solve": plan.solve if plan.solve else {"enabled": False},
            # loud runtime flag (docs/status.md): the incremental
            # planner's probes never run preemption, and this plan's
            # pods could have preempted — their priorities were ignored
            "preemption_ignored": bool(
                getattr(plan, "preemption_ignored", False)
            ),
        }
        if self.opts.trace:
            from ..obs.trace import export_trace

            path = export_trace(self.opts.trace)
            if progress is not None:
                progress(f"span trace written to {path} (load in Perfetto)")
        return plan
