"""Global-solver planning backend tests (simtpu/solve, ISSUE 19).

The load-bearing pins:

- exact-minimum parity: on a feasible mix `plan_capacity(..., solver=True)`
  ships the SAME certified minimum node count as the exact
  doubling+bisection, and the auditor certifies the shipped placement;
- proof-or-step-aside: an infeasible-by-construction spec makes the
  solver report a PROVEN infeasibility (never a rounded garbage
  placement), and the exact search still owns the final verdict;
- deterministic rounding: tie-broken fractional masses always round
  toward the lower node index, and the repair loop moves load off
  overfull nodes in exact arithmetic;
- audit-dirty fallback: SIMTPU_AUDIT_INJECT=1 corrupts the audit's view
  of the solver's rounded answer — the serial exact engine re-places the
  candidate, only ITS certified answer ships, and the --json engine
  block records `accepted_fallback` (the wavefront-rollback shape);
- trace budget: the vmapped solve rides the pow2 shape buckets — a
  capacity sweep traces the kernel once per bucket, not per plan
  (`compile.solve`, same contract as TestProbeCompileBudget);
- preemption honesty: priority-bearing specs through the incremental
  planner raise the loud IGNORED notice and set
  `PlanResult.preemption_ignored` (satellite 1).
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from simtpu import AppResource, ResourceTypes
from simtpu.plan.capacity import plan_capacity
from simtpu.plan.incremental import plan_capacity_incremental
from simtpu.plan.resilience import plan_resilience
from simtpu.solve.relax import (
    RESIDUAL_TOL,
    RelaxProblem,
    build_relax_problem,
    infeasibility_certificate,
    relax_candidates,
)
from simtpu.solve.rounding import round_candidate
from simtpu.workloads.expand import seed_name_hashes

from .fixtures import make_fake_deployment, make_fake_node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _seed():
    seed_name_hashes(11)


def _small_plan_problem(replicas=7, cpu="2", memory="4Gi"):
    """1×(4cpu,8Gi) base + N×(2cpu,4Gi) pods + (4cpu,8Gi) template —
    the same shape tests/test_audit.py pins (min clones = 3 at N=7)."""
    cluster = ResourceTypes()
    cluster.nodes = [make_fake_node("base-1", "4", "8Gi")]
    apps = [
        AppResource(
            name="app",
            resource=ResourceTypes(
                deployments=[
                    make_fake_deployment("web", "default", replicas, cpu, memory)
                ]
            ),
        )
    ]
    template = make_fake_node("template", "4", "8Gi")
    return cluster, apps, template


def _assembled(cluster, apps, template, max_new=7):
    from simtpu.parallel.sweep import assemble_planning_problem

    tz, all_nodes, n_base, ordered = assemble_planning_problem(
        cluster, apps, template, max_new, ()
    )
    batch = tz.add_pods(ordered)
    tensors = tz.freeze()
    clone_idx = np.arange(len(all_nodes)) - n_base
    cands = np.arange(max_new + 1)
    valid_s = (clone_idx[None, :] < cands[:, None]) | (clone_idx[None, :] < 0)
    return tensors, batch, valid_s


class TestRelaxCore:
    def test_vmapped_relaxation_finds_the_exact_minimum(self):
        """One dispatch answers every candidate count: first
        relax-feasible index == the exact search's minimum (3), and the
        boundary candidate below it carries a float64 infeasibility
        proof."""
        tensors, batch, valid_s = _assembled(*_small_plan_problem())
        prob = build_relax_problem(tensors, batch)
        verd = relax_candidates(prob, valid_s)
        feasible = np.flatnonzero(verd.residual <= RESIDUAL_TOL)
        assert feasible.size and int(feasible[0]) == 3
        from simtpu.solve.relax import fetch_y

        assert infeasibility_certificate(prob, fetch_y(verd, 2), valid_s[2])
        # and the proof does NOT fire on the feasible side
        assert not infeasibility_certificate(
            prob, fetch_y(verd, 3), valid_s[3]
        )

    def test_infeasible_spec_is_proven_not_rounded(self):
        """A pod larger than every node: the solver must report a PROVEN
        infeasibility over the whole candidate range — no placement, no
        rounded garbage — and the exact search still renders the final
        (failing) verdict."""
        cluster, apps, template = _small_plan_problem(replicas=2, cpu="16")
        plan = plan_capacity(cluster, apps, template, 4, solver=True)
        assert not plan.success
        assert plan.solve["status"] == "infeasible"
        assert plan.solve["lower_bound"] == 4  # beyond the whole range
        assert "k" not in plan.solve  # nothing was ever rounded


def _toy_problem(cap, feas, cnt=3.0, req=1.0):
    """Single-class single-resource RelaxProblem for rounding tests."""
    cap = np.asarray(cap, np.float64).reshape(-1, 1)
    n = cap.shape[0]
    scale = np.maximum(cap.max(axis=0), 1e-9)
    return RelaxProblem(
        cls_rows=[np.arange(int(cnt))],
        cls_group=np.zeros(1, np.int32),
        cnt=np.array([cnt], np.float32),
        req=np.array([[req]], np.float32) / scale.astype(np.float32),
        req_raw=np.array([[req]], np.float64),
        feas=np.asarray(feas, bool).reshape(1, n),
        fixed=np.zeros((n, 1), np.float32),
        fixed_raw=np.zeros((n, 1), np.float64),
        cap=(cap / scale).astype(np.float32),
        cap_raw=cap,
        scale=scale,
        lr=0.1,
        pinned_rows=np.zeros(0, np.int64),
    )


class TestRounding:
    def test_tied_fractional_masses_round_toward_lower_index(self):
        """y = [1.5, 1.5] over two identical nodes, 3 pods: the single
        remainder lands on node 0 — deterministically, every time."""
        prob = _toy_problem([4.0, 4.0], [True, True])
        valid = np.ones(2, bool)
        y = np.array([[1.5, 1.5]])
        results = [round_candidate(prob, y, valid) for _ in range(5)]
        for m, why in results:
            assert why == ""
            assert m.tolist() == [[2, 1]]

    def test_reversed_tie_still_prefers_lower_index(self):
        prob = _toy_problem([4.0, 4.0, 4.0], [True, True, True])
        y = np.array([[0.5, 1.0, 1.5]])  # fracs 0.5, 0.0, 0.5 after floor
        m, why = round_candidate(prob, y, np.ones(3, bool))
        assert why == ""
        # remainder 1 → tie between node 0 and node 2 at frac 0.5 → node 0
        assert m.tolist() == [[1, 1, 1]]

    def test_repair_moves_load_off_overfull_nodes(self):
        """floor lands 3 pods on a 2-capacity node: the exact-arithmetic
        repair relocates the overflow instead of shipping it."""
        prob = _toy_problem([2.0, 4.0], [True, True])
        m, why = round_candidate(
            prob, np.array([[3.0, 0.0]]), np.ones(2, bool)
        )
        assert why == ""
        assert m.tolist() == [[2, 1]]

    def test_repair_failure_is_a_reason_never_garbage(self):
        """Total demand exceeds total capacity: rounding must FAIL with a
        reason (the planner rejects) — it may not return an overfull m."""
        prob = _toy_problem([2.0], [True])  # 3 pods, capacity 2
        m, why = round_candidate(prob, np.array([[3.0]]), np.ones(1, bool))
        assert m is None and why in ("repair_budget", "repair_stuck")


class TestSolverPlanners:
    def test_facade_solver_matches_exact_search(self):
        cluster, apps, template = _small_plan_problem()
        exact = plan_capacity(cluster, apps, template, 8)
        cluster, apps, template = _small_plan_problem()
        solved = plan_capacity(cluster, apps, template, 8, solver=True)
        assert solved.success and exact.success
        assert solved.nodes_added == exact.nodes_added == 3
        assert solved.solve["status"] == "accepted"
        assert solved.solve["certified_lb"] is True
        assert solved.audit["ok"] is True
        # the accepted path never ran the probe search
        assert solved.probes == {3: 0}

    def test_incremental_solver_matches_exact_search(self):
        cluster, apps, template = _small_plan_problem()
        exact = plan_capacity_incremental(cluster, apps, template, 8)
        cluster, apps, template = _small_plan_problem()
        solved = plan_capacity_incremental(
            cluster, apps, template, 8, solver=True
        )
        assert solved.success and exact.success
        assert solved.nodes_added == exact.nodes_added
        assert solved.solve["status"] == "accepted"
        assert solved.audit["ok"] is True

    def test_solver_off_is_bit_identical_and_unrecorded(self):
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity(cluster, apps, template, 8, solver=False)
        assert plan.success and plan.solve == {}

    def test_env_default_consults_the_solver(self, monkeypatch):
        monkeypatch.setenv("SIMTPU_SOLVER", "1")
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity(cluster, apps, template, 8)
        assert plan.solve.get("enabled") is True

    def test_no_solver_overrides_the_env_default(self, monkeypatch):
        monkeypatch.setenv("SIMTPU_SOLVER", "1")
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity(cluster, apps, template, 8, solver=False)
        assert plan.solve == {}

    def test_resilience_lower_bound_warm_start(self):
        """plan_resilience never ships a solver placement — it consumes
        the relax-only certified lower bound (the no-failure fit is
        necessary for survivability) and must land on the exact search's
        answer."""
        cluster, apps, template = _small_plan_problem()
        exact = plan_resilience(cluster, apps, template, k=1, max_new_nodes=10)
        cluster, apps, template = _small_plan_problem()
        solved = plan_resilience(
            cluster, apps, template, k=1, max_new_nodes=10, solver=True
        )
        assert solved.success and exact.success
        assert solved.nodes_added == exact.nodes_added
        assert solved.solve["mode"] == "lower_bound"
        assert solved.solve["status"] == "certified"
        assert solved.solve["lower_bound"] <= solved.nodes_added


class TestAuditInjectFallback:
    """SIMTPU_AUDIT_INJECT corrupts the audit's view of the SOLVER's
    rounded answer: the serial exact engine must re-place the candidate
    and only its certified answer may ship (mirrors
    test_audit.TestPlannerFallback for the new backend)."""

    def _assert_fallback(self, plan):
        assert plan.success
        assert plan.solve["status"] == "accepted_fallback"
        assert plan.solve["fallback"] is True
        doc = plan.audit
        assert doc["fallback"] is True
        assert doc["violations"] >= 1
        assert doc["fallback_audit"]["ok"] is True
        assert doc["ok"] is True  # the SHIPPED answer is certified

    def test_facade_solver_falls_back_to_exact(self, monkeypatch):
        monkeypatch.setenv("SIMTPU_AUDIT_INJECT", "1")
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity(cluster, apps, template, 8, solver=True)
        self._assert_fallback(plan)
        assert plan.nodes_added == 3  # the certified count still ships
        assert not plan.result.unscheduled_pods

    def test_incremental_solver_falls_back_to_exact(self, monkeypatch):
        monkeypatch.setenv("SIMTPU_AUDIT_INJECT", "1")
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity_incremental(
            cluster, apps, template, 8, solver=True
        )
        self._assert_fallback(plan)
        assert plan.nodes_added == 3

    def test_fallback_matches_uninjected_answer(self, monkeypatch):
        cluster, apps, template = _small_plan_problem()
        clean = plan_capacity(cluster, apps, template, 8, solver=True)
        monkeypatch.setenv("SIMTPU_AUDIT_INJECT", "1")
        cluster, apps, template = _small_plan_problem()
        dirty = plan_capacity(cluster, apps, template, 8, solver=True)
        assert dirty.nodes_added == clean.nodes_added
        assert clean.solve["status"] == "accepted"
        assert dirty.solve["status"] == "accepted_fallback"


class TestSolveCompileBudget:
    """Satellite 2: the vmapped solve rides the pow2 shape buckets — a
    second plan in the same bucket reuses the compiled kernel, so the
    `compile.solve` trace count stays bounded across a capacity sweep
    (the TestProbeCompileBudget contract, extended to the new kind)."""

    def test_same_bucket_plans_trace_the_kernel_once(self):
        # max_new_nodes=17 puts the candidate axis in a pow2 bucket no
        # other test touches, so compile accounting starts cold WITHOUT
        # jax.clear_caches() (which would force every later module to
        # re-trace the engine kernels).
        cluster, apps, template = _small_plan_problem()
        p1 = plan_capacity_incremental(
            cluster, apps, template, 17, solver=True
        )
        first = p1.compiles.get("solve", {}).get("solve", 0)
        assert first >= 1  # the cold run traced the kernel
        # replicas=6 pads into the same pow2 buckets as replicas=7
        cluster, apps, template = _small_plan_problem(replicas=6)
        p2 = plan_capacity_incremental(
            cluster, apps, template, 17, solver=True
        )
        assert p2.success
        assert p2.compiles.get("solve", {}).get("solve", 0) == 0, p2.compiles

    def test_solve_rides_compile_count_kinds(self):
        from simtpu.engine.scan import COMPILE_COUNT_KINDS

        assert "solve" in COMPILE_COUNT_KINDS


class TestPreemptionWarning:
    """Satellite 1: priority-bearing specs through the incremental
    planner (which never runs preemption) raise a loud notice and set
    the machine-readable flag; clean specs stay silent."""

    def _priority_problem(self):
        # the notice fires where pods could preempt: priorities that differ
        cluster, apps, template = _small_plan_problem()
        dep = apps[0].resource.deployments[0]
        dep["spec"]["template"]["spec"]["priority"] = 100
        apps[0].resource.deployments.append(
            make_fake_deployment("batch", "default", 1, "1", "1Gi")
        )
        return cluster, apps, template

    def test_priority_specs_raise_the_ignored_notice(self, capsys):
        cluster, apps, template = self._priority_problem()
        plan = plan_capacity_incremental(cluster, apps, template, 8)
        assert plan.success
        assert plan.preemption_ignored is True
        assert "IGNORED" in capsys.readouterr().err

    def test_clean_specs_stay_silent(self, capsys):
        cluster, apps, template = _small_plan_problem()
        plan = plan_capacity_incremental(cluster, apps, template, 8)
        assert plan.preemption_ignored is False
        assert "IGNORED" not in capsys.readouterr().err

    # the --json ride-along for this flag is pinned inside
    # TestCLI.test_no_solver_flag_records_not_consulted (one CLI run
    # covers both engine-block fields).


class TestCLI:
    @pytest.fixture(autouse=True)
    def _chdir_repo(self, monkeypatch):
        monkeypatch.chdir(REPO)

    def test_apply_solver_json_records_the_backend(self, capsys):
        from simtpu.cli import main

        rc = main([
            "apply", "-f", "examples/simtpu-config.yaml", "--json",
            "--solver",
        ])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        solve = doc["engine"]["solve"]
        assert solve["status"] == "accepted"
        assert solve["certified_lb"] is True
        assert doc["engine"]["audit"]["ok"] is True

    def test_no_solver_flag_records_not_consulted(self, capsys):
        from simtpu.cli import main

        rc = main([
            "apply", "-f", "examples/simtpu-config.yaml", "--json",
            "--no-solver",
        ])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["engine"]["solve"] == {"enabled": False}
        # satellite 1 ride-along: clean specs keep the honesty flag down
        assert doc["engine"]["preemption_ignored"] is False

    def test_injected_divergence_solver_fallback_exit_4(
        self, monkeypatch, capsys
    ):
        """The --json evidence for the audit-dirty fallback: the engine
        block names the backend that ANSWERED (accepted_fallback), the
        shipped plan is certified, and the exit code is the documented
        audit-divergence code."""
        from simtpu.cli import EXIT_AUDIT, main

        monkeypatch.setenv("SIMTPU_AUDIT_INJECT", "1")
        rc = main([
            "apply", "-f", "examples/simtpu-config.yaml", "--json",
            "--solver",
        ])
        doc = json.loads(capsys.readouterr().out)
        assert rc == EXIT_AUDIT == 4
        assert doc["success"] is True
        solve = doc["engine"]["solve"]
        assert solve["status"] == "accepted_fallback"
        audit = doc["engine"]["audit"]
        assert audit["fallback"] is True
        assert audit["fallback_audit"]["ok"] is True
