# Build/test entry points — the analog of the reference's Makefile
# (`/root/reference/Makefile:24-25`, `go test ./...`).  simtpu is pure
# Python + a self-building ctypes extension, so there is no build step;
# `install` wires an editable checkout, `test` is the CI gate.

PY ?= python

.PHONY: all install lint test test-all test-perf bench bench-cold bench-faults bench-layout bench-durable bench-audit bench-solve bench-obs bench-explain bench-multihost bench-serve bench-timeline bench-grow fuzz-smoke clean

all: test

install:
	$(PY) -m pip install -e .

# static-analysis gate: compileall catches parse/syntax errors everywhere,
# then ruff (config-minimal, [tool.ruff] in pyproject.toml) enforces the
# pyflakes/pycodestyle core.  ruff is a test-extra (`pip install -e
# ".[test]"` — CI installs it); on hosts without it the syntax gate still
# runs and the skip is announced rather than silent.
lint:
	$(PY) -m compileall -q simtpu tools tests bench.py __graft_entry__.py
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check .; \
	else \
		echo "ruff not installed (pip install -e '.[test]'); syntax gate only"; \
	fi

# fast tier: every module, slow-marked tests deselected (<10 min target)
test: lint
	$(PY) tools/run_tests.py --fast

# the full suite, one subprocess per module (see tools/run_tests.py for
# why plain `pytest tests/` cannot be the canonical entry on CPU hosts)
test-all: lint
	$(PY) tools/run_tests.py

# dedicated perf runs: wall-clock envelopes armed (idle host required)
test-perf:
	SIMTPU_PERF_ASSERT=1 $(PY) tools/run_tests.py

bench:
	$(PY) bench.py

# cold-start smoke at a small shape with the persistent compilation cache
# OFF: every executable really compiles, so the JSON line's expand/
# tensorize/compile/first-dispatch breakdown (and compile wall < serial
# overlap) measures the AOT pipeline itself, not cache reads.  Compare
# against SIMTPU_BENCH_PRECOMPILE=0 for the serialized-compile baseline.
bench-cold:
	SIMTPU_COMPILATION_CACHE=off SIMTPU_BENCH_NODES=2000 \
	SIMTPU_BENCH_PODS=20000 SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 \
	SIMTPU_BENCH_MATRIX=0 SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 \
	$(PY) bench.py

# fault-injection smoke at a small shape (mirrors bench-cold): exhaustive
# single-node scenario sweep through the batched engine vs the serial
# drain/requeue replay floor, plus a small N+k plan_resilience search —
# fault_scenarios_per_s / fault_sweep_speedup land in the JSON line
bench-faults:
	SIMTPU_BENCH_FAULTS=1 SIMTPU_BENCH_NODES=2000 SIMTPU_BENCH_PODS=20000 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 $(PY) bench.py

# carried-state layout smoke at a small shape (mirrors bench-cold): the
# compact-vs-dense A/B point alone, ASSERTING bit-identical placements and
# a >= 2x carried-byte reduction on the multi-domain synthetic cluster —
# state_bytes / state_bytes_dense / state_compact_ratio land in the JSON
# line (CI runs this alongside lint + the fast tier)
bench-layout:
	SIMTPU_BENCH_LAYOUT=1 SIMTPU_BENCH_LAYOUT_ASSERT=1 \
	SIMTPU_BENCH_LAYOUT_NODES=2000 SIMTPU_BENCH_LAYOUT_PODS=20000 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	$(PY) bench.py

# durable-execution smoke (mirrors bench-layout): checkpoint a small
# incremental plan, kill it mid-search, resume, and ASSERT the resumed
# PlanResult is bit-identical to the uninterrupted run; plus an injected
# RESOURCE_EXHAUSTED on the bulk dispatcher asserting the chunk-halving
# backoff converges with identical placements — durable_* and
# backoff_events land in the JSON line (CI runs this alongside the fast
# tier)
bench-durable:
	SIMTPU_BENCH_DURABLE=1 SIMTPU_BENCH_DURABLE_ASSERT=1 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 $(PY) bench.py

# trust-but-verify smoke (mirrors bench-durable): mutation-kill every
# corruption class ASSERTING 100% auditor detection, plus a small
# incremental plan with the auditor auto-on asserting a clean verdict and
# < 10% audit overhead — audit_s / audit_violations / audit_kill_rate
# land in the JSON line (CI runs this alongside the fast tier)
bench-audit:
	SIMTPU_BENCH_AUDIT=1 SIMTPU_BENCH_AUDIT_ASSERT=1 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 $(PY) bench.py

# global-solver backend smoke (mirrors bench-audit): one solver consult
# vs the exact doubling+bisection on a solver-eligible aligned mix,
# ASSERTING bit-identical certified node counts, clean audits on both
# answers, and accept rate > 0 — solve_speedup / solve_accept_rate /
# solve_status land in the JSON line (CI runs this alongside the fast
# tier; the >= 2x speedup claim is measured at the 2k-node default
# shape, recorded not asserted at this CI smoke shape)
bench-solve:
	SIMTPU_BENCH_SOLVE=1 SIMTPU_BENCH_SOLVE_ASSERT=1 \
	SIMTPU_BENCH_SOLVE_NODES=100 SIMTPU_BENCH_SOLVE_PODS=6000 \
	SIMTPU_BENCH_SOLVE_MAX_NEW=256 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	$(PY) bench.py

# observability overhead gate (mirrors bench-audit): the same warm bulk
# placement with the span tracer off vs on, ASSERTING < 3% tracing-on
# overhead, zero-overhead no-op spans when disabled, bit-identical
# placements, and a Perfetto-valid exported trace file —
# obs_overhead_pct / obs_spans / obs_trace_valid land in the JSON line
# (CI runs this alongside the fast tier)
bench-obs:
	SIMTPU_BENCH_OBS=1 SIMTPU_BENCH_OBS_ASSERT=1 \
	SIMTPU_BENCH_OBS_NODES=2000 SIMTPU_BENCH_OBS_PODS=20000 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	$(PY) bench.py

# decision-observability smoke (mirrors bench-obs): one fuzz-generated
# gnarly case placed with and without the explain pipeline, ASSERTING
# bit-identical placements, per-stage elimination counts that sum to N
# and match the pure-numpy twin, a named binding resource, consistent
# score attribution, and the explain-pass overhead bound —
# explain_s / explain_pods / explain_groups land in the JSON line
# (CI runs this alongside the fast tier)
bench-explain:
	SIMTPU_BENCH_EXPLAIN=1 SIMTPU_BENCH_EXPLAIN_ASSERT=1 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	SIMTPU_BENCH_OBS=0 $(PY) bench.py

# multihost bench-point smoke: the `--multihost` launcher end to end at a
# tiny shape — a fresh 8-forced-host-device subprocess places the
# north-star mix through the GSPMD ShardedRoundsEngine, ASSERTING record
# schema + pod accounting + the publish round-trip into a scratch
# BASELINE (vs_target recomputed by the one documented formula, no warm
# number from a single run). The full-shape run behind BASELINE.json's
# `published` block is this same path at default knobs with --publish.
bench-multihost:
	SIMTPU_BENCH_MULTIHOST_ASSERT=1 \
	SIMTPU_BENCH_MULTIHOST_NODES=200 SIMTPU_BENCH_MULTIHOST_PODS=1000 \
	SIMTPU_BENCH_PODS_PER_DEP=50 \
	$(PY) bench.py --multihost

# long-lived service smoke (ISSUE 14, mirrors bench-durable): drive
# tools/serve_loadgen.py --smoke against a real `simtpu serve`
# subprocess — seeded mixed burst, ASSERTING the robustness matrix:
# request coalescing counters moved (serve.coalesced > 0, fewer sweep
# dispatches than requests), an over-deadline request answered a
# structured 504 while peers completed, the overload tail drew 429s with
# Retry-After and zero effect on admitted work, kill -9 + restart
# rehydrated the session bit-identically from the checkpoint, and
# SIGTERM drained to a clean exit 0 —
# serve_qps / serve_coalesce_ratio / serve_p99_s land in the JSON line
bench-serve:
	SIMTPU_BENCH_SERVE=1 SIMTPU_BENCH_SERVE_ASSERT=1 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	SIMTPU_BENCH_OBS=0 SIMTPU_BENCH_EXPLAIN=0 $(PY) bench.py

# trace-driven timeline smoke (ISSUE 15, mirrors bench-serve): a seeded
# small-shape arrival stream (gangs, CronJob firings, node events,
# elastic HPA jobs) replayed through simtpu/timeline, ASSERTING the
# batched path's end state (planes, placement log, landing vectors,
# event timestamps) is bit-identical to the serial one-event-at-a-time
# oracle, the auditor certified both, the sim clock is monotone, and the
# timeline.* registry counters moved — timeline_events_per_s /
# timeline_pending_p50_s / timeline_preemptions land in the JSON line
bench-timeline:
	SIMTPU_BENCH_TIMELINE=1 SIMTPU_BENCH_TIMELINE_ASSERT=1 \
	SIMTPU_BENCH_TIMELINE_NODES=16 SIMTPU_BENCH_TIMELINE_PODS=360 \
	SIMTPU_BENCH_TIMELINE_DAYS=0.2 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	SIMTPU_BENCH_OBS=0 SIMTPU_BENCH_EXPLAIN=0 SIMTPU_BENCH_SERVE=0 \
	$(PY) bench.py

# round-20 warm-engine serving smoke (mirrors bench-timeline): append-only
# vocabulary growth A/B — warm grow-engine waves vs re-tensorize+replay
# (bit-identical, zero rebuilds, recompiles bounded by the pow2 buckets
# touched) and the in-process warm-vs-cold serve fit QPS comparison
# (>= 10x, zero retensorize fallbacks on the warm mix) — grow_* land in
# the JSON line (CI runs this alongside the fast tier)
bench-grow:
	SIMTPU_BENCH_GROW=1 SIMTPU_BENCH_GROW_ASSERT=1 \
	SIMTPU_BENCH_NODES=500 SIMTPU_BENCH_PODS=2000 \
	SIMTPU_BENCH_SCAN_PODS=200 SIMTPU_BENCH_BASELINE_PODS=50 \
	SIMTPU_BENCH_SMALL=0 SIMTPU_BENCH_HARD=0 SIMTPU_BENCH_MATRIX=0 \
	SIMTPU_BENCH_PLAN=0 SIMTPU_BENCH_BIG=0 SIMTPU_BENCH_FAULTS=0 \
	SIMTPU_BENCH_LAYOUT=0 SIMTPU_BENCH_DURABLE=0 SIMTPU_BENCH_AUDIT=0 \
	SIMTPU_BENCH_OBS=0 SIMTPU_BENCH_EXPLAIN=0 SIMTPU_BENCH_SERVE=0 \
	SIMTPU_BENCH_TIMELINE=0 $(PY) bench.py

# differential fuzz over the fixed seed corpus at small shapes, across
# the FULL engine-config matrix — 8 forced host devices arm the
# GSPMD-sharded cell on CPU-only CI runners (the conftest trick); any
# divergence from the serial baseline or dirty audit fails the target
# with a shrunk reproducer YAML left under /tmp/simtpu-fuzz
fuzz-smoke:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m simtpu.cli fuzz --cases 6 --nodes 12 --pods 48 --seed 0 \
	--out /tmp/simtpu-fuzz --json

clean:
	rm -rf build dist *.egg-info simtpu/native/_build
	find . -name __pycache__ -type d -exec rm -rf {} +
