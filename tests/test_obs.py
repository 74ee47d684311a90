"""The unified observability layer (ISSUE 8, docs/observability.md):

- span tracer: nesting, thread-safety under the AOT pool, ring-buffer
  wraparound, Perfetto (Chrome trace-event) export validity, and the
  zero-overhead no-op contract when disabled;
- metrics registry: typed instruments, snapshot/delta protocol, and the
  engine counter families (`fetch.*` / `compile.*` / `wavefront.*` /
  `backoff.*` / `state.*`) read directly off the registry across the
  wavefront/compact engine A/Bs (the one-release legacy alias views are
  gone — ISSUE 13 — and their removal is pinned here);
- flight recorder: a bundle lands on the injected exit-3 (deadline) and
  exit-4 (audit divergence) CLI paths, and SIMTPU_FLIGHT=0 disables it;
- CLI surface: `apply --trace` writes a valid trace whose span sums
  reconcile with the --json phase timings, the --json document carries
  `schema_version` + the `metrics` block with the legacy engine-block
  families as bit-equal aliases, and `simtpu version --json` reports the
  schema stamp.
"""

from __future__ import annotations

import glob
import json
import os
import threading

import numpy as np
import pytest

from simtpu.obs import trace as obs_trace
from simtpu.obs.metrics import REGISTRY, SCHEMA_VERSION, MetricsRegistry


@pytest.fixture
def tracer():
    """Fresh tracer for a test; restores the prior (disabled) state."""
    was = obs_trace.enabled()
    obs_trace.enable()
    yield obs_trace
    if not was:
        obs_trace.disable()


def _spans():
    """The buffered events less the tracer's own `obs.clock` anchors."""
    return [e for e in obs_trace.events() if e[0] != "obs.clock"]


class TestSpanTracer:
    def test_nesting_depth_and_containment(self, tracer):
        with obs_trace.span("outer", phase="x"):
            with obs_trace.span("inner"):
                pass
        evs = {e[0]: e for e in _spans()}
        assert set(evs) == {"outer", "inner"}
        name, ts_o, dur_o, _, depth_o, attrs, *_ = evs["outer"]
        _, ts_i, dur_i, _, depth_i, *_ = evs["inner"]
        assert depth_o == 0 and depth_i == 1
        assert attrs == {"phase": "x"}
        # the inner interval is contained in the outer one
        assert ts_o <= ts_i and ts_i + dur_i <= ts_o + dur_o

    def test_mid_span_attributes(self, tracer):
        with obs_trace.span("s", a=1) as sp:
            sp.set(b=2)
        ((_, _, _, _, _, attrs, *_),) = _spans()
        assert attrs == {"a": 1, "b": 2}

    def test_thread_safety_many_threads(self, tracer):
        """Concurrent spans from worker threads lose no events and keep
        per-thread nesting depths (the AOT pool regime)."""
        n_threads, per_thread = 8, 50
        # every worker is alive at once (idents are reused only after a
        # thread exits), so each records under its own ident
        start = threading.Barrier(n_threads)

        def work():
            start.wait(timeout=30)
            for _ in range(per_thread):
                with obs_trace.span("t.outer"):
                    with obs_trace.span("t.inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        evs = _spans()
        assert len(evs) == n_threads * per_thread * 2
        for name, _, _, _, depth, *_ in evs:
            assert depth == (1 if name == "t.inner" else 0)
        assert len({e[3] for e in evs}) == n_threads

    def test_aot_pool_compile_spans(self, tracer):
        """The precompile pipeline's per-signature compile spans are
        recorded FROM the pool threads (engine/precompile.py)."""
        import jax
        import jax.numpy as jnp

        from simtpu.engine.precompile import AotPipeline, _sds

        pipe = AotPipeline(workers=2)
        try:
            fn = jax.jit(lambda x: x * 2)
            assert pipe.submit("obs_test", (), fn, (_sds((4,), jnp.int32),))
            pipe.wait_all(timeout=60)
        finally:
            pipe.shutdown()
        spans = [e for e in _spans() if e[0] == "aot.compile"]
        assert len(spans) == 1
        assert spans[0][5]["sig"] == "obs_test"
        assert spans[0][3] != threading.get_ident(), "span must be on a pool thread"

    def test_ring_wraparound_keeps_newest(self):
        obs_trace.enable(capacity=8)
        try:
            for i in range(20):
                with obs_trace.span(f"s{i}"):
                    pass
            # each root span brings its clock anchor: enable's anchor, then
            # (anchor, span) per root — 41 events, the newest 8 survive
            evs = obs_trace.events()
            assert [e[0] for e in evs] == [
                n for i in range(16, 20) for n in ("obs.clock", f"s{i}")
            ]
            assert obs_trace.dropped() == 33
            # timestamps stay chronological across the wrap
            ts = [e[1] for e in evs]
            assert ts == sorted(ts)
        finally:
            obs_trace.disable()

    def test_perfetto_export_valid(self, tracer, tmp_path):
        with obs_trace.span("a", pods=3):
            obs_trace.instant("mark", n=1)
        path = obs_trace.export_trace(str(tmp_path / "t.json"))
        with open(path) as f:
            doc = json.loads(f.read())
        events = doc["traceEvents"]
        assert isinstance(events, list) and events
        for ev in events:
            for key in ("name", "ph", "pid", "tid"):
                assert key in ev
        complete = [e for e in events if e["ph"] == "X"]
        assert len(complete) == 1 and complete[0]["name"] == "a"
        assert complete[0]["args"]["pods"] == 3
        assert isinstance(complete[0]["ts"], int)
        assert complete[0]["dur"] >= 1
        instants = [e for e in events if e["ph"] == "i" and e["name"] != "obs.clock"]
        assert len(instants) == 1 and instants[0]["name"] == "mark"
        # thread-name metadata rides along for the Perfetto lane labels
        assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)

    def test_noop_mode_no_allocation_no_events(self):
        obs_trace.disable()
        # one shared singleton — no per-span object when disabled
        assert obs_trace.span("a") is obs_trace.span("b", x=1)
        with obs_trace.span("c") as sp:
            sp.set(y=2)  # signature parity: attribute sets are no-ops too
        obs_trace.instant("d")
        assert obs_trace.events() == []
        assert not obs_trace.enabled()

    def test_span_summary_orders_by_total(self, tracer):
        import time

        for _ in range(3):
            with obs_trace.span("fast"):
                pass
        with obs_trace.span("slow"):
            time.sleep(0.02)
        rows = obs_trace.span_summary(top=10)
        assert rows[0]["name"] == "slow"
        fast = next(r for r in rows if r["name"] == "fast")
        assert fast["count"] == 3


class TestMetricsRegistry:
    def test_instrument_semantics_and_delta(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        reg.gauge("g").set({"x": 1})
        reg.histogram("h").observe(2.0)
        reg.histogram("h").observe(6.0)
        snap = reg.snapshot()
        assert snap["c"] == 5
        assert snap["g"] == {"x": 1}
        assert snap["h"] == {"count": 2, "total": 8.0, "min": 2.0, "max": 6.0}
        before = snap
        reg.counter("c").inc(2)
        reg.gauge("g").set(7)
        reg.histogram("h").observe(1.0)
        delta = reg.delta_since(before)
        assert delta["c"] == 2  # counters are flows
        assert delta["g"] == 7  # gauges are levels
        assert delta["h"]["count"] == 1 and delta["h"]["total"] == 1.0

    def test_type_conflict_refuses(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_snapshot_never_aliases_live_dicts(self):
        reg = MetricsRegistry()
        reg.gauge("g").set({"a": 1})
        snap = reg.snapshot()
        snap["g"]["a"] = 99
        assert reg.snapshot()["g"] == {"a": 1}


@pytest.fixture(scope="module")
def problem():
    from simtpu.synth import synth_apps, synth_cluster
    from simtpu.workloads.expand import get_valid_pods_exclude_daemonset

    cluster = synth_cluster(16, seed=71, zones=4, taint_frac=0.1)
    apps = synth_apps(
        48, seed=72, zones=4, pods_per_deployment=12,
        anti_affinity_frac=0.2, spread_frac=0.3,
    )
    pods = []
    for app in apps:
        pods.extend(get_valid_pods_exclude_daemonset(app.resource))
    return cluster, pods


class TestRegistryCounters:
    """The engine counter families read directly off the registry —
    across the wavefront and compact-carry engine A/Bs (the GSPMD shard
    A/B rides the same counters through tests/test_telemetry.py's
    sharded-plan cases).  The one-release legacy alias views
    (`fetch_counts` et al.) are gone; their absence is pinned so they
    cannot silently resurrect."""

    def test_legacy_alias_views_removed(self):
        import simtpu.durable.backoff as backoff_mod
        import simtpu.engine.scan as scan_mod
        import simtpu.engine.state as state_mod

        for mod, name in (
            (scan_mod, "fetch_counts"),
            (scan_mod, "trace_counts"),
            (scan_mod, "wave_counts"),
            (backoff_mod, "backoff_counts"),
            (state_mod, "state_gauge"),
        ):
            assert not hasattr(mod, name), (
                f"{mod.__name__}.{name} was removed in ISSUE 13 — read "
                "the obs registry instead"
            )

    @pytest.mark.parametrize("speculate", [False, True])
    @pytest.mark.parametrize("compact", [False, True])
    def test_registry_counters_after_placement(
        self, problem, speculate, compact
    ):
        from simtpu.core.tensorize import Tensorizer
        from simtpu.engine.scan import Engine
        from simtpu.obs.metrics import family

        cluster, pods = problem
        before = REGISTRY.snapshot()
        tz = Tensorizer(cluster.nodes, storage_classes=cluster.storage_classes)
        eng = Engine(tz)
        eng.speculate = speculate
        eng.compact = compact
        nodes, _, _ = eng.place(tz.add_pods(pods))

        from simtpu.durable.backoff import BACKOFF_KEYS
        from simtpu.engine.scan import FETCH_KEYS, WAVE_KEYS

        fetch = family("fetch", FETCH_KEYS)
        assert fetch["get"] > before.get("fetch.get", 0)
        assert fetch["bytes"] - before.get("fetch.bytes", 0) >= nodes.size * 4

        waves = family("wavefront", WAVE_KEYS)
        if speculate:
            assert waves["pods"] > before.get("wavefront.pods", 0)
        # accept/rollback accounting is complete: every drafted pod is
        # either accepted or rolled back
        assert waves["accepted"] + waves["rollback_pods"] == waves["pods"]

        gauge_bytes = REGISTRY.value("state.carried_bytes")
        planes = REGISTRY.value("state.planes", default={})
        assert gauge_bytes == sum(planes.values())

        back = family("backoff", BACKOFF_KEYS)
        assert back["events"] >= 0 and back["splits"] >= 2 * back["events"] - 1

    def test_compact_ab_same_placements_different_gauge(self, problem):
        from simtpu.core.tensorize import Tensorizer
        from simtpu.engine.rounds import RoundsEngine

        cluster, pods = problem
        results = {}
        for compact in (True, False):
            tz = Tensorizer(
                cluster.nodes, storage_classes=cluster.storage_classes
            )
            eng = RoundsEngine(tz)
            eng.compact = compact
            nodes, _, _ = eng.place(tz.add_pods(pods))
            results[compact] = (
                np.asarray(nodes),
                bool(REGISTRY.value("state.compact", default=False)),
            )
        assert np.array_equal(results[True][0], results[False][0])
        assert results[True][1] is True
        assert results[False][1] is False


class TestFlightRecorder:
    def test_bundle_document_shape(self, tmp_path, monkeypatch, tracer):
        monkeypatch.setenv("SIMTPU_FLIGHT_DIR", str(tmp_path))
        from simtpu.obs.flight import dump_flight

        with obs_trace.span("pre-crash"):
            pass
        path = dump_flight("test reason", 3, engine={"search": "binary"})
        assert path and os.path.isfile(path)
        doc = json.load(open(path))
        assert doc["format"] == "simtpu-flight-v1"
        assert doc["reason"] == "test reason"
        assert doc["exit_code"] == 3
        assert doc["schema_version"] == SCHEMA_VERSION
        assert doc["engine"] == {"search": "binary"}
        assert isinstance(doc["metrics"], dict)
        names = [
            e["name"] for e in doc["spans"]["traceEvents"] if e["ph"] == "X"
        ]
        assert "pre-crash" in names

    def test_flight_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIMTPU_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("SIMTPU_FLIGHT", "0")
        from simtpu.obs.flight import dump_flight

        assert dump_flight("r", 4) is None
        assert not glob.glob(str(tmp_path / "simtpu-flight-*.json"))

    def test_flight_lands_next_to_checkpoint_dir(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.delenv("SIMTPU_FLIGHT_DIR", raising=False)
        from simtpu.obs.flight import dump_flight

        ck = tmp_path / "nested" / "ck"
        ck.mkdir(parents=True)
        path = dump_flight("r", 3, checkpoint=str(ck))
        assert os.path.dirname(path) == str(tmp_path / "nested")

    def test_cli_exit_3_dumps_bundle(self, tmp_path, monkeypatch, capsys):
        """--deadline 0 = injected partial exit (3): the flight bundle
        lands next to the checkpoint dir with the partial reason."""
        from simtpu.cli import EXIT_PARTIAL, main

        monkeypatch.setenv("SIMTPU_FLIGHT_DIR", str(tmp_path / "fl"))
        rc = main([
            "apply", "-f", "examples/simtpu-config.yaml", "--json",
            "--deadline", "0", "--checkpoint", str(tmp_path / "ck"),
        ])
        capsys.readouterr()
        assert rc == EXIT_PARTIAL
        (path,) = glob.glob(str(tmp_path / "fl" / "simtpu-flight-*.json"))
        doc = json.load(open(path))
        assert doc["exit_code"] == EXIT_PARTIAL
        assert "partial" in doc["reason"]
        assert isinstance(doc["metrics"], dict)

    @pytest.mark.slow
    def test_cli_exit_4_dumps_bundle(self, tmp_path, monkeypatch, capsys):
        """SIMTPU_AUDIT_INJECT=1 = injected audit divergence (exit 4):
        the bundle carries the engine block and the buffered spans."""
        from simtpu.cli import EXIT_AUDIT, main

        monkeypatch.setenv("SIMTPU_FLIGHT_DIR", str(tmp_path / "fl"))
        monkeypatch.setenv("SIMTPU_AUDIT_INJECT", "1")
        obs_trace.enable()
        try:
            rc = main([
                "apply", "-f", "examples/simtpu-config.yaml", "--json",
            ])
        finally:
            obs_trace.disable()
        capsys.readouterr()
        assert rc == EXIT_AUDIT
        (path,) = glob.glob(str(tmp_path / "fl" / "simtpu-flight-*.json"))
        doc = json.load(open(path))
        assert doc["exit_code"] == EXIT_AUDIT
        assert "audit" in doc["reason"]
        assert doc["engine"]["audit"]["fallback"] is True
        assert [
            e for e in doc["spans"]["traceEvents"] if e["ph"] == "X"
        ], "armed tracer's spans must ride the bundle"


class TestCLIObs:
    def test_version_json_schema_stamp(self, capsys):
        from simtpu import __version__
        from simtpu.cli import main

        assert main(["version", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc == {
            "version": __version__, "schema_version": SCHEMA_VERSION,
        }

    def test_apply_trace_json_reconciles(self, tmp_path, capsys):
        """The ISSUE-8 acceptance run: one `apply --trace t.json --json`
        on the examples yields (a) a Perfetto-valid trace whose
        ingest/plan span wall-clock reconciles with the --json phase
        timings within 5%, and (b) a metrics block whose values the
        legacy engine-block families alias bit-equally."""
        from simtpu.cli import main

        tpath = str(tmp_path / "t.json")
        rc = main([
            "apply", "-f", "examples/simtpu-config.yaml", "--json",
            "--trace", tpath,
        ])
        out = capsys.readouterr().out
        assert rc == 0
        doc = json.loads(out)
        assert doc["schema_version"] == SCHEMA_VERSION
        m, e = doc["metrics"], doc["engine"]

        # (b) every legacy counter family under the unified schema,
        # values bit-equal to the legacy engine-block fields
        assert e["fetch"] == {"get": m["fetch.get"], "bytes": m["fetch.bytes"]}
        assert e["backoff"] == {
            "events": m["backoff.events"],
            "splits": m["backoff.splits"],
            "chunk_min": m["backoff.chunk_min"],
        }
        assert e["wavefront"] == {
            k: m[f"wavefront.{k}"] for k in e["wavefront"]
        }
        assert e["compact"] == m["state.compact"]
        assert e["state_bytes"] == {
            "carried_bytes": m["state.carried_bytes"],
            "dense_bytes": m["state.dense_bytes"],
            "planes": m["state.planes"],
        }
        for k in ("ok", "checked", "violations", "wall_s", "mode"):
            assert m[f"audit.{k}"] == e["audit"][k]
        assert any(k.startswith("compile.") for k in m)

        # (a) Perfetto-valid trace whose phase spans reconcile with the
        # --json timings within 5%
        trace = json.load(open(tpath))
        complete = [x for x in trace["traceEvents"] if x["ph"] == "X"]
        assert complete
        sums = {}
        for x in complete:
            sums[x["name"]] = sums.get(x["name"], 0.0) + x["dur"] / 1e6
        # (the --json timings keep milliseconds: an ingest of a few ms is
        # reconciled to that last digit)
        for phase in ("ingest", "plan"):
            span_s, json_s = sums[phase], doc["timings"][phase]
            assert span_s == pytest.approx(json_s, rel=0.05, abs=1e-3), phase
        # the engine layers all reported in: dispatch chunks, audit
        names = set(sums)
        assert {"tensorize", "expand", "audit.pass"} <= names
        assert "scan.chunk" in names or "rounds.chunk" in names

    def test_simulate_trace_kwarg_exports(self, tmp_path, problem):
        from simtpu.api import simulate
        from simtpu.core.objects import ResourceTypes

        cluster, pods = problem
        trial = ResourceTypes(**{k: list(v) for k, v in vars(cluster).items()})
        trial.pods = list(pods[:24])
        tpath = str(tmp_path / "sim.json")
        # an earlier CLI --trace run leaves the process tracer armed (by
        # design — flight-recorder visibility); this test is about the
        # own-tracer path, so start from the disabled state
        obs_trace.disable()
        simulate(trial, trace=tpath)
        assert not obs_trace.enabled(), "simulate() must disarm its own tracer"
        doc = json.load(open(tpath))
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"tensorize", "expand", "schedule.cluster"} <= names


class TestSpanIdentity:
    """Span ids, parents and roots (docs/observability.md §1): the
    causality the self-time partition and the per-answer readings are
    built on, across threads and into the Chrome export."""

    @staticmethod
    def _by_name():
        return {e[0]: e for e in _spans()}

    def test_nested_parent_and_root_ids(self, tracer):
        with obs_trace.span("answer"):
            with obs_trace.span("stage"):
                with obs_trace.span("step"):
                    pass
            obs_trace.instant("mark")
        evs = self._by_name()
        ans, stage, step, mark = (evs[n] for n in ("answer", "stage", "step", "mark"))
        sids = {e[6] for e in (ans, stage, step, mark)}
        assert len(sids) == 4, "every event gets its own id"
        assert ans[7] is None and ans[8] == ans[6]
        assert (stage[7], stage[8]) == (ans[6], ans[6])
        assert (step[7], step[8]) == (stage[6], ans[6])
        assert (mark[7], mark[8]) == (ans[6], ans[6])
        assert [e[4] for e in (ans, stage, step)] == [0, 1, 2]

    def test_root_span_records_clock_anchor(self, tracer):
        with obs_trace.span("answer"):
            with obs_trace.span("inner"):
                pass
        anchors = [e for e in obs_trace.events() if e[0] == "obs.clock"]
        # one at enable(), one at the start of the one root span
        assert len(anchors) == 2
        root = self._by_name()["answer"]
        assert anchors[1][8] == root[6]
        assert set(anchors[1][5]) == {"ts_ns", "wall_ns"}

    def test_adopted_thread_inherits_parent_and_root(self, tracer):
        """Work handed to another thread keeps the submitting span as
        parent and root, captured at submit time, not at run time."""
        with obs_trace.span("answer"):
            with obs_trace.span("submit"):
                ctx = obs_trace.current()
            # the worker runs after `submit` closed: the captured id holds

            def work():
                with obs_trace.adopt(ctx):
                    with obs_trace.span("worker"):
                        with obs_trace.span("worker.inner"):
                            pass
                with obs_trace.span("unrelated"):
                    pass

            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
        evs = self._by_name()
        ans, sub = evs["answer"], evs["submit"]
        worker, inner = evs["worker"], evs["worker.inner"]
        assert worker[3] != ans[3], "recorded on the worker thread"
        assert (worker[7], worker[8]) == (sub[6], ans[6])
        assert (inner[7], inner[8]) == (worker[6], ans[6])
        assert worker[4] == 0 and inner[4] == 1
        # after adopt() exits the thread's spans are roots of their own
        assert evs["unrelated"][7] is None and evs["unrelated"][8] == evs["unrelated"][6]

    def test_current_and_adopt_are_free_when_off(self):
        obs_trace.disable()
        assert obs_trace.current() is None
        assert obs_trace.adopt(None) is obs_trace.span("x")

    def test_aot_pool_span_parent_is_submitting_candidate(self, tracer):
        """A real AotPipeline: the pool thread's compile span names the
        candidate span that enumerated it as parent, and its answer as
        root."""
        import jax
        import jax.numpy as jnp

        from simtpu.engine.precompile import AotPipeline, _sds

        pipe = AotPipeline(workers=2)
        try:
            with obs_trace.span("apply"):
                with obs_trace.span("plan.candidate", count=3):
                    fn = jax.jit(lambda x: x + 7)
                    assert pipe.submit("obs_cause", (), fn, (_sds((6,), jnp.int32),))
                pipe.wait_all(timeout=60)
        finally:
            pipe.shutdown()
        evs = self._by_name()
        comp, cand, root = evs["aot.compile"], evs["plan.candidate"], evs["apply"]
        assert comp[3] != cand[3], "compiled on a pool thread"
        assert comp[7] == cand[6] and comp[8] == root[6]

    def test_chrome_export_carries_parent_root_and_anchors(self, tracer, tmp_path):
        with obs_trace.span("outer"):
            with obs_trace.span("inner"):
                pass
        doc = json.load(open(obs_trace.export_trace(str(tmp_path / "t.json"))))
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        outer, inner = spans["outer"]["args"], spans["inner"]["args"]
        assert outer["parent"] is None and outer["root"] == inner["root"]
        assert inner["parent"] == outer["root"]
        anchors = doc["otherData"]["clock_anchors"]
        assert len(anchors) == 2 and all(len(a) == 2 for a in anchors)
        clocks = [e for e in doc["traceEvents"] if e["name"] == "obs.clock"]
        assert [[c["args"]["ts_ns"], c["args"]["wall_ns"]] for c in clocks] == anchors

    def test_profiler_ns_monotone_across_anchors(self, tracer, monkeypatch):
        import time

        for _ in range(4):
            with obs_trace.span("answer"):
                time.sleep(0.002)
        anchors = list(obs_trace._ANCHORS)
        assert len(anchors) == 5
        # at an anchor the map reads the anchor's own wall clock
        for ts_ns, wall_ns in anchors:
            assert abs(obs_trace.profiler_ns(ts_ns / 1000) - wall_ns) <= 1
        lo, hi = anchors[0][0] / 1000 - 5000, anchors[-1][0] / 1000 + 5000
        grid = [lo + (hi - lo) * i / 997 for i in range(998)]
        mapped = [obs_trace.profiler_ns(t) for t in grid]
        assert mapped == sorted(mapped)
        # offsets that wander between anchors still map monotonically
        jitter = [(a, w + (-40_000 if i % 2 else 40_000)) for i, (a, w) in
                  enumerate((i * 1_000_000, 5_000_000_000 + i * 1_000_000) for i in range(6))]
        monkeypatch.setattr(obs_trace, "_ANCHORS", jitter)
        mapped = [obs_trace.profiler_ns(t) for t in range(-500, 6500, 7)]
        assert mapped == sorted(mapped)

    def test_profiler_ns_none_without_anchors(self):
        obs_trace.disable()
        assert obs_trace.profiler_ns(123.0) is None


class TestJitEvents:
    """JAX's compile duration events (obs/profile.py): always into the
    `jit.*_s` histograms, and into the ring as spans while tracing."""

    NAMES = ("jit.trace_s", "jit.lower_s", "jit.compile_s")

    def test_fresh_jit_yields_one_of_each_then_none(self, tracer):
        import jax

        from simtpu.obs.profile import install_jit_listener

        install_jit_listener()

        def obs_jit_probe(x):
            return jax.lax.add(jax.lax.mul(x, x), x)

        f = jax.jit(obs_jit_probe)
        arg = np.arange(7, dtype=np.int32)

        def counts():
            return {n: REGISTRY.value(n, default={"count": 0})["count"] for n in self.NAMES}

        def jit_spans():
            return [e for e in _spans() if e[0].startswith("jit.")
                    and "obs_jit_probe" in (e[5] or {}).get("fun", "")]

        before = counts()
        with obs_trace.span("caller"):
            np.asarray(f(arg))
        caller = {e[0]: e for e in _spans()}["caller"]
        spans = jit_spans()
        assert sorted(e[0] for e in spans) == ["jit.compile", "jit.lower", "jit.trace"]
        for e in spans:
            assert e[7] == caller[6] and e[8] == caller[6]
            assert e[2] >= 1 and caller[1] <= e[1]
        assert {n: counts()[n] - before[n] for n in self.NAMES} == dict.fromkeys(self.NAMES, 1)

        mid = counts()
        np.asarray(f(arg + 1))  # the same shape: the executable is cached
        assert len(jit_spans()) == 3
        assert counts() == mid

    def test_listener_installs_once(self):
        import jax

        from simtpu.obs import profile

        profile.install_jit_listener()
        n = len(jax._src.monitoring._event_duration_secs_listeners)
        profile.install_jit_listener()
        from simtpu.cache import enable_compilation_cache

        enable_compilation_cache()
        assert len(jax._src.monitoring._event_duration_secs_listeners) == n


def _write_fixture(root, n_nodes=3, replicas=5):
    """A simon config over `n_nodes` nodes and one deployment, written as
    one manifest document each; returns (config path, documents)."""
    import yaml

    os.makedirs(root / "cluster")
    os.makedirs(root / "app")

    def node(name):
        res = {"cpu": "8", "memory": "32Gi", "pods": "110"}
        return {"apiVersion": "v1", "kind": "Node",
                "metadata": {"name": name, "labels": {"kubernetes.io/hostname": name}},
                "status": {"allocatable": res, "capacity": res}}

    nodes = [node(f"n{i}") for i in range(n_nodes)]
    dep = {"apiVersion": "apps/v1", "kind": "Deployment",
           "metadata": {"name": "web", "namespace": "default"},
           "spec": {"replicas": replicas,
                    "selector": {"matchLabels": {"app": "web"}},
                    "template": {"metadata": {"labels": {"app": "web"}},
                                 "spec": {"containers": [{
                                     "name": "web", "image": "web:1",
                                     "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}}]}}}}
    with open(root / "cluster" / "nodes.yaml", "w") as f:
        yaml.safe_dump_all(nodes, f)
    with open(root / "app" / "web.yaml", "w") as f:
        yaml.safe_dump(dep, f)
    with open(root / "newnode.yaml", "w") as f:
        yaml.safe_dump(node("tmpl"), f)
    config = root / "simon.yaml"
    with open(config, "w") as f:
        yaml.safe_dump({"apiVersion": "simon/v1alpha1", "kind": "Config",
                        "metadata": {"name": "obs"},
                        "spec": {"cluster": {"customConfig": str(root / "cluster")},
                                 "appList": [{"name": "web", "path": str(root / "app")}],
                                 "newNode": str(root / "newnode.yaml")}}, f)
    return str(config), n_nodes + 2


@pytest.mark.parametrize("search", ["binary", "incremental"])
def test_apply_json_records_layer_spans(tmp_path, capsys, search):
    """One `apply --json` answer records a span for every host layer
    (decode, objects, expand, tensorize, materialize, report) under the
    root `apply`, and counts the documents it decoded."""
    from simtpu.cli import main

    config, n_docs = _write_fixture(tmp_path)
    obs_trace.enable()
    try:
        before = REGISTRY.snapshot()
        rc = main(["apply", "-f", config, "--json", "--search", search])
        delta = REGISTRY.delta_since(before)
        evs = _spans()
    finally:
        obs_trace.disable()
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["success"] is True
    (root,) = [e for e in evs if e[0] == "apply"]
    assert root[7] is None
    names = {e[0] for e in evs if e[8] == root[6]}
    assert {"ingest.decode", "ingest.objects", "expand", "tensorize",
            "plan.materialize", "report"} <= names
    assert delta["ingest.docs"] == n_docs
    assert delta["ingest.bytes"] == sum(
        os.path.getsize(p) for p in glob.glob(str(tmp_path / "**" / "*.yaml"), recursive=True)
        if not p.endswith("simon.yaml"))


class TestPreemptionSpans:
    """The preemption waves' spans and counters (docs/observability.md):
    `preempt.wave` under `schedule.app`, its phases under it, and
    `preempt.victims` equal to the victims the result reports."""

    PHASES = ("preempt.propose", "preempt.evict", "preempt.verify")

    @staticmethod
    def _simulate(priorities: bool):
        from simtpu.api import simulate
        from simtpu.core.objects import AppResource, ResourceTypes

        from .fixtures import make_fake_node, make_fake_pod

        def pod(name, cpu, prio):
            p = make_fake_pod(name, "default", cpu, "500Mi")
            if priorities:
                p["spec"]["priority"] = prio
            return p

        cluster = ResourceTypes(
            nodes=[make_fake_node(f"n{i}", "4", "32Gi") for i in range(2)],
            pods=[pod(f"low-{i}", "900m", 1) for i in range(8)],
        )
        app = AppResource(name="high", resource=ResourceTypes(
            pods=[pod(f"high-{i}", "3", 10) for i in range(2)]))
        return simulate(cluster, [app])

    def test_waves_nest_under_the_app_and_count_the_victims(self, tracer):
        before = REGISTRY.snapshot()
        result = self._simulate(priorities=True)
        delta = REGISTRY.delta_since(before)
        evs = _spans()
        assert len(result.preempted_pods) == 6 and not result.unscheduled_pods
        by_id = {e[6]: e for e in evs}
        (app,) = [e for e in evs if e[0] == "schedule.app"]
        waves = [e for e in evs if e[0] == "preempt.wave"]
        assert waves and all(w[7] == app[6] for w in waves)
        for name in self.PHASES:
            spans = [e for e in evs if e[0] == name]
            assert spans, name
            assert all(by_id[e[7]][0] == "preempt.wave" for e in spans)
        assert delta["preempt.victims"] == len(result.preempted_pods)
        assert delta["preempt.waves"] >= 1
        assert delta["preempt.preemptors"] == 2
        assert delta["preempt.final_failures"] == 0

    def test_no_priorities_record_no_preemption(self, tracer):
        before = REGISTRY.snapshot()
        result = self._simulate(priorities=False)
        delta = REGISTRY.delta_since(before)
        assert len(result.unscheduled_pods) == 2 and not result.preempted_pods
        assert not [e for e in _spans() if e[0].startswith("preempt.")]
        assert not any(v for k, v in delta.items() if k.startswith("preempt."))
