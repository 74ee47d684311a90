#!/usr/bin/env python
"""Seeded concurrent load generator + robustness smoke for `simtpu serve`
(ISSUE 14 satellite; `make bench-serve` runs `--smoke --json`).

Owns a real daemon subprocess (`python -m simtpu.cli serve`) unless
pointed at a running one with --url, then fires a seeded mixed burst —
coalescible drain/resilience queries, one over-deadline request, one
malformed request, and an overload tail past the admission queue — and
reads the daemon's own /metrics registry to report:

    serve_qps             completed queries / burst wall
    serve_p50_s / serve_p99_s   burst latency quantiles
    serve_coalesce_ratio  coalesced / sweep-shaped requests
    serve_requests / serve_coalesced / serve_sweeps / serve_shed /
    serve_timeouts        raw counter deltas

With --smoke the run ASSERTS the robustness matrix end to end on the
subprocess daemon: coalescing counters moved, the over-deadline request
answered a structured 504 while its peers completed, the malformed
request answered 400, the overload tail drew 429s with Retry-After and
zero effect on admitted work, kill -9 + restart rehydrated the session
bit-identically from --state-dir, and SIGTERM drained to a clean exit 0.
Any violated assertion exits 1 (the finding IS the failure).

Stdlib only — the generator must not need more than the daemon does.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time


def request(base, method, path, body=None, timeout=300):
    host, port = base
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(
            method, path,
            json.dumps(body) if body is not None else None,
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        return resp.status, doc, dict(resp.getheaders())
    finally:
        conn.close()


class Daemon:
    """One owned `simtpu serve` subprocess."""

    def __init__(self, state_dir: str, queue_depth: int, argv_extra=()):
        env = dict(os.environ)
        # the generator lives next to the simtpu package — make the
        # daemon subprocess importable from ANY cwd, installed or not
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = (
            repo + os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else repo
        )
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "simtpu.cli", "serve",
                "--port", "0", "--state-dir", state_dir,
                "--queue-depth", str(queue_depth),
                *argv_extra,
            ],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
        )
        self.port = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                if self.proc.poll() is not None:
                    raise RuntimeError("daemon died during startup")
                time.sleep(0.05)
                continue
            if "listening on http://" in line:
                self.port = int(line.rsplit(":", 1)[1].split()[0])
                break
        if self.port is None:
            raise RuntimeError("daemon never printed its address")
        self.base = ("127.0.0.1", self.port)

    def kill9(self):
        self.proc.kill()
        self.proc.wait(30)

    def sigterm_and_wait(self) -> tuple:
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(120)
        return rc, self.proc.stdout.read()


def serve_metrics(base) -> dict:
    _, doc, _ = request(base, "GET", "/metrics")
    return {
        k: v for k, v in doc["metrics"].items() if k.startswith("serve.")
    }


def grow_metrics(base) -> dict:
    """The warm-engine counter family (grow.* + serve.warm_*): the
    arrival sweep asserts the common path stayed append-only
    (`grow.retensorize_fallbacks` unmoved)."""
    _, doc, _ = request(base, "GET", "/metrics")
    return {
        k: v for k, v in doc["metrics"].items()
        if k.startswith("grow.") or k.startswith("serve.warm")
        or k == "compile.grow"
    }


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k, 0)
        out[k] = v - b if isinstance(v, (int, float)) and isinstance(b, (int, float)) else v
    return out


def quantile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def run_burst(base, sid, n_nodes, burst, threads, seed, say):
    """The seeded mixed burst: coalescible sweeps + one over-deadline +
    one malformed, `threads`-wide.  Returns (results, latencies, wall)."""
    rng = random.Random(seed)
    jobs = []
    for i in range(burst):
        if rng.random() < 0.8:
            jobs.append(("drain", {
                "nodes": [rng.randrange(n_nodes)],
            }))
        else:
            jobs.append(("resilience", {"spec": "k=1"}))
    # the two adversarial riders, at seeded positions
    jobs.insert(rng.randrange(len(jobs)), ("drain", {
        "nodes": [0], "deadline_s": 0.0, "_expect": 504,
    }))
    jobs.insert(rng.randrange(len(jobs)), ("drain", {
        "nodes": ["no-such-node"], "_expect": 400,
    }))
    results = [None] * len(jobs)
    latencies = []
    lat_lock = threading.Lock()
    cursor = {"i": 0}
    cursor_lock = threading.Lock()

    retries = {"n": 0}

    def worker():
        while True:
            with cursor_lock:
                i = cursor["i"]
                if i >= len(jobs):
                    return
                cursor["i"] = i + 1
            kind, payload = jobs[i]
            expect = payload.pop("_expect", 200)
            t0 = time.perf_counter()
            budget = time.monotonic() + 120
            while True:
                status, doc, headers = request(
                    base, "POST", f"/v1/sessions/{sid}/{kind}", payload
                )
                if status != 429 or time.monotonic() >= budget:
                    break
                # a well-behaved client honors the shed: back off for
                # Retry-After and resubmit — admission control degrades
                # arrival rate, not outcomes
                with lat_lock:
                    retries["n"] += 1
                time.sleep(
                    min(float(headers.get("Retry-After", 1)), 0.5)
                )
            dt = time.perf_counter() - t0
            results[i] = (expect, status, doc)
            if expect == 200 and status == 200:
                with lat_lock:
                    latencies.append(dt)

    t0 = time.perf_counter()
    pool = [threading.Thread(target=worker) for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    wall = time.perf_counter() - t0
    say(
        f"burst: {len(jobs)} queries over {threads} threads in {wall:.2f}s "
        f"({retries['n']} shed-retries honored)"
    )
    return results, sorted(latencies), wall


def overload_tail(base, sid, n_nodes, width, say):
    """Fire `width` drains at once against a small admission queue;
    report (ok_count, shed_responses)."""
    results = [None] * width

    def fire(i):
        try:
            results[i] = request(
                base, "POST", f"/v1/sessions/{sid}/drain",
                {"nodes": [i % n_nodes]},
            )
        except OSError as exc:
            # a refused/reset connection under deliberate overload is a
            # shed-shaped outcome, not a generator crash
            results[i] = (0, {"error": str(exc)}, {})

    pool = [threading.Thread(target=fire, args=(i,)) for i in range(width)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    ok = [r for r in results if r[0] == 200]
    shed = [r for r in results if r[0] == 429]
    say(f"overload tail: {len(ok)} served, {len(shed)} shed (429)")
    return ok, shed


def fit_payload(i: int) -> dict:
    """One of two fixed fit-query shapes (alternating): a serving mix
    repeats shapes, which is exactly what the warm engine's append-only
    vocabulary is built for — after the first occurrence of each shape
    the session must answer with ZERO re-tensorization."""
    shape = i % 2
    name = f"arrival-{shape}"
    return {
        "workloads": [{
            "apiVersion": "apps/v1",
            "kind": "Deployment",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {
                "replicas": 1 + shape,
                "template": {
                    "metadata": {"labels": {"app": name}},
                    "spec": {"containers": [{
                        "name": "c", "image": "nginx",
                        "resources": {"requests": {
                            "cpu": "250m" if shape else "100m",
                            "memory": "128Mi",
                        }},
                    }]},
                },
            },
        }],
    }


def arrival_sweep(base, sid, rates, duration, say):
    """Sustained OPEN-LOOP arrival sweep: for each rate, fit queries fire
    at fixed inter-arrival periods for `duration` seconds regardless of
    completions (each request on its own thread — a slow server builds a
    queue instead of slowing the generator, the way real arrival streams
    behave).  Returns per-rate latency records.  The sweep measures WARM
    serving: one fit per shape runs serially first so trace/compile
    cost (paid once per session, docs/serving.md) stays out of the
    latency quantiles."""
    for i in range(2):
        status, _doc, _ = request(
            base, "POST", f"/v1/sessions/{sid}/fit", fit_payload(i)
        )
        if status != 200:
            say(f"arrival warm-up query {i} answered {status}")
    records = []
    for rate in rates:
        period = 1.0 / rate
        lats, statuses = [], []
        lock = threading.Lock()
        threads = []

        def fire(i):
            t0 = time.perf_counter()
            status, doc, _ = request(
                base, "POST", f"/v1/sessions/{sid}/fit", fit_payload(i)
            )
            dt = time.perf_counter() - t0
            with lock:
                statuses.append(status)
                if status == 200:
                    lats.append(dt)

        t_start = time.perf_counter()
        i = 0
        while True:
            t_next = t_start + i * period
            now = time.perf_counter()
            if t_next >= t_start + duration:
                break
            if now < t_next:
                time.sleep(t_next - now)
            th = threading.Thread(target=fire, args=(i,))
            th.start()
            threads.append(th)
            i += 1
        for th in threads:
            th.join()
        wall = time.perf_counter() - t_start
        lats.sort()
        rec = {
            "rate": rate,
            "sent": i,
            "ok": sum(1 for s in statuses if s == 200),
            "shed": sum(1 for s in statuses if s == 429),
            "achieved_qps": round(len(lats) / wall, 2) if wall > 0 else 0.0,
            "p50_s": round(quantile(lats, 0.50), 4),
            "p99_s": round(quantile(lats, 0.99), 4),
        }
        records.append(rec)
        say(
            f"arrival {rate:g}/s: sent={rec['sent']} ok={rec['ok']} "
            f"achieved={rec['achieved_qps']}/s p50={rec['p50_s']}s "
            f"p99={rec['p99_s']}s"
        )
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="examples/simtpu-config.yaml")
    ap.add_argument("--state-dir", default="",
                    help="daemon state dir (default: a temp dir)")
    ap.add_argument("--url", default="",
                    help="target a running daemon (host:port) instead of "
                    "owning a subprocess; disables the kill/SIGTERM checks")
    ap.add_argument("--burst", type=int, default=24)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue-depth", type=int, default=4,
                    help="owned daemon's admission bound (small so the "
                    "overload tail actually sheds; default 4)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert the full robustness matrix (kill -9 "
                    "restart recovery + SIGTERM drain included)")
    ap.add_argument("--arrival-sweep", default="",
                    help="comma list of sustained open-loop fit-query "
                    "arrival rates (QPS), e.g. '4,12'; asserts p50/p99 "
                    "bounds and zero warm-path retensorize fallbacks")
    ap.add_argument("--arrival-duration", type=float, default=3.0,
                    help="seconds per arrival rate (default 3)")
    ap.add_argument("--p99-max", type=float, default=5.0,
                    help="p99 latency bound asserted at the LOWEST "
                    "arrival rate (default 5s)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    say = (lambda m: print(m, file=sys.stderr, flush=True)) if args.json \
        else (lambda m: print(m, flush=True))
    checks = {}
    failures = []

    def check(name, ok, detail=""):
        checks[name] = bool(ok)
        if not ok:
            failures.append(f"{name}: {detail}")
            say(f"FAIL {name}: {detail}")
        else:
            say(f"ok   {name}")

    state_dir = args.state_dir
    if not state_dir:
        import tempfile

        state_dir = tempfile.mkdtemp(prefix="simtpu-serve-loadgen-")
    daemon = None
    if args.url:
        host, port = args.url.replace("http://", "").split(":")
        base = (host, int(port))
    else:
        say("starting daemon...")
        daemon = Daemon(state_dir, args.queue_depth)
        base = daemon.base
    summary = {}
    try:
        _, health, _ = request(base, "GET", "/healthz")
        summary["serve_platform"] = health["platform"]
        status, doc, _ = request(
            base, "POST", "/v1/sessions", {"config": args.config}
        )
        if status not in (200, 201):
            raise RuntimeError(f"session create failed: {status} {doc}")
        sid, n_nodes = doc["session"], doc["nodes"]
        say(f"session {sid}: {n_nodes} nodes, {doc['pods']} pods")

        before = serve_metrics(base)
        results, lats, wall = run_burst(
            base, sid, n_nodes, args.burst, args.threads, args.seed, say
        )
        after = serve_metrics(base)
        d = delta(after, before)
        sweep_requests = max(
            int(d.get("serve.requests", 0)) - 2, 1
        )  # minus the deadline/malformed riders
        summary.update({
            "serve_qps": round(len(lats) / wall, 2) if wall > 0 else 0.0,
            "serve_p50_s": round(quantile(lats, 0.50), 4),
            "serve_p99_s": round(quantile(lats, 0.99), 4),
            "serve_requests": int(d.get("serve.requests", 0)),
            "serve_coalesced": int(d.get("serve.coalesced", 0)),
            "serve_sweeps": int(d.get("serve.sweeps", 0)),
            "serve_shed": int(d.get("serve.shed", 0)),
            "serve_timeouts": int(d.get("serve.timeouts", 0)),
            "serve_coalesce_ratio": round(
                int(d.get("serve.coalesced", 0)) / sweep_requests, 4
            ),
        })

        # burst verdicts: every job answered its expected status
        mis = [
            (expect, status)
            for expect, status, _ in results
            if status != expect
        ]
        check("burst_statuses", not mis, f"mismatches: {mis[:5]}")
        deadline_docs = [
            doc for expect, status, doc in results
            if expect == 504 and status == 504
        ]
        check(
            "deadline_structured_504",
            deadline_docs and all(
                d.get("error") == "deadline" and "partial" in d
                for d in deadline_docs
            ),
            f"got {deadline_docs!r}",
        )
        check(
            "coalescing_happened",
            summary["serve_coalesced"] > 0
            and summary["serve_sweeps"] < sweep_requests,
            f"coalesced={summary['serve_coalesced']} "
            f"sweeps={summary['serve_sweeps']} vs {sweep_requests} requests",
        )

        # sustained open-loop arrival sweep (fit queries, warm path)
        if args.arrival_sweep:
            rates = [float(r) for r in args.arrival_sweep.split(",") if r]
            gbefore = grow_metrics(base)
            records = arrival_sweep(
                base, sid, rates, args.arrival_duration, say
            )
            gafter = grow_metrics(base)
            gd = delta(gafter, gbefore)
            summary["arrival"] = records
            summary["serve_fit_p50_s"] = records[0]["p50_s"]
            summary["serve_fit_p99_s"] = records[0]["p99_s"]
            summary["serve_warm_fits"] = int(gd.get("serve.warm_fits", 0))
            summary["serve_warm_fallbacks"] = int(
                gd.get("grow.retensorize_fallbacks", 0)
            )
            check(
                "arrival_statuses",
                all(r["ok"] + r["shed"] == r["sent"] for r in records),
                f"non-200/429s: {records}",
            )
            check(
                "arrival_low_rate_unshed",
                records[0]["shed"] == 0 and records[0]["ok"] > 0,
                f"sheds at the lowest rate: {records[0]}",
            )
            check(
                "arrival_p99_bound",
                records[0]["p99_s"] <= args.p99_max,
                f"p99 {records[0]['p99_s']}s > {args.p99_max}s "
                f"at {records[0]['rate']:g}/s",
            )
            if gd.get("serve.warm_fits", 0) > 0:
                # the acceptance bar: a repeating serving mix must ride
                # the append-only vocabulary — zero re-tensorizations
                check(
                    "warm_zero_fallbacks",
                    gd.get("grow.retensorize_fallbacks", 0) == 0,
                    f"retensorize fallbacks on the common path: {gd}",
                )

        # overload tail (only meaningful against our own small queue)
        if daemon is not None:
            ok, shed = overload_tail(
                base, sid, n_nodes, width=4 * args.queue_depth, say=say
            )
            check("overload_sheds_429", len(shed) > 0, "no 429 seen")
            check(
                "shed_carries_retry_after",
                all("Retry-After" in h for _, _, h in shed),
                "missing Retry-After header",
            )
            check(
                "admitted_work_unharmed",
                all(doc.get("ok") for _, doc, _ in ok) and len(ok) > 0,
                "an admitted query failed",
            )

        if args.smoke and daemon is not None:
            # kill -9 + restart: the session rehydrates bit-identically
            status, before_doc, _ = request(
                base, "POST", f"/v1/sessions/{sid}/drain", {"nodes": [0]}
            )
            check("pre_kill_drain", status == 200, f"{status}")
            say("kill -9 ...")
            daemon.kill9()
            daemon = Daemon(state_dir, args.queue_depth)
            base = daemon.base
            status, summary_doc, _ = request(
                base, "GET", f"/v1/sessions/{sid}"
            )
            check(
                "recovered_session",
                status == 200 and summary_doc.get("recovered") is True,
                f"{status} {summary_doc}",
            )
            status, after_doc, _ = request(
                base, "POST", f"/v1/sessions/{sid}/drain", {"nodes": [0]}
            )
            check(
                "recovery_bit_identical",
                status == 200 and after_doc == before_doc,
                f"before={before_doc} after={after_doc}",
            )
            # SIGTERM: graceful drain, clean exit 0
            rc, out = daemon.sigterm_and_wait()
            daemon = None
            check(
                "sigterm_clean_exit",
                rc == 0 and "drained" in out,
                f"rc={rc} out={out[-200:]!r}",
            )
    except RuntimeError as exc:
        # daemon startup/session failures (e.g. a starved CI box blowing
        # the boot budget) must still produce the structured JSON verdict
        # the caller parses, never a bare traceback
        check("driver", False, str(exc))
    finally:
        if daemon is not None:
            daemon.kill9()

    summary["ok"] = not failures
    summary["checks"] = checks
    if args.json:
        print(json.dumps(summary))
    else:
        for k, v in summary.items():
            say(f"{k}: {v}")
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
