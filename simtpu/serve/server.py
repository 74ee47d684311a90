"""The `simtpu serve` daemon: stdlib HTTP front-end over the session
store and the coalescing batcher (ISSUE 14).

Stack: `http.server.ThreadingHTTPServer` (one thread per connection — no
new dependencies) parses and validates; admitted queries cross one
bounded queue to the single dispatch worker (`batching.Batcher`); the
HTTP thread blocks on the query's completion event with the request's
deadline.  The daemon's robustness contract, endpoint by endpoint:

- every query carries a cooperative deadline (`durable/deadline.py`);
  expiry answers a structured 504 body — with the capacity search's
  partial result when the `RunControl` salvaged one — and the in-flight
  dispatch completes harmlessly off-wire;
- a full queue answers 429 + Retry-After, touching nothing admitted;
- OOM rides the chunk-halving backoff inside every dispatcher; exhausted
  backoff evicts idle sessions (rehydratable from checkpoint) and
  answers 503 + Retry-After;
- SIGTERM flips /readyz to 503 and refuses new work (503 Degraded) while
  the probe endpoints keep answering, drains the queue and every
  in-flight request, then releases the port and exits 0; a second signal
  abandons the drain;
- kill -9 loses nothing durable: sessions checkpoint at creation and
  rehydrate bit-identically on the next daemon (session.py);
- 500s (bugs, by the taxonomy's design rule) dump a flight-recorder
  bundle (obs/flight.py) with the request context — structured 503/504
  responses deliberately do not — and every request runs under a
  `serve.request` span.

Routes (all bodies JSON):

    GET    /healthz                   process liveness
    GET    /readyz                    accepting? (503 while draining)
    GET    /metrics                   full PR-8 registry snapshot
    GET    /v1/sessions               list sessions (live + recoverable)
    POST   /v1/sessions               {"config": path} -> load snapshot
    GET    /v1/sessions/<sid>         session summary
    DELETE /v1/sessions/<sid>         drop session + checkpoint
    POST   /v1/sessions/<sid>/fit         {"workloads": [...]|"app": path}
    POST   /v1/sessions/<sid>/drain       {"nodes": ["name", ...]}
    POST   /v1/sessions/<sid>/capacity    {"workloads": ...?, "max_new_nodes"?}
    POST   /v1/sessions/<sid>/resilience  {"spec": "k=1", "samples"?, "seed"?}

Every POST query accepts `"deadline_s"` (default: the daemon's
`--default-deadline`).  Error bodies follow `errors.error_doc` and the
status table `errors.HTTP_TAXONOMY` (docs/serving.md).
"""

from __future__ import annotations

import json
import logging
import signal
import threading
import time
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence, Tuple

from ..durable.deadline import RunControl
from ..obs.metrics import REGISTRY, SCHEMA_VERSION
from ..obs.trace import span
from .batching import QUERY_KINDS, Batcher, Query
from .errors import (
    BadRequest,
    DeadlineExceeded,
    Degraded,
    InternalError,
    NotFound,
    ServeError,
    error_doc,
)
from .session import SessionStore

log = logging.getLogger("simtpu.serve")

_TIMEOUTS = REGISTRY.counter("serve.timeouts")
_ERRORS = REGISTRY.counter("serve.errors")
_DRAINING = REGISTRY.gauge("serve.draining")

#: request-body ceiling: bodies buffer in RAM before validation, so an
#: uncapped Content-Length would bypass every admission/memory valve
MAX_BODY_BYTES = 8 << 20


@dataclass
class ServeOptions:
    """Daemon configuration (the `simtpu serve` flags)."""

    host: str = "127.0.0.1"
    port: int = 8090  # 0 = ephemeral; the chosen port is printed/attr
    state_dir: str = ""  # "" = memory-only sessions (no crash recovery)
    max_sessions: int = 8
    queue_depth: int = 64
    default_deadline_s: float = 30.0
    #: extra wall the handler waits past the deadline for the worker's
    #: cooperative partial (a capacity search returns it at the next
    #: candidate boundary) before answering 504 with partial=null
    grace_s: float = 0.5
    coalesce_window_s: float = 0.0
    audit: Optional[bool] = None
    sched_config: str = ""
    extended_resources: Sequence[str] = ()
    #: drain budget on SIGTERM before in-flight work is abandoned
    drain_timeout_s: float = 30.0


class _Httpd(ThreadingHTTPServer):
    daemon_threads = True  # stragglers must not block a forced exit
    app: "SimtpuServer" = None


class SimtpuServer:
    """One daemon instance: session store + batcher + HTTP listener.
    Usable in-process (tests, loadgen) or via `serve_main` (CLI)."""

    def __init__(self, opts: ServeOptions, progress=None):
        self.opts = opts
        self._say = progress or (lambda msg: None)
        self.store = SessionStore(
            state_dir=opts.state_dir,
            max_sessions=opts.max_sessions,
            audit=opts.audit,
            sched_config_path=opts.sched_config,
            extended_resources=opts.extended_resources,
            progress=self._say,
        )
        self.batcher = Batcher(
            self.store,
            queue_depth=opts.queue_depth,
            coalesce_window_s=opts.coalesce_window_s,
        )
        self.httpd: Optional[_Httpd] = None
        self.port: Optional[int] = None
        self.draining = False
        self._t0 = time.monotonic()
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._serve_thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()
        self._shutdown_once = threading.Lock()
        self._shutdown_started = False

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> int:
        """Bind, recover checkpointed sessions, start the worker and the
        accept loop (on a background thread).  Returns the bound port."""
        self.store.recover()
        self.batcher.start()
        self.httpd = _Httpd(
            (self.opts.host, self.opts.port), _Handler
        )
        self.httpd.app = self
        self.port = self.httpd.server_address[1]
        _DRAINING.set(0)
        self._serve_thread = threading.Thread(
            target=self.httpd.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="simtpu-serve-accept",
            daemon=True,
        )
        self._serve_thread.start()
        return self.port

    def request_shutdown(self, reason: str = "shutdown") -> None:
        """Begin a graceful drain (idempotent): stop accepting, let the
        queue and in-flight requests finish, then release the port.  Runs
        on its own thread — callable from a signal handler."""
        with self._shutdown_once:
            if self._shutdown_started:
                return
            self._shutdown_started = True
        self.draining = True
        _DRAINING.set(1)
        self._say(f"serve: draining ({reason})")
        threading.Thread(
            target=self._graceful_stop, name="simtpu-serve-drain",
            daemon=True,
        ).start()

    def _graceful_stop(self) -> None:
        # order matters: the listener stays up through the drain so
        # /healthz + /readyz keep answering (the load-balancer contract —
        # readyz flipped to 503 the moment `draining` was set, and new
        # mutating requests answer 503 Degraded); only once the queue and
        # in-flight requests are done does the accept loop stop and the
        # port release
        budget = self.opts.drain_timeout_s
        t0 = time.monotonic()
        self.batcher.stop(drain=True, timeout=budget)
        with self._inflight_cv:
            while self._inflight > 0:
                left = budget - (time.monotonic() - t0)
                if left <= 0:
                    log.warning(
                        "serve: drain budget exhausted with %d request(s) "
                        "in flight; abandoning them", self._inflight,
                    )
                    break
                self._inflight_cv.wait(timeout=min(left, 0.5))
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
        self._stopped.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until a requested shutdown completed."""
        return self._stopped.wait(timeout)

    def force_stop(self) -> None:
        """Abandon the drain: fail the backlog fast and release the port
        (second-signal path; also the tests' cleanup)."""
        self.draining = True
        _DRAINING.set(1)
        self.batcher.stop(drain=False, timeout=1.0)
        if self.httpd is not None:
            try:
                self.httpd.shutdown()
                self.httpd.server_close()
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass
        self._stopped.set()

    # -- request accounting ------------------------------------------------

    def enter(self) -> None:
        with self._inflight_cv:
            self._inflight += 1

    def leave(self) -> None:
        with self._inflight_cv:
            self._inflight -= 1
            self._inflight_cv.notify_all()

    @property
    def uptime_s(self) -> float:
        return time.monotonic() - self._t0


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server: _Httpd

    # -- plumbing ----------------------------------------------------------

    def log_message(self, fmt, *args):  # noqa: N802 — stdlib signature
        log.debug("serve: %s %s", self.address_string(), fmt % args)

    def _send(self, status: int, doc: dict, retry_after=None) -> None:
        body = json.dumps(doc).encode()
        try:
            self.send_response(int(status))
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after is not None:
                self.send_header(
                    "Retry-After", str(max(int(retry_after), 1))
                )
            self.end_headers()
            self.wfile.write(body)
        except OSError:
            # the client gave up (reset/timeout mid-response): nothing
            # to salvage, and a routine disconnect must NOT escape to the
            # 500 path and masquerade as a daemon bug with a flight
            # bundle behind it
            self.close_connection = True

    def _fail(self, exc: ServeError, context: str) -> None:
        _ERRORS.inc()
        if exc.status == 500:
            # a 500 is a bug report: leave the post-mortem bundle behind
            # (spans + registry + the request context), never raise.
            # 503/504 are deliberately excluded — they are STRUCTURED
            # responses of the taxonomy (load shedding, deadlines), and
            # a deadline-heavy workload must not fill the disk with
            # bundles one routine response at a time
            from ..obs.flight import dump_flight

            dump_flight(
                f"serve {exc.code}: {exc}", exc.status,
                extra={"serve_request": context},
            )
        self._send(exc.status, error_doc(exc), retry_after=exc.retry_after)

    def _body(self) -> dict:
        raw = self._raw_body
        if not raw:
            return {}
        try:
            doc = json.loads(raw.decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as exc:
            raise BadRequest(f"body is not valid JSON: {exc}") from exc
        if not isinstance(doc, dict):
            raise BadRequest("body must be a JSON object")
        return doc

    # -- routing -----------------------------------------------------------

    def _route(self) -> Tuple[str, ...]:
        path = self.path.split("?", 1)[0].rstrip("/")
        return tuple(p for p in path.split("/") if p)

    def _dispatch(self, method: str) -> None:
        app = self.server.app
        parts = self._route()
        context = f"{method} {self.path}"
        # consume the request body UP FRONT, whatever route or error the
        # request hits: protocol_version is HTTP/1.1 (keep-alive), and an
        # error response sent with unread body bytes still in the socket
        # would desync the connection — the leftover bytes would parse as
        # the client's next request line.  Both a malformed and an
        # oversized Content-Length are the client's structured 400 (the
        # connection closes: the body was not, or must not be, read)
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            self.close_connection = True
            self._fail(
                BadRequest("Content-Length must be an integer"), context
            )
            return
        if length > MAX_BODY_BYTES:
            self.close_connection = True
            self._fail(
                BadRequest(
                    f"body too large ({length} bytes; the limit is "
                    f"{MAX_BODY_BYTES})"
                ),
                context,
            )
            return
        self._raw_body = self.rfile.read(length) if length > 0 else b""
        app.enter()
        try:
            with span("serve.request", method=method, path=self.path):
                self._handle(app, method, parts)
        except ServeError as exc:
            self._fail(exc, context)
        except Exception as exc:  # noqa: BLE001 — taxonomy boundary
            log.exception("serve: unhandled error on %s", context)
            self._fail(
                InternalError(f"{type(exc).__name__}: {exc}"), context
            )
        finally:
            app.leave()

    def do_GET(self):  # noqa: N802
        self._dispatch("GET")

    def do_POST(self):  # noqa: N802
        self._dispatch("POST")

    def do_DELETE(self):  # noqa: N802
        self._dispatch("DELETE")

    # -- handlers ----------------------------------------------------------

    def _handle(self, app: SimtpuServer, method: str, parts) -> None:
        if method == "GET" and parts == ("healthz",):
            import jax

            self._send(200, {
                "ok": True,
                "uptime_s": round(app.uptime_s, 3),
                "schema_version": SCHEMA_VERSION,
                # the backend this daemon computes on: a load generator
                # labels its record with it, never with its own guess
                "platform": jax.default_backend(),
            })
            return
        if method == "GET" and parts == ("readyz",):
            if app.draining:
                self._send(
                    503,
                    {"ready": False, "reason": "draining"},
                    retry_after=5,
                )
            else:
                self._send(200, {"ready": True})
            return
        if method == "GET" and parts == ("metrics",):
            self._send(200, {
                "schema_version": SCHEMA_VERSION,
                "metrics": REGISTRY.snapshot(),
            })
            return
        if len(parts) >= 2 and parts[0] == "v1" and parts[1] == "sessions":
            self._sessions(app, method, parts[2:])
            return
        raise NotFound(f"no route {method} {self.path!r}")

    def _sessions(self, app: SimtpuServer, method: str, rest) -> None:
        if app.draining and method != "GET":
            raise Degraded(
                "daemon is draining; retry against the next instance",
                retry_after=5,
            )
        if not rest:
            if method == "GET":
                self._send(200, {"sessions": app.store.list()})
                return
            if method == "POST":
                body = self._body()
                session, created = app.store.create(
                    str(body.get("config", ""))
                )
                self._send(201 if created else 200, session.summary())
                return
            raise NotFound(f"no route {method} /v1/sessions")
        sid = rest[0]
        if len(rest) == 1:
            if method == "GET":
                self._send(200, app.store.get(sid).summary())
                return
            if method == "DELETE":
                app.store.delete(sid)
                self._send(200, {"ok": True, "deleted": sid})
                return
            raise NotFound(f"no route {method} on a session")
        if len(rest) == 2 and method == "POST":
            kind = rest[1]
            if kind not in QUERY_KINDS:
                raise NotFound(
                    f"unknown query kind {kind!r} "
                    f"(one of {', '.join(QUERY_KINDS)})"
                )
            self._query(app, sid, kind, self._body())
            return
        raise NotFound(f"no route {method} {self.path!r}")

    def _query(self, app: SimtpuServer, sid, kind, payload) -> None:
        deadline = payload.pop("deadline_s", None)
        if deadline is None:
            deadline = app.opts.default_deadline_s
        try:
            deadline = float(deadline)
        except (TypeError, ValueError):
            raise BadRequest(
                f"deadline_s must be a number, got {deadline!r}"
            ) from None
        if deadline < 0:
            raise BadRequest("deadline_s must be >= 0")
        session = app.store.get(sid)
        query = Query(
            kind=kind, session=session, payload=payload,
            control=RunControl(deadline=deadline),
        )
        app.batcher.submit(query)  # Overloaded -> 429, Degraded -> 503
        if query.done.wait(timeout=deadline):
            self._complete(query)
            return
        # deadline passed on the wire: ask the in-flight work to stop at
        # its next cooperative boundary, give it `grace_s` to hand back a
        # structured partial, then answer 504 either way — the dispatch
        # finishes off-wire and the daemon is unharmed
        query.control.trigger("deadline")
        done = query.done.wait(timeout=app.opts.grace_s)
        if done and not isinstance(query.error, DeadlineExceeded):
            # the dispatch finished inside the grace window with a REAL
            # outcome (result or a non-deadline error): answer it — a
            # slightly late answer beats a 504 that throws it away
            self._complete(query)
            return
        _TIMEOUTS.inc()
        partial = None
        if isinstance(query.error, DeadlineExceeded):
            partial = query.error.extra.get("partial")
        self._fail(
            DeadlineExceeded(
                f"deadline of {deadline:g}s exceeded",
                extra={"partial": partial, "kind": kind},
            ),
            f"POST /v1/sessions/{sid}/{kind}",
        )

    def _complete(self, query: Query) -> None:
        if query.error is None:
            self._send(200, query.result)
            return
        if isinstance(query.error, DeadlineExceeded):
            _TIMEOUTS.inc()
        err = (
            query.error
            if isinstance(query.error, ServeError)
            else InternalError(str(query.error))
        )
        self._fail(
            err, f"POST {self.path} ({query.kind})"
        )


def serve_main(opts: ServeOptions, progress=None) -> int:
    """Blocking CLI entry: start, print the bound address, run until
    SIGTERM/SIGINT drains (exit 0).  A second signal abandons the drain
    (exit 1)."""
    say = progress or (lambda msg: print(msg, flush=True))
    server = SimtpuServer(opts, progress=say)
    port = server.start()
    say(
        f"simtpu serve: listening on http://{opts.host}:{port} "
        f"(sessions={opts.max_sessions}, queue={opts.queue_depth}, "
        f"deadline={opts.default_deadline_s:g}s, "
        f"state={opts.state_dir or 'memory-only'})"
    )
    hard = {"n": 0}

    def on_signal(signum, frame):
        hard["n"] += 1
        name = signal.Signals(signum).name
        if hard["n"] > 1:
            log.warning("serve: second %s — abandoning drain", name)
            server.force_stop()
            return
        server.request_shutdown(reason=name)

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig, on_signal)
        except ValueError:  # not the main thread (tests)
            break
    try:
        while not server.wait(timeout=0.5):
            pass
    finally:
        for sig, old in prev.items():
            signal.signal(sig, old)
    say("simtpu serve: drained; bye")
    return 0 if hard["n"] <= 1 else 1
