"""JAX profiler integration (ISSUE 8): `--profile DIR` device traces whose
annotation vocabulary matches the span tracer's.

`jax.profiler.trace(DIR)` captures the XLA-level timeline (device kernels,
host callbacks, transfers) into a TensorBoard-loadable log dir.  On its
own that timeline names HLO modules, not simtpu phases; the bridge here
makes every `obs.span(...)` opened while a capture is live ALSO emit a
`jax.profiler.TraceAnnotation` with the same name, so the device profile
and the Perfetto span trace line up on one vocabulary ("scan.chunk",
"plan.probes", "aot.compile", ...).

Entry points:
- `profile_capture(dir)` — context manager: starts the jax profiler
  capture (Python tracer off, host tracer at level 1: the default options
  trace every Python call, too slow for a 150,000-pod answer), arms the
  span tracer if it was off (annotations ride spans), records a clock
  anchor, installs the annotation bridge, and tears all of it down on
  exit.  `dir=None/""` is a no-op nullcontext, so call sites stay
  unconditional.
- `install_jit_listener()` — once per process (simtpu/cache.py calls it
  before the first compile): JAX's compile duration events feed the
  `jit.*_s` registry histograms always, and `jit.*` spans while tracing.
- CLI: `simtpu apply/resilience/fuzz --profile DIR` (SIMTPU_PROFILE=DIR
  is the env equivalent — note this REPLACES the pre-ISSUE-8 meaning of
  SIMTPU_TRACE, which now arms the span tracer).

The import of jax is deferred into the context manager: `simtpu.obs` must
stay importable (and the tracer usable) in tooling that never touches
jax, e.g. tools/run_tests.py's trace aggregation.
"""

from __future__ import annotations

import contextlib
import logging

from . import trace as _trace
from .metrics import REGISTRY

log = logging.getLogger("simtpu.obs")

#: jax 0.9 monitoring duration events -> span names; each feeds the
#: histogram `<name>_s`.  The cache load nests inside `jit.compile`
#: (pxla wraps compile_or_get_cached in the backend-compile timer), so
#: it is reported beside the compile total, never added to it.
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
#: the histograms whose totals are compile work (trace + lower + compile)
JIT_WORK = ("jit.trace_s", "jit.lower_s", "jit.compile_s")

_LISTENING = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = JIT_EVENTS.get(event)
    if name is None:
        return
    REGISTRY.histogram(name + "_s").observe(duration_secs)
    if _trace.enabled():
        fun = kwargs.get("fun_name")
        if fun is None:
            _trace.complete(name, duration_secs)
        else:
            _trace.complete(name, duration_secs, fun=str(fun))


def install_jit_listener() -> None:
    """Register the compile-event listener, once per process."""
    global _LISTENING
    if _LISTENING:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENING = True


@contextlib.contextmanager
def profile_capture(log_dir: str):
    """Capture a jax.profiler trace under `log_dir` for the body's
    duration, with span-named TraceAnnotations.  Empty/None dir = no-op.
    A profiler that fails to start (unsupported backend, dir not
    writable) logs ONE warning and runs the body unprofiled — profiling
    must never take the run down."""
    if not log_dir:
        yield False
        return
    try:
        import jax
    except Exception as exc:  # noqa: BLE001 - jax-free tooling contexts
        log.warning("--profile ignored (jax unavailable: %s)", exc)
        yield False
        return
    was_tracing = _trace.enabled()
    started = False
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        started = True
    except Exception as exc:  # noqa: BLE001 - loud no-op, by contract
        log.warning(
            "jax profiler capture under %r failed to start (%s: %s); "
            "the run continues unprofiled",
            log_dir, type(exc).__name__, exc,
        )
    if started:
        if not was_tracing:
            # annotations ride spans — a profile without the span tracer
            # armed would capture an unannotated timeline
            _trace.enable()
        _trace.anchor()
        _trace._ANNOTATION_FACTORY = jax.profiler.TraceAnnotation
    try:
        yield started
    finally:
        if started:
            _trace._ANNOTATION_FACTORY = None
            try:
                jax.profiler.stop_trace()
            except Exception as exc:  # noqa: BLE001
                log.warning("jax profiler stop failed: %s", exc)
            if not was_tracing:
                _trace.disable()
