"""Cold-start pipeline: parallel AOT precompilation of the engine's jit
executables.

The reference pays zero compile cost (`simon apply` is an AOT-compiled Go
binary, `pkg/apply/apply.go:88`); simtpu's cold path is XLA-compile-dominated
— each scan/round body costs seconds to compile and, without this module,
those compiles serialize one-by-one at the moment each shape is first
dispatched.  PR 1 made every executable shape deterministic (the pow2 chunk
plans, `RoundsEngine.snap_shapes` bucketing), which is exactly the
precondition for compiling them *ahead of and in parallel with* the host
work:

1. ENUMERATE: as soon as tensorization fixes the shape buckets, walk the
   same deterministic chunk plans the dispatch path will walk
   (`scan.plan_scan_chunks`, `RoundsEngine._segments`/`_chunk_runs`/
   `_chunk_shape`) and derive the abstract (shape, dtype) signature of every
   distinct jit callable the run will need — scan bodies, bulk round bodies,
   quota/matrix variants, sharded variants.
2. COMPILE IN PARALLEL: drive `jit(...).lower(...).compile()` for each on a
   background thread pool.  XLA releases the GIL during compilation, so the
   compiles overlap each other (multi-core hosts / backend compile servers)
   and the host-side work that precedes the first dispatch.
3. REGISTER: finished executables land in the pipeline's registry keyed by
   the exact dispatch signature; `Engine._scan_call` /
   `RoundsEngine._bulk_call(_sliced)` consult the registry first, so first
   dispatch finds the executable warm.  (In jax 0.4.x an AOT
   `lower().compile()` does NOT warm the jit function's own dispatch cache —
   tracing is shared, compilation is not — so the registry holds the
   `jax.stages.Compiled` objects and calls them directly.)

Race pinning (tested in tests/test_precompile.py):

- A dispatch whose signature has an IN-FLIGHT background compile blocks on
  that future and then calls the one finished executable — background
  compile and eager first dispatch can never produce two executables for
  one signature, and the registry holds at most one entry per key by
  construction (lock-guarded submit).
- A dispatch whose signature was never enumerated (data-dependent leftover
  probe shapes, snap fallbacks) misses the registry and takes the plain jit
  path — exactly yesterday's behavior.
- A failed background compile (AOT lowering unsupported on a backend, OOM,
  ...) is LOUD: one warning per executable names the failure, and the
  dispatch falls back to the jit path, which compiles as if the pipeline
  never existed.  Placements are bit-identical with the pipeline on or off
  in every case — the pipeline changes when and where compilation happens,
  never what executes.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from ..obs.metrics import REGISTRY
from ..obs.trace import adopt, current, span

log = logging.getLogger("simtpu.precompile")


def tree_sig(tree) -> tuple:
    """Hashable (treedef, ((shape, dtype), ...)) signature of an argument
    pytree.  Dtypes are canonicalized the way jit canonicalizes its inputs
    (64-bit narrowing under the default x64-off config), so a host numpy
    array and the ShapeDtypeStruct that enumerated it agree."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        (tuple(np.shape(leaf)), jax.dtypes.canonicalize_dtype(leaf.dtype).name)
        for leaf in leaves
    )


def _as_sds(tree):
    """Map a pytree of concrete arrays (or SDS) to ShapeDtypeStructs with
    jit-canonicalized dtypes."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            tuple(np.shape(x)), jax.dtypes.canonicalize_dtype(x.dtype)
        ),
        tree,
    )


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(
        tuple(shape), jax.dtypes.canonicalize_dtype(dtype)
    )


# public names for out-of-package enumerators (the fault sweep registers
# its scenario-batched executable under these, simtpu/faults/sweep.py)
as_sds = _as_sds
sds = _sds


def state_sds(tensors):
    """The SchedState signature a fresh engine carries for `tensors`,
    derived from build_state ITSELF via jax.eval_shape (tracing its
    empty-log path allocates nothing) — definitionally in sync with
    engine/state.py, so a future state-field change cannot silently
    desynchronize the enumerated signatures from the real dispatches."""
    import jax

    from .state import build_state

    r = tensors.alloc.shape[1]
    return jax.eval_shape(
        lambda: build_state(
            tensors,
            np.zeros(0, np.int32),
            np.zeros(0, np.int32),
            np.zeros((0, r), np.float32),
            None,
        )
    )


class _Job:
    __slots__ = ("future", "seconds", "warned")

    def __init__(self):
        self.future = None
        self.seconds = 0.0
        self.warned = False


class AotPipeline:
    """Registry of background-AOT-compiled executables keyed by dispatch
    signature, plus the thread pool that fills it.

    One pipeline can be SHARED by several engines (the incremental planner
    hands one to its base, probe and verify engines the way it shares the
    bulk-shape registry): keys are pure (callable identity, static config,
    argument shapes) signatures, so engines over the same tensors
    deduplicate naturally."""

    def __init__(self, workers: Optional[int] = None):
        if workers is None:
            workers = min(8, max(2, os.cpu_count() or 2))
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="simtpu-aot"
        )
        self._lock = threading.Lock()
        self._jobs: dict = {}
        self._hits = 0
        self._misses = 0
        self._failures = 0
        self._done = 0
        self._compile_serial = 0.0
        self._t0 = None
        self._t_last = None

    # -- background side ---------------------------------------------------

    def submit(self, name, static_tail, fn, args_sds) -> bool:
        """Queue one AOT compile of `fn.lower(*args_sds, *static_tail)`.
        Returns False (and does nothing) when the signature is already
        queued or finished — at most one executable per key ever exists."""
        key = (name, static_tail, tree_sig(args_sds))
        with self._lock:
            if key in self._jobs:
                return False
            job = _Job()
            self._jobs[key] = job
            if self._t0 is None:
                self._t0 = time.perf_counter()
            # the span that caused this compile, taken at submit: the pool
            # thread's compile span names it as parent and root
            job.future = self._pool.submit(
                self._compile, job, name, fn, args_sds, static_tail,
                current(),
            )
        return True

    def _compile(self, job, name, fn, args_sds, static_tail, cause):
        t0 = time.perf_counter()
        # per-signature compile span ON the pool thread: the Perfetto view
        # shows the compile lanes overlapping the dispatch lane — the
        # pipelining win (and any straggler signature) made visible
        with adopt(cause), span("aot.compile", sig=str(name)):
            compiled = fn.lower(*args_sds, *static_tail).compile()
        job.seconds = time.perf_counter() - t0
        with self._lock:
            self._done += 1
            self._compile_serial += job.seconds
            self._t_last = time.perf_counter()
        return compiled

    # -- dispatch side -----------------------------------------------------

    def call(self, name, static_tail, args, fallback):
        """Run one dispatch through the registry: a finished executable is
        called directly, an in-flight compile is awaited first (one
        executable per signature, never two), an unknown signature or a
        failed compile falls back to the plain jit path — the failure is
        warned ONCE per executable, never swallowed silently."""
        key = (name, static_tail, tree_sig(args))
        job = self._jobs.get(key)
        if job is None:
            with self._lock:
                self._misses += 1
            return fallback()
        try:
            compiled = job.future.result()
        except Exception as exc:  # noqa: BLE001 — loud fallback, by contract
            with self._lock:
                first = not job.warned
                job.warned = True
                self._failures += 1
            # process-wide: a compile the device's compiler refused must
            # stay visible after the pipeline that hid it is gone
            REGISTRY.counter("aot.failures").inc()
            if first:
                log.warning(
                    "AOT precompile of %r failed (%s: %s); falling back to "
                    "plain jit dispatch for this executable",
                    name, type(exc).__name__, exc,
                )
            return fallback()
        with self._lock:
            self._hits += 1
        return compiled(*args)

    # -- lifecycle / observability ----------------------------------------

    def wait_all(self, timeout: Optional[float] = None) -> None:
        """Block until every queued compile settles (used by the bench's
        compile-wall accounting; dispatch never needs it)."""
        from concurrent.futures import wait

        with self._lock:
            futures = [j.future for j in self._jobs.values()]
        wait(futures, timeout=timeout)

    def stats(self) -> dict:
        """submitted/done/hits/misses/failures plus the two compile
        timings the bench reports: `compile_wall_s` (first submit → last
        completion — the pipelined cost) and `compile_serial_s` (sum of
        per-executable compile seconds — what serializing them would have
        cost; wall < serial is the overlap win)."""
        with self._lock:
            wall = 0.0
            if self._t0 is not None:
                wall = (self._t_last or time.perf_counter()) - self._t0
            return {
                "submitted": len(self._jobs),
                "done": self._done,
                "hits": self._hits,
                "misses": self._misses,
                "failures": self._failures,
                "compile_serial_s": self._compile_serial,
                "compile_wall_s": wall,
            }

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


# -- shape enumeration -------------------------------------------------------


def _pods_sds(pods, rows: int):
    """SDS tuple for a pod-tuple slice padded to `rows` (the layout of
    scan.build_pod_arrays, shared by scan segments and bulk
    representatives)."""
    return tuple(_sds((rows,) + arr.shape[1:], arr.dtype) for arr in pods)


def _plan_scan_jobs(
    pipe: AotPipeline, engine, tensors, st_sds, state_tree, pods,
    groups: np.ndarray, flags, pods_rows=None,
) -> None:
    """Enumerate + submit the scan AND wavefront executables
    `run_scan_chunked` will dispatch for `groups` — the same chunk plan
    (incl. its wavefront sub-plan), turned into signatures.  `pods_rows`
    is the host pod-tuple slice aligned with `groups` (defaults to `pods`
    whole) — the wavefront eligibility mask reads its pins/demands."""
    from .scan import (
        _pow2_up,
        _sliced_statics_fields,
        flatten_wave_segments,
        plan_scan_chunks,
        wave_eligibility,
        wave_static_spec,
    )

    if groups.shape[0] == 0:
        return
    n = state_tree.cnt_match.shape[1]
    t_cap = st_sds.g_terms.shape[1]
    name, fn, tail = engine._aot_scan(flags)
    wave_ok = None
    if getattr(engine, "speculate", False):
        wave_ok = wave_eligibility(
            pods if pods_rows is None else pods_rows, groups, tensors
        )
    for c0, c1, gs_p, rows_p, waves in plan_scan_chunks(
        groups, tensors, flags, wave_ok=wave_ok
    ):
        eff = st_sds
        if gs_p is not None:
            fields = _sliced_statics_fields(st_sds, rows_p)
            eff = eff._replace(**{
                f: _sds(
                    (len(gs_p),) + getattr(st_sds, f).shape[1:],
                    getattr(st_sds, f).dtype,
                )
                for f in fields
            })
            if rows_p is not None:
                eff = eff._replace(
                    g_terms=_sds((len(gs_p), t_cap), np.int32)
                )
        state_c = state_tree
        if rows_p is not None:
            r = len(rows_p)
            eff = eff._replace(
                term_topo=_sds((r,), np.int32),
                ip_of=_sds((r,), np.int32),
            )
            state_c = state_c._replace(
                cnt_match=_sds((r, n), np.float32),
                cnt_total=_sds((r,), np.float32),
            )
        for kind, a, b, w_mode in flatten_wave_segments(c0, c1, waves):
            seg = _pods_sds(pods, _pow2_up(b - a))
            if kind == "wave":
                w_name, w_fn, w_tail = engine._aot_wave(
                    flags,
                    wave_static_spec(tensors, w_mode[0], w_mode[1], w_mode[2]),
                )
                pipe.submit(w_name, w_tail, w_fn, (eff, state_c, seg))
            else:
                pipe.submit(name, tail, fn, (eff, state_c, seg))


def _plan_bulk_jobs(
    pipe: AotPipeline, engine, tensors, batch, st_sds, state_tree, pods,
    flags,
) -> None:
    """Enumerate + submit every executable a RoundsEngine `place(batch)`
    will dispatch: bulk round bodies per (variant, shape bucket) — walking
    `_chunk_shape` in dispatch order so the shape registry it seeds is
    exactly the one the dispatches later snap into — and the serial-scan
    bodies of the interleaved scan segments.  Leftover-probe shapes are
    data-dependent and stay on the plain jit path (registry misses)."""
    segments = engine._segments(batch, tensors)
    groups = np.asarray(batch.group)
    g_terms_shape = engine._host_term_maps(tensors)[0].shape
    idx = 0
    while idx < len(segments):
        kind, a, b = segments[idx]
        if kind == "scan":
            _plan_scan_jobs(
                pipe, engine, tensors, st_sds, state_tree, pods,
                groups[a:b], flags,
                pods_rows=tuple(np.asarray(p)[a:b] for p in pods),
            )
            idx += 1
            continue
        # the SAME stretch-group + chunk walk the dispatcher runs
        # (engine._stretch_group/_group_work_items) — shared code, so the
        # enumerated signatures cannot drift from the dispatched ones
        group_runs, idx = engine._stretch_group(segments, idx)
        for chunk, rows_p, quota, self_aff, ext_mats in (
            engine._group_work_items(group_runs, batch, tensors)
        ):
            s_pad, k_cap, rows_p = engine._chunk_shape(
                chunk, rows_p, tensors, flags, quota, self_aff, ext_mats
            )
            seg = _pods_sds(pods, s_pad)
            ks = _sds((s_pad,), np.int32)
            if rows_p is None:
                name, fn, tail = engine._aot_bulk(
                    tensors.n_domains, k_cap, flags, quota, self_aff,
                    ext_mats,
                )
                pipe.submit(name, tail, fn, (st_sds, state_tree, seg, ks))
            else:
                r = len(rows_p)
                name, fn, tail = engine._aot_bulk_sliced(
                    tensors.n_domains, k_cap, flags, quota, self_aff,
                    ext_mats,
                )
                args = (
                    st_sds, state_tree, _sds((r,), np.int32),
                    _sds(g_terms_shape, np.int32), _sds((r,), np.int32),
                    _sds((r,), np.int32), seg, ks,
                )
                pipe.submit(name, tail, fn, args)


def precompile_place(
    engine, batch, pipeline: Optional[AotPipeline] = None,
    workers: Optional[int] = None,
) -> AotPipeline:
    """Enumerate every jit executable `engine.place(batch)` will dispatch
    and queue their AOT compiles on the pipeline's thread pool; attaches
    the pipeline to the engine so the dispatches find the executables (or
    wait on their in-flight compiles).  Returns the pipeline — pass it
    back in for later batches/engines to share the registry.

    Cheap and side-effect-compatible by construction: the enumeration runs
    the same host-side planning the dispatch path runs (freeze, flags,
    segment/chunk plans, shape-bucket registration) and touches no device
    state beyond the memoized statics transfer `place()` would pay anyway.
    """
    from .rounds import RoundsEngine
    from .scan import build_pod_arrays, flags_from, statics_from

    pipe = pipeline if pipeline is not None else AotPipeline(workers)
    engine.pipeline = pipe
    with span("aot.enumerate", pods=len(batch.group)):
        tensors = engine.tensorizer.freeze()
        statics = statics_from(tensors, engine.sched_config)
        flags = flags_from(tensors, batch.ext)
        _, pods = build_pod_arrays(batch, tensors.alloc.shape[1])
        st_sds, state_tree = engine._precompile_shapes(
            _as_sds(statics), state_sds(tensors)
        )
        if isinstance(engine, RoundsEngine):
            _plan_bulk_jobs(
                pipe, engine, tensors, batch, st_sds, state_tree, pods, flags
            )
        else:
            _plan_scan_jobs(
                pipe, engine, tensors, st_sds, state_tree, pods,
                np.asarray(batch.group), flags,
            )
    return pipe
