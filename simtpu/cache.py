"""Persistent XLA compilation cache.

The reference pays no compilation cost (a Go binary is ahead-of-time
compiled); simtpu's cold path is XLA-compile-dominated — the north-star
first run costs ~2 minutes of compilation against a ~10 s warm run, and the
one-shot CLI user (`simtpu apply`, the reference's only UX,
`pkg/apply/apply.go:88`) always pays cold. Wiring JAX's persistent
compilation cache lets a fresh process reuse executables compiled by any
earlier run on the same machine/topology, collapsing cold → warm + a few
seconds of cache reads.

The planner's shape bucketing (`plan/incremental.py`, `engine/rounds.py
RoundsEngine.snap_shapes`) is the other half of the cold-path attack: probe
and verify executables are padded into the same deterministic shape buckets
on every run, so a cold `simtpu apply` finds the whole probe sweep's
round/scan bodies already in this cache instead of compiling
per-candidate-size specializations the previous process never produced.

Enabled by default for the CLI, the bench and `chip_smoke.py`. Where the
cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory, which JAX reads
  itself; nothing here overrides it.
- otherwise one fixed path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored). The path is part of the cache key, so it never depends
  on the home directory, a temp name, a pid or the time.
- ``SIMTPU_COMPILATION_CACHE=0``/``off`` disables the cache (`make
  bench-cold` measures the cold path that way).
- cache entries are written for every compilation taking >= 0.5 s (the
  engine's scan/round bodies all cost seconds to compile; tiny dispatches
  stay out of the cache).

Call :func:`enable_compilation_cache` BEFORE the first jit dispatch —
config flags apply to compilations that happen after the call.

ACCELERATOR BACKENDS ONLY: on the CPU backend the cache is left off —
jax 0.9.0's XLA:CPU ahead-of-time executable loader records compile-time
machine features that this host's runtime detection doesn't re-derive
(`+prefer-no-gather` etc.), and deserializing such an entry SEGFAULTS the
process (observed killing the test suite mid-run). CPU compiles are cheap
anyway; the 2-minute cold path the cache exists for is the TPU one.
"""

from __future__ import annotations

import os
import sys

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
_OFF_VALUES = ("0", "off", "false", "none", "no", "disabled")


def _skip_note(reason: str) -> None:
    """One stderr line whenever the persistent cache stays off — a silently
    disabled cache looks exactly like a slow cold path, and cold-path
    triage should never have to guess which one it is."""
    print(f"simtpu: persistent compilation cache off ({reason})", file=sys.stderr)


def enable_compilation_cache() -> str | None:
    """Turn JAX's persistent compilation cache on (see the module docstring
    for where it lives). Returns the cache directory, or None when
    disabled — via SIMTPU_COMPILATION_CACHE=0/off or because the backend
    is CPU; every disabled exit says so on stderr."""
    import jax

    from .obs.profile import install_jit_listener

    # every caller reaches here before its first compile: the one place
    # the compile-event listener is registered (obs/profile.py)
    install_jit_listener()
    env = os.environ.get("SIMTPU_COMPILATION_CACHE", "")
    if env.lower() in _OFF_VALUES:
        _skip_note(f"SIMTPU_COMPILATION_CACHE={env}")
        return None
    outside_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    try:
        if jax.default_backend() == "cpu":
            # ACCELERATOR ONLY — the XLA:CPU deserialize segfault (module
            # docstring); an outside JAX_COMPILATION_CACHE_DIR would arm
            # JAX's own cache, so the refusal switches it off explicitly
            if outside_dir:
                jax.config.update("jax_enable_compilation_cache", False)
            _skip_note("CPU backend: the XLA:CPU executable loader "
                       "segfaults on cache deserialization")
            return None
    except RuntimeError as exc:
        _skip_note(f"backend probe failed: {exc}")
        return None
    cache_dir = outside_dir or _DEFAULT_DIR
    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        # cache regardless of executable size (the default also caches
        # everything; pinned for stability across jax versions)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if not outside_dir:
            # the dir flag LAST: it alone activates the cache, so a partial
            # failure above leaves the cache fully off and the None return
            # honest
            jax.config.update("jax_compilation_cache_dir", cache_dir)
    except OSError as exc:  # cache is an optimization — never fail the run
        _skip_note(f"setup failed: {exc}")
        return None
    return cache_dir
