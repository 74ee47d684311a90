"""Persistent-compilation-cache gating tests (`simtpu/cache.py`): the cache
must stay OFF on the CPU backend (the documented XLA:CPU deserialize
segfault), honor the env kill-switch, and say so on stderr either way —
cold-path triage must never have to guess whether the cache was silently
disabled.
"""

from __future__ import annotations

import os

import pytest

import simtpu.cache as cache_mod


def test_cpu_backend_leaves_cache_off(capsys, monkeypatch):
    # the test process runs on the CPU backend (conftest pins it), so the
    # accelerator-only gate must refuse without touching jax.config
    monkeypatch.delenv("SIMTPU_COMPILATION_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    called = []

    import jax

    monkeypatch.setattr(jax.config, "update", lambda *a: called.append(a))
    assert cache_mod.enable_compilation_cache() is None
    assert called == []  # never partially configured
    err = capsys.readouterr().err
    assert "persistent compilation cache off" in err
    assert "CPU backend" in err


@pytest.mark.parametrize("value", ["off", "0"])
def test_env_kill_switch_wins(value, tmp_path, capsys, monkeypatch):
    import jax

    monkeypatch.setenv("SIMTPU_COMPILATION_CACHE", value)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cache_mod.enable_compilation_cache() is None
    err = capsys.readouterr().err
    assert "persistent compilation cache off" in err
    assert f"SIMTPU_COMPILATION_CACHE={value}" in err


@pytest.mark.parametrize("outside", [False, True], ids=["in_checkout", "env_dir"])
def test_accelerator_backend_enables(outside, tmp_path, capsys, monkeypatch):
    """With a non-CPU backend the cache configures and returns its dir (the
    jax.config writes are captured, not applied — this process IS on CPU).
    JAX_COMPILATION_CACHE_DIR, when set, is the cache and is never
    overridden; unset, the fixed in-checkout default is."""
    import jax

    monkeypatch.delenv("SIMTPU_COMPILATION_CACHE", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    want = str(tmp_path / "xla")
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(cache_mod, "_DEFAULT_DIR", want)
    out = cache_mod.enable_compilation_cache()
    assert out == want
    if outside:
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert updates["jax_compilation_cache_dir"] == out
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.5
    assert "persistent compilation cache off" not in capsys.readouterr().err


def test_default_dir_is_fixed_inside_the_checkout():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(cache_mod.__file__)))
    assert cache_mod._DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_turns_an_outside_cache_off(tmp_path, monkeypatch):
    """JAX arms its own cache from JAX_COMPILATION_CACHE_DIR; on the CPU
    backend the refusal must switch it off, not merely skip the setup."""
    import jax

    monkeypatch.delenv("SIMTPU_COMPILATION_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    assert cache_mod.enable_compilation_cache() is None
    assert updates == {"jax_enable_compilation_cache": False}
