"""utils/platform.py: the device facts every record names, the peak MFU
is taken against, and the one compile-cache rule every entry point shares
(serving server, engine CLI, benchmark runner)."""

import os
import sys

import pytest

import k8s_device_plugin_tpu.utils.platform as platform_mod
from k8s_device_plugin_tpu.utils.platform import (
    DEFAULT_COMPILATION_CACHE_DIR,
    enable_compilation_cache,
    peak_bf16_flops,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


def test_v5e_device_kind_has_a_peak():
    # "TPU v5 lite" is what the v5e reports (chip run, PR 21).
    assert peak_bf16_flops(_Device("TPU v5 lite")) == 197e12
    assert peak_bf16_flops(_Device("TPU v5p")) == 459e12


def test_unknown_device_kind_raises_where_mfu_is_asked_for():
    """An MFU against a guessed peak is worse than none, and a silently
    missing one hides a stale table: the call that wants the peak fails."""
    with pytest.raises(ValueError, match="TPU v9"):
        peak_bf16_flops(_Device("TPU v9"))
    with pytest.raises(ValueError, match="device_kind"):
        peak_bf16_flops(_Device("cpu"))


def test_device_facts_are_what_jax_reports():
    import jax

    facts = platform_mod.device_facts()
    assert facts == {
        "platform": "cpu",
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }


def test_debug_state_names_the_backend(shared_engine):
    """/debug/state's engine block says which backend the replica serves
    from — a server that came up on the CPU can no longer do so silently."""
    _, _, eng = shared_engine
    state = eng.debug_state()
    assert {
        k: state[k] for k in ("platform", "device_kind", "device_count")
    } == platform_mod.device_facts()


# ---------------------------------------------------------------- comp cache


class _FakeConfig:
    def __init__(self):
        self.calls = {}

    def update(self, key, value):
        self.calls[key] = value


def _rule(monkeypatch, env_value):
    fake = _FakeConfig()

    class _FakeJax:
        config = fake

    if env_value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_value)
    monkeypatch.setitem(sys.modules, "jax", _FakeJax)
    logs = []
    used = enable_compilation_cache(log=logs.append)
    return used, fake.calls, logs


def test_cache_dir_from_the_environment_is_not_set_in_code(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, the program
    sets no directory (only the entry-size filter)."""
    used, calls, logs = _rule(monkeypatch, "/some/dir")
    assert used == "/some/dir"
    assert "jax_compilation_cache_dir" not in calls
    assert calls == {"jax_persistent_cache_min_compile_time_secs": 1.0}
    assert logs == ["persistent compilation cache at /some/dir"]


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    """Unset: <checkout>/.jax_cache, derived from the package's own
    location — the same path from every entry point and every cwd."""
    used, calls, _ = _rule(monkeypatch, None)
    assert used == os.path.join(REPO_ROOT, ".jax_cache")
    assert calls["jax_compilation_cache_dir"] == used
    assert used == DEFAULT_COMPILATION_CACHE_DIR


def test_cache_dir_never_comes_from_tmp_pid_or_clock(monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)
    first, _, _ = _rule(monkeypatch, None)
    second, _, _ = _rule(monkeypatch, None)
    assert first == second
    assert not first.startswith(("/tmp", "/var/tmp"))
    assert str(os.getpid()) not in first


def _cache_run(cache_dir):
    """Run a tiny jitted program in a fresh process with
    JAX_COMPILATION_CACHE_DIR pointing at ``cache_dir``; returns entry
    count after."""
    import subprocess

    code = (
        "import jax, jax.numpy as jnp\n"
        "from k8s_device_plugin_tpu.utils.platform import "
        "enable_compilation_cache\n"
        "enable_compilation_cache(min_compile_seconds=0.0)\n"
        "x = jnp.ones((64, 64), jnp.float32)\n"
        "print(float(jax.jit(lambda a: (a @ a) * 1.61803).lower(x)"
        ".compile()(x).sum()))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    entries = [
        f for f in os.listdir(cache_dir)
        if not f.startswith(".")
    ]
    return len(entries)


def test_compilation_cache_persists_and_reuses(tmp_path):
    """The serving cold-start lever: a first process writes cache entries
    where the environment variable says; an identical second process
    reuses them (same computation key -> no new entry), which is what
    lets a liveness-restarted pod skip its recompiles."""
    cache = tmp_path / "xla-cache"
    first = _cache_run(cache)
    assert first > 0, "no cache entries written"
    second = _cache_run(cache)
    assert second == first, (
        f"second run changed the entry count ({first} -> {second}): "
        "the computation was recompiled, not reused"
    )
