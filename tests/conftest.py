"""Test configuration.

Forces JAX onto a virtual 8-device CPU backend BEFORE jax is imported anywhere,
so sharding/mesh tests exercise real multi-device paths without TPU hardware.
"""

import os
import sys

# Force, don't setdefault: the chip machine exports JAX_PLATFORMS=tpu,cpu
# to every process, and tests must never grab the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Repo root on sys.path so `import k8s_device_plugin_tpu` works without install.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Test tiers.  The hermetic plugin/protocol tier (no JAX imports, no XLA
# compiles — pure gRPC/filesystem/threading) is auto-marked `plugin` so the
# ~2-minute kubelet-facing signal is runnable without the multi-minute
# model/engine compile grind:
#
#     python -m pytest tests/ -q -m "plugin and not slow"   # fast tier
#     python -m pytest tests/ -q -m "not plugin"            # JAX tier
#
PLUGIN_TIER_FILES = {
    "test_attribution.py",
    "test_cli.py",
    "test_codelint.py",
    "test_controller.py",
    "test_discovery.py",
    "test_envs.py",
    "test_health.py",
    "test_manager.py",
    "test_native.py",
    "test_postmortem.py",
    "test_prober.py",
    "test_protocol.py",
    "test_resources.py",
    "test_router.py",
    "test_selftest.py",
    "test_server.py",
    "test_spans.py",
    "test_stress.py",
    "test_topology.py",
    "test_trace_assemble.py",
    "test_watcher.py",
}


# Chaos scenario files MUST collect-but-deselect under tier-1 (`-m 'not
# slow'`): the scenario suite drives multi-node fleets, loaded engines,
# and router fleets for minutes, and `--dist loadfile` puts a whole file
# on one worker — ONE unmarked scenario file leaking into tier-1 would
# hold that worker past the budget below.  The guard fails COLLECTION
# (every run, not just tier-1) the moment a chaos test is missing the
# `slow` marker.  Any file named test_chaos_*.py is guarded (the router
# scenarios of ISSUE 8 ride the same file today; a future split-out file
# is auto-covered).
CHAOS_SCENARIO_FILES = {"test_chaos_scenarios.py"}


def _is_chaos_file(base: str) -> bool:
    return base in CHAOS_SCENARIO_FILES or base.startswith("test_chaos_")


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    for item in items:
        base = os.path.basename(str(item.fspath))
        if base in PLUGIN_TIER_FILES:
            item.add_marker(_pytest.mark.plugin)
        if _is_chaos_file(base) and not any(
            m.name == "slow" for m in item.iter_markers()
        ):
            raise _pytest.UsageError(
                f"{item.nodeid}: chaos scenarios must carry the `slow` "
                "marker (module-level `pytestmark = pytest.mark.slow`) so "
                "tier-1 deselects them — fleet simulations run for minutes"
            )
        if base == "test_codelint.py" and not any(
            m.name == "plugin" for m in item.iter_markers()
        ):
            # The static-analyzer suite is jax-free AST work and MUST
            # stay in the fast plugin tier: it is the whole-repo
            # contract gate (tools/codelint), and `-m 'plugin and not
            # slow'` is where builder sessions expect it to run.
            raise _pytest.UsageError(
                f"{item.nodeid}: test_codelint.py must carry the "
                "`plugin` marker (PLUGIN_TIER_FILES keeps it in the "
                "fast jax-free tier)"
            )


# ---------------------------------------------------------------------------
# Tier-1 wall-clock budget guard.  The driver runs tier-1 as
# /root/TESTS_LAST_RUN.json `commands` gives it: `-m 'not slow'` over six
# xdist workers, `--dist loadfile` (a whole file on one worker), under
# `timeout 1470`; a run the timeout cuts counts only as far as it got.
# The wall clock is therefore the busiest worker's, and a file that grows
# only surfaces as a timeout kill (no report, no culprit).  This hook
# prints the run's wall clock against the budget on EVERY run and fails
# the run with a clear message once it crosses the soft threshold
# (900 s of the 1,470), so drift is visible while there is still room to
# fix it.  Override with TIER1_WALL_BUDGET_S (0 disables the failure, the
# report stays).
# ---------------------------------------------------------------------------

_TIER1_TIMEOUT_S = 1470.0
_TIER1_BUDGET_S = 900.0
_tier1_t0 = None
# Budget attribution: wall clock split plugin-tier vs jax/engine-tier so
# a future over-budget run names which side grew (session-fixture
# compiles accrue to the first test that triggers them).
_tier_seconds = {"plugin": 0.0, "jax": 0.0}


def _tier1_budget_s() -> float:
    try:
        return float(os.environ.get("TIER1_WALL_BUDGET_S", _TIER1_BUDGET_S))
    except ValueError:
        return _TIER1_BUDGET_S


def pytest_sessionstart(session):
    global _tier1_t0
    import time

    _tier1_t0 = time.monotonic()


def pytest_runtest_logreport(report):
    tier = (
        "plugin"
        if os.path.basename(str(report.fspath)) in PLUGIN_TIER_FILES
        else "jax"
    )
    _tier_seconds[tier] += getattr(report, "duration", 0.0) or 0.0


def pytest_sessionfinish(session, exitstatus):
    import time

    if _tier1_t0 is None:
        return
    elapsed = time.monotonic() - _tier1_t0
    budget = _tier1_budget_s()
    if budget > 0 and elapsed > budget and exitstatus == 0:
        # Turn an otherwise-green over-budget run into a failure NOW,
        # while there is still headroom to the hard timeout; a red run
        # keeps its own status (the budget message still prints below).
        session.exitstatus = 1


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    import time

    if _tier1_t0 is None:
        return
    elapsed = time.monotonic() - _tier1_t0
    budget = _tier1_budget_s()
    terminalreporter.write_line(
        f"tier-1 wall clock: {elapsed:.0f}s of the {_TIER1_TIMEOUT_S:.0f}s "
        f"driver timeout (soft budget {budget:.0f}s, "
        f"headroom {budget - elapsed:+.0f}s)"
    )
    terminalreporter.write_line(
        f"tier-1 split: plugin tier {_tier_seconds['plugin']:.0f}s, "
        f"jax/engine tier {_tier_seconds['jax']:.0f}s (session-fixture "
        "compiles accrue to the first test that triggers them)"
    )
    if budget > 0 and elapsed > budget:
        terminalreporter.write_line(
            f"FAILED: suite wall clock {elapsed:.0f}s exceeded the "
            f"{budget:.0f}s soft budget of the {_TIER1_TIMEOUT_S:.0f}s "
            "driver timeout.  Under `--dist loadfile` the wall clock is "
            "the longest file's: split that file, reuse the "
            "session-scoped `shared_engine` fixture (tests/conftest.py) "
            "instead of compiling new engines, or raise "
            "TIER1_WALL_BUDGET_S deliberately.",
            red=True,
        )


# ---------------------------------------------------------------------------
# Shared compiled serving-engine fixture.  Tests that only exercise
# host-side step-loop scheduling (the overlap pipeline suite) do NOT
# compile their own engines — they share this ONE instance and its
# jitted step/prefill programs.  Safe to share because the engine drains
# to idle between runs, and the overlap knob (``eng._overlap_steps``)
# selects host-side scheduling over the SAME compiled programs, not a
# new program.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


@pytest.fixture
def plugin_dir():
    """A kubelet device-plugin directory for unix sockets.  A socket path
    holds at most 107 characters, and pytest's `tmp_path` spends most of
    them on its run number, the xdist worker and the test's name; the
    kubelet's real directory is short, so this one is too."""
    import tempfile

    with tempfile.TemporaryDirectory(
        prefix="dp", dir="/tmp", ignore_cleanup_errors=True
    ) as path:
        yield path


@pytest.fixture(scope="session")
def shared_engine():
    """(cfg, params, engine): one compiled tiny engine, racecheck on so
    the overlap dispatch/consume handoff runs under the OwnerGuard."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from k8s_device_plugin_tpu.models.engine import ServingEngine
    from k8s_device_plugin_tpu.models.transformer import (
        GPTConfig,
        PagedConfig,
        TransformerLM,
    )

    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    paged = PagedConfig(page_size=4, num_pages=32, max_pages_per_seq=8)
    return cfg, params, ServingEngine(
        cfg, params, paged, max_slots=2, racecheck=True
    )
