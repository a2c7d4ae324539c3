"""End-to-end preemption/resume through the real benchmark runner.

Checkpoint machinery existed but no workload entry point
took a checkpoint dir, so the preemption-resume flow (BASELINE config 5's
health-check-preemption Job) was never exercised end to end.  These tests run
`models/benchmark.py` as a subprocess — the same command the benchmark pods
run — kill it mid-training, restart with --resume, and assert it continues
from the saved step instead of step 0.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_CMD = [
    sys.executable,
    "-m",
    "k8s_device_plugin_tpu.models.benchmark",
    "--model",
    "gpt",
    "--tiny",
    "--batch-size",
    "4",
    "--seq-len",
    "32",
    "--warmup",
    "1",
]


def _env():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "")
    # Single CPU device is enough and compiles fastest.
    env["XLA_FLAGS"] = (
        env["XLA_FLAGS"].replace("--xla_force_host_platform_device_count=8", "")
        + " --xla_force_host_platform_device_count=1"
    ).strip()
    return env


def _run(extra, timeout=240):
    proc = subprocess.run(
        BASE_CMD + extra,
        env=_env(),
        cwd=REPO_ROOT,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    return json.loads(proc.stdout.decode().strip().splitlines()[-1]), proc.stderr.decode()


def _latest_step(ckpt_dir: str):
    """Newest committed orbax step dir (atomic rename => no partial reads)."""
    try:
        steps = [int(d) for d in os.listdir(ckpt_dir) if d.isdigit()]
    except FileNotFoundError:
        return None
    return max(steps, default=None)


@pytest.mark.slow
def test_clean_exit_then_resume(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    first, _ = _run(["--steps", "4", "--checkpoint-dir", ckpt, "--checkpoint-every", "2"])
    assert first["final_step"] == 4
    assert _latest_step(ckpt) == 4

    # Second invocation continues to the absolute target from step 4.
    second, err = _run(
        ["--steps", "6", "--checkpoint-dir", ckpt, "--resume", "--checkpoint-every", "2"]
    )
    assert second["resumed_from"] == 4
    assert second["final_step"] == 6
    assert second["noop"] is False
    assert "resumed from checkpoint step 4" in err

    # Stale-checkpoint rerun (same target): trains nothing, says so loudly.
    third, err3 = _run(
        ["--steps", "6", "--checkpoint-dir", ckpt, "--resume", "--checkpoint-every", "2"]
    )
    assert third["noop"] is True
    assert third["final_step"] == 6
    assert "nothing to train" in err3


@pytest.mark.slow
def test_kill_mid_run_resumes_at_saved_step(tmp_path):
    """The real preemption shape: SIGKILL mid-training (no goodbye saves),
    restart with --resume, continue from the last *committed* step."""
    ckpt = str(tmp_path / "ckpt")
    proc = subprocess.Popen(
        BASE_CMD
        + [
            "--steps",
            "100000",  # far more than we'll let it do
            "--checkpoint-dir",
            ckpt,
            "--checkpoint-every",
            "2",
        ],
        env=_env(),
        cwd=REPO_ROOT,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and _latest_step(ckpt) is None:
            time.sleep(0.2)
        saved = _latest_step(ckpt)
        assert saved is not None, "no checkpoint committed within 180s"
    finally:
        proc.kill()
        proc.wait()

    result, err = _run(
        [
            "--steps",
            str(saved + 2),
            "--checkpoint-dir",
            ckpt,
            "--resume",
            "--checkpoint-every",
            "2",
        ]
    )
    # It may have committed more steps between our poll and the kill; the
    # invariant is: resumed from SOME committed step >= what we saw, never 0.
    assert result["resumed_from"] >= saved > 0
    assert "resumed from checkpoint step" in err
    assert result["final_step"] >= saved
