"""chip_smoke.py measures the chip or says that it cannot: without a TPU
it exits non-zero and prints no result (a number from the CPU is never
written under a device metric's name)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _run(argv, cwd=REPO_ROOT, **env):
    return subprocess.run(
        [sys.executable, *argv],
        cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_chip_smoke_refuses_without_a_tpu_and_runs_no_leg():
    """JAX_PLATFORMS=cpu: the probe names the platform it found, no leg
    runs, the exit code is non-zero, and the parent never imported jax."""
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main()\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "sys.exit(rc)\n"
    )
    proc = _run(["-c", code], PYTHONPATH=REPO_ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "platform 'cpu'" in proc.stdout
    legs = [l for l in proc.stdout.splitlines() if l.startswith("--- leg")]
    assert legs == ["--- leg probe"]
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
    assert "no leg was run" in proc.stderr


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "run it from a checkout" in proc.stderr


def test_chip_smoke_serves_the_shipped_geometry():
    """The smoke's flags ARE the deployed replica's: every one of them
    appears in deploy/k8s-deploy-serve-http.yaml (the port aside)."""
    import chip_smoke

    with open(os.path.join(REPO_ROOT, "deploy", "k8s-deploy-serve-http.yaml")) as f:
        manifest = f.read()
    for flag in chip_smoke.SERVE_FLAGS:
        if not flag.startswith("--http-port"):
            assert f'"{flag}"' in manifest, flag
