"""Unit tests for per-chip health checking.

The reference's check is node-global — one open() of /dev/kfd flips every
device (reference main.go:83-91, TODOs at main.go:120-121).  Ours is per-chip
with an operator/fault-injection override seam; each behavior is pinned here.
"""

import os

from k8s_device_plugin_tpu.plugin.discovery import TpuChip
from k8s_device_plugin_tpu.plugin.health import HEALTH_OVERRIDE_DIR, ChipHealthChecker


def chip(i: int) -> TpuChip:
    return TpuChip(index=i, device_path=f"/dev/accel{i}")


def make_dev(root, i: int) -> str:
    path = os.path.join(str(root), "dev", f"accel{i}")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("")
    return path


def write_override(root, i: int, text: str) -> None:
    d = os.path.join(str(root), HEALTH_OVERRIDE_DIR)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"accel{i}"), "w") as f:
        f.write(text + "\n")


def test_present_device_is_healthy(tmp_path):
    make_dev(tmp_path, 0)
    assert ChipHealthChecker(root=str(tmp_path)).check(chip(0)) is True


def test_missing_device_is_unhealthy(tmp_path):
    make_dev(tmp_path, 0)
    checker = ChipHealthChecker(root=str(tmp_path))
    assert checker.check(chip(1)) is False  # accel1 never created


def test_per_chip_independence(tmp_path):
    """The core upgrade over the reference: one bad chip does not taint the
    rest."""
    for i in range(4):
        make_dev(tmp_path, i)
    os.unlink(os.path.join(str(tmp_path), "dev", "accel2"))
    checker = ChipHealthChecker(root=str(tmp_path))
    assert [checker.check(chip(i)) for i in range(4)] == [True, True, False, True]


def test_unopenable_busy_device_counts_healthy(tmp_path):
    # EACCES/EPERM/EBUSY mean "held by a workload", not dead.  A mode-000
    # file makes open() fail with EACCES for non-root users; root bypasses
    # DAC, so only assert when the probe actually fails.
    path = make_dev(tmp_path, 0)
    os.chmod(path, 0o000)
    try:
        assert ChipHealthChecker(root=str(tmp_path)).check(chip(0)) is True
    finally:
        os.chmod(path, 0o644)


def test_vfio_group_node_is_probed_by_presence_never_opened(tmp_path, monkeypatch):
    """A VFIO group node admits one opener: a probe that opened it could
    cost a starting workload its chip, so presence is the whole probe —
    on the Python path and through the native prober alike."""
    node = tmp_path / "dev" / "vfio" / "2"
    node.parent.mkdir(parents=True)
    node.write_text("")
    vfio_chip = TpuChip(index=0, device_path="/dev/vfio/2")

    class NeverProbe:
        def probe(self, path):
            raise AssertionError(f"opened {path}")

        def probe_many(self, paths):
            assert paths == [], f"opened {paths}"
            return []

    real_open = os.open

    def guarded_open(path, *a, **kw):
        assert "vfio" not in str(path), f"opened {path}"
        return real_open(path, *a, **kw)

    monkeypatch.setattr(os, "open", guarded_open)
    for prober in (None, NeverProbe()):
        checker = ChipHealthChecker(root=str(tmp_path), prober=prober)
        assert checker.check(vfio_chip) is True
        assert checker.check_many([vfio_chip]) == {"tpu-0": True}
    node.unlink()
    assert ChipHealthChecker(root=str(tmp_path), prober=None).check(vfio_chip) is False


def test_non_device_file_type_is_unhealthy(tmp_path):
    # A directory where the chardev should be = broken node.
    os.makedirs(os.path.join(str(tmp_path), "dev", "accel0"))
    assert ChipHealthChecker(root=str(tmp_path)).check(chip(0)) is False


def test_override_forces_unhealthy(tmp_path):
    make_dev(tmp_path, 0)
    write_override(tmp_path, 0, "Unhealthy")
    assert ChipHealthChecker(root=str(tmp_path)).check(chip(0)) is False


def test_override_forces_healthy_despite_missing_device(tmp_path):
    write_override(tmp_path, 3, "Healthy")
    assert ChipHealthChecker(root=str(tmp_path)).check(chip(3)) is True


def test_override_is_per_chip(tmp_path):
    for i in range(2):
        make_dev(tmp_path, i)
    write_override(tmp_path, 0, "unhealthy")
    checker = ChipHealthChecker(root=str(tmp_path))
    assert checker.check(chip(0)) is False
    assert checker.check(chip(1)) is True


def test_override_falsy_spellings(tmp_path):
    make_dev(tmp_path, 0)
    for text in ["unhealthy", "Unhealthy", "0", "false"]:
        write_override(tmp_path, 0, text)
        assert ChipHealthChecker(root=str(tmp_path)).check(chip(0)) is False


# ----------------------------------------------------- flap debounce


def sweep(checker, n=2):
    return checker.check_many([chip(i) for i in range(n)])


def test_flap_debounce_suppresses_single_transient(tmp_path):
    """One failing sweep of a Healthy chip must NOT flip it Unhealthy
    (threshold 2): the suppressed flip emits a health.flap_suppressed
    flight event, and a recovering probe resets the streak."""
    from k8s_device_plugin_tpu.utils.flight import FlightRecorder

    for i in range(2):
        make_dev(tmp_path, i)
    box = FlightRecorder(name="t")
    checker = ChipHealthChecker(
        root=str(tmp_path), prober=None, flight=box, flap_threshold=2
    )
    assert sweep(checker) == {"tpu-0": True, "tpu-1": True}
    # Transient: accel1 vanishes for exactly one sweep.
    os.unlink(os.path.join(str(tmp_path), "dev", "accel1"))
    assert sweep(checker) == {"tpu-0": True, "tpu-1": True}  # suppressed
    suppressed = box.window(kinds=["health.flap_suppressed"])
    assert suppressed == [
        {
            "ts": suppressed[0]["ts"], "kind": "health.flap_suppressed",
            "device": "tpu-1", "streak": 1, "threshold": 2,
        }
    ]
    make_dev(tmp_path, 1)
    assert sweep(checker) == {"tpu-0": True, "tpu-1": True}
    # Streak reset: the next single failure is again suppressed.
    os.unlink(os.path.join(str(tmp_path), "dev", "accel1"))
    assert sweep(checker)["tpu-1"] is True


def test_flap_debounce_sustained_failure_transitions(tmp_path):
    """K consecutive failures DO transition (threshold is a debounce,
    not a blindfold), and recovery is never debounced."""
    make_dev(tmp_path, 0)
    checker = ChipHealthChecker(
        root=str(tmp_path), prober=None, flap_threshold=3
    )
    assert sweep(checker, n=1) == {"tpu-0": True}
    os.unlink(os.path.join(str(tmp_path), "dev", "accel0"))
    assert sweep(checker, n=1)["tpu-0"] is True  # streak 1: suppressed
    assert sweep(checker, n=1)["tpu-0"] is True  # streak 2: suppressed
    assert sweep(checker, n=1)["tpu-0"] is False  # streak 3: reported
    # Once Unhealthy, staying broken keeps reporting Unhealthy with no
    # re-suppression dance.
    assert sweep(checker, n=1)["tpu-0"] is False
    make_dev(tmp_path, 0)
    assert sweep(checker, n=1)["tpu-0"] is True  # recovery is immediate


def test_flap_threshold_one_keeps_first_failure_reporting(tmp_path):
    """The library default (1) preserves report-on-first-failure — the
    behavior every pre-debounce test and caller relies on."""
    make_dev(tmp_path, 0)
    checker = ChipHealthChecker(root=str(tmp_path), prober=None)
    assert sweep(checker, n=1) == {"tpu-0": True}
    os.unlink(os.path.join(str(tmp_path), "dev", "accel0"))
    assert sweep(checker, n=1) == {"tpu-0": False}


def test_flap_threshold_validation():
    import pytest

    with pytest.raises(ValueError):
        ChipHealthChecker(flap_threshold=0)
