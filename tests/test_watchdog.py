"""Hung-step watchdog + chip-health feed (models/engine_watchdog.py).

All StepWatchdog units run on a FAKE clock — zero sleeps, zero jax:
the watchdog's contract (warmup grace, compile-grace no-trip, hang
trip, trip-once + rearm, baseline hygiene) is pure host-side state.
ChipHealthFeed units probe a fake devfs tree and a tiny in-process
daemon double serving /debug/devices.  The fence these detectors
TRIGGER (admission 503, healthz, stream cut) is integration-tested in
tests/test_http_server.py and scored under chaos in
tests/test_chaos_scenarios.py.
"""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from k8s_device_plugin_tpu.models.engine_watchdog import (
    ChipHealthFeed,
    StepWatchdog,
    visible_chip_paths,
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _watchdog(clock, **kw):
    fences: list[dict] = []
    kw.setdefault("warmup", 4)
    kw.setdefault("factor", 8.0)
    kw.setdefault("min_deadline_s", 0.5)
    kw.setdefault("grace_deadline_s", 30.0)
    wd = StepWatchdog(fences.append, clock=clock, **kw)
    return wd, fences


def _complete_steps(wd, clock, n, wall=0.01):
    for _ in range(n):
        wd.step_started()
        clock.advance(wall)
        wd.step_finished(wall)


def test_warmup_steps_get_grace_deadline():
    clock = FakeClock()
    wd, fences = _watchdog(clock)
    _complete_steps(wd, clock, 3)  # below warmup=4
    wd.step_started()
    clock.advance(5.0)  # way past the tight deadline
    assert wd.check() is None, "warmup steps must be judged on grace"
    assert not fences
    clock.advance(26.0)  # past grace_deadline_s=30
    assert wd.check() is not None, "even warmup steps trip past grace"


def test_baseline_trip_fires_once_and_rearms():
    clock = FakeClock()
    wd, fences = _watchdog(clock)
    _complete_steps(wd, clock, 8, wall=0.02)
    # deadline = max(0.5, 8 * 0.02) = 0.5 (the floor)
    assert wd.deadline_s() == pytest.approx(0.5)
    wd.step_started()
    clock.advance(0.4)
    assert wd.check() is None
    clock.advance(0.2)  # 0.6s into the step
    trip = wd.check()
    assert trip is not None and trip["kind"] == "hung_step"
    assert fences and fences[0]["observed_s"] >= 0.5
    # Trip-once: the same hang never fences twice.
    clock.advance(5.0)
    assert wd.check() is None and len(fences) == 1
    # Rearm (the unfence path): a STILL-hung step trips again.
    wd.rearm()
    assert wd.check() is not None
    assert len(fences) == 2 and wd.trips == 2


def test_compile_grace_prevents_false_trip():
    clock = FakeClock()
    wd, fences = _watchdog(clock)
    _complete_steps(wd, clock, 8, wall=0.02)
    wd.step_started()
    wd.note_grace("compile:step")  # engine built a fresh jitted program
    clock.advance(10.0)  # a real XLA compile can run this long
    assert wd.check() is None, "compile steps must never false-trip"
    assert not fences
    wd.step_finished(10.0)
    # The compile outlier must NOT have polluted the baseline.
    wd.step_started()
    clock.advance(0.6)
    assert wd.check() is not None, "post-compile deadline must stay tight"


def test_baseline_scales_the_deadline():
    clock = FakeClock()
    wd, fences = _watchdog(clock, min_deadline_s=0.01)
    _complete_steps(wd, clock, 8, wall=0.2)
    # deadline = 8 * p99(0.2) = 1.6s, well above the floor
    assert wd.deadline_s() == pytest.approx(1.6)
    wd.step_started()
    clock.advance(1.0)
    assert wd.check() is None
    clock.advance(0.7)
    assert wd.check() is not None


def test_tripped_step_wall_never_feeds_baseline():
    clock = FakeClock()
    wd, fences = _watchdog(clock)
    _complete_steps(wd, clock, 8, wall=0.02)
    wd.step_started()
    clock.advance(3.0)
    assert wd.check() is not None
    wd.step_finished(3.0)  # the hang eventually released
    wd.rearm()
    # Baseline still reflects the 20ms steps, not the 3s hang.
    assert wd.deadline_s() == pytest.approx(0.5)


def test_no_trip_between_steps():
    clock = FakeClock()
    wd, fences = _watchdog(clock)
    _complete_steps(wd, clock, 8, wall=0.02)
    clock.advance(120.0)  # idle engine: no step in flight
    assert wd.check() is None and not fences


def test_snapshot_shape():
    clock = FakeClock()
    wd, _ = _watchdog(clock)
    _complete_steps(wd, clock, 2)
    snap = wd.snapshot()
    assert snap["completed_steps"] == 2
    assert snap["tripped"] is False
    assert json.dumps(snap)  # JSON-safe for /debug/state


# ---------------------------------------------------------------- chip feed


def _fake_devfs(tmp_path, chips=(0, 1)):
    dev = tmp_path / "dev"
    dev.mkdir(exist_ok=True)
    paths = []
    for i in chips:
        p = dev / f"accel{i}"
        p.write_text("")
        paths.append(str(p))
    return paths


def test_visible_chip_paths():
    assert visible_chip_paths({"TPU_VISIBLE_CHIPS": "0,2"}, root="/r") == [
        "/r/dev/accel0",
        "/r/dev/accel2",
    ]
    assert visible_chip_paths({}, root="/r") == []
    assert visible_chip_paths({"TPU_VISIBLE_CHIPS": "bogus"}, root="/r") == []


def test_visible_chip_paths_on_a_vfio_host():
    """TPU_VISIBLE_CHIPS indexes resolve through discovery, so on the
    captured v5e host chip 2 is /dev/vfio/2 — not an absent /dev/accel2
    that would fence the replica as unplugged two seconds after start."""
    import os

    root = os.path.join(os.path.dirname(__file__), "testdata", "tpu-host-v5e-vfio")
    assert visible_chip_paths({"TPU_VISIBLE_CHIPS": "2,0"}, root=root) == [
        os.path.join(root, "dev/vfio/2"),
        os.path.join(root, "dev/vfio/0"),
    ]


def test_devfs_presence_probe_fires_once_then_rearms(tmp_path):
    paths = _fake_devfs(tmp_path)
    faults: list[dict] = []
    feed = ChipHealthFeed(faults.append, device_paths=paths)
    assert feed.check_once() is None and not faults
    (tmp_path / "dev" / "accel1").unlink()  # yank the chip
    fault = feed.check_once()
    assert fault == {"kind": "unplugged", "device": "accel1", "probe": "devfs"}
    assert faults == [fault]
    # Trip-once until rearm (the unfence path).
    assert feed.check_once() is None and len(faults) == 1
    feed.rearm()
    feed.check_once()
    assert len(faults) == 2


class _FakeDaemon:
    """Minimal plugin-daemon double: GET /debug/devices only."""

    def __init__(self):
        daemon = self
        self.chips: list[dict] = []
        self.fail = False

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                if daemon.fail or self.path.split("?")[0] != "/debug/devices":
                    self.send_error(500)
                    return
                body = json.dumps({"chips": daemon.chips}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        threading.Thread(
            # 50ms shutdown poll: the default 0.5s would dominate the
            # fixture teardown (same rationale as FakeReplica).
            target=lambda: self._httpd.serve_forever(poll_interval=0.05),
            daemon=True,
        ).start()
        self.url = (
            f"http://127.0.0.1:{self._httpd.server_address[1]}/debug/devices"
        )

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


@pytest.fixture()
def daemon():
    d = _FakeDaemon()
    yield d
    d.stop()


def test_daemon_feed_unhealthy_and_unplug(tmp_path, daemon):
    paths = _fake_devfs(tmp_path)
    daemon.chips = [
        {"id": "tpu-0", "device_path": "/dev/accel0", "healthy": True},
        {"id": "tpu-1", "device_path": "/dev/accel1", "healthy": True},
    ]
    faults: list[dict] = []
    feed = ChipHealthFeed(faults.append, url=daemon.url, device_paths=paths)
    assert feed.check_once() is None
    daemon.chips[1]["healthy"] = False
    fault = feed.check_once()
    assert fault == {
        "kind": "unhealthy", "device": "accel1", "probe": "daemon",
    }
    feed.rearm()
    # An unplugged chip LEAVES the daemon inventory entirely.
    daemon.chips = daemon.chips[:1]
    fault = feed.check_once()
    assert fault == {
        "kind": "unplugged", "device": "accel1", "probe": "daemon",
    }


def test_daemon_outage_falls_back_to_devfs(tmp_path, daemon):
    """A dead daemon is a daemon problem, not a chip fault: no fence
    until the fallback threshold — then devfs presence decides."""

    class Box:
        def __init__(self):
            self.events = []

        def record(self, kind, **fields):
            self.events.append({"kind": kind, **fields})

    paths = _fake_devfs(tmp_path)
    faults: list[dict] = []
    box = Box()
    feed = ChipHealthFeed(
        faults.append,
        url=daemon.url,
        device_paths=paths,
        url_failures_to_fallback=2,
        flight=box,
    )
    daemon.chips = [
        {"id": "tpu-0", "device_path": "/dev/accel0", "healthy": True},
        {"id": "tpu-1", "device_path": "/dev/accel1", "healthy": True},
    ]
    assert feed.check_once() is None
    daemon.fail = True
    assert feed.check_once() is None, "first daemon failure never fences"
    assert any(e["kind"] == "chip_health.feed_down" for e in box.events)
    # Fallback active, devfs healthy: still no fence.
    assert feed.check_once() is None and not faults
    # Devfs says the chip is GONE: fence even with the daemon dead.
    (tmp_path / "dev" / "accel0").unlink()
    fault = feed.check_once()
    assert fault == {"kind": "unplugged", "device": "accel0", "probe": "devfs"}
    # Daemon recovery resets the failure streak (feed_up event).
    feed.rearm()
    daemon.fail = False
    (tmp_path / "dev" / "accel0").write_text("")
    assert feed.check_once() is None
    assert any(e["kind"] == "chip_health.feed_up" for e in box.events)


def test_feed_requires_a_source():
    with pytest.raises(ValueError):
        ChipHealthFeed(lambda f: None)
