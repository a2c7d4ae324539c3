"""Lifecycle-manager tests: registration, kubelet-restart recovery, heartbeat.

Exercises hermetically what the reference never tests at all (SURVEY.md §4):
the register → serve → re-register dance of dpm/manager.go + dpm/plugin.go.
"""

import os
import time

import grpc
import pytest

from k8s_device_plugin_tpu.kubelet import constants
from k8s_device_plugin_tpu.kubelet.api import pb
from k8s_device_plugin_tpu.plugin import discovery
from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
from k8s_device_plugin_tpu.plugin.manager import PluginManager
from k8s_device_plugin_tpu.plugin.server import TpuDevicePlugin
from tests.fakes import FakeKubelet, make_fake_tpu_host


def wait_until(predicate, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


@pytest.fixture
def host_root(tmp_path):
    return make_fake_tpu_host(tmp_path / "host", n_chips=4)


@pytest.fixture
def plugin(host_root):
    return TpuDevicePlugin(
        discover=lambda: discovery.discover(root=host_root, environ={}),
        health_checker=ChipHealthChecker(root=host_root),
    )


@pytest.fixture
def kubelet(plugin_dir):
    kubelet = FakeKubelet(plugin_dir)
    kubelet.start()
    yield kubelet
    kubelet.stop()


def make_manager(plugin, kubelet, **kwargs) -> PluginManager:
    kwargs.setdefault("watch_poll_interval", 0.1)
    kwargs.setdefault("register_retry_delay", 0.1)
    return PluginManager(plugin, plugin_dir=kubelet.plugin_dir, **kwargs)


def test_start_registers_with_kubelet(plugin, kubelet):
    manager = make_manager(plugin, kubelet)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        req = kubelet.requests[0]
        assert req.version == constants.VERSION
        assert req.resource_name == "google.com/tpu"
        assert req.endpoint == "google.com_tpu.sock"
        assert req.options.get_preferred_allocation_available is True
        # The kubelet can now dial back and stream devices.
        stream = kubelet.plugin_stub().ListAndWatch(pb.Empty())
        assert len(next(stream).devices) == 4
    finally:
        manager.stop_all()
    # Socket cleaned up on stop (≙ dpm/plugin.go:174-181).
    assert not os.path.exists(manager.socket_path)


def test_registration_failure_rolls_back_server(plugin, plugin_dir):
    # No kubelet at all: registration must fail after retries and the plugin
    # socket must NOT be left behind (≙ dpm/plugin.go:83-87).
    manager = PluginManager(
        plugin,
        plugin_dir=plugin_dir,
        register_retries=2,
        register_retry_delay=0.05,
    )
    with pytest.raises(RuntimeError):
        manager.start()
    assert not os.path.exists(manager.socket_path)
    manager.stop_all()


def test_kubelet_restart_triggers_reregistration(plugin, kubelet):
    manager = make_manager(plugin, kubelet)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        first_count = len(kubelet.requests)

        kubelet.restart()
        assert wait_until(lambda: len(kubelet.requests) > first_count)
        # And the plugin is immediately usable again.
        stream = kubelet.plugin_stub().ListAndWatch(pb.Empty())
        assert len(next(stream).devices) == 4
        assert manager.registrations >= 2
    finally:
        manager.stop_all()


def test_kubelet_socket_removal_stops_server(plugin, kubelet):
    manager = make_manager(plugin, kubelet)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        sock = manager.socket_path
        assert os.path.exists(sock)

        kubelet.stop(remove_socket=True)
        assert wait_until(lambda: not os.path.exists(sock))

        # Kubelet comes back: plugin re-registers and serves again.
        kubelet.restart()
        assert wait_until(lambda: kubelet.registered.is_set())
        assert wait_until(lambda: os.path.exists(sock))
    finally:
        manager.stop_all()


def test_heartbeat_streams_health_transitions(plugin, kubelet, host_root):
    manager = make_manager(plugin, kubelet, pulse=0.05)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        stream = kubelet.plugin_stub().ListAndWatch(pb.Empty())
        first = next(stream)
        assert all(d.health == constants.HEALTHY for d in first.devices)

        # Break chip 1 behind the manager's back; the heartbeat must notice.
        os.makedirs(os.path.join(host_root, "run/tpu/health"), exist_ok=True)
        with open(os.path.join(host_root, "run/tpu/health/accel1"), "w") as f:
            f.write("Unhealthy\n")
        second = next(stream)
        assert {d.ID: d.health for d in second.devices}["tpu-1"] == constants.UNHEALTHY
        assert len(second.devices) == 4
    finally:
        manager.stop_all()


def test_reconciler_retries_failed_reregistration(plugin, kubelet, monkeypatch):
    """A kubelet that comes back REJECTING registration (version skew during
    an upgrade) must not park the plugin forever: no further filesystem event
    arrives, so recovery rides the reconciler's retry timer alone."""
    manager = make_manager(plugin, kubelet)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        # Kubelet restarts; the plugin now (artificially) speaks a version
        # the kubelet's hardcoded set rejects.
        monkeypatch.setattr(constants, "VERSION", "v0alpha1")
        kubelet.restart()
        time.sleep(1.0)  # several reconcile attempts, all rejected
        assert not kubelet.registered.is_set()
        # "Upgrade" the plugin.  NO new socket event fires — only the retry
        # timer can notice and re-register.
        monkeypatch.setattr(constants, "VERSION", "v1beta1")
        assert wait_until(lambda: kubelet.registered.is_set(), timeout=10)
        assert manager.alive()
    finally:
        manager.stop_all()


def test_kubelet_socket_flap_storm(plugin, kubelet, monkeypatch):
    """Rapid kubelet create/remove/rebind flapping (the hardest part of the
    recovery story, SURVEY §7) against a LIVE manager: 100 storm cycles of
    stop/start with and without socket removal, then one clean restart.
    Asserts (a) the manager converges to a registered, serving state,
    (b) at most ONE DevicePlugin gRPC server was ever live at a time (no
    double-serve across the watcher-callback / startup races), and (c) no
    thread leak accumulates across the 100 recovery cycles."""
    import threading

    from k8s_device_plugin_tpu.plugin import manager as manager_mod

    real_grpc = manager_mod.grpc
    live: set = set()
    max_live = [0]
    guard = threading.Lock()

    class TrackedServer:
        def __init__(self, inner):
            self._inner = inner

        def start(self):
            with guard:
                live.add(self)
                max_live[0] = max(max_live[0], len(live))
            return self._inner.start()

        def stop(self, grace=None):
            with guard:
                live.discard(self)
            return self._inner.stop(grace)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    class GrpcProxy:
        # Only the manager module sees this proxy; the FakeKubelet's own
        # grpc.server stays untracked.
        def server(self, *a, **k):
            return TrackedServer(real_grpc.server(*a, **k))

        def __getattr__(self, name):
            return getattr(real_grpc, name)

    monkeypatch.setattr(manager_mod, "grpc", GrpcProxy())

    manager = make_manager(plugin, kubelet, watch_poll_interval=0.05)
    manager.start()
    try:
        assert kubelet.registered.wait(5)
        baseline_threads = threading.active_count()

        for i in range(100):
            if i % 3 == 2:
                # Remove-only phase: kubelet goes down and STAYS down for a
                # beat — the manager must stop serving, then recover on the
                # create that follows.
                kubelet.stop(remove_socket=True)
                time.sleep(0.005)
                kubelet.registered.clear()
                kubelet.start()
            else:
                # Tight unlink+rebind (what an in-place kubelet rebind looks
                # like to a poller; inotify sees delete+create back to back).
                kubelet.restart()
            if i % 7 == 0:
                time.sleep(0.02)  # let some callbacks interleave mid-storm

        # Settle: one final clean restart, then the manager must converge.
        kubelet.restart()
        assert wait_until(lambda: kubelet.registered.is_set(), timeout=20)
        # Serving again end to end — a fresh kubelet-side dial-back works.
        assert wait_until(
            lambda: os.path.exists(manager.socket_path), timeout=10
        )

        def _serving():
            try:
                stream = kubelet.plugin_stub().ListAndWatch(pb.Empty())
                return len(next(stream).devices) == 4
            except grpc.RpcError:
                return False

        assert wait_until(_serving, timeout=10)

        # (b) never two DevicePlugin servers alive at once.
        assert max_live[0] == 1, f"double-serve: {max_live[0]} servers live"
        # (c) threads wind down to (near) the pre-storm baseline; grpc pool
        # threads unwind asynchronously, so poll with slack for the pools of
        # the final live server.
        assert wait_until(
            lambda: threading.active_count() <= baseline_threads + 10,
            timeout=15,
        ), f"thread leak: {baseline_threads} -> {threading.active_count()}"
        assert manager.registrations >= 2
        assert manager.alive()
    finally:
        manager.stop_all()
    assert not os.path.exists(manager.socket_path)
    assert len(live) == 0


def test_cli_wiring(host_root, kubelet):
    # Drive main() far enough to register, then deliver the shutdown path via
    # the manager (signal handlers only bind on the main thread of a real
    # process; here we call shutdown directly).
    import threading

    from k8s_device_plugin_tpu.plugin import cli

    rc: list[int] = []
    manager_holder: dict = {}

    orig_run = PluginManager.run

    def capturing_run(self):
        manager_holder["m"] = self
        orig_run(self)

    PluginManager.run = capturing_run
    try:
        t = threading.Thread(
            target=lambda: rc.append(
                cli.main(
                    [
                        "--root",
                        host_root,
                        "--plugin-dir",
                        kubelet.plugin_dir,
                        "--pulse",
                        "0.05",
                    ]
                )
            )
        )
        t.start()
        assert kubelet.registered.wait(5)
        assert wait_until(lambda: "m" in manager_holder)
        manager_holder["m"].shutdown()
        t.join(timeout=10)
        assert not t.is_alive()
        assert rc == [0]
    finally:
        PluginManager.run = orig_run
