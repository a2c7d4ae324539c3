"""Multi-resource lifecycle manager: the dpm lister contract, TPU-native.

Hermetic coverage of what the reference's generic DPM does (reference
dpm/lister.go:11-26 Discover/NewPlugin contract; dpm/manager.go:96-136
start/stop-on-list-diff) and round 1 hardcoded away:
a second resource appears → its plugin socket registers; it vanishes → the
socket unregisters; kubelet restarts → every live resource re-registers.
"""

from __future__ import annotations

import os
import threading
import time

import grpc
import pytest

from k8s_device_plugin_tpu.kubelet import constants
from k8s_device_plugin_tpu.kubelet.api import pb
from k8s_device_plugin_tpu.plugin import discovery
from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
from k8s_device_plugin_tpu.plugin.manager import PluginManager
from k8s_device_plugin_tpu.plugin.resources import (
    MultiResourceManager,
    StaticLister,
)
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
def kubelet(plugin_dir):
    kubelet = FakeKubelet(plugin_dir)
    kubelet.start()
    yield kubelet
    kubelet.stop()


def make_plugin(host_root) -> TpuDevicePlugin:
    return TpuDevicePlugin(
        discover=lambda: discovery.discover(root=host_root, environ={}),
        health_checker=ChipHealthChecker(root=host_root),
    )


class PushLister:
    """Test lister: hand-fed lists, like dpm's ResUpdateChan relay
    (reference main.go:171-181)."""

    namespace = "google.com"

    def __init__(self, host_root):
        self.host_root = host_root
        self.publish = None
        self.published = threading.Event()

    def discover(self, publish, stop):
        self.publish = publish
        self.published.set()
        # Real listers may keep polling; pushing from the test thread via
        # self.publish models the update stream.

    def new_plugin(self, name):
        return make_plugin(self.host_root)


def make_multi(lister, kubelet, **kwargs) -> MultiResourceManager:
    kwargs.setdefault("watch_poll_interval", 0.1)
    kwargs.setdefault("register_retry_delay", 0.1)
    return MultiResourceManager(lister, plugin_dir=kubelet.plugin_dir, **kwargs)


def test_static_lister_single_resource(host_root, kubelet):
    lister = StaticLister(["tpu"], lambda name: make_plugin(host_root))
    multi = make_multi(lister, kubelet)
    multi.start()
    try:
        assert kubelet.registered.wait(5)
        req = kubelet.requests[0]
        assert req.resource_name == "google.com/tpu"
        assert req.endpoint == "google.com_tpu.sock"
        stream = kubelet.plugin_stub().ListAndWatch(pb.Empty())
        assert len(next(stream).devices) == 4
    finally:
        multi.stop_all()
    assert not os.path.exists(os.path.join(kubelet.plugin_dir, "google.com_tpu.sock"))


def test_add_then_remove_second_resource(host_root, kubelet):
    """The done-criterion: add then remove a second fake resource
    and observe both plugin sockets register/unregister."""
    lister = PushLister(host_root)
    multi = make_multi(lister, kubelet)
    multi.start()
    try:
        assert lister.published.wait(5)
        lister.publish(["tpu"])
        assert wait_until(lambda: len(kubelet.requests) == 1)

        # Second resource appears: its own socket + registration.
        lister.publish(["tpu", "tpu-slice"])
        assert wait_until(lambda: len(kubelet.requests) == 2)
        by_name = {r.resource_name: r for r in kubelet.requests}
        assert set(by_name) == {"google.com/tpu", "google.com/tpu-slice"}
        slice_sock = os.path.join(kubelet.plugin_dir, "google.com_tpu-slice.sock")
        assert os.path.exists(slice_sock)
        # Both servers answer independently.
        for endpoint in ("google.com_tpu.sock", "google.com_tpu-slice.sock"):
            stream = kubelet.plugin_stub(endpoint).ListAndWatch(pb.Empty())
            assert len(next(stream).devices) == 4
        assert multi.resources() == ["tpu", "tpu-slice"]

        # Second resource vanishes: socket unlinked, manager stopped, the
        # surviving resource untouched.
        lister.publish(["tpu"])
        assert wait_until(lambda: multi.resources() == ["tpu"])
        assert wait_until(lambda: not os.path.exists(slice_sock))
        stream = kubelet.plugin_stub("google.com_tpu.sock").ListAndWatch(pb.Empty())
        assert len(next(stream).devices) == 4
    finally:
        multi.stop_all()


def test_kubelet_restart_reregisters_every_resource(host_root, kubelet):
    lister = PushLister(host_root)
    multi = make_multi(lister, kubelet)
    multi.start()
    try:
        assert lister.published.wait(5)
        lister.publish(["tpu", "tpu-slice"])
        assert wait_until(lambda: len(kubelet.requests) == 2)

        kubelet.restart()
        # Both resources must come back (4 total registrations, 2 post-restart).
        assert wait_until(lambda: len(kubelet.requests) >= 4, timeout=15)
        post = {r.resource_name for r in kubelet.requests[2:]}
        assert post == {"google.com/tpu", "google.com/tpu-slice"}
    finally:
        multi.stop_all()


def test_duplicate_publish_is_idempotent(host_root, kubelet):
    lister = PushLister(host_root)
    multi = make_multi(lister, kubelet)
    multi.start()
    try:
        assert lister.published.wait(5)
        lister.publish(["tpu"])
        assert wait_until(lambda: len(kubelet.requests) == 1)
        lister.publish(["tpu"])  # same list again: no churn
        time.sleep(0.3)
        assert len(kubelet.requests) == 1
        assert multi.resources() == ["tpu"]
    finally:
        multi.stop_all()


# ---------------------------------------------------------------- versioning


class VersionRejectingKubelet(FakeKubelet):
    """A kubelet that refuses our API version — the first operator-visible
    failure on version skew (protocol contract: reference api.proto:20-22)."""

    def Register(self, request, context):
        self.requests.append(request)
        context.abort(
            grpc.StatusCode.INVALID_ARGUMENT,
            f"unsupported device-plugin API version {request.version}, "
            "kubelet supports [v1alpha1]",
        )


def test_version_mismatch_logged_and_retried(host_root, plugin_dir, caplog):
    kubelet = VersionRejectingKubelet(plugin_dir)
    kubelet.start()
    manager = PluginManager(
        make_plugin(host_root),
        plugin_dir=kubelet.plugin_dir,
        register_retries=3,
        register_retry_delay=0.05,
    )
    try:
        with caplog.at_level("ERROR"):
            with pytest.raises(RuntimeError, match="could not register"):
                manager.start()
        # All retry attempts hit the kubelet (with backoff), and the
        # operator-facing skew message fired.
        assert len(kubelet.requests) == 3
        assert any("version skew" in r.message for r in caplog.records)
        # Registration failure rolled the server back (protocol contract).
        assert not os.path.exists(manager.socket_path)
    finally:
        manager.stop_all()
        kubelet.stop()


def test_failed_start_retried_when_kubelet_appears(host_root, plugin_dir):
    """Kubelet down at publish time: the resource must NOT be dropped forever
    — the kubelet-create event retries it (multi-resource parity with the
    single-resource daemon's crash-and-restart behavior)."""
    lister = PushLister(host_root)
    multi = MultiResourceManager(
        lister,
        plugin_dir=plugin_dir,
        watch_poll_interval=0.05,
        register_retries=1,
        register_retry_delay=0.05,
    )
    multi.start()
    kubelet = None
    try:
        assert lister.published.wait(5)
        lister.publish(["tpu"])  # no kubelet.sock: start fails
        assert wait_until(lambda: multi.resources() == [], timeout=5)

        # Kubelet comes up; the watcher fires create; the resource recovers.
        kubelet = FakeKubelet(plugin_dir)
        kubelet.start()
        assert wait_until(lambda: multi.resources() == ["tpu"], timeout=10)
        assert kubelet.registered.wait(5)
        assert multi.alive()
    finally:
        multi.stop_all()
        if kubelet is not None:
            kubelet.stop()


def test_failed_start_retried_on_timer_without_events(host_root, kubelet, monkeypatch):
    """Kubelet UP but REJECTING registration (version skew mid-upgrade): the
    socket never flaps, so no create event will ever retry the failed start —
    recovery must ride the retry timer, exactly like PluginManager's
    reconciler does for the single-resource path."""
    lister = PushLister(host_root)
    multi = make_multi(lister, kubelet, register_retries=1)
    multi.start()
    try:
        assert lister.published.wait(5)
        monkeypatch.setattr(constants, "VERSION", "v0alpha1")
        lister.publish(["tpu"])
        assert wait_until(lambda: multi.resources() == [], timeout=5)
        # "Upgrade" the plugin; NO filesystem event fires from here on.
        monkeypatch.setattr(constants, "VERSION", "v1beta1")
        assert wait_until(lambda: multi.resources() == ["tpu"], timeout=10)
        assert kubelet.registered.wait(5)
        assert multi.alive()
    finally:
        multi.stop_all()


def test_discover_crash_flips_liveness(host_root, kubelet):
    class CrashingLister(PushLister):
        def discover(self, publish, stop):
            raise RuntimeError("boom")

    multi = make_multi(CrashingLister(host_root), kubelet)
    multi.start()
    try:
        assert wait_until(lambda: not multi.alive(), timeout=5)
    finally:
        multi.stop_all()
