"""The launch path of every cell: the real plugin daemon, a kubelet peer,
``ListAndWatch`` and a timed ``Allocate``; the child then runs under
exactly the variables ``Allocate`` returned.

Copied in shape from chip_smoke.py's ``PluginPeer`` / ``chip_env`` (PR 21),
with a kubelet peer of its own: tests/fakes.py imports the serving engine
(and so JAX), which the parent of a run must never load.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent import futures

from .procs import Child

PACKAGE = "k8s_device_plugin_tpu"
RESOURCE = "google.com/tpu"


class KubeletPeer:
    """The kubelet's side of the device-plugin protocol: serves
    ``Registration`` on ``<dir>/kubelet.sock`` and, like the kubelet,
    dials back into the plugin's socket inside ``Register``."""

    def __init__(self, plugin_dir: str):
        import grpc

        from k8s_device_plugin_tpu.kubelet import api, constants

        self._grpc, self._api = grpc, api
        self.plugin_dir = plugin_dir
        self.socket_path = os.path.join(plugin_dir, constants.KUBELET_SOCKET_NAME)
        self.registered = threading.Event()
        self.endpoint: str | None = None
        self._channels: list = []
        self._server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        api.add_registration_servicer(self, self._server)
        self._server.add_insecure_port(f"unix://{self.socket_path}")
        self._server.start()

    def Register(self, request, context):  # noqa: N802 — gRPC servicer API
        grpc, api = self._grpc, self._api
        if request.version != "v1beta1" or "/" not in request.resource_name:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, "bad registration")
        try:
            self.stub(request.endpoint).GetDevicePluginOptions(api.pb.Empty(), timeout=5)
        except grpc.RpcError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"cannot dial plugin: {e.code()}")
        self.endpoint = request.endpoint
        self.registered.set()
        return api.pb.Empty()

    def stub(self, endpoint: str | None = None):
        sock = os.path.join(self.plugin_dir, endpoint or self.endpoint)
        channel = self._grpc.insecure_channel(f"unix://{sock}")
        self._channels.append(channel)
        return self._api.DevicePluginStub(channel)

    def stop(self) -> None:
        for channel in self._channels:
            channel.close()
        self._server.stop(grace=None).wait()


def make_fake_host(root: str, chips: int) -> str:
    """A devfs/sysfs tree of ``chips`` accel nodes for the CPU rehearsal
    (the layout plugin/discovery.py reads; see tests/fakes.py)."""
    os.makedirs(os.path.join(root, "dev"), exist_ok=True)
    for i in range(chips):
        open(os.path.join(root, "dev", f"accel{i}"), "w").close()
        dev = os.path.join(root, "sys/class/accel", f"accel{i}", "device")
        os.makedirs(dev, exist_ok=True)
        for name, text in (
            ("vendor", "0x1ae0"), ("device", "0x0063"), ("numa_node", "0"),
            ("uevent", f"DRIVER=accel\nPCI_CLASS=120000\nPCI_SLOT_NAME=0000:00:{4 + i:02x}.0"),
        ):
            with open(os.path.join(dev, name), "w") as f:
                f.write(text + "\n")
    meta = os.path.join(root, "run/tpu")
    os.makedirs(meta, exist_ok=True)
    with open(os.path.join(meta, "accelerator-type"), "w") as f:
        f.write("v5litepod-4\n")
    return root


class PluginPeer:
    """Daemon and kubelet peer, kept up for the whole run like a DaemonSet
    pod.  ``allocate(chips)`` returns the granted variables and the
    milliseconds the RPC took."""

    def __init__(self, run_dir: str, program_root: str, host_root: str = "/"):
        self.daemon: Child | None = None
        self.kubelet: KubeletPeer | None = None
        # A unix socket's path holds 107 bytes: the sockets live in a short
        # directory under TMPDIR (the driver gives each side its own), not
        # under the run directory, whose path can be long.
        self.socket_dir = plugin_dir = tempfile.mkdtemp(prefix="cb-")
        self.kubelet = KubeletPeer(plugin_dir)
        env = dict(os.environ, PYTHONPATH=program_root)
        env.pop("TPU_PROBE_LIB", None)
        self.daemon = Child(
            "plugin",
            [sys.executable, "-m", f"{PACKAGE}.plugin.cli", "--root", host_root,
             "--plugin-dir", plugin_dir, "--pulse", "1"],
            env, run_dir, program_root,
        )

    def allocate(self, chips: int) -> tuple[dict[str, str], float, list[str]]:
        from k8s_device_plugin_tpu.kubelet.api import pb

        if not self.kubelet.registered.wait(30):
            raise RuntimeError("plugin daemon did not register within 30 s\n" + self.daemon.tail())
        stub = self.kubelet.stub()
        stream = stub.ListAndWatch(pb.Empty(), timeout=10)
        try:
            devices = list(next(stream).devices)
        finally:
            stream.cancel()
        healthy = sorted(d.ID for d in devices if d.health == "Healthy")
        if len(healthy) < chips:
            raise RuntimeError(
                f"the plugin lists {len(healthy)} healthy {RESOURCE} device(s), "
                f"the cell needs {chips}: {[(d.ID, d.health) for d in devices]}"
            )
        request = pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(devicesIDs=healthy[:chips])]
        )
        t0 = time.perf_counter()
        resp = stub.Allocate(request, timeout=10).container_responses[0]
        ms = (time.perf_counter() - t0) * 1e3
        return dict(resp.envs), ms, [d.host_path for d in resp.devices]

    def close(self) -> None:
        if self.daemon is not None:
            self.daemon.stop()
        if self.kubelet is not None:
            self.kubelet.stop()
        shutil.rmtree(self.socket_dir, ignore_errors=True)


def chip_env(alloc_env: dict[str, str], program_root: str, bench_root: str, platform: str) -> dict[str, str]:
    """The child's environment: what ``Allocate`` returned in place of
    every ambient TPU_* variable (a pod has no others), the platform
    pinned so that a libtpu that cannot start is an error, JAX's compile
    log on, and the compile cache where the program's one rule puts it."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env.update(alloc_env)
    env.update(
        PYTHONPATH=os.pathsep.join(dict.fromkeys([bench_root, program_root])),
        JAX_PLATFORMS=platform,
        JAX_LOG_COMPILES="1",
    )
    if platform == "cpu":
        # A rehearsal: libtpu is not to be touched, whatever Allocate named;
        # the CPU backend stands in with as many devices as were granted.
        chips = len(alloc_env.get("TPU_VISIBLE_CHIPS", "0").split(","))
        for k in list(env):
            if k.startswith("TPU_"):
                del env[k]
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env.pop("BENCH_RUN", None)
    return env
