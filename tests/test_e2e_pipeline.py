"""The minimum end-to-end slice (SURVEY.md §7): fake kubelet registers the
plugin, receives the device stream, allocates chips, and a JAX workload runs
with exactly the environment the plugin injected (CPU backend standing in for
the chips).  On real hardware the same code path needs only the fixture root
swapped for /."""

import json
import os
import subprocess
import sys

import pytest

from k8s_device_plugin_tpu.kubelet import constants
from k8s_device_plugin_tpu.kubelet.api import pb
from k8s_device_plugin_tpu.plugin import discovery
from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
from k8s_device_plugin_tpu.plugin.manager import PluginManager
from k8s_device_plugin_tpu.plugin.server import TpuDevicePlugin
from tests.fakes import FakeKubelet, make_fake_tpu_host

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What an allocated pod would run: honor the injected TPU env (bounds drive
# the mesh shape) and do real sharded compute on it.
WORKLOAD = r"""
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
visible = os.environ["TPU_VISIBLE_CHIPS"].split(",")
bounds = [int(v) for v in os.environ["TPU_CHIPS_PER_HOST_BOUNDS"].split(",")]
n_chips = len(visible)
assert n_chips == bounds[0] * bounds[1] * bounds[2], (visible, bounds)
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_chips}"
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
mesh = Mesh(np.array(jax.devices()[:n_chips]), ("dp",))
x = jax.device_put(jnp.ones((8 * n_chips, 64)), NamedSharding(mesh, P("dp")))
y = jax.jit(lambda a: (a @ a.T).sum())(x)
print(json.dumps({"devices": n_chips, "result": float(y),
                  "worker": os.environ.get("TPU_WORKER_ID")}))
"""


@pytest.fixture
def stack(tmp_path, plugin_dir):
    host_root = make_fake_tpu_host(tmp_path / "host", n_chips=4)
    kubelet = FakeKubelet(plugin_dir)
    kubelet.start()
    plugin = TpuDevicePlugin(
        discover=lambda: discovery.discover(root=host_root, environ={}),
        health_checker=ChipHealthChecker(root=host_root),
    )
    manager = PluginManager(
        plugin, plugin_dir=plugin_dir, watch_poll_interval=0.1
    )
    manager.start()
    assert kubelet.registered.wait(5)
    yield kubelet
    manager.stop_all()
    kubelet.stop()


def test_full_pipeline_single_chip(stack):
    kubelet = stack
    stub = kubelet.plugin_stub()

    # kubelet sees the advertised devices...
    devices = next(stub.ListAndWatch(pb.Empty())).devices
    assert [d.ID for d in devices] == ["tpu-0", "tpu-1", "tpu-2", "tpu-3"]

    # ...asks the plugin which chips it prefers, allocates them...
    pref = stub.GetPreferredAllocation(
        pb.PreferredAllocationRequest(
            container_requests=[
                pb.ContainerPreferredAllocationRequest(
                    available_deviceIDs=[d.ID for d in devices], allocation_size=2
                )
            ]
        )
    )
    chosen = list(pref.container_responses[0].deviceIDs)
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(devicesIDs=chosen)]
        )
    )
    car = resp.container_responses[0]
    assert len(car.devices) == 2

    # ...and "starts the container": run a real JAX program with exactly the
    # injected env, chips stood in by virtual CPU devices.
    env = dict(os.environ)
    env.update(dict(car.envs))
    out = subprocess.run(
        [sys.executable, "-c", WORKLOAD],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    assert result["devices"] == 2
    assert result["worker"] == "0"
    assert result["result"] == pytest.approx(64.0 * 16 * 16)


def test_full_pipeline_whole_host(stack):
    kubelet = stack
    stub = kubelet.plugin_stub()
    all_ids = [f"tpu-{i}" for i in range(4)]
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(devicesIDs=all_ids)]
        )
    )
    car = resp.container_responses[0]
    assert car.envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    env = dict(os.environ)
    env.update(dict(car.envs))
    out = subprocess.run(
        [sys.executable, "-c", WORKLOAD],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)["devices"] == 4
