"""DevicePlugin server tests over a real gRPC unix socket, driven by the fake
kubelet's client stub (the hermetic harness the reference lacks, SURVEY.md §4)."""

import os
import threading
import time
from concurrent import futures

import grpc
import pytest

from k8s_device_plugin_tpu.kubelet import constants
from k8s_device_plugin_tpu.kubelet.api import DevicePluginStub, add_device_plugin_servicer, pb
from k8s_device_plugin_tpu.plugin import discovery
from k8s_device_plugin_tpu.plugin.health import ChipHealthChecker
from k8s_device_plugin_tpu.plugin.server import TpuDevicePlugin
from tests.fakes import make_fake_tpu_host


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
def stub(plugin, tmp_path):
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
    add_device_plugin_servicer(plugin, server)
    sock = tmp_path / "plugin.sock"
    server.add_insecure_port(f"unix://{sock}")
    server.start()
    channel = grpc.insecure_channel(f"unix://{sock}")
    yield DevicePluginStub(channel)
    channel.close()
    server.stop(grace=None)


def test_options(stub):
    opts = stub.GetDevicePluginOptions(pb.Empty())
    assert opts.pre_start_required is False
    assert opts.get_preferred_allocation_available is True


def test_list_and_watch_initial(stub):
    first = next(stub.ListAndWatch(pb.Empty()))
    assert [d.ID for d in first.devices] == ["tpu-0", "tpu-1", "tpu-2", "tpu-3"]
    assert all(d.health == constants.HEALTHY for d in first.devices)
    # NUMA topology flows through (fixture puts chips 0,1 on node 0; 2,3 on 1).
    assert first.devices[0].topology.nodes[0].ID == 0
    assert first.devices[3].topology.nodes[0].ID == 1


def test_list_and_watch_streams_health_change(stub, plugin, host_root):
    stream = stub.ListAndWatch(pb.Empty())
    first = next(stream)
    assert all(d.health == constants.HEALTHY for d in first.devices)

    # Fault-inject chip 2 via the health override drop-in, then poll.
    os.makedirs(os.path.join(host_root, "run/tpu/health"), exist_ok=True)
    with open(os.path.join(host_root, "run/tpu/health/accel2"), "w") as f:
        f.write("Unhealthy\n")
    assert plugin.poll_once() is True

    second = next(stream)
    health = {d.ID: d.health for d in second.devices}
    assert health["tpu-2"] == constants.UNHEALTHY
    assert health["tpu-0"] == constants.HEALTHY
    # Full list was REBUILT, not appended (the reference's defect,
    # reference main.go:126-132).
    assert len(second.devices) == 4

    # Recover and verify a third full snapshot arrives.
    os.unlink(os.path.join(host_root, "run/tpu/health/accel2"))
    assert plugin.poll_once() is True
    third = next(stream)
    assert {d.ID: d.health for d in third.devices}["tpu-2"] == constants.HEALTHY
    assert len(third.devices) == 4


def test_list_and_watch_hot_unplug(stub, plugin, host_root):
    stream = stub.ListAndWatch(pb.Empty())
    assert len(next(stream).devices) == 4
    os.unlink(os.path.join(host_root, "dev", "accel3"))
    assert plugin.poll_once() is True
    assert [d.ID for d in next(stream).devices] == ["tpu-0", "tpu-1", "tpu-2"]


def test_poll_once_no_change_is_quiet(plugin):
    assert plugin.poll_once() is False


def test_allocate_single_chip(stub):
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[pb.ContainerAllocateRequest(devicesIDs=["tpu-1"])]
        )
    )
    car = resp.container_responses[0]
    assert [d.host_path for d in car.devices] == ["/dev/accel1"]
    assert car.devices[0].permissions == "rw"
    assert car.envs["TPU_VISIBLE_CHIPS"] == "1"
    assert car.envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert car.envs["TPU_SKIP_MDS_QUERY"] == "true"
    assert car.envs["TPU_ACCELERATOR_TYPE"] == "v5litepod-4"
    assert car.annotations["tpu.google.com/chips"] == "tpu-1"


def test_allocate_on_a_vfio_host_mounts_group_and_container_nodes(tmp_path):
    """The captured v5e host (tests/testdata/tpu-host-v5e-vfio): a chip's
    node is its IOMMU group, every container also gets /dev/vfio/vfio,
    and a busy group node is never opened by the health probe."""
    root = os.path.join(os.path.dirname(__file__), "testdata", "tpu-host-v5e-vfio")
    vfio_plugin = TpuDevicePlugin(
        discover=lambda: discovery.discover(root=root, environ={}),
        health_checker=ChipHealthChecker(root=root),
    )
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    add_device_plugin_servicer(vfio_plugin, server)
    sock = tmp_path / "vfio.sock"
    server.add_insecure_port(f"unix://{sock}")
    server.start()
    try:
        with grpc.insecure_channel(f"unix://{sock}") as channel:
            car = DevicePluginStub(channel).Allocate(
                pb.AllocateRequest(
                    container_requests=[
                        pb.ContainerAllocateRequest(devicesIDs=["tpu-2", "tpu-3"])
                    ]
                )
            ).container_responses[0]
    finally:
        server.stop(grace=None)
    assert [d.host_path for d in car.devices] == [
        "/dev/vfio/2", "/dev/vfio/3", "/dev/vfio/vfio",
    ]
    assert car.envs["TPU_VISIBLE_CHIPS"] == "2,3"
    assert car.envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,1,1"
    assert car.annotations["tpu.google.com/pci-addresses"] == (
        "0000:00:09.0,0000:00:0b.0"
    )


def test_allocate_full_host(stub):
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[
                pb.ContainerAllocateRequest(
                    devicesIDs=["tpu-0", "tpu-1", "tpu-2", "tpu-3"]
                )
            ]
        )
    )
    car = resp.container_responses[0]
    assert [d.host_path for d in car.devices] == [f"/dev/accel{i}" for i in range(4)]
    assert car.envs["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert car.envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    assert car.envs["TPU_WORKER_ID"] == "0"


def test_allocate_contiguous_pair_bounds(stub):
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[
                pb.ContainerAllocateRequest(devicesIDs=["tpu-1", "tpu-3"])
            ]
        )
    )
    car = resp.container_responses[0]
    # chips 1,3 form the right column of the 2x2: a 1x2 block.
    assert car.envs["TPU_VISIBLE_CHIPS"] == "1,3"
    assert car.envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"


def test_allocate_fragmented_claims_chain(stub):
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[
                pb.ContainerAllocateRequest(devicesIDs=["tpu-0", "tpu-3"])
            ]
        )
    )
    # Diagonal of the 2x2: no adjacency claimed.
    assert resp.container_responses[0].envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,1,1"


def test_allocate_unknown_id_rejected(stub):
    with pytest.raises(grpc.RpcError) as err:
        stub.Allocate(
            pb.AllocateRequest(
                container_requests=[pb.ContainerAllocateRequest(devicesIDs=["tpu-9"])]
            )
        )
    assert err.value.code() == grpc.StatusCode.NOT_FOUND


def test_allocate_unhealthy_rejected(stub, plugin, host_root):
    os.makedirs(os.path.join(host_root, "run/tpu/health"), exist_ok=True)
    with open(os.path.join(host_root, "run/tpu/health/accel0"), "w") as f:
        f.write("Unhealthy\n")
    plugin.poll_once()
    with pytest.raises(grpc.RpcError) as err:
        stub.Allocate(
            pb.AllocateRequest(
                container_requests=[pb.ContainerAllocateRequest(devicesIDs=["tpu-0"])]
            )
        )
    assert err.value.code() == grpc.StatusCode.FAILED_PRECONDITION


def test_allocate_multi_container(stub):
    resp = stub.Allocate(
        pb.AllocateRequest(
            container_requests=[
                pb.ContainerAllocateRequest(devicesIDs=["tpu-0"]),
                pb.ContainerAllocateRequest(devicesIDs=["tpu-2", "tpu-3"]),
            ]
        )
    )
    assert len(resp.container_responses) == 2
    assert resp.container_responses[1].envs["TPU_VISIBLE_CHIPS"] == "2,3"


def test_preferred_allocation_contiguous(stub):
    resp = stub.GetPreferredAllocation(
        pb.PreferredAllocationRequest(
            container_requests=[
                pb.ContainerPreferredAllocationRequest(
                    available_deviceIDs=["tpu-0", "tpu-1", "tpu-2", "tpu-3"],
                    allocation_size=2,
                )
            ]
        )
    )
    ids = list(resp.container_responses[0].deviceIDs)
    assert ids == ["tpu-0", "tpu-1"]  # an adjacent row, not a diagonal


def test_preferred_allocation_respects_must_include(stub):
    resp = stub.GetPreferredAllocation(
        pb.PreferredAllocationRequest(
            container_requests=[
                pb.ContainerPreferredAllocationRequest(
                    available_deviceIDs=["tpu-0", "tpu-1", "tpu-2", "tpu-3"],
                    must_include_deviceIDs=["tpu-3"],
                    allocation_size=2,
                )
            ]
        )
    )
    ids = list(resp.container_responses[0].deviceIDs)
    assert "tpu-3" in ids and len(ids) == 2
    # The pair containing tpu-3 must be contiguous: {2,3} (row) or {1,3} (col).
    assert set(ids) in ({"tpu-2", "tpu-3"}, {"tpu-1", "tpu-3"})


def test_prestart_container(stub):
    stub.PreStartContainer(pb.PreStartContainerRequest(devicesIDs=["tpu-0"]))


def test_preferred_allocation_unknown_device_fallback_is_index_dense(tmp_path):
    # On a >9-chip host the unknown-device fallback must sort by chip index:
    # lexicographic order would put tpu-10..tpu-15 before tpu-2 and hand the
    # kubelet a mesh-scattered set.
    root = make_fake_tpu_host(tmp_path / "host16", n_chips=16)
    plugin = TpuDevicePlugin(
        discover=lambda: discovery.discover(root=root, environ={}),
        health_checker=ChipHealthChecker(root=root),
    )
    available = [f"tpu-{i}" for i in range(16)] + ["tpu-ghost"]
    resp = plugin.GetPreferredAllocation(
        pb.PreferredAllocationRequest(
            container_requests=[
                pb.ContainerPreferredAllocationRequest(
                    available_deviceIDs=available,
                    allocation_size=4,
                )
            ]
        ),
        None,
    )
    ids = list(resp.container_responses[0].deviceIDs)
    assert ids == ["tpu-0", "tpu-1", "tpu-2", "tpu-3"]
