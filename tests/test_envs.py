"""Unit tests for TPU environment injection.

The reference injects no env at all (reference main.go:139-159); for the TPU
build the env IS the multi-chip contract (SURVEY.md §5.8), so every branch of
`allocation_envs` — whole host, contiguous sub-block, fragmented fallback —
is pinned down here.
"""

from k8s_device_plugin_tpu.plugin.discovery import TpuChip, TpuHostInventory
from k8s_device_plugin_tpu.plugin.envs import allocation_annotations, allocation_envs
from k8s_device_plugin_tpu.plugin.topology import SubMesh


def make_inventory(n=8, bounds=(2, 4, 1), worker_id=0, hostnames=()):
    chips = tuple(
        TpuChip(
            index=i,
            device_path=f"/dev/accel{i}",
            vendor_id="0x1ae0",
            device_id="0x0063",
            pci_address=f"0000:00:{4 + i:02x}.0",
            numa_node=i // 4,
            generation="v5e",
        )
        for i in range(n)
    )
    return TpuHostInventory(
        chips=chips,
        host_bounds=bounds,
        accelerator_type="v5litepod-8",
        worker_id=worker_id,
        worker_hostnames=tuple(hostnames),
    )


def test_whole_host_envs():
    inv = make_inventory(worker_id=2, hostnames=["h0", "h1", "h2", "h3"])
    envs = allocation_envs(inv, list(inv.chips), sub_mesh=None)
    assert envs["TPU_VISIBLE_CHIPS"] == "0,1,2,3,4,5,6,7"
    assert envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,4,1"
    assert envs["TPU_WORKER_ID"] == "2"
    assert envs["TPU_WORKER_HOSTNAMES"] == "h0,h1,h2,h3"
    assert envs["TPU_SKIP_MDS_QUERY"] == "true"
    assert envs["TPU_ACCELERATOR_TYPE"] == "v5litepod-8"


def test_whole_single_host_no_hostnames():
    inv = make_inventory(n=4, bounds=(2, 2, 1))
    envs = allocation_envs(inv, list(inv.chips), sub_mesh=None)
    assert envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    assert envs["TPU_WORKER_ID"] == "0"
    assert "TPU_WORKER_HOSTNAMES" not in envs


def test_host_showing_fewer_chips_than_its_bounds_is_a_sub_block():
    """One chip of a 2x2 board (the one-chip v5e machine, PR 21): granting
    everything the host shows is still a 1,1,1 block, not the host mesh."""
    inv = make_inventory(n=1, bounds=(2, 2, 1), hostnames=["localhost"])
    envs = allocation_envs(
        inv, list(inv.chips), sub_mesh=SubMesh(origin=(0, 0, 0), bounds=(1, 1, 1))
    )
    assert envs["TPU_VISIBLE_CHIPS"] == "0"
    assert envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert envs["TPU_WORKER_ID"] == "0"
    assert "TPU_WORKER_HOSTNAMES" not in envs


def test_sub_block_envs_use_block_bounds():
    inv = make_inventory()
    chips = [inv.chips[2], inv.chips[3], inv.chips[4], inv.chips[5]]
    sub = SubMesh(origin=(0, 1, 0), bounds=(2, 2, 1))
    envs = allocation_envs(inv, chips, sub_mesh=sub)
    assert envs["TPU_VISIBLE_CHIPS"] == "2,3,4,5"
    # The container sees a standalone 2x2 mesh, not the host's 2x4.
    assert envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    assert envs["TPU_WORKER_ID"] == "0"
    assert "TPU_WORKER_HOSTNAMES" not in envs


def test_fragmented_fallback_claims_chain():
    inv = make_inventory()
    chips = [inv.chips[0], inv.chips[7], inv.chips[3]]
    envs = allocation_envs(inv, chips, sub_mesh=None)
    assert envs["TPU_VISIBLE_CHIPS"] == "0,3,7"  # sorted
    assert envs["TPU_CHIPS_PER_HOST_BOUNDS"] == "3,1,1"
    assert envs["TPU_WORKER_ID"] == "0"


def test_sub_block_never_leaks_slice_worker_identity():
    # A sub-host allocation must NOT inherit the host's worker id/hostnames:
    # it is its own single-host slice from the workload's point of view.
    inv = make_inventory(worker_id=1, hostnames=["h0", "h1"])
    sub = SubMesh(origin=(0, 0, 0), bounds=(2, 1, 1))
    envs = allocation_envs(inv, [inv.chips[0], inv.chips[1]], sub_mesh=sub)
    assert envs["TPU_WORKER_ID"] == "0"
    assert "TPU_WORKER_HOSTNAMES" not in envs


def test_no_accelerator_type_omits_env():
    inv = make_inventory(n=1, bounds=(1, 1, 1))
    inv = TpuHostInventory(
        chips=inv.chips,
        host_bounds=inv.host_bounds,
        accelerator_type=None,
        worker_id=0,
        worker_hostnames=(),
    )
    envs = allocation_envs(inv, list(inv.chips), sub_mesh=None)
    assert "TPU_ACCELERATOR_TYPE" not in envs


def test_annotations_sorted_by_index():
    inv = make_inventory(n=4, bounds=(2, 2, 1))
    ann = allocation_annotations([inv.chips[3], inv.chips[1]])
    assert ann["tpu.google.com/chips"] == "tpu-1,tpu-3"
    assert ann["tpu.google.com/pci-addresses"] == "0000:00:05.0,0000:00:07.0"
