"""Discovery + topology tests against fake TPU host trees (the fixture-root
seam, generalizing reference main_test.go:7-14)."""

import pytest

from k8s_device_plugin_tpu.plugin import discovery, topology
from tests.fakes import make_fake_tpu_host


def test_discover_v5e_quad(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=4)
    inv = discovery.discover(root=root, environ={})
    assert inv.chip_count == 4
    assert [c.k8s_id for c in inv.chips] == ["tpu-0", "tpu-1", "tpu-2", "tpu-3"]
    assert inv.chips[0].device_path == "/dev/accel0"
    assert inv.chips[0].vendor_id == "0x1ae0"
    assert inv.chips[0].generation == "v5e"
    assert inv.chips[2].pci_address == "0000:00:06.0"
    assert inv.chips[3].numa_node == 1
    assert inv.host_bounds == (2, 2, 1)
    assert inv.accelerator_type == "v5litepod-4"


def test_discover_empty_host(tmp_path):
    inv = discovery.discover(root=str(tmp_path), environ={})
    assert inv.chip_count == 0
    assert inv.host_bounds == (0, 1, 1)


def test_discover_skips_foreign_vendor(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=2, vendor_id="0x10de")
    inv = discovery.discover(root=root, environ={})
    assert inv.chip_count == 0


def test_discover_dev_node_missing(tmp_path):
    # sysfs shows 4 chips but one dev node is missing: advertise only 3,
    # while the PHYSICAL mesh bounds stay 2x2 so the surviving chips keep
    # their true ICI coordinates (chip 3 is still at (1,1,0)).
    root = make_fake_tpu_host(tmp_path, n_chips=4, skip_dev_for=(2,))
    inv = discovery.discover(root=root, environ={})
    assert [c.index for c in inv.chips] == [0, 1, 3]
    assert inv.host_bounds == (2, 2, 1)
    assert inv.coords_of(inv.chip_by_k8s_id("tpu-3")) == (1, 1, 0)


def test_metadata_files_win_over_env(tmp_path):
    # Drop-in files are authoritative: a daemon can inherit ambient TPU_* env
    # (the v5e chip machine exports them to every process), which must not
    # shadow node-level truth.
    root = make_fake_tpu_host(tmp_path, n_chips=4, accelerator_type="v5litepod-4")
    inv = discovery.discover(
        root=root, environ={"TPU_ACCELERATOR_TYPE": "v5litepod-16"}
    )
    assert inv.accelerator_type == "v5litepod-4"


def test_env_fallback_when_files_absent(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=4, accelerator_type=None)
    inv = discovery.discover(
        root=root,
        environ={
            "TPU_ACCELERATOR_TYPE": "v5litepod-16",
            "TPU_WORKER_ID": "2",
            "TPU_WORKER_HOSTNAMES": "h0,h1,h2,h3",
        },
    )
    assert inv.accelerator_type == "v5litepod-16"
    assert inv.worker_id == 2
    assert inv.worker_hostnames == ("h0", "h1", "h2", "h3")


def test_unknown_device_id_still_discovers(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=4, device_id="0x9999")
    inv = discovery.discover(root=root, environ={})
    assert inv.chip_count == 4
    assert inv.chips[0].generation is None


def test_extra_generations_table(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=1, device_id="0x9999")
    inv = discovery.discover(
        root=root, environ={}, extra_generations={"0x9999": "v7"}
    )
    assert inv.chips[0].generation == "v7"


def test_explicit_bounds_metadata(tmp_path):
    root = make_fake_tpu_host(tmp_path, n_chips=8, chips_per_host_bounds="2,4,1")
    inv = discovery.discover(root=root, environ={})
    assert inv.host_bounds == (2, 4, 1)


# ---------------------------------------------------------------------------
# Topology model
# ---------------------------------------------------------------------------


def test_chip_coords_roundtrip():
    bounds = (2, 4, 1)
    for i in range(8):
        assert topology.chip_index(topology.chip_coords(i, bounds), bounds) == i
    assert topology.chip_coords(0, bounds) == (0, 0, 0)
    assert topology.chip_coords(1, bounds) == (1, 0, 0)
    assert topology.chip_coords(2, bounds) == (0, 1, 0)


@pytest.mark.parametrize(
    "count,available,bounds,expected",
    [
        # 2 chips from a full 2x2: an adjacent pair, compact (1x2 or 2x1).
        (2, [0, 1, 2, 3], (2, 2, 1), (0, 1)),
        # 4 chips from a full 2x4 host: the 2x2 square, not a 1x4 chain.
        (4, [0, 1, 2, 3, 4, 5, 6, 7], (2, 4, 1), (0, 1, 2, 3)),
        # only the right column of a 2x2 is free.
        (2, [1, 3], (2, 2, 1), (1, 3)),
        # everything.
        (8, list(range(8)), (2, 4, 1), tuple(range(8))),
    ],
)
def test_select_contiguous(count, available, bounds, expected):
    sub = topology.select_contiguous(count, available, bounds)
    assert sub is not None
    assert sub.chip_indices(bounds) == expected


def test_select_contiguous_prefers_square():
    sub = topology.select_contiguous(4, range(8), (2, 4, 1))
    assert sub.bounds in {(2, 2, 1)}


def test_select_contiguous_none_when_fragmented():
    # Diagonal chips of a 2x2 are not an axis-aligned block.
    assert topology.select_contiguous(2, [0, 3], (2, 2, 1)) is None
    # Not enough available at all.
    assert topology.select_contiguous(3, [0], (2, 2, 1)) is None


def test_host_bounds_for_count_fallback():
    assert topology.host_bounds_for_count(4) == (2, 2, 1)
    assert topology.host_bounds_for_count(8) == (2, 4, 1)
    assert topology.host_bounds_for_count(3) == (3, 1, 1)


# ------------------------------------------------- committed v5e testdata


def test_discovery_against_committed_v5e_tree():
    """Pin discovery against the static tests/testdata/tpu-vm-v5e tree — a
    hand-authored v5e host layout, NOT generated by tests/fakes.py, so the
    discovery code is checked against an independent encoding of the TPU-VM
    surface (≙ the reference's captured testdata/topology-parsing fixture,
    reference main_test.go:7-14)."""
    import os

    root = os.path.join(os.path.dirname(__file__), "testdata", "tpu-vm-v5e")
    inv = discovery.discover(root=root, environ={})
    assert inv.chip_count == 8
    assert [c.index for c in inv.chips] == list(range(8))
    assert inv.accelerator_type == "v5litepod-8"
    assert inv.host_bounds == (2, 4, 1)
    assert inv.chips_per_host_bounds_str == "2,4,1"
    assert inv.worker_id == 0
    assert inv.worker_hostnames == ("t1v-n-8f2c1d-w-0",)
    # NUMA split 4+4 from sysfs numa_node.
    assert [c.numa_node for c in inv.chips] == [0, 0, 0, 0, 1, 1, 1, 1]
    # Generation decoding from the PCI device id (0x0063 = v5e).
    assert all(c.generation == "v5e" for c in inv.chips)
    # Device nodes resolve under the tree's /dev.
    assert inv.chips[7].device_path.endswith("dev/accel7")


# ------------------------------------------- captured v5e VFIO host (PR 21)

VFIO_TREE = "tpu-host-v5e-vfio"


def _vfio_root():
    import os

    return os.path.join(os.path.dirname(__file__), "testdata", VFIO_TREE)


def test_discovery_against_captured_vfio_host():
    """Pin discovery against tests/testdata/tpu-host-v5e-vfio, captured
    from the four-chip v5e host of PR 21's chip runs: no /dev/accel*, an
    empty /sys/class/accel, four Google PCI functions behind vfio-pci —
    IOMMU groups NOT in PCI order — and /dev/vfio/{0..3,vfio}."""
    inv = discovery.discover(root=_vfio_root(), environ={})
    assert inv.chip_count == 4
    assert [c.k8s_id for c in inv.chips] == ["tpu-0", "tpu-1", "tpu-2", "tpu-3"]
    # Chip index = the group node's rank in numeric order (how libtpu
    # counts TPU_VISIBLE_CHIPS), whatever PCI function sits behind it.
    assert [(c.device_path, c.pci_address) for c in inv.chips] == [
        ("/dev/vfio/0", "0000:00:0a.0"),
        ("/dev/vfio/1", "0000:00:08.0"),
        ("/dev/vfio/2", "0000:00:09.0"),
        ("/dev/vfio/3", "0000:00:0b.0"),
    ]
    assert all(c.generation == "v5e" and c.numa_node == 0 for c in inv.chips)
    assert inv.host_bounds == (2, 2, 1)
    assert inv.shared_device_paths == ("/dev/vfio/vfio",)


def test_vfio_machine_granted_one_chip_of_four(tmp_path):
    """The one-chip machine of PR 21's chip runs: sysfs still shows all
    four functions, devfs one group node, and the ambient environment
    still says 2,2,1.  One chip is advertised, as index 0 — the index
    under which libtpu came up on it."""
    import os
    import shutil

    root = tmp_path / "host"
    shutil.copytree(_vfio_root(), root, symlinks=True)
    for group in ("0", "1", "3"):
        os.unlink(root / "dev" / "vfio" / group)
    inv = discovery.discover(
        root=str(root),
        environ={
            "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
            "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        },
    )
    assert [(c.index, c.device_path) for c in inv.chips] == [(0, "/dev/vfio/2")]
    assert inv.host_bounds == (2, 2, 1)
    assert inv.accelerator_type == "v5litepod-4"


def test_accel_host_has_no_shared_nodes(tmp_path):
    inv = discovery.discover(root=make_fake_tpu_host(tmp_path, n_chips=2), environ={})
    assert inv.shared_device_paths == ()
