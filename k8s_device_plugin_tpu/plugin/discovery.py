"""TPU chip discovery from devfs + sysfs (+ node metadata drop-ins).

Replaces the reference's sysfs scanner (`countGPUDev`, reference main.go:50-81,
which globs /sys/class/kfd/kfd/topology/nodes/*/properties and counts
`simd_count > 0`) with a TPU-native inventory.  A host shows its chips in
one of two forms:

- accel driver: ``/dev/accel*`` chardev nodes (the analogue of the
  reference's /dev/kfd at main.go:84,144) cross-checked against
  ``/sys/class/accel/accel*``;
- VFIO (the v5e hosts of PR 21's chip runs, tests/testdata/tpu-host-v5e-vfio):
  no accel node at all — each chip is a Google PCI function bound to
  vfio-pci, reached through ``/dev/vfio/<iommu group>`` plus the shared
  ``/dev/vfio/vfio`` container node.  Chip index = the group node's rank
  in numeric order, as libtpu counts them;
- per-chip PCI identity (vendor/device/numa/PCI address) is read from sysfs,
- host mesh bounds / accelerator type / multi-host worker metadata come from
  the environment or ``/run/tpu`` drop-in files written by node bootstrap.

Like the reference's ``topoRootParam`` test seam (main.go:52-56), every path
is resolved under an injectable filesystem root so tests (and the hermetic
demo) run against a fixture tree instead of the real ``/``.
"""

from __future__ import annotations

import glob
import logging
import os
import re
from dataclasses import dataclass
from typing import Mapping

from . import native
from .topology import bounds_str, chip_coords, host_bounds_for_count

log = logging.getLogger(__name__)

# PCI vendor id for Google accelerators.
GOOGLE_VENDOR_ID = "0x1ae0"

# Best-effort PCI device-id -> TPU generation table.  Detection never *relies*
# on this: accelerator type is taken from node metadata when present, and an
# unknown id degrades to generation=None with discovery still succeeding.
# Extend via the `extra_generations` argument to discover().
GENERATION_BY_DEVICE_ID: dict[str, str] = {
    "0x0062": "v4",
    "0x0063": "v5e",
    "0x0064": "v5p",
    "0x0065": "v6e",
}

# Node-metadata drop-in directory (under the injectable root).  Written by the
# node bootstrap / DaemonSet init container on real nodes; absent values fall
# back to environment variables and then to inference from the chip count.
TPU_METADATA_DIR = "run/tpu"

_ACCEL_DEV_RE = re.compile(r"accel(\d+)$")


# The VFIO container node every VFIO user opens beside its group node.
VFIO_CONTAINER_PATH = "/dev/vfio/vfio"


@dataclass(frozen=True)
class TpuChip:
    """One discovered TPU chip (one /dev/accelN or /dev/vfio/<group> node)."""

    index: int  # host-local chip index (accelN's N; group-node rank under VFIO)
    device_path: str  # host devfs path: "/dev/accel0" or "/dev/vfio/2"
    vendor_id: str | None = None
    device_id: str | None = None
    pci_address: str | None = None
    numa_node: int | None = None
    generation: str | None = None

    @property
    def k8s_id(self) -> str:
        """Stable device ID advertised to the kubelet."""
        return f"tpu-{self.index}"


@dataclass(frozen=True)
class TpuHostInventory:
    """Everything discovery learned about this host's TPU complement."""

    chips: tuple[TpuChip, ...]
    host_bounds: tuple[int, int, int]  # chip-mesh bounds on this host
    accelerator_type: str | None  # e.g. "v5litepod-16"
    worker_id: int  # index of this host within its slice
    worker_hostnames: tuple[str, ...]  # all hosts in the slice, worker order
    # Device nodes every container needs beside its own chips' nodes
    # (VFIO hosts: the container node; accel hosts: none).
    shared_device_paths: tuple[str, ...] = ()

    @property
    def chip_count(self) -> int:
        return len(self.chips)

    @property
    def chips_per_host_bounds_str(self) -> str:
        return bounds_str(self.host_bounds)

    def chip_by_k8s_id(self, k8s_id: str) -> TpuChip:
        for chip in self.chips:
            if chip.k8s_id == k8s_id:
                return chip
        raise KeyError(k8s_id)

    def coords_of(self, chip: TpuChip) -> tuple[int, int, int]:
        return chip_coords(chip.index, self.host_bounds)


def _read_text(path: str) -> str | None:
    try:
        with open(path, "r") as f:
            return f.read().strip()
    except OSError:
        return None


def _read_int(path: str) -> int | None:
    text = _read_text(path)
    if text is None:
        return None
    try:
        return int(text, 0)
    except ValueError:
        return None


def _pci_address_from_uevent(uevent_path: str) -> str | None:
    text = _read_text(uevent_path)
    if not text:
        return None
    for line in text.splitlines():
        key, _, value = line.partition("=")
        if key.strip() == "PCI_SLOT_NAME":
            return value.strip()
    return None


def _sysfs_chip_info(root: str, index: int) -> dict:
    """Read one chip's identity from /sys/class/accel/accelN/device/."""
    dev_dir = os.path.join(root, "sys/class/accel", f"accel{index}", "device")
    return {
        "vendor_id": _read_text(os.path.join(dev_dir, "vendor")),
        "device_id": _read_text(os.path.join(dev_dir, "device")),
        "numa_node": _read_int(os.path.join(dev_dir, "numa_node")),
        "pci_address": _pci_address_from_uevent(os.path.join(dev_dir, "uevent")),
    }


def _vfio_chips(
    root: str, generations: Mapping[str, str]
) -> tuple[list[TpuChip], int]:
    """Chips of a VFIO host and the count of Google functions sysfs shows.

    Every Google-vendor PCI function with an IOMMU group is one chip,
    reached through ``/dev/vfio/<group>``.  Its index is the rank of that
    node among the host's TPU group nodes in NUMERIC order — which is how
    libtpu 0.0.34 counts ``TPU_VISIBLE_CHIPS`` (four-chip v5e host, PR 21:
    a child granted index i held ``/dev/vfio/i`` there, while PCI order
    was groups 2, 1, 3, 0; on the one-chip machine the single node, named
    1 or 2, is index 0).  A function whose group node is absent is not
    advertised.
    """
    by_group: dict[int, str] = {}
    for dev_dir in sorted(glob.glob(os.path.join(root, "sys/bus/pci/devices/*"))):
        vendor = _read_text(os.path.join(dev_dir, "vendor"))
        if vendor is None or vendor.lower() != GOOGLE_VENDOR_ID:
            continue
        try:
            group = os.path.basename(os.readlink(os.path.join(dev_dir, "iommu_group")))
        except OSError:
            continue  # not behind an IOMMU: not a VFIO-served chip
        if group.isdigit():
            by_group[int(group)] = dev_dir
    chips: list[TpuChip] = []
    for group in sorted(by_group):
        dev_dir = by_group[group]
        if not os.path.exists(os.path.join(root, "dev/vfio", str(group))):
            log.warning(
                "sysfs shows TPU function %s (iommu group %d) but "
                "/dev/vfio/%d is absent; not advertising it",
                os.path.basename(dev_dir), group, group,
            )
            continue
        device_id = _read_text(os.path.join(dev_dir, "device"))
        chips.append(
            TpuChip(
                index=len(chips),
                device_path=f"/dev/vfio/{group}",
                vendor_id=GOOGLE_VENDOR_ID,
                device_id=device_id,
                pci_address=os.path.basename(dev_dir),
                numa_node=_read_int(os.path.join(dev_dir, "numa_node")),
                generation=generations.get((device_id or "").lower()),
            )
        )
    return chips, len(by_group)


def _metadata(root: str, name: str, environ: Mapping[str, str], env_key: str) -> str | None:
    """Node metadata: the /run/tpu drop-in file is authoritative; the env var
    is the fallback.  (A daemon inherits ambient env — the v5e chip machine
    exports TPU_ACCELERATOR_TYPE, TPU_CHIPS_PER_HOST_BOUNDS and more to every
    process — so node-level files must win over whatever the environment
    carries.)"""
    value = _read_text(os.path.join(root, TPU_METADATA_DIR, name))
    if value:
        return value
    return environ.get(env_key) or None


def discover(
    root: str = "/",
    environ: Mapping[str, str] | None = None,
    extra_generations: Mapping[str, str] | None = None,
) -> TpuHostInventory:
    """Enumerate this host's TPU chips and slice metadata.

    ``root`` redirects all devfs/sysfs/metadata reads (the test seam).
    ``environ`` defaults to ``os.environ``.
    """
    environ = os.environ if environ is None else environ
    generations = dict(GENERATION_BY_DEVICE_ID)
    if extra_generations:
        generations.update(extra_generations)

    # --- chip enumeration: /dev/accel* is authoritative for existence -------
    # One readdir in C when libtpu_probe.so is loaded (plugin/native.py);
    # glob+regex is the fallback and the behavioral reference.
    indices: set[int] = set()
    prober = native.shared_prober()
    scanned = (
        prober.scan_accel_indices(os.path.join(root, "dev")) if prober else None
    )
    if scanned is not None:
        indices = set(scanned)
    else:
        for path in glob.glob(os.path.join(root, "dev", "accel[0-9]*")):
            m = _ACCEL_DEV_RE.search(os.path.basename(path))
            if m:
                indices.add(int(m.group(1)))
    # Cross-check sysfs: a chip the driver bound but whose dev node is missing
    # is worth logging (it will be advertised Unhealthy-from-birth territory,
    # but we do not advertise what cannot be mounted).
    sysfs_indices: set[int] = set()
    for path in glob.glob(os.path.join(root, "sys/class/accel", "accel[0-9]*")):
        m = _ACCEL_DEV_RE.search(os.path.basename(path))
        if m:
            sysfs_indices.add(int(m.group(1)))
    for missing_dev in sorted(sysfs_indices - indices):
        log.warning(
            "sysfs shows accel%d but /dev/accel%d is absent; not advertising it",
            missing_dev,
            missing_dev,
        )

    chips = []
    for index in sorted(indices):
        info = _sysfs_chip_info(root, index)
        vendor = info["vendor_id"]
        if vendor is not None and vendor.lower() != GOOGLE_VENDOR_ID:
            log.warning(
                "accel%d has non-Google vendor id %s; skipping", index, vendor
            )
            continue
        device_id = info["device_id"]
        chips.append(
            TpuChip(
                index=index,
                # Advertised host path is always the real devfs path; only
                # discovery reads go through `root`.
                device_path=f"/dev/accel{index}",
                vendor_id=vendor,
                device_id=device_id,
                pci_address=info["pci_address"],
                numa_node=info["numa_node"],
                generation=generations.get((device_id or "").lower()),
            )
        )

    shared_device_paths: tuple[str, ...] = ()
    if not indices and not sysfs_indices:
        # No accel driver on this host: the chips are VFIO functions.
        chips, n_functions = _vfio_chips(root, generations)
        physical_span = n_functions
        if chips:
            shared_device_paths = (VFIO_CONTAINER_PATH,)
    else:
        # Bounds describe the PHYSICAL mesh, so infer them from the full
        # index span the driver exposed (sysfs ∪ devfs), not from how many
        # chips survived filtering: on a 2x2 host with accel2's dev node
        # missing the remaining chips {0,1,3} still sit at their 2x2
        # coordinates.
        physical_span = max(indices | sysfs_indices, default=-1) + 1

    # --- host/slice metadata ------------------------------------------------
    accelerator_type = _metadata(
        root, "accelerator-type", environ, "TPU_ACCELERATOR_TYPE"
    )

    bounds_text = _metadata(
        root, "chips-per-host-bounds", environ, "TPU_CHIPS_PER_HOST_BOUNDS"
    )
    if bounds_text:
        try:
            bx, by, bz = (int(v) for v in bounds_text.split(","))
            host_bounds = (bx, by, bz)
        except ValueError:
            log.warning("malformed chips-per-host bounds %r; inferring", bounds_text)
            host_bounds = host_bounds_for_count(physical_span)
    else:
        host_bounds = host_bounds_for_count(physical_span)

    worker_id_text = _metadata(root, "worker-id", environ, "TPU_WORKER_ID")
    try:
        worker_id = int(worker_id_text) if worker_id_text else 0
    except ValueError:
        worker_id = 0

    hostnames_text = _metadata(
        root, "worker-hostnames", environ, "TPU_WORKER_HOSTNAMES"
    )
    worker_hostnames = (
        tuple(h.strip() for h in hostnames_text.split(",") if h.strip())
        if hostnames_text
        else ()
    )

    inventory = TpuHostInventory(
        chips=tuple(chips),
        host_bounds=host_bounds,
        accelerator_type=accelerator_type,
        worker_id=worker_id,
        worker_hostnames=worker_hostnames,
        shared_device_paths=shared_device_paths,
    )
    log.info(
        "discovered %d TPU chip(s), bounds=%s, accelerator_type=%s, worker %d/%d",
        inventory.chip_count,
        inventory.chips_per_host_bounds_str,
        accelerator_type,
        worker_id,
        max(len(worker_hostnames), 1),
    )
    return inventory
