"""TPU environment injection for Allocate responses.

The reference injects NO environment (reference main.go:139-159 builds only
DeviceSpecs; isolation is left to the workload setting HIP_VISIBLE_DEVICES by
hand, k8s-pod-example-gpu.yaml:12-13).  For TPUs this env is the whole
multi-chip story (SURVEY.md §2.4/§5.8): libtpu forms the host-local ICI mesh
and jax.distributed coordinates across hosts purely from variables like these.
The plugin never moves tensor bytes — it tells the workload where its chips
sit so the workload's collectives ride ICI.

Checked against libtpu 0.0.34 on v5e hosts (chip runs, PR 21): with no
other TPU_* variable in the environment, the set below brings the runtime
up on exactly the granted chips, renumbered from device 0 with coordinates
from (0,0,0).
"""

from __future__ import annotations

import math

from .discovery import TpuChip, TpuHostInventory
from .topology import SubMesh, bounds_str


def allocation_envs(
    inventory: TpuHostInventory,
    chips: list[TpuChip],
    sub_mesh: SubMesh | None,
) -> dict[str, str]:
    """Environment for one container allocated ``chips``.

    ``sub_mesh`` is the contiguous block the chips form, when one was found;
    None means a fragmented selection (the kubelet ignored or couldn't honor
    our GetPreferredAllocation advice).  libtpu requires SOME bounds covering
    the chip count, so the fallback claims a 1-D chain — which DOES assert
    links that may not physically exist; mesh bring-up may then run degraded
    or fail.  That is why GetPreferredAllocation steers allocations toward
    contiguous blocks in the first place, and why the fragmented path logs a
    warning rather than being treated as normal.
    """
    indices = sorted(c.index for c in chips)
    envs: dict[str, str] = {
        # Which of the host's chips belong to this container.
        "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in indices),
        # The container must not ask the GCE metadata server for topology —
        # everything it needs is injected right here.
        "TPU_SKIP_MDS_QUERY": "true",
    }

    if len(chips) == inventory.chip_count == math.prod(inventory.host_bounds):
        # Whole host: advertise the true host mesh bounds, and (if this host
        # is part of a multi-host slice) the worker coordinates jax.distributed
        # needs to stitch hosts together over DCN.  A host that shows fewer
        # chips than its bounds hold (the one-chip v5e machine of PR 21's
        # chip runs: one VFIO group of a 2x2 board, node metadata still
        # saying 2,2,1) is a sub-block like any other.
        envs["TPU_CHIPS_PER_HOST_BOUNDS"] = inventory.chips_per_host_bounds_str
        envs["TPU_WORKER_ID"] = str(inventory.worker_id)
        if inventory.worker_hostnames:
            envs["TPU_WORKER_HOSTNAMES"] = ",".join(inventory.worker_hostnames)
    elif sub_mesh is not None:
        # Sub-host contiguous block: the container sees a standalone mesh of
        # the block's bounds; it is always worker 0 of a single-host slice.
        envs["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds_str(sub_mesh.bounds)
        envs["TPU_WORKER_ID"] = "0"
    else:
        # Fragmented fallback: claim a chain (see docstring — a known lie the
        # protocol forces; kept rare by GetPreferredAllocation).
        envs["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds_str((len(chips), 1, 1))
        envs["TPU_WORKER_ID"] = "0"

    if inventory.accelerator_type:
        envs["TPU_ACCELERATOR_TYPE"] = inventory.accelerator_type
    return envs


def allocation_annotations(chips: list[TpuChip]) -> dict[str, str]:
    """Debugging/observability annotations mirrored onto the container."""
    return {
        "tpu.google.com/chips": ",".join(c.k8s_id for c in sorted(chips, key=lambda c: c.index)),
        "tpu.google.com/pci-addresses": ",".join(
            c.pci_address or "?" for c in sorted(chips, key=lambda c: c.index)
        ),
    }
