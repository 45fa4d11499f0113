"""TPU device manager — the component that replaces the reference's GPU
manager wholesale (``pkg/worker/nvidia.go``: device assignment map, CDI spec
generation, env injection).

On a TPU VM host, chips appear as ``/dev/accel{0..n}`` (or ``/dev/vfio/*``)
and user code reaches them through libtpu. The manager:

- inventories chips (``/dev/accel*``, else the numbered ``/dev/vfio``
  groups a v5e host exposes; ``TPU9_FAKE_TPU_CHIPS`` fakes an inventory for
  tests/dev, playing the role nvidia-smi mocks play in the reference);
- assigns chips to containers exclusively (scheduler guarantees fit; the
  manager enforces it);
- emits the device list + env a container needs: ``JAX_PLATFORMS``,
  ``TPU_VISIBLE_CHIPS``, ``TPU_CHIPS_PER_PROCESS_BOUNDS``,
  ``TPU_PROCESS_BOUNDS``, ``TPU_ACCELERATOR_TYPE``, ``TPU_SKIP_MDS_QUERY``,
  plus gang env (``TPU9_GANG_*``, ``TPU_WORKER_ID``,
  ``TPU_WORKER_HOSTNAMES``, ``JAX_COORDINATOR_ADDRESS``) for multi-host
  slices — the TPU analogue of ``NVIDIA_VISIBLE_DEVICES`` injection
  (nvidia.go:289-440). The container starts from an allowlisted environment,
  so this is ALL libtpu learns about the host. A v5e host initialised from
  exactly this set with one process owning one chip of one and four of four
  (PR 21); several processes sharing a host's chips are unproven.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Optional

from ..config import env_tpu_gen
from ..types import ContainerRequest, TpuSpec


@dataclass
class TpuAssignment:
    chip_ids: list[int]
    devices: list[str]
    env: dict[str, str] = field(default_factory=dict)


class TpuDeviceManager:
    def __init__(self, generation: str = "", hostnames: str = "") -> None:
        self.generation = generation or env_tpu_gen()
        self.hostnames = hostnames
        fake = int(os.environ.get("TPU9_FAKE_TPU_CHIPS") or 0)
        # a faked chip can only be driven by the CPU backend
        self._platform = "cpu" if fake else "tpu"
        self._devices = [f"/dev/fake-accel{i}" for i in range(fake)] \
            if fake else self._inventory()
        self._assigned: dict[str, list[int]] = {}   # container_id -> chip ids

    @staticmethod
    def _inventory() -> list[str]:
        return sorted(glob.glob("/dev/accel*")) or sorted(
            glob.glob("/dev/vfio/[0-9]*"))

    @property
    def chip_count(self) -> int:
        return len(self._devices)

    @property
    def free_chips(self) -> int:
        used = sum(len(v) for v in self._assigned.values())
        return self.chip_count - used

    def assign(self, request: ContainerRequest) -> Optional[TpuAssignment]:
        """Exclusively assign the chips a request needs on this host.
        Returns None for CPU-only requests; raises if capacity is violated
        (the scheduler should never let that happen)."""
        spec = request.tpu_spec()
        if spec is None:
            return None
        need = spec.chips_per_host
        free = [i for i in range(self.chip_count)
                if not any(i in v for v in self._assigned.values())]
        if len(free) < need:
            raise RuntimeError(
                f"worker out of chips: need {need}, free {len(free)} "
                f"(scheduler/manager disagree)")
        chip_ids = free[:need]
        self._assigned[request.container_id] = chip_ids
        return TpuAssignment(
            chip_ids=chip_ids,
            devices=[self._devices[i] for i in chip_ids],
            env=self._env_for(request, spec, chip_ids),
        )

    def release(self, container_id: str) -> None:
        self._assigned.pop(container_id, None)

    def _env_for(self, request: ContainerRequest, spec: TpuSpec,
                 chip_ids: list[int]) -> dict[str, str]:
        env = {
            # explicit, never inferred: a TPU container whose libtpu cannot
            # reach the chip must fail, not fall back to the CPU backend
            # (and a zygote-forked runner re-points jax at exactly this)
            "JAX_PLATFORMS": self._platform,
            "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in chip_ids),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": _bounds_for(len(chip_ids)),
            "TPU_PROCESS_BOUNDS": "1,1,1",
            # the platform's own spelling ("v5litepod-4", not "v5e-4")
            "TPU_ACCELERATOR_TYPE": spec.gce_accelerator_type,
            # topology comes from this env, not the metadata server: where
            # none answers, libtpu init never returns (PR 21: every probed
            # env without this hung past 120 s)
            "TPU_SKIP_MDS_QUERY": "1",
            "TPU9_SLICE_TOPOLOGY": spec.topology,
        }
        gang = request.gang
        if gang is not None and gang.size > 1:
            env.update({
                "TPU9_GANG_ID": gang.gang_id,
                "TPU9_GANG_RANK": str(gang.rank),
                "TPU9_GANG_SIZE": str(gang.size),
                "TPU9_COORDINATOR_ADDR": gang.coordinator_addr,
                # libtpu multi-host wiring (the reference sets the NCCL
                # equivalents MASTER_ADDR etc. only for CRIU, criu.go:62)
                "TPU_WORKER_ID": str(gang.rank),
                "TPU_WORKER_HOSTNAMES": self.hostnames or gang.coordinator_addr.split(":")[0],
                "JAX_COORDINATOR_ADDRESS": gang.coordinator_addr,
            })
        return env


def _bounds_for(chips: int) -> str:
    """Chips-per-process bounds string for common per-host chip counts."""
    return {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}.get(
        chips, f"{chips},1,1")
