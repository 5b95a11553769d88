"""Host parsing and rank/slot assignment.

(ref: horovod/runner/common/util/hosts.py:106-155 — parse_hosts +
get_host_assignments packing hosts in order into SlotInfo{rank,
local_rank, cross_rank, sizes}.)

On TPU pods the "hosts" are TPU-VM workers; `discover_tpu_hosts` maps
the slice topology into the same HostInfo shape so one assignment path
serves ssh clusters and TPU slices.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional


@dataclasses.dataclass
class HostInfo:
    hostname: str
    slots: int

    @staticmethod
    def from_string(host_string: str) -> "HostInfo":
        if ":" in host_string:
            hostname, slots = host_string.strip().rsplit(":", 1)
            return HostInfo(hostname, int(slots))
        return HostInfo(host_string.strip(), 1)


@dataclasses.dataclass
class SlotInfo:
    hostname: str
    rank: int
    local_rank: int
    cross_rank: int
    size: int
    local_size: int
    cross_size: int

    def to_response_string(self) -> str:
        # Wire format used by the elastic rendezvous `rank_and_size`
        # endpoint (ref: runner/elastic/rendezvous.py:40-52).
        return ",".join(
            str(v) for v in (
                self.rank, self.size, self.local_rank, self.local_size,
                self.cross_rank, self.cross_size,
            )
        )


INVALID_SLOT = SlotInfo("", -1, -1, -1, -1, -1, -1)


def parse_hosts(hosts_string: str) -> List[HostInfo]:
    """"h1:2,h2:4" → [HostInfo] (ref: hosts.py parse_hosts)."""
    return [HostInfo.from_string(s) for s in hosts_string.split(",") if s]


def parse_hostfile(path: str) -> List[HostInfo]:
    """mpirun-style hostfile: `host slots=N` or `host:N` per line."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            if "slots=" in line:
                name, _, slots = line.partition("slots=")
                out.append(HostInfo(name.strip(), int(slots)))
            else:
                out.append(HostInfo.from_string(line))
    return out


def get_host_assignments(
    hosts: List[HostInfo], min_np: int, max_np: Optional[int] = None
) -> List[SlotInfo]:
    """Pack hosts in order into global/local/cross ranks
    (ref: hosts.py:106-155). Raises if fewer than min_np slots exist;
    stops at max_np slots when given."""
    rank = 0
    assignments: List[List[SlotInfo]] = []
    for cross_rank_base, host in enumerate(hosts):
        local: List[SlotInfo] = []
        for local_rank in range(host.slots):
            if max_np is not None and rank >= max_np:
                break
            local.append(
                SlotInfo(
                    hostname=host.hostname,
                    rank=rank,
                    local_rank=local_rank,
                    cross_rank=len(assignments),
                    size=0,
                    local_size=0,
                    cross_size=0,
                )
            )
            rank += 1
        if local:
            assignments.append(local)
    world = rank
    if world < min_np:
        raise ValueError(
            f"requested {min_np} processes but hosts provide only {world} "
            f"slots: {[f'{h.hostname}:{h.slots}' for h in hosts]}"
        )
    # Fill sizes: local_size per host, cross_size per local_rank column.
    slots = [s for host_slots in assignments for s in host_slots]
    local_sizes = {i: len(hs) for i, hs in enumerate(assignments)}
    cross_sizes: Dict[int, int] = {}
    for s in slots:
        cross_sizes[s.local_rank] = cross_sizes.get(s.local_rank, 0) + 1
    for s in slots:
        s.size = world
        s.local_size = local_sizes[s.cross_rank]
        s.cross_size = cross_sizes[s.local_rank]
    return slots


def discover_tpu_hosts() -> Optional[List[HostInfo]]:
    """TPU-VM slice topology → hosts (one slot per host process; chips
    are addressed through the jax mesh, not extra ranks). Returns None
    off a multi-host slice. (Replaces the reference's ssh+NIC probing,
    ref: runner/driver/driver_service.py:124-192, per SURVEY.md §5.8.)

    Reads the Cloud TPU VM metadata env only (TPU_WORKER_HOSTNAMES, set
    on every worker of a pod slice). The launcher must finish discovery
    with no jax backend initialised: a chip belongs to one process at a
    time, and a parent that opened it would keep it from its workers."""
    names = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    hosts = [h.strip() for h in names.split(",") if h.strip()]
    if len(hosts) > 1:
        return [HostInfo(h, 1) for h in hosts]
    return None


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted from the device nodes the
    runtime opens (/dev/vfio/<n> on v5e and later, /dev/accel<n>
    before) — without loading jax or libtpu. The PCI bus is not used:
    it lists chips the VM was not given."""
    return (len(glob.glob("/dev/vfio/[0-9]*"))
            or len(glob.glob("/dev/accel[0-9]*")))

