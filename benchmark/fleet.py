"""Shared by the configurations' builders: seeded ids and a node of the
reference's mock shape (linux/amd64, docker+exec drivers, 100 MHz /
256 MB reserved), constructed directly — `Node.copy()` of a template
is a deepcopy and no cheaper than building one."""

from __future__ import annotations

import random
from typing import List

BASE_ATTRIBUTES = {
    "kernel.name": "linux", "arch": "amd64", "cpu.arch": "amd64",
    "os.name": "ubuntu", "os.version": "22.04", "driver.docker": "1",
    "driver.exec": "1", "nomad.version": "1.6.0",
}
DRIVERS = {"docker": True, "exec": True, "raw_exec": True, "mock": True}
RESERVED = (100, 256)                      # cpu MHz, memory MB


def seeded_ids(rng: random.Random, count: int) -> List[str]:
    """UUIDv4-shaped ids drawn from `rng`."""
    return ["%08x-%04x-4%03x-%04x-%012x" % (
        rng.getrandbits(32), rng.getrandbits(16), rng.getrandbits(12),
        rng.getrandbits(16), rng.getrandbits(48)) for _ in range(count)]


def make_node(node_id: str, i: int, datacenter: str, cpu: int,
              memory_mb: int, attributes: dict, csi_plugins=(),
              disk_mb: int = 100 * 1024):
    from nomad_tpu.structs import (Node, NodeReservedResources,
                                   NodeResources)

    attrs = dict(BASE_ATTRIBUTES)
    attrs["unique.hostname"] = f"bench-node-{i}"
    attrs.update(attributes)
    return Node(
        id=node_id, name=f"bench-node-{i}", datacenter=datacenter,
        attributes=attrs,
        resources=NodeResources(cpu=cpu, memory_mb=memory_mb,
                                disk_mb=disk_mb),
        reserved=NodeReservedResources(cpu=RESERVED[0],
                                       memory_mb=RESERVED[1]),
        drivers=dict(DRIVERS),
        csi_node_plugins={p: True for p in csi_plugins})


def check_placements(fleet: dict, jobs: list, by_job: dict) -> List[str]:
    """What every configuration guarantees, by plain host code over the
    fleet this benchmark built: committed == asked per job, every alloc
    on a known node in the job's datacenters, no node over
    `resources - reserved` summed over the whole run.  `fleet` maps node
    id -> (index, datacenter, cpu, memory_mb) with reserved already
    taken off; `jobs` are wire-form jobs; `by_job` maps job id -> node
    ids of its live allocations."""
    failures: List[str] = []
    used_cpu: dict = {}
    used_mem: dict = {}
    short, unknown, bad_dc = {}, 0, 0
    asked_ids = set()
    for job in jobs:
        tg = job["TaskGroups"][0]
        res = tg["Tasks"][0]["Resources"]
        cpu, mem = res["CPU"], res["MemoryMB"]
        dcs = set(job["Datacenters"])
        placed = by_job.get(job["ID"], ())
        asked_ids.add(job["ID"])
        if len(placed) != tg["Count"]:
            short[job["ID"]] = (len(placed), tg["Count"])
        for node_id in placed:
            node = fleet.get(node_id)
            if node is None:
                unknown += 1
                continue
            if node[1] not in dcs:
                bad_dc += 1
            used_cpu[node_id] = used_cpu.get(node_id, 0) + cpu
            used_mem[node_id] = used_mem.get(node_id, 0) + mem
    if short:
        failures.append(f"committed != asked for {len(short)} jobs, "
                        f"(placed, asked) e.g. {list(short.items())[:3]}")
    stray = set(by_job) - asked_ids
    if stray:
        failures.append(f"allocations of {len(stray)} jobs nobody "
                        f"registered, e.g. {sorted(stray)[:3]}")
    if unknown:
        failures.append(f"{unknown} allocs on nodes not in the fleet")
    if bad_dc:
        failures.append(f"{bad_dc} allocs outside their job's datacenters")
    over = [n for n in used_cpu
            if used_cpu[n] > fleet[n][2] or used_mem[n] > fleet[n][3]]
    if over:
        failures.append(f"{len(over)} nodes over resources - reserved, "
                        f"e.g. {over[:3]}")
    return failures
