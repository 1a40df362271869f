"""csi50k: BASELINE config 5's fleet, jobs and its plain checker.

Sizes come from csi50k.json (`cfg`), ids and capacities from the seed.
"""

from __future__ import annotations

import random

from benchmark import fleet as fleetlib


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem) net of reserved})."""
    rng = random.Random(f"fleet:{seed}")
    n = cfg["nodes"]
    ids = fleetlib.seeded_ids(rng, n)
    nodes, table = [], {}
    for i in range(n):
        dc = f"dc{1 + i % cfg['datacenters']}"
        cpu = rng.choice(cfg["node_cpu_mhz"])
        mem = rng.choice(cfg["node_memory_mb"])
        nodes.append(fleetlib.make_node(
            ids[i], i, dc, cpu, mem,
            {"platform.rack": f"r{i % cfg['racks']}",
             "storage.topology": f"zone{i % cfg['zones']}"},
            csi_plugins=(cfg["csi_plugin"],)))
        table[ids[i]] = (i, dc, cpu - fleetlib.RESERVED[0],
                         mem - fleetlib.RESERVED[1])
    return nodes, table


def install(cfg: dict, nodes: list, put) -> None:
    """One volume per zone, registered over HTTP; its topology is the
    zone's nodes."""
    zones = cfg["zones"]
    for z in range(zones):
        put(f"/v1/volume/csi/vol-zone{z}", {"Volume": {
            "ID": f"vol-zone{z}", "PluginID": cfg["csi_plugin"],
            "AccessMode": cfg["volume_access_mode"],
            "AttachmentMode": "file-system", "Schedulable": True,
            "TopologyNodeIds": [n.id for n in nodes[z::zones]]}})


_TEMPLATE: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: batch, all datacenters, zone-pinned by a
    read-only CSI claim on vol-zone{i % zones}."""
    if not _TEMPLATE:
        from nomad_tpu import mock
        from nomad_tpu.structs import VolumeRequest, codec

        job = mock.batch_job()
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        tg = job.task_groups[0]
        tg.count = cfg["count_per_job"]
        tg.tasks[0].resources.cpu = cfg["ask_cpu_mhz"]
        tg.tasks[0].resources.memory_mb = cfg["ask_memory_mb"]
        tg.volumes = {"data": VolumeRequest(
            name="data", type="csi", source="vol-zone0", read_only=True)}
        _TEMPLATE.update(codec.encode(job))
    job = dict(_TEMPLATE, ID=f"csi-batch-{i:06d}")
    tg = dict(job["TaskGroups"][0])
    tg["Volumes"] = {"data": dict(
        tg["Volumes"]["data"],
        Source=f"vol-zone{i % cfg['zones']}")}
    job["TaskGroups"] = [tg]
    return job


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    failures = fleetlib.check_placements(fleet, jobs, by_job)
    zones = cfg["zones"]
    bad_zone = 0
    for job in jobs:
        source = job["TaskGroups"][0]["Volumes"]["data"]["Source"]
        zone = int(source[len("vol-zone"):])
        for node_id in by_job.get(job["ID"], ()):
            node = fleet.get(node_id)
            if node is not None and node[0] % zones != zone:
                bad_zone += 1
    if bad_zone:
        failures.append(f"{bad_zone} allocs outside their volume's zone")
    return failures
