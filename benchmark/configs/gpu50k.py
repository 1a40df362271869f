"""gpu50k: the GPU fleet.  csi50k's fleet with a device group on one node
in five, batch jobs that ask for device instances, the plain reference
and the checker.

Sizes come from gpu50k.json (`cfg`), ids and capacities from the seed.
The fleet is csi50k's own builder, found by name; what is added here is
the device groups.
"""

from __future__ import annotations

import random
import sys

from benchmark import fleet as fleetlib
from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_device_path() -> None:
    """The deployment is the one gpu50k.json's `main_layer` names:
    device-asking evals riding the batched wave.  A program whose generic
    scheduler lacks that path runs each such eval alone, rebuilding a
    device mask over every node on the host: about a minute a cycle of
    256 jobs (PERF.md section 6, PR 30), so two warm-up cycles and six
    timed ones would outlast any run's limit, and a killed run refuses a
    PR.  Another deployment, whose speed is not reported under this
    name.  Said at load, before a fleet is built or a job is sent."""
    from nomad_tpu.scheduler import generic

    if not hasattr(generic, "DEVICE_BATCHED"):
        print("benchmark: gpu50k needs the generic scheduler's batched "
              "device path (scheduler/generic.py DEVICE_BATCHED); this "
              "program has none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_device_path()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem, group, instances)}),
    cpu and mem net of reserved; `group` is the node's device group
    name ("" where it has none) and `instances` how many it holds."""
    from nomad_tpu.structs import NodeDeviceResource

    nodes, base = load_module("configs", cfg["fleet_of"]).build_fleet(
        cfg, seed)
    shapes = {s["at"]: s for s in cfg["gpu_shapes"]}
    rng = random.Random(f"gpus:{seed}")
    table = {}
    for i, node in enumerate(nodes):
        shape = shapes.get(i % cfg["gpu_every"])
        group, n = "", 0
        if shape is not None:
            group, n = shape["group"], shape["instances"]
            vendor, dtype, model = group.split("/", 2)
            node.resources.devices = [NodeDeviceResource(
                vendor=vendor, type=dtype, name=model,
                instance_ids=["GPU-" + u
                              for u in fleetlib.seeded_ids(rng, n)],
                attributes=dict(shape["attributes"]))]
        table[node.id] = base[node.id] + (group, n)
    return nodes, table


_TEMPLATE: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: batch, all datacenters, one task group of one
    task, one device request by i % len(job_mix), cpu and memory per
    GPU asked."""
    if not _TEMPLATE:
        from nomad_tpu import mock
        from nomad_tpu.structs import RequestedDevice, codec

        job = mock.batch_job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        tg = job.task_groups[0]
        tg.count = cfg["count_per_job"]
        tg.tasks[0].resources.devices = [RequestedDevice(name="x", count=1)]
        _TEMPLATE.update(codec.encode(job))
    ask = cfg["job_mix"][i % len(cfg["job_mix"])]
    job = dict(_TEMPLATE, ID=f"gpu-batch-{i:06d}")
    tg = dict(job["TaskGroups"][0])
    task = dict(tg["Tasks"][0])
    res = dict(task["Resources"])
    res["CPU"] = cfg["ask_cpu_mhz_per_gpu"] * ask["count"]
    res["MemoryMB"] = cfg["ask_memory_mb_per_gpu"] * ask["count"]
    res["Devices"] = [dict(res["Devices"][0], Name=ask["device"],
                           Count=ask["count"])]
    task["Resources"] = res
    tg["Tasks"] = [task]
    job["TaskGroups"] = [tg]
    return job


def _request(job: dict):
    """(request name, instances a task) of a wire-form job."""
    (dev,) = job["TaskGroups"][0]["Tasks"][0]["Resources"]["Devices"]
    return dev["Name"], dev["Count"]


def accepts(request: str, group: str) -> bool:
    """Nomad's device-name match, in plain Python: a request names a
    type, a vendor/type or a vendor/type/model, and accepts the groups
    whose id agrees on the parts it names."""
    if not group:
        return False
    want, have = request.split("/"), group.split("/", 2)
    if len(want) == 1:
        return have[1] == want[0]
    return have[:len(want)] == want


def reference_ok(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """The plain reference, independent of nomad_tpu: plain Python over
    the fleet table.  `jobs` were live together.  Per job: every
    allocation on a node whose device group the request's name accepts.
    Per node, over all of them: the GPUs asked by the allocations on it
    within its instance count.  (Counts, datacenters, cpu and memory:
    fleetlib.check_placements.)  No tolerance: the semantics are exact.
    What it cannot see is WHICH instance an allocation got: the check
    receives node ids only."""
    del cfg
    failures = []
    wrong_group = {}
    held: dict = {}
    for job in jobs:
        request, count = _request(job)
        for node_id in by_job.get(job["ID"], ()):
            node = fleet.get(node_id)
            if node is None:
                continue                # check_placements reports it
            if not accepts(request, node[4]):
                wrong_group.setdefault(job["ID"], []).append(
                    (request, node[4] or "no device"))
            held[node_id] = held.get(node_id, 0) + count
    if wrong_group:
        failures.append(
            f"{len(wrong_group)} jobs with allocations on nodes whose "
            f"device group their request does not accept, e.g. "
            f"{[(j, v[:2]) for j, v in list(wrong_group.items())[:2]]}")
    over = [(n, g, fleet[n][5]) for n, g in held.items() if g > fleet[n][5]]
    if over:
        failures.append(f"{len(over)} nodes with more GPUs asked by their "
                        f"allocations than they have instances, e.g. "
                        f"(node, asked, has) {over[:3]}")
    return failures


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together on a fleet with nothing else on it (a
    cycle's; the traffic purges between cycles)."""
    failures = []
    # gpu50k.json why_these_counts: what makes "no failed task group"
    # a fair demand whatever the order of evals
    tasks = sum(j["TaskGroups"][0]["Count"] for j in jobs)
    asked = sum(j["TaskGroups"][0]["Count"] * _request(j)[1] for j in jobs)
    whole = sum(1 for n in fleet.values()
                if n[4] == cfg["whole_node_group"])
    have = sum(n[5] for n in fleet.values())
    if tasks >= whole or asked > have:
        failures.append(
            f"the configuration does not hold its own inequality: {tasks} "
            f"tasks live together against {whole} nodes of "
            f"{cfg['whole_node_group']} (must be fewer), {asked} "
            f"instances asked of {have}")
    failures += fleetlib.check_placements(fleet, jobs, by_job)
    failures += reference_ok(cfg, fleet, jobs, by_job)
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs, "
          f"{tasks} tasks, {asked} instances asked of {have} on "
          f"{whole} + {sum(1 for n in fleet.values() if n[5]) - whole} "
          f"GPU nodes, held to the plain reference; "
          f"{len(failures)} failures", flush=True)
    return failures
