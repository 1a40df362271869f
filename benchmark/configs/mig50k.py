"""mig50k: the A100 fleet, half of it in MIG mixed mode.  csi50k's fleet
with device groups on one node in five (a node of whole A100s, or one of
three groups: whole A100s, 3g.20gb and 1g.5gb profiles), batch jobs that
ask one group's instances, the plain reference and the checker.

Sizes come from mig50k.json (`cfg`), ids and capacities from the seed.
The fleet is csi50k's own builder, found by name; what is added here is
the device groups.
"""

from __future__ import annotations

import random
import sys

from benchmark import fleet as fleetlib
from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_slots() -> None:
    """The deployment is the one mig50k.json's `main_layer` names:
    device asks on a fleet with multi-group nodes riding the batched
    wave.  A program without device group slots sends every such eval
    down the solo path, a host walk of about a minute a cycle (gpu50k.json
    `needs`), so two warm-up cycles and six timed ones would outlast any
    run's limit, and a killed run refuses a PR.  Another deployment,
    whose speed is not reported under this name.  Said at load, before a
    fleet is built or a job is sent."""
    from nomad_tpu.ops import engine

    if not hasattr(engine, "DEVICE_GROUP_SLOTS"):
        print("benchmark: mig50k needs a wave that carries device group "
              "slots (ops/engine.py DEVICE_GROUP_SLOTS); this program has "
              "none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_slots()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem, groups)}), cpu and
    mem net of reserved; `groups` is the node's device groups in its own
    order, each (name, instances, memory attribute), () where it has
    none."""
    from nomad_tpu.structs import NodeDeviceResource

    nodes, base = load_module("configs", cfg["fleet_of"]).build_fleet(
        cfg, seed)
    shapes = {s["at"]: s["groups"] for s in cfg["gpu_shapes"]}
    rng = random.Random(f"gpus:{seed}")
    table = {}
    for i, node in enumerate(nodes):
        groups = shapes.get(i % cfg["gpu_every"], ())
        devs = []
        for g in groups:
            vendor, dtype, model = g["group"].split("/", 2)
            devs.append(NodeDeviceResource(
                vendor=vendor, type=dtype, name=model,
                instance_ids=["GPU-" + u for u in
                              fleetlib.seeded_ids(rng, g["instances"])],
                attributes=dict(g["attributes"])))
        node.resources.devices = devs
        table[node.id] = base[node.id] + (tuple(
            (g["group"], g["instances"], g["attributes"]["memory"])
            for g in groups),)
    return nodes, table


_TEMPLATE: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: batch, all datacenters, one task group of one
    task, one device request by i % len(job_mix) (a constraint on the
    group's attributes where the mix gives one), cpu and memory per
    instance asked."""
    if not _TEMPLATE:
        from nomad_tpu import mock
        from nomad_tpu.structs import Constraint, RequestedDevice, codec

        job = mock.batch_job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        tg = job.task_groups[0]
        tg.count = cfg["count_per_job"]
        tg.tasks[0].resources.devices = [RequestedDevice(
            name="x", count=1, constraints=[Constraint()])]
        _TEMPLATE.update(codec.encode(job))
    ask = cfg["job_mix"][i % len(cfg["job_mix"])]
    job = dict(_TEMPLATE, ID=f"mig-batch-{i:06d}")
    tg = dict(job["TaskGroups"][0])
    task = dict(tg["Tasks"][0])
    res = dict(task["Resources"])
    res["CPU"] = cfg["ask_cpu_mhz_per_gpu"] * ask["count"]
    res["MemoryMB"] = cfg["ask_memory_mb_per_gpu"] * ask["count"]
    dev = dict(res["Devices"][0], Name=ask["device"], Count=ask["count"])
    (con,) = dev["Constraints"]
    c = ask.get("constraint")
    dev["Constraints"] = [dict(con, LTarget=c["ltarget"],
                               Operand=c["operand"],
                               RTarget=c["rtarget"])] if c else []
    res["Devices"] = [dev]
    task["Resources"] = res
    tg["Tasks"] = [task]
    job["TaskGroups"] = [tg]
    return job


def _request(job: dict):
    """(request name, instances a task, its constraints as (ltarget,
    operand, rtarget)) of a wire-form job."""
    (dev,) = job["TaskGroups"][0]["Tasks"][0]["Resources"]["Devices"]
    return dev["Name"], dev["Count"], tuple(
        (c["LTarget"], c["Operand"], c["RTarget"])
        for c in dev.get("Constraints") or ())


def accepts(request: str, group: str) -> bool:
    """Nomad's device-name match, in plain Python: a request names a
    type, a vendor/type or a vendor/type/model, and accepts the groups
    whose id agrees on the parts it names."""
    want, have = request.split("/"), group.split("/", 2)
    if len(want) == 1:
        return have[1] == want[0]
    return have[:len(want)] == want


def satisfies(constraints, memory: str) -> bool:
    """The mix's device constraints in plain Python: an order on
    ${device.attr.memory}, numeric, as Nomad compares two numbers."""
    ops = {">=": lambda a, b: a >= b, ">": lambda a, b: a > b,
           "<=": lambda a, b: a <= b, "<": lambda a, b: a < b,
           "=": lambda a, b: a == b, "==": lambda a, b: a == b,
           "!=": lambda a, b: a != b}
    for ltarget, operand, rtarget in constraints:
        if ltarget != "${device.attr.memory}" or operand not in ops:
            return False            # not a form this configuration asks
        if not ops[operand](float(memory), float(rtarget)):
            return False
    return True


def group_accepts(request: tuple, group: tuple) -> bool:
    """Whether a request (name, count, constraints) accepts a node's
    group (name, instances, memory)."""
    name, _, constraints = request
    return accepts(name, group[0]) and satisfies(constraints, group[2])


def assignable(groups: tuple, asks: list) -> bool:
    """Whether the allocations on one node, `asks` [(request, count)],
    can each take all of their instances from ONE group of `groups` that
    their request accepts with no group over its instances: a dynamic
    program over the groups' free counts, allocation by allocation (a
    set of the reachable free-count vectors; a node has a handful of
    groups, so a few hundred vectors at most)."""
    reach = {tuple(g[1] for g in groups)}
    for request, count in sorted(asks, key=lambda a: -a[1]):
        ok = [k for k, g in enumerate(groups) if group_accepts(request, g)]
        reach = {free[:k] + (free[k] - count,) + free[k + 1:]
                 for free in reach for k in ok if free[k] >= count}
        if not reach:
            return False
    return True


def reference_ok(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """The plain reference, independent of nomad_tpu: plain Python over
    the fleet table.  `jobs` were live together.  Per node, over all of
    them, a group assignment that puts every allocation on it wholly in
    one group its request accepts, by name and constraint, with no group
    over its instances (`assignable`).  (Counts, datacenters, cpu and
    memory: fleetlib.check_placements.)  No tolerance: the semantics are
    exact.  What it cannot see is WHICH group and instances an
    allocation got: the check receives node ids only."""
    del cfg
    on_node: dict = {}
    no_group = {}
    for job in jobs:
        request = _request(job)
        for node_id in by_job.get(job["ID"], ()):
            node = fleet.get(node_id)
            if node is None:
                continue                # check_placements reports it
            if not any(group_accepts(request, g) for g in node[4]):
                no_group.setdefault(job["ID"], []).append(
                    (request[0], [g[0] for g in node[4]] or "no device"))
            on_node.setdefault(node_id, []).append((request, request[1]))
    failures = []
    if no_group:
        failures.append(
            f"{len(no_group)} jobs with allocations on nodes of no device "
            f"group their request accepts, e.g. "
            f"{[(j, v[:1]) for j, v in list(no_group.items())[:2]]}")
    over = [(n, [(r[0], c) for r, c in asks])
            for n, asks in on_node.items()
            if not assignable(fleet[n][4], asks)]
    if over:
        failures.append(f"{len(over)} nodes whose allocations no group "
                        f"assignment holds, e.g. (node, [(request, "
                        f"instances)]) {over[:2]}")
    return failures


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together on a fleet with nothing else on it (a
    cycle's; the traffic purges between cycles)."""
    failures = []
    # mig50k.json why_these_counts: what makes "no failed task group" a
    # fair demand whatever the order of evals
    whole = (cfg["whole_node_group"], cfg["whole_node_instances"])
    wholes, whole_group, have = 0, None, {}
    for n in fleet.values():
        for g in n[4]:
            have[g[0]] = have.get(g[0], 0) + g[1]
            if g[:2] == whole:
                wholes += 1
                whole_group = g
    touching = sum(j["TaskGroups"][0]["Count"] for j in jobs
                   if whole_group is not None
                   and group_accepts(_request(j), whole_group))
    asked = {}
    for j in jobs:
        name, count, _ = _request(j)
        asked[name] = (asked.get(name, 0)
                       + j["TaskGroups"][0]["Count"] * count)
    short = {name: (a, sum(h for g, h in have.items() if accepts(name, g)))
             for name, a in asked.items()}
    short = {k: v for k, v in short.items() if v[0] > v[1]}
    if touching >= wholes or short:
        failures.append(
            f"the configuration does not hold its own inequality: "
            f"{touching} tasks that accept {whole[0]} live together "
            f"against {wholes} nodes of it whole (must be fewer); "
            f"instances asked over those accepted: {short}")
    failures += fleetlib.check_placements(fleet, jobs, by_job)
    failures += reference_ok(cfg, fleet, jobs, by_job)
    multi = sum(1 for n in fleet.values() if len(n[4]) > 1)
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs, "
          f"{sum(j['TaskGroups'][0]['Count'] for j in jobs)} tasks, "
          f"{touching} of them accepting whole nodes ({wholes}), "
          f"{sum(asked.values())} instances asked of {sum(have.values())} "
          f"on {wholes} + {multi} GPU nodes, held to the plain reference; "
          f"{len(failures)} failures", flush=True)
    return failures
