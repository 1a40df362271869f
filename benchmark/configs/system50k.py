"""system50k: one fleet-wide system job on csi50k's fleet, its plain
reference and its checker.

Sizes come from system50k.json (`cfg`), ids and capacities from the seed.
The fleet is csi50k's own builder, found by name; what is added here is
the nodes held out of scheduling.
"""

from __future__ import annotations

import sys

from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_device_path() -> None:
    """The deployment is the one system50k.json's `main_layer` names: a
    system eval placed by one place_system launch and one block.  A
    program whose system scheduler lacks that path walks the 50,000
    nodes on the host, some 15 times slower (PERF.md section 6, PR 26):
    another deployment, whose speed is not reported under this name.
    Said at load, before a fleet is built or a job is sent."""
    from nomad_tpu.scheduler import system

    if not hasattr(system, "SYSTEM_BATCHED"):
        print("benchmark: system50k needs the system scheduler's device "
              "path (scheduler/system.py SYSTEM_BATCHED, place_system); "
              "this program has none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_device_path()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem, eligible,
    attributes)}), cpu and mem net of reserved."""
    nodes, base = load_module("configs", cfg["fleet_of"]).build_fleet(
        cfg, seed)
    table = {}
    for i, node in enumerate(nodes):
        eligible = i % cfg["ineligible_every"] != cfg["ineligible_at"]
        if not eligible:
            node.scheduling_eligibility = "ineligible"
        table[node.id] = base[node.id] + (eligible, node.attributes)
    return nodes, table


_TEMPLATE: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: a system job over every datacenter, one task
    group of one task, the mock job's kernel.name constraint."""
    if not _TEMPLATE:
        from nomad_tpu import mock
        from nomad_tpu.structs import codec

        job = mock.system_job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        task = job.task_groups[0].tasks[0]
        task.resources.cpu = cfg["ask_cpu_mhz"]
        task.resources.memory_mb = cfg["ask_memory_mb"]
        _TEMPLATE.update(codec.encode(job))
    return dict(_TEMPLATE, ID=f"system-daemon-{i:06d}")


def _ask(job: dict):
    tasks = job["TaskGroups"][0]["Tasks"]
    return (sum(t["Resources"]["CPU"] for t in tasks),
            sum(t["Resources"]["MemoryMB"] for t in tasks))


def _passes(attributes: dict, job: dict) -> bool:
    """The job's constraints and its tasks' drivers against one node's
    attributes.  `${attr.<key>} = <value>` is the only form the
    configuration's jobs carry; another is an error, not a pass."""
    tg = job["TaskGroups"][0]
    rows = (list(job.get("Constraints") or ())
            + list(tg.get("Constraints") or ())
            + [c for t in tg["Tasks"] for c in t.get("Constraints") or ()])
    for c in rows:
        target = c["LTarget"]
        if c["Operand"] != "=" or not target.startswith("${attr."):
            raise ValueError(f"the plain reference cannot judge {c}")
        if attributes.get(target[len("${attr."):-1]) != c["RTarget"]:
            return False
    return all(attributes.get("driver." + t["Driver"]) for t in tg["Tasks"])


def reference_nodes(cfg: dict, fleet: dict, job: dict, held=None) -> set:
    """The node ids a system job belongs on, by plain host code over the
    fleet table: eligible, in the job's datacenters, passing its
    constraints, with cpu and memory left for its ask net of reserved
    and of what the cycle's earlier jobs hold (`held`: {node id: [cpu,
    mem]}).  Independent of nomad_tpu."""
    del cfg
    held = held or {}
    dcs = set(job["Datacenters"])
    cpu, mem = _ask(job)
    out = set()
    for node_id, (_, dc, cap_cpu, cap_mem, eligible, attrs) in fleet.items():
        if not eligible or dc not in dcs or not _passes(attrs, job):
            continue
        used_cpu, used_mem = held.get(node_id, (0, 0))
        if used_cpu + cpu <= cap_cpu and used_mem + mem <= cap_mem:
            out.add(node_id)
    return out


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together and scheduled in this order on a fleet
    with nothing else on it (a cycle's; the traffic purges between
    cycles).  Each is held to SET EQUALITY with the plain reference:
    exactly one allocation on each reference node, none elsewhere, none
    twice.  No tolerance: the semantics are exact."""
    failures = []
    held: dict = {}
    wrong = {}
    asked_ids = set()
    for job in jobs:
        asked_ids.add(job["ID"])
        want = reference_nodes(cfg, fleet, job, held)
        placed = by_job.get(job["ID"], ())
        got = set(placed)
        if len(placed) != len(got) or got != want:
            wrong[job["ID"]] = {
                "twice": len(placed) - len(got),
                "missing": len(want - got), "elsewhere": len(got - want),
                "e.g.": sorted(want ^ got)[:3]}
        if len(want) != cfg["count_per_job"]:
            failures.append(
                f"{job['ID']}: the reference places {len(want)}, the "
                f"configuration states {cfg['count_per_job']} a job")
        cpu, mem = _ask(job)
        for node_id in placed:
            if node_id in fleet:
                acc = held.setdefault(node_id, [0, 0])
                acc[0] += cpu
                acc[1] += mem
    if wrong:
        failures.append(f"{len(wrong)} jobs not on exactly the reference's "
                        f"nodes, e.g. {list(wrong.items())[:2]}")
    stray = set(by_job) - asked_ids
    if stray:
        failures.append(f"allocations of {len(stray)} jobs nobody "
                        f"registered, e.g. {sorted(stray)[:3]}")
    over = [n for n, (cpu, mem) in held.items()
            if cpu > fleet[n][2] or mem > fleet[n][3]]
    if over:
        failures.append(f"{len(over)} nodes over resources - reserved, "
                        f"e.g. {over[:3]}")
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs "
          f"held to set equality with the plain reference, "
          f"{len(wrong)} off it", flush=True)
    return failures
