"""groups50k: service jobs of three task groups (web / api / worker, each
with its own count) on csi50k's fleet; the plain reference and the
checker.

Sizes come from groups50k.json (`cfg`), ids and capacities from the
seed.  The fleet is csi50k's own builder, found by name.
"""

from __future__ import annotations

import sys

from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_multi_group_wave() -> None:
    """The deployment is the one groups50k.json's `main_layer` names:
    evals of several task groups riding the batched wave.  A program
    without it sends every eval of the mix down the solo path, one
    exact-scan launch over 50,000 nodes and one plan waited out at a
    time: another mechanism, whose speed is not reported under this
    name.  Said at load, before a fleet is built or a job is sent."""
    from nomad_tpu.scheduler import generic

    if not getattr(generic, "MULTI_GROUP_WAVE", False):
        print("benchmark: groups50k needs a wave that takes evals of "
              "several task groups (scheduler/generic.py MULTI_GROUP_WAVE);"
              " this program has none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_multi_group_wave()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem) net of reserved}):
    csi50k's, as it is."""
    return load_module("configs", cfg["fleet_of"]).build_fleet(cfg, seed)


_TEMPLATES: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: service, all datacenters, no update stanza
    (groups50k.json `reduced`: deployments), one task group of one task
    for each name in cfg["groups"], with the counts and the one ask of
    job_mix[i % len(job_mix)]."""
    mix = cfg["job_mix"]
    k = i % len(mix)
    if k not in _TEMPLATES:
        import copy

        from nomad_tpu import mock
        from nomad_tpu.structs import codec

        kind = mix[k]
        job = mock.job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        job.update = None
        base = job.task_groups[0]
        base.tasks[0].resources.cpu = kind["cpu_mhz"]
        base.tasks[0].resources.memory_mb = kind["memory_mb"]
        groups = []
        for name, count in zip(cfg["groups"], kind["counts"]):
            tg = copy.deepcopy(base)
            tg.name = name
            tg.tasks[0].name = name
            tg.count = count
            groups.append(tg)
        job.task_groups = groups
        _TEMPLATES[k] = codec.encode(job)
    return dict(_TEMPLATES[k], ID=f"groups-mix-{i:06d}")


# ------------------------------------------------------- plain reference

def asked(job: dict) -> int:
    """Placements a wire-form job asks: the sum of its groups' counts."""
    return sum(tg["Count"] for tg in job["TaskGroups"])


def job_ask(job: dict):
    """(cpu, memory) of one allocation of the job, or None where its
    groups ask differently (the check sees node ids a job, not which
    group an allocation is of, so it cannot tell their asks apart)."""
    asks = {(t["Resources"]["CPU"], t["Resources"]["MemoryMB"])
            for tg in job["TaskGroups"] for t in tg["Tasks"]}
    if len(asks) != 1 or any(len(tg["Tasks"]) != 1
                             for tg in job["TaskGroups"]):
        return None
    return asks.pop()


def reference_ok(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """The plain reference, independent of nomad_tpu: plain Python over
    the wire-form jobs, the fleet table ({node id: (index, dc, cpu,
    memory) net of reserved}) and the node ids of each job's live
    allocations.  `jobs` were live together.  Committed == the SUM of
    the job's group counts; every allocation on a known node of the
    job's datacenters; no node over `resources - reserved` over the
    jobs, each allocation at its own job's ask; no allocation of a job
    nobody registered.  No tolerance: the semantics are exact."""
    del cfg
    failures = []
    short, odd_ask, unknown, bad_dc = {}, [], 0, 0
    used: dict = {}             # node id -> [cpu, memory]
    for job in jobs:
        nodes = by_job.get(job["ID"], ())
        if len(nodes) != asked(job):
            short[job["ID"]] = (len(nodes), asked(job))
        ask = job_ask(job)
        if ask is None:
            odd_ask.append(job["ID"])
            continue
        dcs = set(job["Datacenters"])
        for node_id in nodes:
            node = fleet.get(node_id)
            if node is None:
                unknown += 1
                continue
            if node[1] not in dcs:
                bad_dc += 1
            acc = used.setdefault(node_id, [0, 0])
            acc[0] += ask[0]
            acc[1] += ask[1]
    if short:
        failures.append(f"committed != asked (the sum of the groups' "
                        f"counts) for {len(short)} jobs, (placed, asked) "
                        f"e.g. {list(short.items())[:3]}")
    if odd_ask:
        failures.append(f"{len(odd_ask)} jobs whose groups ask differently:"
                        f" the reference cannot attribute their allocations,"
                        f" e.g. {odd_ask[:3]}")
    stray = set(by_job) - {job["ID"] for job in jobs}
    if stray:
        failures.append(f"allocations of {len(stray)} jobs nobody "
                        f"registered, e.g. {sorted(stray)[:3]}")
    if unknown:
        failures.append(f"{unknown} allocs on nodes not in the fleet")
    if bad_dc:
        failures.append(f"{bad_dc} allocs outside their job's datacenters")
    over = [n for n, (cpu, mem) in used.items()
            if cpu > fleet[n][2] or mem > fleet[n][3]]
    if over:
        failures.append(f"{len(over)} nodes over resources - reserved, "
                        f"e.g. {over[:3]}")
    return failures


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together (a cycle's: the traffic purges between
    cycles).  The plain reference alone: benchmark/fleet.py
    check_placements reads a job's first group, so it is not called."""
    failures = reference_ok(cfg, fleet, jobs, by_job)
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs of "
          f"{sum(len(j['TaskGroups']) for j in jobs)} groups, "
          f"{sum(asked(j) for j in jobs)} placements, held to the plain "
          f"reference; {len(failures)} failures", flush=True)
    return failures
