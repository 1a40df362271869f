"""ports50k: service jobs with a `network` block on csi50k's fleet:
dynamic ports on small evals, a static port on every eighth job, a
`distinct_hosts` job and a job without ports beside them; the plain
reference and the checker.

Sizes come from ports50k.json (`cfg`), ids and capacities from the seed.
The fleet is csi50k's own builder, found by name.
"""

from __future__ import annotations

import sys

from benchmark import fleet as fleetlib
from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_static_port_feasibility() -> None:
    """The deployment is the one ports50k.json's `main_layer` names: a
    static port as a feasibility rule of the placement kernels.  A
    program whose kernels hold no port picks a node by bin-packing and
    lets the host find the port taken, so static asks that have a free
    node fail, leave failed task groups and blocked evals behind, and
    the run can never read `correct` (on 40 nodes such a program placed 6
    and 12 of 24: ISSUE 38).  Another deployment.  Said at load, before
    a fleet is built or a job is sent."""
    from nomad_tpu.ops import engine

    if not getattr(engine, "STATIC_PORT_FEASIBILITY", False):
        print("benchmark: ports50k needs placement kernels that see "
              "static ports (ops/engine.py STATIC_PORT_FEASIBILITY); "
              "this program has none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_static_port_feasibility()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem) net of reserved}):
    csi50k's, as it is."""
    return load_module("configs", cfg["fleet_of"]).build_fleet(cfg, seed)


_TEMPLATES: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: service, all datacenters, one task group of
    one task, no update stanza (ports50k.json `reduced`: deployments),
    and the count, ask, network block and constraint of
    job_mix[i % len(job_mix)]; a static port's value goes round
    `static_ports` by i // len(job_mix)."""
    mix = cfg["job_mix"]
    k = i % len(mix)
    if k not in _TEMPLATES:
        from nomad_tpu import mock
        from nomad_tpu.structs import (OP_DISTINCT_HOSTS, Constraint,
                                       NetworkResource, Port, codec)

        kind = mix[k]
        job = mock.job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        job.update = None
        tg = job.task_groups[0]
        tg.count = kind["count"]
        tg.tasks[0].resources.cpu = kind["cpu_mhz"]
        tg.tasks[0].resources.memory_mb = kind["memory_mb"]
        if kind.get("static") or kind.get("dynamic"):
            tg.networks = [NetworkResource(
                reserved_ports=[Port(label=label, value=1)
                                for label in kind.get("static", ())],
                dynamic_ports=[Port(label=label)
                               for label in kind.get("dynamic", ())])]
        if kind.get("distinct_hosts"):
            tg.constraints.append(Constraint(operand=OP_DISTINCT_HOSTS,
                                             rtarget="true"))
        _TEMPLATES[k] = codec.encode(job)
    job = dict(_TEMPLATES[k], ID=f"ports-mix-{i:06d}")
    if mix[k].get("static"):
        value = cfg["static_ports"][(i // len(mix)) % len(cfg["static_ports"])]
        tg = dict(job["TaskGroups"][0])
        (net,) = tg["Networks"]
        tg["Networks"] = [dict(net, ReservedPorts=[
            dict(p, Value=value) for p in net["ReservedPorts"]])]
        job["TaskGroups"] = [tg]
    return job


# ------------------------------------------------------- plain reference

def static_asks(job: dict) -> list:
    """The static port values a wire-form job's group asks."""
    tg = job["TaskGroups"][0]
    nets = list(tg.get("Networks") or ())
    for task in tg["Tasks"]:
        nets += task["Resources"].get("Networks") or ()
    return [p["Value"] for net in nets
            for p in net.get("ReservedPorts") or () if p["Value"]]


def distinct_hosts(job: dict) -> bool:
    tg = job["TaskGroups"][0]
    return any(c["Operand"] == "distinct_hosts"
               for c in (job.get("Constraints") or [])
               + (tg.get("Constraints") or []))


def reference_ok(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """The plain reference, independent of nomad_tpu: plain Python over
    the wire-form jobs and the node ids of their live allocations.
    `jobs` were live together.  A `distinct_hosts` job's allocations sit
    on different nodes; for each static port value, no node holds two
    live allocations that ask it, across ALL the jobs (a host has one
    port 8080); an allocation that asks a static port twice over cannot
    exist.  (Counts, datacenters, cpu and memory:
    fleetlib.check_placements.)  No tolerance: the semantics are exact.
    What it cannot see is port VALUES as assigned: the check receives
    node ids only; the ask is the job's."""
    del cfg, fleet
    failures = []
    doubled = {}
    holders: dict = {}          # static value -> {node id: job ids}
    for job in jobs:
        nodes = by_job.get(job["ID"], ())
        if distinct_hosts(job) and len(set(nodes)) != len(nodes):
            doubled[job["ID"]] = len(nodes) - len(set(nodes))
        for value in static_asks(job):
            on = holders.setdefault(value, {})
            for node_id in nodes:
                on.setdefault(node_id, []).append(job["ID"])
    if doubled:
        failures.append(
            f"{len(doubled)} distinct_hosts jobs with two allocations on "
            f"one node, e.g. {list(doubled.items())[:3]}")
    for value, on in sorted(holders.items()):
        twice = {n: j for n, j in on.items() if len(j) > 1}
        if twice:
            failures.append(
                f"static port {value}: {len(twice)} nodes hold two live "
                f"allocations that ask it, e.g. "
                f"{[(n, j[:3]) for n, j in list(twice.items())[:2]]}")
    return failures


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together (the traffic keeps every job: all the
    run's)."""
    failures = fleetlib.check_placements(fleet, jobs, by_job)
    failures += reference_ok(cfg, fleet, jobs, by_job)
    asked = {}
    for job in jobs:
        for value in static_asks(job):
            asked[value] = asked.get(value, 0) + job["TaskGroups"][0]["Count"]
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs, "
          f"{sum(j['TaskGroups'][0]['Count'] for j in jobs)} placements, "
          f"{sum(1 for j in jobs if distinct_hosts(j))} distinct_hosts "
          f"jobs, static asks by value {dict(sorted(asked.items()))}, "
          f"held to the plain reference; {len(failures)} failures",
          flush=True)
    return failures
