"""spread5k: BASELINE config 3's fleet, jobs, its plain reference and its
checker.

Sizes come from spread5k.json (`cfg`), node ids from the seed.
"""

from __future__ import annotations

import json
import random

import numpy as np

from benchmark import fleet as fleetlib


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem) net of reserved})."""
    rng = random.Random(f"fleet:{seed}")
    n = cfg["nodes"]
    ids = fleetlib.seeded_ids(rng, n)
    cpu, mem = cfg["node_cpu_mhz"], cfg["node_memory_mb"]
    nodes, table = [], {}
    for i in range(n):
        dc = f"dc{1 + i % cfg['datacenters']}"
        nodes.append(fleetlib.make_node(
            ids[i], i, dc, cpu, mem,
            {"platform.rack": f"r{i % cfg['racks']}"},
            disk_mb=cfg["node_disk_mb"]))
        table[ids[i]] = (i, dc, cpu - fleetlib.RESERVED[0],
                         mem - fleetlib.RESERVED[1])
    return nodes, table


_TEMPLATE: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: service, spread over the datacenters plus a
    rack affinity, so `prepare_batch` sends it down the solo path."""
    if not _TEMPLATE:
        from nomad_tpu import mock
        from nomad_tpu.structs import (OP_EQ, Affinity, Spread,
                                       SpreadTarget, codec)

        job = mock.job()
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        tg = job.task_groups[0]
        tg.count = cfg["count_per_job"]
        tg.tasks[0].resources.cpu = cfg["ask_cpu_mhz"]
        tg.tasks[0].resources.memory_mb = cfg["ask_memory_mb"]
        sp, af = cfg["spread"], cfg["affinity"]
        job.spreads = [Spread(
            attribute=sp["attribute"], weight=sp["weight"],
            targets=tuple(SpreadTarget(dc, pct)
                          for dc, pct in sp["targets"].items()))]
        job.affinities = [Affinity(af["attribute"], OP_EQ, af["value"],
                                   weight=af["weight"])]
        _TEMPLATE.update(codec.encode(job))
    return dict(_TEMPLATE, ID=f"spread-svc-{i:06d}")


_REFERENCE: dict = {}


def reference_shares(cfg: dict, n_jobs: int) -> list:
    """Per-datacenter shares (percent, one dict a job) of `n_jobs` jobs
    placed one after another on the empty fleet by a plain reference of
    Nomad's rank chain, in numpy and independent of the program: for
    each placement every node that fits is scored and the best taken.
    A node's score is the mean of the scorers that apply to it
    (scheduler/rank.go, spread.go): bin-packing (structs.ScoreFit over
    18), the job's anti-affinity where the job already has allocations
    on the node, the node affinity, and the spread boost towards the
    targets."""
    key = (json.dumps(cfg, sort_keys=True), n_jobs)
    if key not in _REFERENCE:
        used = np.zeros((cfg["nodes"], 3))
        _REFERENCE[key] = [_reference_job(cfg, used) for _ in range(n_jobs)]
    return _REFERENCE[key]


def _reference_job(cfg: dict, used) -> dict:
    """Places one job on the fleet whose usage is `used`, and adds it."""
    n, count = cfg["nodes"], cfg["count_per_job"]
    idx = np.arange(n)
    dc = idx % cfg["datacenters"]
    sp, af = cfg["spread"], cfg["affinity"]
    on_rack = (idx % cfg["racks"]) == int(af["value"].lstrip("r"))
    cap = np.array([cfg["node_cpu_mhz"] - fleetlib.RESERVED[0],
                    cfg["node_memory_mb"] - fleetlib.RESERVED[1],
                    cfg["node_disk_mb"]], float)
    ask = np.array([cfg["ask_cpu_mhz"], cfg["ask_memory_mb"],
                    make_job(cfg, 0)["TaskGroups"][0]["EphemeralDisk"][
                        "SizeMB"]], float)
    names = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
    desired = np.array([sp["targets"][d] / 100.0 * count for d in names])
    placed = np.zeros(len(desired))
    mine = np.zeros(n)                # this job's allocations a node
    affinity = np.where(on_rack, af["weight"] / abs(af["weight"]), 0.0)

    def fit_and_binpack(rows):
        """Whether one more allocation fits, and ScoreFit / 18 with it."""
        after = used[rows] + ask
        free = 1.0 - np.minimum(after[:, :2] / cap[:2], 1.0)
        return ((after <= cap).all(axis=1), np.clip(
            20.0 - 10.0 ** free[:, 0] - 10.0 ** free[:, 1], 0.0, 18.0) / 18.0)

    fits, binpack = fit_and_binpack(idx)
    for _ in range(count):
        collide = mine > 0
        anti = np.where(collide, -(mine + 1.0) / count, 0.0)
        boost = (sp["weight"] / 100.0
                 * ((desired - (placed + 1.0)) / desired)[dc])
        score = (binpack + anti + affinity + boost) / (3.0 + collide)
        pick = int(np.argmax(np.where(fits, score, -np.inf)))
        used[pick] += ask
        mine[pick] += 1
        placed[dc[pick]] += 1
        # only the picked node's fit and bin-pack score moved
        fits[pick], binpack[pick] = (x[0] for x in fit_and_binpack([pick]))
    return {d: float(100.0 * placed[k] / count) for k, d in enumerate(names)}


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together and scheduled in this order on a fleet
    with nothing else on it (a cycle's; the traffic purges between
    cycles)."""
    failures = fleetlib.check_placements(fleet, jobs, by_job)
    tol = cfg["spread_tolerance_points"]
    want = reference_shares(cfg, len(jobs))
    off = {}
    worst = 0.0
    series = []
    for job, ref in zip(jobs, want):
        placed = [fleet[n][1] for n in by_job.get(job["ID"], ())
                  if n in fleet]
        if not placed:
            continue
        # never tighter than one and a half allocations (a rehearsal's
        # job of 30 cannot split to a point)
        allowed = max(tol, 150.0 / len(placed))
        series.append("/".join(f"{100.0 * placed.count(d) / len(placed):.1f}"
                               for d in ref))
        for d, pct in ref.items():
            share = 100.0 * placed.count(d) / len(placed)
            worst = max(worst, abs(share - pct))
            if abs(share - pct) > allowed:
                off.setdefault(job["ID"], {})[d] = round(share, 2)
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: per-datacenter "
          f"shares {' '.join(series)} against the plain reference's "
          + " ".join("/".join(f"{v:.1f}" for v in r.values()) for r in want)
          + f"; worst {worst:.2f} points off (allowed {tol})", flush=True)
    if off:
        failures.append(f"{len(off)} jobs off the reference's spread by "
                        f"more than {tol} points, e.g. "
                        f"{list(off.items())[:2]}")
    return failures
