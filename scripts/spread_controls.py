"""What spread50k's `check` reads on schedulers with a known fault, and on
two sound ones that only break ties differently: the readings that
spread50k.json's two limits are set between (PERF.md section 6, PR 32).

No device and no server: a cycle's jobs are placed on the seeded fleet by
a plain numpy scheduler, the reference's own rule with ONE fault planted,
and the placements are handed to `spread50k.check` as a run's would be.
Counts only, so the full size runs on any host in about a minute:

    PYTHONPATH=. python3 scripts/spread_controls.py [seed] [rehearse]

Rules: `sound` (spread50k.json `score_formulas`), `late` (the boost reads
the counts one placement late: (expected - placed) / expected, what
ops/scoring.py spread_boost did before PR 32), `frozen` (the boost reads
the counts the job started with), `round4` (the boost is refreshed every
fourth placement: a round of `want` 4), `even` (every datacenter expected
a third), `ignored` (no boost at all).  `sound` with lowest-index ties IS
the reference's free run; with a `tie` seed it is a second correct
scheduler: what it reads is the room a limit needs above zero, and the jobs
it places 2 and more apart from the first are why the per-job limit is
held from the state each job met.  One JSON line at the end."""
import json
import sys

import numpy as np

from benchmark.loader import load_json, load_module

RULES = ("sound", "late", "round4", "frozen", "even", "ignored")


def schedule(cfg, mod, table, jobs, rule="sound", tie=None):
    """{job id: [node id]} of `jobs` placed in order, one allocation at a
    time, by spread50k's reference rule with `rule`'s fault planted; exact
    ties fall to the lowest index, or by seed `tie` where it is given."""
    fl = mod._Fleet(cfg, table)
    n = len(fl.dc)
    ids = [None] * n
    for nid, v in table.items():
        ids[v[0]] = nid
    noise = (np.random.default_rng(tie).random(n) * 1e-9
             if tie is not None else 0.0)
    used = np.zeros((n, 3))
    by_job = {}
    for job in jobs:
        res = job["TaskGroups"][0]["Tasks"][0]["Resources"]
        ask = np.array([res["CPU"], res["MemoryMB"],
                        job["TaskGroups"][0]["EphemeralDisk"]["SizeMB"]],
                       float)
        fits, binpack = mod._binpack(fl.cap, used, ask, np.arange(n))
        count, weight, expected, rack = mod._kind(job)
        in_dc = np.isin(fl.dc, [fl.names.index(d)
                                for d in job["Datacenters"]])
        exp = np.array([expected.get(d, 0.0) for d in fl.names])
        if rule == "even":
            exp = np.full(len(fl.names), count / len(fl.names))
        affinity = ((fl.rack == int(rack.lstrip("r"))).astype(float)
                    if rack else None)
        placed, mine, rows = np.zeros(len(fl.names)), np.zeros(n), []
        stale = placed.copy()
        for k in range(count):
            if k % 4 == 0:
                stale = placed.copy()
            collide = mine > 0
            score = binpack + np.where(collide, -(mine + 1.0) / count, 0.0)
            parts = 1.0 + collide
            if affinity is not None:
                score, parts = score + affinity, parts + 1.0
            if weight and rule != "ignored":
                seen = {"late": placed, "round4": stale + 1.0,
                        "frozen": 1.0}.get(rule, placed + 1.0)
                boost = np.clip((exp - seen) / np.maximum(exp, 1.0), -1.0, 1.0)
                score = score + (weight / 100.0) * boost[fl.dc]
                parts = parts + 1.0
            pick = int(np.argmax(np.where(fits & in_dc,
                                          score / parts + noise, -np.inf)))
            used[pick] += ask
            mine[pick] += 1
            placed[fl.dc[pick]] += 1
            rows.append(pick)
            fits[pick], binpack[pick] = (
                x[0] for x in mod._binpack(fl.cap, used, ask, [pick]))
        by_job[job["ID"]] = [ids[r] for r in rows]
    return by_job


def reading(cfg, mod, table, jobs, by_job):
    """The worst job's allocations off the reference, the jobs over that
    limit, the worst kind's points off, `check`'s verdict; and the worst
    kind's points off the reference's own FREE run of the cycle, which no
    limit holds."""
    per_job, per_kind = mod.gaps(cfg, table, jobs, by_job)
    names = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
    sums = {}
    for job, own in zip(jobs, mod.reference_counts(cfg, table, jobs)):
        if own is not None:
            got = [table[nid][1] for nid in by_job[job["ID"]]]
            kind = sums.setdefault(mod._kind(job)[0],
                                   np.zeros((2, len(names))))
            kind += ([got.count(d) for d in names], [own[d] for d in names])
    return {
        "worst_job_allocs": max(g for g, *_ in per_job),
        "jobs_over_limit": sum(g > cfg["count_tolerance_allocs"]
                               for g, *_ in per_job),
        "worst_kind_points": round(max(g for g, *_ in per_kind.values()), 3),
        "correct": mod.check(cfg, table, jobs, by_job) == [],
        "worst_kind_points_off_free_run": round(max(
            float(np.abs(100.0 * k[0] / k[0].sum()
                         - 100.0 * k[1] / k[1].sum()).max())
            for k in sums.values()), 3),
    }


def jobs_apart(cfg, mod, table, jobs, a, b):
    """Jobs with a stanza whose per-datacenter counts differ by 2 and more
    between two schedulers' own free runs of the cycle."""
    names = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
    apart = 0
    for job in jobs:
        if mod._kind(job)[1]:
            ca, cb = ([[table[nid][1] for nid in run[job["ID"]]].count(d)
                       for d in names] for run in (a, b))
            apart += max(abs(x - y) for x, y in zip(ca, cb)) >= 2
    return int(apart)


def main(argv):
    seed = int(argv[1]) if len(argv) > 1 else 2147932401
    cfg = load_json("configs", "spread50k")
    traffic = load_json("traffic", "drain256-mixed")
    if len(argv) > 2:
        cfg.update(cfg["rehearse"])
        traffic.update(traffic["rehearse"])
    mod = load_module("configs", "spread50k")
    _, table = mod.build_fleet(cfg, seed)
    jobs = [mod.make_job(cfg, i) for i in range(traffic["jobs_per_cycle"])]
    out = {"seed": seed, "nodes": len(table), "jobs": len(jobs)}
    runs = {rule: schedule(cfg, mod, table, jobs, rule) for rule in RULES}
    for rule, by_job in runs.items():
        out[rule] = reading(cfg, mod, table, jobs, by_job)
    for tie in (1, 2, 3):
        other = schedule(cfg, mod, table, jobs, "sound", tie=tie)
        out[f"sound_tie{tie}"] = dict(
            reading(cfg, mod, table, jobs, other),
            jobs_2_apart_in_free_runs=jobs_apart(cfg, mod, table, jobs,
                                                 runs["sound"], other))
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv)
