"""spread50k: services with a `spread` stanza over three datacenters on
csi50k's fleet, mixed with plain jobs: the jobs, the plain reference and
the checker.

Sizes come from spread50k.json (`cfg`), ids and capacities from the
seed.  The fleet is csi50k's own builder, found by name.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark import fleet as fleetlib
from benchmark.loader import load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"
DISK_MB = 100 * 1024       # fleetlib.make_node's default, none reserved


def _require_spread_wave() -> None:
    """The deployment is the one spread50k.json's `main_layer` names:
    spread evals riding the batched wave.  A program whose generic
    scheduler sends every job with a spread stanza down the solo path
    runs a cycle's 192 such evals one at a time, each a launch of the
    exact scan over 50,000 nodes and a commit the worker waits out: two
    warm-up cycles and six timed ones would outlast any run's limit, and
    a killed run refuses a PR.  Another deployment, whose speed is not
    reported under this name.  Said at load, before a fleet is built or
    a job is sent."""
    from nomad_tpu.scheduler import generic

    if not hasattr(generic, "SPREAD_WAVE_MAX"):
        print("benchmark: spread50k needs a generic scheduler that admits "
              "spread evals to the wave (scheduler/generic.py "
              "SPREAD_WAVE_MAX); this program has none", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_spread_wave()


def build_fleet(cfg: dict, seed: int):
    """(nodes to load, {node id: (index, dc, cpu, mem) net of reserved}):
    csi50k's, as it is."""
    return load_module("configs", cfg["fleet_of"]).build_fleet(cfg, seed)


_TEMPLATES: dict = {}


def make_job(cfg: dict, i: int) -> dict:
    """Job i in wire form: service, all datacenters, one task group of one
    task, no update stanza (spread50k.json `reduced`: deployments), and
    the stanzas of job_mix[i % len(job_mix)]."""
    k = i % len(cfg["job_mix"])
    if k not in _TEMPLATES:
        from nomad_tpu import mock
        from nomad_tpu.structs import (OP_EQ, Affinity, Spread,
                                       SpreadTarget, codec)

        kind = cfg["job_mix"][k]
        job = mock.job()
        job.priority = cfg["job_priority"]
        job.datacenters = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        job.update = None
        tg = job.task_groups[0]
        tg.count = kind["count"]
        tg.tasks[0].resources.cpu = cfg["ask_cpu_mhz"]
        tg.tasks[0].resources.memory_mb = cfg["ask_memory_mb"]
        if "spread_weight" in kind:
            job.spreads = [Spread(
                attribute=cfg["spread_attribute"],
                weight=kind["spread_weight"],
                targets=tuple(SpreadTarget(dc, pct) for dc, pct
                              in cfg["spread_targets"].items()))]
        af = kind.get("affinity")
        if af:
            job.affinities = [Affinity(af["attribute"], OP_EQ, af["value"],
                                       weight=af["weight"])]
        _TEMPLATES[k] = codec.encode(job)
    return dict(_TEMPLATES[k], ID=f"spread-mix-{i:06d}")


# ------------------------------------------------------- plain reference

def _kind(job: dict):
    """(count, spread weight or 0, {dc: expected}, affinity rack or "")
    of a wire-form job."""
    count = job["TaskGroups"][0]["Count"]
    spreads = job.get("Spreads") or []
    weight, expected = 0, {}
    if spreads:
        (sp,) = spreads
        weight = sp["Weight"]
        expected = {t["Value"]: t["Percent"] / 100.0 * count
                    for t in sp["Targets"]}
    affinities = job.get("Affinities") or []
    rack = affinities[0]["RTarget"] if affinities else ""
    return count, weight, expected, rack


class _Fleet:
    """The fleet table as arrays, in the table's index order."""

    def __init__(self, cfg: dict, fleet: dict) -> None:
        n = len(fleet)
        self.row = {nid: v[0] for nid, v in fleet.items()}
        self.cap = np.zeros((n, 3))
        self.dc = np.zeros(n, np.int64)
        self.names = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
        for v in fleet.values():
            self.cap[v[0]] = (v[2], v[3], DISK_MB)
            self.dc[v[0]] = self.names.index(v[1])
        self.rack = np.arange(n) % cfg["racks"]


def _binpack(cap, used, ask, rows):
    """Whether one more allocation fits on `rows`, and ScoreFit / 18
    with it counted."""
    after = used[rows] + ask
    free = 1.0 - np.minimum(after[:, :2] / cap[rows, :2], 1.0)
    return ((after <= cap[rows]).all(axis=1), np.clip(
        20.0 - 10.0 ** free[:, 0] - 10.0 ** free[:, 1], 0.0, 18.0) / 18.0)


def _reference_job(fl: _Fleet, used, fits, binpack, ask, job: dict,
                   keep: bool):
    """Per-datacenter counts of `job` placed one allocation at a time on
    the fleet whose usage is `used`: each placement the arg-max, over the
    nodes of the job's datacenters where it fits, of the mean of the
    components that apply (spread50k.json `score_formulas`).  The job's
    usage stays on the fleet where `keep`, else the fleet is left as it
    was."""
    count, weight, expected, rack = _kind(job)
    n = len(fl.dc)
    exp = np.array([expected.get(d, 0.0) for d in fl.names])
    in_dc = np.isin(fl.dc, [fl.names.index(d) for d in job["Datacenters"]])
    affinity = ((fl.rack == int(rack.lstrip("r"))).astype(float)
                if rack else None)
    placed = np.zeros(len(fl.names))
    mine = np.zeros(n)
    touched = {}                   # row -> (fits, binpack, used) before
    for _ in range(count):
        collide = mine > 0
        score = binpack + np.where(collide, -(mine + 1.0) / count, 0.0)
        parts = 1.0 + collide
        if affinity is not None:
            score, parts = score + affinity, parts + 1.0
        if weight:
            boost = np.where(exp > 0,
                             (exp - (placed + 1.0)) / np.maximum(exp, 1.0),
                             0.0)
            score = score + (weight / 100.0) * np.clip(boost, -1.0,
                                                       1.0)[fl.dc]
            parts = parts + 1.0
        pick = int(np.argmax(np.where(fits & in_dc, score / parts,
                                      -np.inf)))
        if not (fits[pick] and in_dc[pick]):
            break                         # nothing fits: the job is short
        if pick not in touched:
            touched[pick] = (fits[pick], binpack[pick], used[pick].copy())
        used[pick] += ask
        mine[pick] += 1
        placed[fl.dc[pick]] += 1
        fits[pick], binpack[pick] = (
            x[0] for x in _binpack(fl.cap, used, ask, [pick]))
    if not keep:
        for row, (f, b, u) in touched.items():
            fits[row], binpack[row], used[row] = f, b, u
    return {d: int(placed[k]) for k, d in enumerate(fl.names)}


def reference_counts(cfg: dict, fleet: dict, jobs: list,
                     by_job: dict | None = None) -> list:
    """The plain reference, independent of nomad_tpu: numpy, float64.
    `jobs` were live together on a fleet with nothing else on it and
    were scheduled in this order.  Returns, per job with a stanza,
    {dc: count} as the reference places it (None for a job without one).
    Without `by_job` it is the reference's own free run of the cycle:
    every job, the plain ones too, is placed by the reference and moves
    the bin-pack scores its successors see.  With `by_job`, which says
    where the program put the jobs, each job is placed on the fleet AS
    THE JOB MET IT, the program's own earlier placements replayed: what a
    job-by-job comparison needs (spread50k.json `count_tolerance_why`)."""
    fl = _Fleet(cfg, fleet)
    n = len(fl.dc)
    used = np.zeros((n, 3))
    out = []
    ask = fits = binpack = None
    for job in jobs:
        res = job["TaskGroups"][0]["Tasks"][0]["Resources"]
        job_ask = np.array([res["CPU"], res["MemoryMB"],
                            job["TaskGroups"][0]["EphemeralDisk"]["SizeMB"]],
                           float)
        if ask is None or (job_ask != ask).any():
            ask = job_ask
            fits, binpack = _binpack(fl.cap, used, ask, np.arange(n))
        stanza = bool(_kind(job)[1])
        counts = (_reference_job(fl, used, fits, binpack, ask, job,
                                 keep=by_job is None)
                  if stanza or by_job is None else None)
        out.append(counts if stanza else None)
        rows = np.array([fl.row[nid] for nid in (by_job or {}).get(
            job["ID"], ()) if nid in fl.row], np.int64)
        if rows.size:
            np.add.at(used, rows, ask)
            uniq = np.unique(rows)
            fits[uniq], binpack[uniq] = _binpack(fl.cap, used, ask, uniq)
    return out


def gaps(cfg: dict, fleet: dict, jobs: list, by_job: dict):
    """What `check` holds to its two limits, both against the reference
    from the state each job met.  Per job with a stanza: (allocations
    off in its worst datacenter, job id, placed counts, reference
    counts).  Per kind of job, summed over the kind's jobs: (points off
    in the worst datacenter, placed shares, reference shares)."""
    # the order within a wave: the harness stamps the events of one
    # stream frame with one time and breaks the tie by job index, but the
    # read lists allocations as the store committed them, one plan after
    # another, so `by_job`'s own order is the order the jobs were placed in
    seen = {job_id: k for k, job_id in enumerate(by_job)}
    jobs = sorted(jobs, key=lambda job: seen.get(job["ID"], len(seen)))
    want = reference_counts(cfg, fleet, jobs, by_job)
    names = [f"dc{d + 1}" for d in range(cfg["datacenters"])]
    per_job, sums = [], {}
    for job, ref in zip(jobs, want):
        if ref is None:
            continue
        placed = [fleet[n][1] for n in by_job.get(job["ID"], ())
                  if n in fleet]
        got = [placed.count(d) for d in names]
        refs = [ref[d] for d in names]
        per_job.append((max(abs(g - r) for g, r in zip(got, refs)),
                        job["ID"], got, refs))
        count, weight, _, rack = _kind(job)
        kind = sums.setdefault((count, weight, rack),
                               np.zeros((2, len(names))))
        kind += (got, refs)
    per_kind = {}
    for kind, (got, refs) in sorted(sums.items()):
        g = 100.0 * got / max(got.sum(), 1.0)
        r = 100.0 * refs / max(refs.sum(), 1.0)
        # never tighter than one and a half allocations (a rehearsal's
        # kind is 80 placements: one allocation is 1.25 points)
        allowed = max(cfg["share_tolerance_points"],
                      150.0 / max(refs.sum(), 1.0))
        per_kind[kind] = (float(np.abs(g - r).max()), g, r, allowed)
    return per_job, per_kind


def check(cfg: dict, fleet: dict, jobs: list, by_job: dict) -> list:
    """`jobs` were live together and scheduled in this order on a fleet
    with nothing else on it (a cycle's; the traffic purges between
    cycles)."""
    failures = fleetlib.check_placements(fleet, jobs, by_job)
    per_job, per_kind = gaps(cfg, fleet, jobs, by_job)
    tol = cfg["count_tolerance_allocs"]
    worst = max(per_job, default=(0, "-", (), ()))
    off = {j: (got, refs) for gap, j, got, refs in per_job if gap > tol}
    shares_off = {kind: (g.round(2).tolist(), r.round(2).tolist())
                  for kind, (gap, g, r, allowed) in per_kind.items()
                  if gap > allowed}
    lines = [f"count {kind[0]} weight {kind[1]}"
             + (f" affinity {kind[2]}" if kind[2] else "") + ": "
             + "/".join(f"{v:.2f}" for v in g) + " against "
             + "/".join(f"{v:.2f}" for v in r) + f", {gap:.2f} points off "
             f"(allowed {allowed:.2f})"
             for kind, (gap, g, r, allowed) in per_kind.items()]
    print(f"check: {jobs[0]['ID'] if jobs else '-'}..: {len(jobs)} jobs, "
          f"{len(per_job)} with a stanza; summed per-datacenter shares "
          f"against the plain reference's, each job from the state it met, "
          f"by kind: " + "; ".join(lines) + "; "
          + (f"worst job {worst[1]} {list(worst[2])} against "
             f"{list(worst[3])}, {worst[0]:g} allocations off"
             if worst[0] else "every job on the reference's counts")
          + f" (allowed {tol})", flush=True)
    if off:
        failures.append(f"{len(off)} spread jobs off the reference's "
                        f"per-datacenter counts by more than {tol} "
                        f"allocations, (placed, reference) e.g. "
                        f"{list(off.items())[:2]}")
    if shares_off:
        failures.append(f"summed per-datacenter shares off the "
                        f"reference's by more than "
                        f"{cfg['share_tolerance_points']} points: "
                        f"{shares_off}")
    return failures
