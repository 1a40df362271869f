"""Sharded scale soak (ISSUE 7): the quality gauges must hold their
50k-node envelope when the SAME zoned workload runs mesh-sharded at
>=200k virtual nodes — scale must buy throughput, not quality drift.

The cluster is the bench's north-star shape (3 DCs, 5 storage zones,
zone-pinned CSI volumes) shrunk to a soak-sized placement count.  Two
gauges, two sources:

  - per-STORAGE-zone nodes-used balance (bench.py's
    quality_zone_balance_max_over_min; 1.0 at 50k on the bench) must
    stay <= 1.05 at 200k — density never collapses a volume zone;
  - the live state-store aggregates behind
    nomad.quality.{zone_balance_max_over_min,binpack_fill} (PR 5, zone
    = datacenter there) must not DRIFT from what the identical
    workload measures at 50k.

Tier-1 excludes this (slow marker); the CI multichip stage runs it.
"""

import time

import jax
import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.structs import CSIVolume, VolumeRequest

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(jax.device_count() < 2,
                       reason="needs the virtual multi-device mesh"),
]

N_EVALS = 20
PER_EVAL = 800


def _zoned_nodes(n):
    import random
    rng = random.Random(0)
    nodes = []
    zone_nodes = {z: [] for z in range(5)}
    for i in range(n):
        nd = mock.node()
        nd.datacenter = f"dc{1 + i % 3}"
        nd.attributes["storage.topology"] = f"zone{i % 5}"
        nd.csi_node_plugins["ebs0"] = True
        nd.resources.cpu = rng.choice([4000, 8000, 16000])
        nd.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(nd)
        zone_nodes[i % 5].append(nd.id)
    vols = [CSIVolume(id=f"vol-zone{z}", plugin_id="ebs0",
                      access_mode="multi-node-multi-writer",
                      topology_node_ids=tuple(zone_nodes[z]))
            for z in range(5)]
    return nodes, vols


def _run_workload(n_nodes):
    """The north-star workload shape at `n_nodes`; returns (live
    quality_summary, per-storage-zone nodes-used balance)."""
    s = Server(dev_mode=False, num_workers=1, eval_batch=N_EVALS,
               heartbeat_ttl=1e9, nack_timeout=600.0)
    assert s.engine.mesh is not None
    assert s.engine.n_devices >= 2
    s.establish_leadership()
    nodes, vols = _zoned_nodes(n_nodes)
    s.state.upsert_nodes(nodes)
    for v in vols:
        s.state.upsert_csi_volume(v)

    evals, jobs = [], []
    for i in range(N_EVALS):
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = PER_EVAL
        tg.tasks[0].resources.cpu = 10
        tg.tasks[0].resources.memory_mb = 10
        tg.volumes = {"data": VolumeRequest(
            name="data", type="csi", source=f"vol-zone{i % 5}",
            read_only=True)}
        evals.append(s.register_job(job, now=time.time()))
        jobs.append(job)

    s.start_scheduling()
    deadline = time.time() + 900
    pending = {e.id for e in evals}
    while pending and time.time() < deadline:
        done = set()
        for eid in pending:
            ev = s.state.eval_by_id(eid)
            if ev is not None and ev.status in ("complete", "failed",
                                                "canceled"):
                done.add(eid)
        pending -= done
        if pending:
            time.sleep(0.1)
    s.stop_scheduling()
    assert not pending, f"{len(pending)} evals never finished"

    snap = s.state.snapshot()
    placed = sum(1 for job in jobs
                 for a in snap.allocs_by_job(job.namespace, job.id)
                 if not a.terminal_status())
    assert placed == N_EVALS * PER_EVAL, placed
    assert s.plan_applier.stats["plans_refuted"] == 0

    # bench.py's quality axis: nodes-used per STORAGE zone (density
    # must not collapse a volume zone)
    zone_of = {nd.id: nd.attributes["storage.topology"] for nd in nodes}
    used = {a.node_id
            for job in jobs
            for a in snap.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()}
    per_zone = {f"zone{z}": 0 for z in range(5)}
    for nid in used:
        per_zone[zone_of[nid]] += 1
    counts = sorted(per_zone.values())
    assert counts[0] > 0, per_zone
    zone_nodes_balance = counts[-1] / counts[0]

    q = s.state.quality_summary()
    s.shutdown()
    return q, zone_nodes_balance


def test_quality_gauges_hold_at_200k_sharded():
    q_50k, znb_50k = _run_workload(50_000)       # the envelope
    q_200k, znb_200k = _run_workload(200_000)    # the scaled run

    # density never collapses a volume zone, at either scale (the 50k
    # bench envelope is 1.0; <= 1.05 is the ISSUE 7 gate)
    assert znb_50k <= 1.05, znb_50k
    assert znb_200k <= 1.05, znb_200k

    # the live gauges hold the 50k envelope: the per-DC alloc-balance
    # gauge must not drift (zone-pinned binpack legitimately skews DCs
    # a little — the gate is "no WORSE sharded at 4x the nodes"), and
    # bin-pack fill stays dense
    assert q_200k["zone_balance_max_over_min"] <= \
        q_50k["zone_balance_max_over_min"] * 1.05, (q_50k, q_200k)
    assert q_200k["nodes_in_use"] > 0
    assert q_200k["fill_cpu"] >= q_50k["fill_cpu"] - 0.15, (q_50k, q_200k)
    assert q_200k["fill_memory"] >= q_50k["fill_memory"] - 0.15, \
        (q_50k, q_200k)
