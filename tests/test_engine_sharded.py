"""Engine-level sharded-vs-single-device parity (SURVEY §7 P7).

The conftest forces 8 virtual CPU devices, so PlacementEngine() auto-builds
a node-axis mesh — THE production multi-device path.  These tests pin that
the full engine (packing, padding, caches, unpack) produces the same Plans
sharded as single-device (`mesh=False`) at realistic node counts, for all
three paths: the exact scan, a solo water-fill eval (the multi-eval
kernel as a wave of one item) and the multi-eval batch.
"""

import random

import jax
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops import PlacementEngine
from nomad_tpu.ops.engine import BatchItem
from nomad_tpu.scheduler import Harness

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs the virtual multi-device mesh")


def build(n_nodes, seed=0):
    rng = random.Random(seed)
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = f"dc{1 + i % 3}"
        n.attributes["platform.rack"] = f"r{i % 20}"
        n.resources.cpu = rng.choice([4000, 8000, 16000])
        n.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(n)
    h.state.upsert_nodes(nodes)
    return h


def engines():
    sharded = PlacementEngine()
    single = PlacementEngine(mesh=False)
    assert sharded.mesh is not None
    assert single.mesh is None
    return sharded, single


class TestShardedEngineParity:
    def test_bulk_plan_parity_5k_nodes(self):
        h = build(5000)
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 2000
        tg.tasks[0].resources.cpu = 100
        tg.tasks[0].resources.memory_mb = 64
        h.state.upsert_job(job)
        snap = h.state.snapshot()
        sharded, single = engines()
        assert sharded is not None
        bd_s = sharded.place(snap, job, job.task_groups, None,
                             seed=13,
                             block=(tg.name, 2000))
        bd_1 = single.place(snap, job, job.task_groups, None,
                            seed=13,
                            block=(tg.name, 2000))
        assert np.array_equal(np.sort(bd_s.picks), np.sort(bd_1.picks))
        for m_s, m_1 in zip(bd_s.metrics, bd_1.metrics):
            assert m_s.nodes_filtered == m_1.nodes_filtered
            assert m_s.nodes_exhausted == m_1.nodes_exhausted
            assert m_s.nodes_evaluated == m_1.nodes_evaluated == 5000

    def test_scan_plan_parity_spread_job(self):
        from nomad_tpu.structs import Affinity, OP_EQ, Spread, SpreadTarget
        h = build(1200, seed=7)
        job = mock.job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 90
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 32
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                              targets=[SpreadTarget("dc1", 50),
                                       SpreadTarget("dc2", 30),
                                       SpreadTarget("dc3", 20)])]
        job.affinities = [Affinity("${attr.platform.rack}", OP_EQ, "r3",
                                   weight=50)]
        h.state.upsert_job(job)
        snap = h.state.snapshot()
        sharded, single = engines()
        from nomad_tpu.ops import PlacementRequest
        reqs = [PlacementRequest(tg_name=tg.name)] * 90
        d_s = sharded.place(snap, job, job.task_groups, reqs, seed=13)
        d_1 = single.place(snap, job, job.task_groups, reqs, seed=13)
        picks_s = [d.node_id for d in d_s]
        picks_1 = [d.node_id for d in d_1]
        # spread state updates sequentially: order-exact parity expected
        assert picks_s == picks_1
        for a, b in zip(d_s, d_1):
            assert abs(a.score - b.score) < 1e-5
            assert a.metric.nodes_filtered == b.metric.nodes_filtered

    def test_multi_eval_batch_parity(self):
        h = build(3000, seed=5)
        jobs = []
        for i in range(8):
            job = mock.batch_job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            tg = job.task_groups[0]
            tg.count = [150, 40, 700, 5, 260, 90, 1, 330][i]
            tg.tasks[0].resources.cpu = 80
            tg.tasks[0].resources.memory_mb = 48
            h.state.upsert_job(job)
            jobs.append(job)
        snap = h.state.snapshot()
        sharded, single = engines()
        items = [BatchItem(job=j, tg=j.task_groups[0],
                           count=j.task_groups[0].count) for j in jobs]
        ds = sharded.place_batch(snap, items, seed=21)
        d1 = single.place_batch(snap, items, seed=21)
        for a, b in zip(ds, d1):
            assert np.array_equal(np.sort(a.picks), np.sort(b.picks))

    def test_padded_rows_never_picked(self):
        """N % n_devices != 0: the engine pads the node axis to a mesh
        multiple with INELIGIBLE rows.  Oversubscribe the cluster so the
        kernel would love extra capacity — every pick must still be a
        real node row, and the padded rows must not leak into the
        filtered-node metrics."""
        h = build(13, seed=3)           # 13 % 8 != 0 -> 3 padded rows
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 400                  # far beyond 13 nodes' capacity
        tg.tasks[0].resources.cpu = 2000
        tg.tasks[0].resources.memory_mb = 1024
        h.state.upsert_job(job)
        snap = h.state.snapshot()
        sharded, single = engines()
        bd_s = sharded.place(snap, job, job.task_groups, None,
                             seed=5, block=(tg.name, 400))
        bd_1 = single.place(snap, job, job.task_groups, None,
                            seed=5, block=(tg.name, 400))
        picks = bd_s.picks
        placed = picks[picks >= 0]
        assert placed.size > 0
        assert placed.max() < 13, "placed onto a padded row"
        assert np.array_equal(np.sort(picks), np.sort(bd_1.picks))
        for m_s, m_1 in zip(bd_s.metrics, bd_1.metrics):
            # padding rows subtracted: filtered counts match single-dev
            assert m_s.nodes_filtered == m_1.nodes_filtered
            assert m_s.nodes_evaluated == 13

    def test_padded_rows_after_gc_shrink_across_shard(self):
        """Node GC shrinks N across a shard boundary (13 -> 7 on an
        8-device mesh: npad 16 -> 8, every row remaps): the rebuilt
        sharded table must still never place onto padding and must stay
        pick-identical to the single-device engine."""
        h = build(13, seed=9)
        sharded, single = engines()

        def place_all(count, seed):
            job = mock.batch_job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = 1000
            tg.tasks[0].resources.memory_mb = 512
            h.state.upsert_job(job)
            snap = h.state.snapshot()
            bd_s = sharded.place(snap, job, job.task_groups, None,
                                 seed=seed,
                                 block=(tg.name, count))
            bd_1 = single.place(snap, job, job.task_groups, None,
                                seed=seed,
                                block=(tg.name, count))
            return bd_s, bd_1

        bd_s, bd_1 = place_all(80, seed=2)
        assert np.array_equal(np.sort(bd_s.picks), np.sort(bd_1.picks))
        # GC 6 nodes -> 7 remain (crosses the 8-row shard boundary)
        snap = h.state.snapshot()
        for nd in snap.nodes()[7:]:
            h.state.delete_node(nd.id)
        bd_s, bd_1 = place_all(80, seed=4)
        picks = bd_s.picks
        placed = picks[picks >= 0]
        assert placed.size > 0
        assert placed.max() < 7, "placed onto a padded row after GC"
        assert np.array_equal(np.sort(picks), np.sort(bd_1.picks))
        assert bd_s.metrics[0].nodes_evaluated == 7

    def test_dirty_shard_patch_uploads_one_shard(self):
        """A single node's eligibility write must re-upload only the
        SHARD holding that node's row (packer row-dirty log -> engine
        _patch_node_shards), not every node tensor — and the patched
        table must stay pick-identical to a fresh single-device
        engine."""
        h = build(64, seed=11)
        sharded = PlacementEngine()
        assert sharded.mesh is not None
        sharded.packer.attach(h.state)
        h2d = {"bytes": 0}
        sharded.h2d_observer = \
            lambda nb, s, cause: h2d.__setitem__("bytes",
                                                 h2d["bytes"] + nb)

        def place(seed):
            job = mock.batch_job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            tg = job.task_groups[0]
            tg.count = 80
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.memory_mb = 64
            h.state.upsert_job(job)
            snap = h.state.snapshot()
            return job, snap

        job, snap = place(1)
        sharded.place(snap, job, job.task_groups, None, seed=1,
                      block=(job.task_groups[0].name, 80))
        full_bytes = h2d["bytes"]
        assert full_bytes > 0
        shard_b0 = sharded.shard_h2d_bytes

        # one node write -> one dirty shard
        nid = h.state.snapshot().nodes()[0].id
        h.state.update_node_eligibility(nid, "ineligible")
        h2d["bytes"] = 0
        job, snap = place(2)
        bd_s = sharded.place(snap, job, job.task_groups, None,
                             seed=2,
                             block=(job.task_groups[0].name, 80))
        assert sharded.shard_h2d_bytes > shard_b0, \
            "dirty-shard patch never engaged"
        # the re-sync moved one shard (1/8th of the rows), not the
        # whole table: generous 2x slack for the used-tensor heal
        assert h2d["bytes"] <= 2 * (full_bytes // 8) + 256, \
            (h2d["bytes"], full_bytes)
        single = PlacementEngine(mesh=False)
        bd_1 = single.place(snap, job, job.task_groups, None,
                            seed=2,
                            block=(job.task_groups[0].name, 80))
        assert np.array_equal(np.sort(bd_s.picks), np.sort(bd_1.picks))
        # the drained node is gone from both engines' picks
        row = 0
        assert row not in bd_s.picks.tolist()

    def test_full_scheduler_on_mesh_engine(self):
        """End-to-end: Harness scheduling through the auto-mesh engine
        produces a valid complete plan (the whole suite also runs on the
        mesh via conftest; this pins the explicit contrast)."""
        sharded, single = engines()
        for eng, h2 in ((sharded, build(500)), (single, build(500))):
            job = mock.job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].count = 40
            e = mock.eval(job_id=job.id, type="service")
            h2.state.upsert_job(job)
            h2.state.upsert_evals([e])
            err = h2.process("service", e, now=1.7e9, engine=eng)
            assert err is None
            placed = sum(len(a) for a in
                         h2.plans[-1].node_allocation.values())
            assert placed == 40
