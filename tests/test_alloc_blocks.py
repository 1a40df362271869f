"""Columnar alloc blocks (structs.block.AllocBlock): bulk placements
commit as picks + template, materialize lazily on read, and convert to
ordinary table rows the moment a member alloc is written.

No reference analog — this replaces stock's per-placement Allocation
materialization (scheduler/generic_sched.go computePlacements), which the
round-3 profile showed costing more than the device placement work.
"""

import numpy as np

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.structs import AllocBlock, Allocation, Resources

NOW = 1.7e9


def run_bulk(count=100, n_nodes=20, eval_batch=0, cpu=100, mem=64):
    s = Server(dev_mode=True, eval_batch=eval_batch)
    s.establish_leadership()
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = 8000
        n.resources.memory_mb = 16384
        s.register_node(n, now=NOW)
    job = mock.batch_job()
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].resources.cpu = cpu
    job.task_groups[0].tasks[0].resources.memory_mb = mem
    s.register_job(job, now=NOW)
    s.process_all(now=NOW)
    return s, job


class TestBlockCommit:
    def test_bulk_placement_commits_columnar(self):
        s, job = run_bulk(count=100)
        # the commit itself stayed columnar: a live block, no table rows
        assert s.state._alloc_blocks, "bulk placements should be a block"
        assert not s.state._allocs_by_job.get((job.namespace, job.id))
        # reads materialize lazily and see ordinary allocs
        snap = s.state.snapshot()
        live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        assert len(live) == 100
        names = {a.name for a in live}
        assert f"{job.id}.{job.task_groups[0].name}[0]" in names
        assert len({a.id for a in live}) == 100
        assert all(a.create_index > 0 for a in live)
        # per-node reads agree with per-job reads
        by_node_total = sum(
            len(snap.allocs_by_node(nid))
            for nid in {a.node_id for a in live})
        assert by_node_total == 100

    def test_alloc_by_id_reads_block_rows(self):
        s, job = run_bulk(count=80)
        snap = s.state.snapshot()
        some = snap.allocs_by_job(job.namespace, job.id)[5]
        assert snap.alloc_by_id(some.id).id == some.id
        assert s.state.alloc_by_id(some.id).id == some.id

    def test_member_write_materializes_block(self):
        s, job = run_bulk(count=80)
        assert s.state._alloc_blocks
        a = s.state.allocs_by_job(job.namespace, job.id)[0]
        upd = a.copy_skip_job()
        upd.client_status = "complete"
        s.state.update_allocs_from_client([upd])
        # representation flipped: block gone, all rows in tables
        assert not s.state._alloc_blocks
        bucket = s.state._allocs_by_job[(job.namespace, job.id)]
        assert len(bucket) == 80
        assert bucket[a.id].client_status == "complete"
        # non-updated rows keep their identity
        live = [x for x in s.state.allocs_by_job(job.namespace, job.id)
                if not x.terminal_status()]
        assert len(live) == 79

    def test_snapshot_isolation_across_materialization(self):
        s, job = run_bulk(count=80)
        snap_before = s.state.snapshot()
        a = s.state.allocs_by_job(job.namespace, job.id)[0]
        upd = a.copy_skip_job()
        upd.client_status = "failed"
        s.state.update_allocs_from_client([upd])
        snap_after = s.state.snapshot()
        # both views count every alloc exactly once
        before = snap_before.allocs_by_job(job.namespace, job.id)
        after = snap_after.allocs_by_job(job.namespace, job.id)
        assert len(before) == len(after) == 80
        assert len({x.id for x in before}) == 80
        # the old snapshot must not see the update
        assert all(x.client_status == "pending" for x in before)
        assert sum(x.client_status == "failed" for x in after) == 1

    def test_usage_tracked_through_block_lifecycle(self):
        s, job = run_bulk(count=100, cpu=50, mem=32)
        packer = s.engine.packer
        t = packer.update(s.state.snapshot())
        assert int(t.used[:, 0].sum()) == 100 * 50
        assert int(t.used[:, 1].sum()) == 100 * 32
        # a member going terminal releases exactly its usage
        a = s.state.allocs_by_job(job.namespace, job.id)[0]
        upd = a.copy_skip_job()
        upd.client_status = "complete"
        s.state.update_allocs_from_client([upd])
        t = packer.update(s.state.snapshot())
        assert int(t.used[:, 0].sum()) == 99 * 50
        assert int(t.used[:, 1].sum()) == 99 * 32

    def test_snapshot_save_restore_flattens_blocks(self):
        s, job = run_bulk(count=80)
        assert s.state._alloc_blocks
        doc = s.state.snapshot_save()
        from nomad_tpu.state import StateStore
        fresh = StateStore()
        fresh.snapshot_restore(doc)
        live = [a for a in fresh.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        assert len(live) == 80
        assert not fresh._alloc_blocks

    def test_same_id_stop_through_plan_materializes(self):
        """A later plan stopping a block member (job update path) sees it
        as its predecessor."""
        s, job = run_bulk(count=80)
        a = s.state.allocs_by_job(job.namespace, job.id)[0]
        from nomad_tpu.structs import Plan, PlanResult
        stop = a.copy_skip_job()
        plan = Plan(eval_id="stop", job=job)
        plan.append_stopped_alloc(stop, "test stop")
        result = PlanResult(node_update=plan.node_update)
        s.state.upsert_plan_results(plan, result)
        got = s.state.alloc_by_id(a.id)
        assert got.desired_status == "stop"
        assert got.create_index == a.create_index   # predecessor seen
        live = [x for x in s.state.allocs_by_job(job.namespace, job.id)
                if not x.terminal_status() and x.desired_status == "run"]
        assert len(live) == 79


class TestBlockApplier:
    def test_broken_fence_expands_blocks(self):
        """With a foreign write between snapshot and apply, block plans
        take the full per-node path (and still commit correctly)."""
        s, job = run_bulk(count=100, eval_batch=64)
        stats = s.plan_applier.stats
        assert stats["fast_path"] >= 1
        # now force full checks: concurrent foreign writes each round
        job2 = mock.batch_job()
        job2.task_groups[0].count = 100
        job2.task_groups[0].tasks[0].resources.cpu = 10
        job2.task_groups[0].tasks[0].resources.memory_mb = 10
        s.register_job(job2, now=NOW + 1)
        # break the fence mid-flight: a node write after the snapshot
        s.register_node(mock.node(), now=NOW + 1)
        s.process_all(now=NOW + 1)
        snap = s.state.snapshot()
        live = [a for a in snap.allocs_by_job(job2.namespace, job2.id)
                if not a.terminal_status()]
        assert len(live) == 100

    def test_down_node_in_block_refutes_only_that_node(self):
        """Whole-block admission fails when a picked node is down; the
        expansion path refutes that node's rows and commits the rest."""
        from nomad_tpu.core import PlanApplier, PlanQueue
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs import Plan

        state = StateStore()
        q = PlanQueue()
        q.set_enabled(True)
        applier = PlanApplier(state, q)
        n1, n2 = mock.node(), mock.node()
        state.upsert_node(n1)
        state.upsert_node(n2)
        job = mock.batch_job()
        state.upsert_job(job)
        tg = job.task_groups[0]
        tmpl = Allocation(namespace=job.namespace, job_id=job.id, job=job,
                          task_group=tg.name, desired_status="run",
                          client_status="pending",
                          resources=Resources(cpu=10, memory_mb=10))
        from nomad_tpu.structs import new_ids
        ids = new_ids(10)
        block = AllocBlock(id="blk1", template=tmpl, ids=ids,
                           name_prefix=f"{job.id}.{tg.name}[",
                           indexes=list(range(10)),
                           picks=np.array([0, 1] * 5, np.int32),
                           node_table=[n1.id, n2.id])
        seq0 = state.placement_seq()
        state.update_node_status(n2.id, "down")
        plan = Plan(eval_id="e1", job=job, coupled_batch=("b1", seq0))
        plan.alloc_blocks = [block]
        p = q.enqueue(plan)
        applier.apply_one(p)
        result, err = p.wait(1)
        assert err is None
        assert result.refuted_nodes == [n2.id]
        snap = state.snapshot()
        assert len(snap.allocs_by_node(n1.id)) == 5
        assert len(snap.allocs_by_node(n2.id)) == 0


# ---------------------------------------------------------------------------
# ISSUE 35: the exact scan's blocks carry ONE METRIC A ROW as columns
# (structs.block.RowMetrics), and a deployment no longer bars a block
# ---------------------------------------------------------------------------

def run_spread(count=120, n_nodes=30):
    """A service job with a spread stanza, a rack affinity and mock.job's
    update stanza (a deployment) through a dev-mode server's solo path:
    the exact scan places it, one block commits it."""
    from nomad_tpu.structs import OP_EQ, Affinity, Spread, SpreadTarget
    s = Server(dev_mode=True)
    s.establish_leadership()
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = f"dc{1 + i % 3}"
        n.meta["rack"] = f"r{i % 5}"
        s.register_node(n, now=NOW)
    job = mock.job()
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(cpu=10, memory_mb=10)
    job.spreads = [Spread(
        attribute="${node.datacenter}", weight=50,
        targets=(SpreadTarget("dc1", 50), SpreadTarget("dc2", 30),
                 SpreadTarget("dc3", 20)))]
    job.affinities = [Affinity("${meta.rack}", OP_EQ, "r3", weight=50)]
    s.register_job(job, now=NOW)
    s.process_all(now=NOW)
    return s, job


def wire_rows(rows, indexes=True):
    """Rows in their full wire form, by name (`indexes` False: less the
    two indexes a commit stamps)."""
    from nomad_tpu.structs import codec
    out = {a.name: codec.encode(a) for a in rows}
    if not indexes:
        for d in out.values():
            del d["CreateIndex"], d["ModifyIndex"]
    return out


class TestRowMetricBlocks:
    def test_scan_block_commits_columnar_with_a_metric_a_row(self):
        s, job = run_spread()
        (block,) = s.state._alloc_blocks.values()
        assert block.count == 120 and not block.metrics
        rm = block.row_metrics
        assert rm.counts.shape == (120, 6) and rm.topk.shape == (120, 3)
        assert rm.topk_scores.dtype == np.float32
        # the candidates' own table, not the fleet's
        assert len(rm.nodes) <= 30 and rm.topk.max() < len(rm.nodes)
        assert not s.state._allocs_by_job.get((job.namespace, job.id))
        rows = s.state.snapshot().allocs_by_job(job.namespace, job.id)
        # a row's first candidate is the node it went to, its own score
        # first: row 0 and row 119 do not share a metric
        assert all(a.metrics.score_meta_data[0].node_id == a.node_id
                   for a in rows)
        assert rows[0].metrics is not rows[-1].metrics
        assert rows[0].metrics.score_meta_data != \
            rows[-1].metrics.score_meta_data
        by_dc = {"dc1": 0, "dc2": 0, "dc3": 0}
        for a in rows:
            by_dc[s.state.node_by_id(a.node_id).datacenter] += 1
        # the stanza's 50 / 30 / 20 of 120, the rack affinity pulling
        assert all(abs(by_dc[dc] - want) <= 6 for dc, want in
                   (("dc1", 60), ("dc2", 36), ("dc3", 24))), by_dc

    def test_block_survives_the_wire_codec(self):
        from nomad_tpu.core import wire
        s, _ = run_spread()
        (block,) = s.state._alloc_blocks.values()
        back = wire.unpackb(wire.packb(block))
        assert isinstance(back, AllocBlock)
        assert back.row_metrics.nodes == block.row_metrics.nodes
        for col in ("counts", "topk", "topk_scores"):
            got = getattr(back.row_metrics, col)
            want = getattr(block.row_metrics, col)
            assert got.dtype == want.dtype and np.array_equal(got, want)
        assert wire_rows(back.materialize_all()) == \
            wire_rows(block.materialize_all())

    def test_block_survives_snapshot_persist_and_restore(self):
        from nomad_tpu.state import StateStore
        s, job = run_spread()
        before = wire_rows(s.state.allocs_by_job(job.namespace, job.id))
        fresh = StateStore()
        fresh.snapshot_restore(s.state.snapshot_save())
        assert not fresh._alloc_blocks
        after = wire_rows(fresh.allocs_by_job(job.namespace, job.id))
        assert len(after) == 120 and after == before

    def test_without_nodes_keeps_each_rows_own_metric(self):
        s, _ = run_spread()
        (block,) = s.state._alloc_blocks.values()
        rows = block.materialize_all()
        bad = {rows[0].node_id, rows[7].node_id}
        kept = block.without_nodes(bad)
        assert 0 < kept.count < 120
        assert kept.row_metrics.counts.shape[0] == kept.count
        assert not bad.intersection(kept.node_table)
        want = wire_rows((a for a in rows if a.node_id not in bad),
                         indexes=False)
        assert wire_rows(kept.materialize_all(), indexes=False) == want

    def test_stop_and_purge_of_a_block_committed_job(self):
        s, job = run_spread()
        assert s.state._alloc_blocks
        s.deregister_job(job.namespace, job.id, purge=True, now=NOW + 1)
        s.process_all(now=NOW + 1)
        rows = s.state.allocs_by_job(job.namespace, job.id)
        assert len(rows) == 120
        assert all(a.desired_status == "stop" for a in rows)
        assert all(a.metrics.score_meta_data for a in rows)
        assert not s.state._alloc_blocks     # a member write: table rows
        t = s.engine.packer.update(s.state.snapshot())
        assert int(t.used[:, 0].sum()) == 0

    def test_deployment_watcher_counts_a_blocks_rows(self):
        from nomad_tpu.structs import DEPLOYMENT_STATUS_RUNNING
        s, job = run_spread()
        (block,) = s.state._alloc_blocks.values()
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        assert dep.status == DEPLOYMENT_STATUS_RUNNING
        assert block.template.deployment_id == dep.id
        assert dep.task_groups["web"].placed_allocs == 0
        s.deployments.tick(now=NOW + 1)
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        st = dep.task_groups["web"]
        assert (st.placed_allocs, st.healthy_allocs, st.unhealthy_allocs,
                st.desired_total) == (120, 0, 0, 120)
        # counted off the block's columns: the tick built no row
        assert block._rows is None
        # a member's first write turns the block into rows; same count
        a = s.state.allocs_by_job(job.namespace, job.id)[0]
        upd = a.copy_skip_job()
        upd.client_status = "running"
        upd.deployment_status = {"healthy": True, "ts": NOW}
        s.state.update_allocs_from_client([upd])
        assert not s.state._alloc_blocks
        s.deployments.tick(now=NOW + 2)
        st = s.state.deployment_by_id(dep.id).task_groups["web"]
        assert (st.placed_allocs, st.healthy_allocs) == (120, 1)


def test_materialize_counter_by_form_on_v1_metrics():
    """`nomad.materialize.placements{form}` through an agent's HTTP API:
    a spread job of 80 (the scan, one block), a batch job of 70 (the bulk
    kernel, one block) and a service job of 5 (rows)."""
    import json
    import re
    import time
    import urllib.request

    from nomad_tpu.agent import Agent
    from nomad_tpu.core.telemetry import REGISTRY
    from nomad_tpu.structs import Spread, SpreadTarget

    def forms():
        got = REGISTRY.counter_labels("nomad.materialize.placements")
        return got.get("form=block", 0), got.get("form=rows", 0)

    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        nodes = []
        for i in range(30):
            node = mock.node()
            node.datacenter = f"dc{1 + i % 3}"
            nodes.append(node)
        srv.state.upsert_nodes(nodes)
        spread = mock.job()
        spread.task_groups[0].count = 80
        spread.spreads = [Spread(
            attribute="${node.datacenter}", weight=100,
            targets=(SpreadTarget("dc1", 50), SpreadTarget("dc2", 50)))]
        bulk = mock.batch_job()
        bulk.task_groups[0].count = 70
        small = mock.job()
        small.task_groups[0].count = 5
        jobs = [spread, bulk, small]
        for job in jobs:
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].tasks[0].resources = Resources(
                cpu=10, memory_mb=10)
        block0, rows0 = forms()
        for job in jobs:
            srv.register_job(job)
        deadline = time.monotonic() + 120
        placed = 0
        while placed < 155 and time.monotonic() < deadline:
            time.sleep(0.05)
            snap = srv.state.snapshot()
            placed = sum(b.count for b in snap.alloc_blocks()) \
                + len(snap.allocs())
        assert placed == 155
        block1, rows1 = forms()
        assert (block1 - block0, rows1 - rows0) == (150, 5)
        with urllib.request.urlopen(agent.address + "/v1/metrics",
                                    timeout=60) as r:
            flat = json.load(r)
        assert flat["nomad.materialize.placements{form=block}"] == block1
        assert flat["nomad.materialize.placements{form=rows}"] == rows1
        with urllib.request.urlopen(
                agent.address + "/v1/metrics?format=prometheus",
                timeout=60) as r:
            text = r.read().decode()
        assert "# TYPE nomad_materialize_placements counter" in text
        for form in ("block", "rows"):
            assert re.search(
                r'^nomad_materialize_placements\{form="%s"\} \d+$' % form,
                text, re.M)
    finally:
        agent.shutdown()
