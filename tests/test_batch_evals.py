"""Multi-eval batched scheduling (DP over evals — SURVEY §3.6 row 1).

The reference processes one eval per worker goroutine (nomad/worker.go);
here compatible pending evals share ONE device launch
(ops.select.place_multi_packed via engine.place_batch) and their plans are
mutually consistent by construction.  These tests pin:
  - kernel parity: a solo water-fill eval == a batch of one
  - capacity coupling: plans inside one batch never oversubscribe and
    never refute each other at the serialized applier
  - end-to-end: Server.process_all with eval_batch handles a mixed queue
    (batchable + system + spread jobs) equivalently to solo processing
"""

import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.ops import PlacementEngine
from nomad_tpu.ops.engine import BatchItem
from nomad_tpu.scheduler import Harness

NOW = 1.7e9


def build_cluster(n_nodes=200, n_dcs=3, seed=0):
    rng = random.Random(seed)
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = f"dc{1 + i % n_dcs}"
        n.resources.cpu = rng.choice([4000, 8000, 16000])
        n.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(n)
    h.state.upsert_nodes(nodes)
    return h, nodes


def batch_jobs(h, counts, cpu=100, mem=64):
    jobs = []
    for c in counts:
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = c
        tg.tasks[0].resources.cpu = cpu
        tg.tasks[0].resources.memory_mb = mem
        h.state.upsert_job(job)
        jobs.append(job)
    return jobs


def metric_key(m):
    """Everything an AllocMetric says but the wall-clock reading."""
    return (m.nodes_evaluated, m.nodes_filtered, m.nodes_in_pool,
            m.nodes_exhausted, dict(m.nodes_available),
            dict(m.dimension_exhausted),
            [(s.node_id, s.scores, s.norm_score)
             for s in m.score_meta_data])


def assert_same_answer(solo, wave):
    """Two BulkDecisions give one answer: the picks in their order on
    one node table, every round's metric (filtered, exhausted, each
    dimension, the top rows and their scores) and so every placement's,
    the evictions."""
    assert solo.node_ids == wave.node_ids
    assert np.array_equal(solo.picks, wave.picks)
    assert solo.round_size == wave.round_size
    assert len(wave.metrics) == -(-len(wave.picks) // wave.round_size)
    assert ([metric_key(m) for m in solo.metrics]
            == [metric_key(m) for m in wave.metrics])
    assert solo.evictions == wave.evictions == {}
    assert solo.nodes_evaluated == wave.nodes_evaluated


class TestPlaceBatchKernel:
    @pytest.mark.parametrize("devices", [1, 8])
    @pytest.mark.parametrize("live", ["fresh", "live", "stopping"])
    @pytest.mark.parametrize("algo", ["binpack", "spread"])
    @pytest.mark.parametrize("count", [64, 100, 200, 1024, 1500, 2100])
    def test_single_item_matches_bulk_kernel(self, count, algo, live,
                                             devices):
        """A solo water-fill eval answers as a one-item wave: picks in
        their order and every round's metric.  At 700 MHz an ask the
        150 nodes hold ~1,900 of, so the largest counts exhaust them.
        `live`: the job already runs two allocations on every seventh
        node (its count rows and their usage).  `stopping`: the solo
        eval's plan also stops one of each pair and another job's
        allocation on every eleventh node, and the wave runs on the
        state with those allocations gone."""
        from nomad_tpu.structs import (Resources, SCHED_ALGO_SPREAD,
                                       SchedulerConfiguration)
        h, nodes = build_cluster(150)
        if algo == "spread":
            h.state.set_scheduler_config(SchedulerConfiguration(
                scheduler_algorithm=SCHED_ALGO_SPREAD))
        (job, other) = batch_jobs(h, [count, 1], cpu=700)
        tg = job.task_groups[0]
        stopped = []
        if live != "fresh":
            def running(j, n):
                return mock.alloc(job=j, node_id=n.id,
                                  task_group=j.task_groups[0].name,
                                  resources=Resources(cpu=700, memory_mb=64),
                                  client_status="running")
            allocs = [running(job, n) for n in nodes[::7] for _ in range(2)]
            foreign = [running(other, n) for n in nodes[::11]]
            h.state.upsert_allocs(allocs + foreign)
            tg.count = count + len(allocs)
            h.state.upsert_job(job)
            if live == "stopping":
                stopped = allocs[::2] + foreign
        snap = h.state.snapshot()
        solo = PlacementEngine(mesh=None if devices > 1 else False).place(
            snap, job, job.task_groups, None, stopped_allocs=stopped,
            seed=9, block=(tg.name, count))
        if stopped:
            gone = [a.copy() for a in stopped]
            for a in gone:
                a.desired_status, a.client_status = "stop", "complete"
            h.state.upsert_allocs(gone)
            snap = h.state.snapshot()
        eng = PlacementEngine(mesh=None if devices > 1 else False)
        assert eng._ndev == devices
        wave = eng.place_batch(
            snap, [BatchItem(job=job, tg=tg, count=count)], seed=9)[0]
        assert_same_answer(solo, wave)

    @pytest.mark.parametrize("devices", [1, 8])
    def test_solo_stops_give_their_nodes_back(self, devices):
        """distinct_hosts makes the job's own count a hard limit: its
        live allocation on every other node keeps that node out, and the
        plan's stops on every fourth node give it back to a solo
        water-fill eval, as the wave on the state without them sees."""
        from nomad_tpu.structs import Constraint, Resources
        h, nodes = build_cluster(150)
        (job,) = batch_jobs(h, [100])
        job.constraints.append(Constraint("", "distinct_hosts", ""))
        tg = job.task_groups[0]
        live = [mock.alloc(job=job, node_id=n.id, task_group=tg.name,
                           resources=Resources(cpu=100, memory_mb=64),
                           client_status="running") for n in nodes[::2]]
        h.state.upsert_allocs(live)
        tg.count = 100 + len(live)
        h.state.upsert_job(job)
        mesh = None if devices > 1 else False
        solo = PlacementEngine(mesh=mesh).place(
            h.state.snapshot(), job, job.task_groups, None,
            stopped_allocs=live[::2], seed=9, block=(tg.name, 100))
        gone = [a.copy() for a in live[::2]]
        for a in gone:
            a.desired_status, a.client_status = "stop", "complete"
        h.state.upsert_allocs(gone)
        wave = PlacementEngine(mesh=mesh).place_batch(
            h.state.snapshot(), [BatchItem(job=job, tg=tg, count=100)],
            seed=9)[0]
        assert_same_answer(solo, wave)
        # 75 nodes hold none of the job, 38 more are given back: all
        # 100 place, some of them on the nodes the stops free
        assert (solo.picks >= 0).all()
        freed = {n.id for n in nodes[::4]}
        assert freed & {solo.node_ids[p] for p in solo.picks.tolist()}

    def test_capacity_coupling_across_items(self):
        """Items in one batch see each other's proposed usage: total
        per-node commitment never exceeds capacity even when the batch
        oversubscribes the cluster."""
        h, nodes = build_cluster(20, seed=3)
        for n in nodes:
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
        h.state.upsert_nodes(nodes)
        jobs = batch_jobs(h, [30, 30, 30], cpu=1000, mem=512)
        snap = h.state.snapshot()
        eng = PlacementEngine()
        items = [BatchItem(job=j, tg=j.task_groups[0],
                           count=j.task_groups[0].count) for j in jobs]
        decisions = eng.place_batch(snap, items, seed=5)
        used = {}
        placed = 0
        for d in decisions:
            for p in d.picks:
                if p < 0:
                    continue
                used[int(p)] = used.get(int(p), 0) + 1000
                placed += 1
        # usable cpu is 4000 minus the node's reserved 100 -> 3 slots
        # per node; 20 nodes x 3 = 60 total capacity for 90 asks
        assert placed == 60, placed
        for row, cpu in used.items():
            assert cpu <= 3900, (row, cpu)
        # failed picks report exhaustion, not filtering
        failed_rounds = [m for d in decisions for m in d.metrics
                         if m.dimension_exhausted]
        assert failed_rounds

    def test_job_anti_affinity_rows_isolated_per_job(self):
        """Each item's anti-affinity sees only ITS job's allocs: two jobs
        placing in one batch spread independently."""
        h, _ = build_cluster(10)
        jobs = batch_jobs(h, [4, 4], cpu=10, mem=10)
        for j in jobs:
            j.type = "service"
            h.state.upsert_job(j)
        snap = h.state.snapshot()
        eng = PlacementEngine()
        items = [BatchItem(job=j, tg=j.task_groups[0], count=4)
                 for j in jobs]
        d1, d2 = eng.place_batch(snap, items, seed=11)
        assert (d1.picks >= 0).all() and (d2.picks >= 0).all()


class TestBatchedWorkerPath:
    def _run(self, eval_batch, n_jobs=6, count=25, system_too=True):
        s = Server(dev_mode=True, eval_batch=eval_batch)
        s.establish_leadership()
        rng = random.Random(1)
        for i in range(60):
            n = mock.node()
            n.datacenter = f"dc{1 + i % 3}"
            n.resources.cpu = rng.choice([8000, 16000])
            n.resources.memory_mb = 16384
            s.register_node(n, now=NOW)
        jobs = []
        for _ in range(n_jobs):
            job = mock.batch_job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].count = count
            # small asks: eval processing ORDER between concurrently
            # pending evals is not a guarantee (coupled batches run
            # before solos), so the fixture must not be capacity-tight
            job.task_groups[0].tasks[0].resources.cpu = 10
            job.task_groups[0].tasks[0].resources.memory_mb = 16
            s.register_job(job, now=NOW)
            jobs.append(job)
        sysjob = None
        if system_too:
            sysjob = mock.system_job()
            s.register_job(sysjob, now=NOW)
        n = s.process_all(now=NOW)
        return s, jobs, sysjob, n

    def test_mixed_queue_batched_equals_solo(self):
        s_b, jobs_b, sys_b, n_b = self._run(eval_batch=64)
        s_s, jobs_s, sys_s, n_s = self._run(eval_batch=0)
        assert n_b == n_s
        for s, jobs, sysjob in ((s_b, jobs_b, sys_b), (s_s, jobs_s, sys_s)):
            snap = s.state.snapshot()
            for job in jobs:
                live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                        if not a.terminal_status()]
                assert len(live) == 25, (job.id, len(live))
                evs = snap.evals_by_job(job.namespace, job.id)
                assert any(e.status == "complete" for e in evs)
            live = [a for a in snap.allocs_by_job(sysjob.namespace,
                                                  sysjob.id)
                    if not a.terminal_status()]
            # system job defaults to dc1 only: a third of the nodes
            assert len(live) == 20

    def test_batched_plans_do_not_refute_each_other(self):
        s, jobs, _, _ = self._run(eval_batch=64, n_jobs=8, count=40,
                                  system_too=False)
        # every plan committed in full: no worker retries happened
        assert s.workers[0].stats["nacked"] == 0
        snap = s.state.snapshot()
        for job in jobs:
            evs = snap.evals_by_job(job.namespace, job.id)
            assert all(e.status in ("complete",) for e in evs), \
                [(e.status, e.status_description) for e in evs]

    def test_batch_oversubscription_creates_blocked_evals(self):
        s = Server(dev_mode=True, eval_batch=64)
        s.establish_leadership()
        for _ in range(4):
            n = mock.node()
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            s.register_node(n, now=NOW)
        jobs = []
        for _ in range(3):
            job = mock.batch_job()
            job.task_groups[0].count = 3
            job.task_groups[0].tasks[0].resources.cpu = 2000
            job.task_groups[0].tasks[0].resources.memory_mb = 64
            s.register_job(job, now=NOW)
            jobs.append(job)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        placed = sum(
            1 for job in jobs
            for a in snap.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status())
        # usable cpu 3900 fits ONE 2000-cpu alloc per node: 4 of 9 place
        assert placed == 4
        assert s.blocked_evals.num_blocked() >= 1
        # capacity arrives -> blocked evals release and place the rest
        big = mock.node()
        big.resources.cpu = 16000
        big.resources.memory_mb = 32768
        s.register_node(big, now=NOW + 1)
        s.process_all(now=NOW + 1)
        snap = s.state.snapshot()
        placed = sum(
            1 for job in jobs
            for a in snap.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status())
        assert placed == 9

    def _spread_beside_plain(self, update):
        """A plain batch job and a spread service job of nine in one
        batch; returns (server, spread job's per-datacenter counts)."""
        from nomad_tpu.structs import Spread, SpreadTarget
        s = Server(dev_mode=True, eval_batch=64, mesh=False)
        s.establish_leadership()
        for i in range(30):
            n = mock.node()
            n.datacenter = f"dc{1 + i % 3}"
            s.register_node(n, now=NOW)
        plain = mock.batch_job()
        plain.datacenters = ["dc1", "dc2", "dc3"]
        plain.task_groups[0].count = 10
        s.register_job(plain, now=NOW)
        spread = mock.job()
        spread.datacenters = ["dc1", "dc2", "dc3"]
        spread.task_groups[0].count = 9
        spread.update = update
        spread.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                                 targets=[SpreadTarget("dc1", 34),
                                          SpreadTarget("dc2", 33),
                                          SpreadTarget("dc3", 33)])]
        s.register_job(spread, now=NOW)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        for job, want in ((plain, 10), (spread, 9)):
            live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == want
        by_dc = {}
        for a in snap.allocs_by_job(spread.namespace, spread.id):
            node = snap.node_by_id(a.node_id)
            by_dc[node.datacenter] = by_dc.get(node.datacenter, 0) + 1
        return s, by_dc

    def test_spread_job_with_update_stanza_falls_back_to_exact_path(self):
        """mock.job's update stanza makes the eval create a deployment,
        which keeps it off the wave: counted, and spread by the scan."""
        from nomad_tpu.core.telemetry import REGISTRY
        solo0 = REGISTRY.counter_labels("nomad.spread.evals_solo").get(
            "rule=deployment", 0.0)
        _, by_dc = self._spread_beside_plain(mock.job().update)
        assert mock.job().update is not None
        assert sorted(by_dc.values()) == [3, 3, 3], by_dc
        assert REGISTRY.counter_labels("nomad.spread.evals_solo").get(
            "rule=deployment", 0.0) - solo0 == 1

    def test_solo_evals_of_one_pass_each_meet_the_applier_fenced(self):
        """Four service jobs with a spread stanza and an update stanza
        (off the wave: rule `deployment`) of 80 in one pass: each takes a
        view of its own after the pass's first commit, so every plan is
        fence-tagged from the state it was computed against and commits
        as ONE block on the applier's fast path, no row built for a
        re-fit (ISSUE 35: tagged from the batch's snapshot, three of the
        four were re-fitted node by node)."""
        from nomad_tpu.structs import Spread, SpreadTarget
        s = Server(dev_mode=True, eval_batch=64, mesh=False)
        s.establish_leadership()
        for i in range(30):
            n = mock.node()
            n.datacenter = f"dc{1 + i % 3}"
            s.register_node(n, now=NOW)
        jobs = []
        for _ in range(4):
            job = mock.job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].count = 80
            job.task_groups[0].tasks[0].resources.cpu = 10
            job.task_groups[0].tasks[0].resources.memory_mb = 10
            job.spreads = [Spread(
                attribute="${node.datacenter}", weight=50,
                targets=[SpreadTarget("dc1", 50), SpreadTarget("dc2", 30),
                         SpreadTarget("dc3", 20)])]
            s.register_job(job, now=NOW)
            jobs.append(job)
        stats = s.plan_applier.stats
        before = {k: stats.get(k, 0) for k in ("fast_path", "full_check")}
        s.process_all(now=NOW)
        assert s.workers[0].pipeline.stats["waves"] == 0
        assert stats.get("fast_path", 0) - before["fast_path"] == 4
        assert stats.get("full_check", 0) == before["full_check"]
        blocks = s.state.snapshot().alloc_blocks()
        assert sorted(b.count for b in blocks) == [80] * 4
        assert all(b._rows is None for b in blocks)
        snap = s.state.snapshot()
        for job in jobs:
            by_dc = {"dc1": 0, "dc2": 0, "dc3": 0}
            for a in snap.allocs_by_job(job.namespace, job.id):
                by_dc[snap.node_by_id(a.node_id).datacenter] += 1
            assert by_dc == {"dc1": 40, "dc2": 24, "dc3": 16}

    def test_spread_job_without_update_stanza_rides_the_batch(self):
        from nomad_tpu.core.telemetry import REGISTRY
        rode0 = REGISTRY.counter_sum("nomad.spread.evals_batched")
        solo0 = REGISTRY.counter_sum("nomad.spread.evals_solo")
        s, by_dc = self._spread_beside_plain(None)
        assert sorted(by_dc.values()) == [3, 3, 3], by_dc
        assert REGISTRY.counter_sum("nomad.spread.evals_batched") \
            - rode0 == 1
        assert REGISTRY.counter_sum("nomad.spread.evals_solo") == solo0
        assert s.workers[0].pipeline.stats["waves"] == 1

    def test_applier_fast_path_and_fence(self):
        """Coupled-batch plans skip the redundant AllocsFit re-check; a
        foreign placement-relevant write mid-chain breaks the fence and
        restores the full optimistic re-check (which refutes a plan the
        fast path would have waved through)."""
        from nomad_tpu.structs import Allocation, Plan

        s, jobs, _, _ = self._run(eval_batch=64, n_jobs=6, count=20,
                                  system_too=False)
        stats = s.plan_applier.stats
        assert stats["fast_path"] >= 5, stats

        # hand-drive a coupled chain against the applier
        snap = s.state.snapshot()
        node = snap.nodes()[0]
        job = jobs[0]

        def mkplan(cpu, bid, seq0):
            a = Allocation(namespace=job.namespace, job_id=job.id, job=job,
                           task_group=job.task_groups[0].name,
                           desired_status="run", client_status="pending")
            a.resources = job.task_groups[0].combined_resources().copy()
            a.resources.cpu = cpu
            a.node_id = node.id
            p = Plan(eval_id="manual", job=job,
                     coupled_batch=(bid, seq0))
            p.append_alloc(a)
            return p

        seq0 = s.state.placement_seq()
        r1 = s.plan_applier.evaluate_plan(
            mkplan(50, "bX", seq0), skip_fit=True)
        assert not r1.refuted_nodes

        # a foreign write to an UNRELATED node must NOT demote the fence
        # (per-node granularity — the whole point: disjoint workers never
        # poison each other's chains)
        s.register_node(mock.node(), now=NOW + 1)
        fp_before = s.plan_applier.stats["fast_path"]
        from nomad_tpu.core.plan_apply import PendingPlan
        ok_plan = mkplan(10, "bX", seq0)
        pending = PendingPlan(ok_plan)
        s.plan_applier.apply_one(pending)
        result, err = pending.wait(timeout=5)
        assert err is None and not result.refuted_nodes
        assert s.plan_applier.stats["fast_path"] == fp_before + 1

        # a plan that oversubscribes the node: a foreign write TO THE
        # PLAN'S NODE breaks its fence, so apply_one full-checks and
        # refutes it.  (The foreign write: an unfenced alloc commit on
        # that node.)
        from nomad_tpu.structs import Resources
        foreign = Allocation(namespace=job.namespace, job_id=job.id,
                             job=job, task_group=job.task_groups[0].name,
                             desired_status="run", client_status="pending",
                             node_id=node.id,
                             resources=Resources(cpu=1, memory_mb=1))
        s.state.upsert_allocs([foreign])
        big = mkplan(10 ** 9, "bX", seq0)
        pending = PendingPlan(big)
        s.plan_applier.apply_one(pending)
        result, err = pending.wait(timeout=5)
        assert err is None
        assert result.refuted_nodes == [node.id]

    def test_cross_batch_prefetch_chain(self):
        """Small eval_batch forces multiple coupled batches per drain:
        the worker prefetch-chains batch k+1 on batch k's device-side
        proposed usage.  Everything must still place exactly, without
        refutes, and with the applier fast path active across batches."""
        s = Server(dev_mode=True, eval_batch=4)
        s.establish_leadership()
        rng = random.Random(7)
        for i in range(30):
            n = mock.node()
            n.datacenter = f"dc{1 + i % 3}"
            n.resources.cpu = rng.choice([8000, 16000])
            n.resources.memory_mb = 16384
            s.register_node(n, now=NOW)
        jobs = []
        for _ in range(12):                      # 3 batches of 4
            job = mock.batch_job()
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].count = 15
            job.task_groups[0].tasks[0].resources.cpu = 20
            job.task_groups[0].tasks[0].resources.memory_mb = 16
            s.register_job(job, now=NOW)
            jobs.append(job)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        for job in jobs:
            live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == 15, (job.id, len(live))
        assert s.workers[0].stats["nacked"] == 0
        # chained batches share the fence: the fast path dominated
        stats = s.plan_applier.stats
        assert stats["fast_path"] >= 8, stats

    def test_chain_resyncs_after_node_table_change(self):
        """A node-table rebuild between chained batches remaps rows; the
        chained usage must be dropped (version guard) — placements stay
        valid."""
        s = Server(dev_mode=True, eval_batch=4)
        s.establish_leadership()
        nodes = []
        for _ in range(6):
            n = mock.node()
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            s.register_node(n, now=NOW)
            nodes.append(n)
        # wave 1 fills some capacity
        first = []
        for _ in range(4):
            job = mock.batch_job()
            job.task_groups[0].count = 3
            job.task_groups[0].tasks[0].resources.cpu = 300
            s.register_job(job, now=NOW)
            first.append(job)
        s.process_all(now=NOW)
        # membership change rebuilds the node table (rows remap)
        s.register_node(mock.node(), now=NOW + 1)
        more = []
        for _ in range(4):
            job = mock.batch_job()
            job.task_groups[0].count = 3
            job.task_groups[0].tasks[0].resources.cpu = 300
            s.register_job(job, now=NOW + 1)
            more.append(job)
        s.process_all(now=NOW + 1)
        snap = s.state.snapshot()
        # capacity accounting stayed exact through the resync
        for job in first + more:
            live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == 3
        by_node = {}
        for job in first + more:
            for a in snap.allocs_by_job(job.namespace, job.id):
                if not a.terminal_status():
                    by_node[a.node_id] = (by_node.get(a.node_id, 0)
                                          + a.resources.cpu)
        for nid, cpu in by_node.items():
            node = snap.node_by_id(nid)
            usable = node.resources.cpu - node.reserved.cpu
            assert cpu <= usable, (nid, cpu, usable)

    def test_preemption_falls_back_to_solo(self):
        from nomad_tpu.structs import (PreemptionConfig,
                                       SchedulerConfiguration)
        s = Server(dev_mode=True, eval_batch=64)
        s.establish_leadership()
        s.state.set_scheduler_config(SchedulerConfiguration(
            preemption_config=PreemptionConfig(
                service_scheduler_enabled=True,
                batch_scheduler_enabled=True)))
        for _ in range(5):
            n = mock.node()
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            s.register_node(n, now=NOW)
        low = mock.batch_job()
        low.priority = 20
        low.task_groups[0].count = 5
        low.task_groups[0].tasks[0].resources.cpu = 3000
        s.register_job(low, now=NOW)
        s.process_all(now=NOW)
        # two high-pri jobs arrive together: each must preempt
        highs = []
        for _ in range(2):
            hi = mock.job()
            hi.priority = 80
            hi.task_groups[0].count = 2
            hi.task_groups[0].tasks[0].resources.cpu = 3000
            s.register_job(hi, now=NOW + 1)
            highs.append(hi)
        s.process_all(now=NOW + 1)
        snap = s.state.snapshot()
        for hi in highs:
            live = [a for a in snap.allocs_by_job(hi.namespace, hi.id)
                    if not a.terminal_status()]
            assert len(live) == 2, (hi.id, len(live))
        evicted = [a for a in snap.allocs_by_job(low.namespace, low.id)
                   if a.desired_status == "evict"]
        assert len(evicted) == 4


def build_zoned_cluster(n_nodes=500, n_zones=5, seed=0):
    """Bench-shaped cluster: per-zone CSI volumes whose topologies pin
    jobs to provably-disjoint node sets (the compact laned kernel's
    activation condition)."""
    from nomad_tpu.structs import CSIVolume
    rng = random.Random(seed)
    h = Harness()
    nodes = []
    zone_nodes = {z: [] for z in range(n_zones)}
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = f"dc{1 + i % 3}"
        n.attributes["storage.topology"] = f"zone{i % n_zones}"
        n.csi_node_plugins["ebs0"] = True
        n.resources.cpu = rng.choice([4000, 8000, 16000])
        n.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(n)
        zone_nodes[i % n_zones].append(n.id)
    h.state.upsert_nodes(nodes)
    for z in range(n_zones):
        h.state.upsert_csi_volume(CSIVolume(
            id=f"vol-zone{z}", plugin_id="ebs0",
            access_mode="multi-node-multi-writer",
            topology_node_ids=tuple(zone_nodes[z])))
    return h, nodes


def zoned_items(h, n_items, count, n_zones=5):
    from nomad_tpu.structs import VolumeRequest
    items = []
    for i in range(n_items):
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 10
        tg.tasks[0].resources.memory_mb = 10
        tg.volumes = {"data": VolumeRequest(
            name="data", type="csi", source=f"vol-zone{i % n_zones}",
            read_only=True)}
        h.state.upsert_job(job)
        items.append(BatchItem(job=job, tg=tg, count=count))
    return items


class TestSignatureDisjointness:
    """The structural disjointness prover gates lane parallelism: a
    FALSE POSITIVE would let two lanes water-fill the same node
    concurrently and oversubscribe it.  Conservative by construction —
    prove only what the lowered rows entail."""

    def _luts(self):
        # rows: 0 = {vocab 0,1}, 1 = {vocab 2,3}, 2 = {vocab 1,2}
        luts = np.zeros((3, 4), bool)
        luts[0, [0, 1]] = True
        luts[1, [2, 3]] = True
        luts[2, [1, 2]] = True
        return luts

    def test_proven_disjoint(self):
        from nomad_tpu.ops.engine import _sig_disjoint
        from nomad_tpu.pack.packer import DOP_EQ, DOP_LUT
        luts = self._luts()
        # EQ/EQ different values on one column
        assert _sig_disjoint([(5, DOP_EQ, 1)], [(5, DOP_EQ, 2)], luts)
        # LUT/LUT with empty intersection ({0,1} vs {2,3})
        assert _sig_disjoint([(7, DOP_LUT, 0)], [(7, DOP_LUT, 1)], luts)
        # EQ value outside the LUT's set (2 not in {0,1})
        assert _sig_disjoint([(7, DOP_LUT, 0)], [(7, DOP_EQ, 2)], luts)
        assert _sig_disjoint([(7, DOP_EQ, 2)], [(7, DOP_LUT, 0)], luts)

    def test_not_proven(self):
        from nomad_tpu.ops.engine import _sig_disjoint
        from nomad_tpu.pack.packer import (
            DOP_EQ, DOP_LUT, DOP_NEQ, DOP_TRUE)
        luts = self._luts()
        # same EQ value: same set
        assert not _sig_disjoint([(5, DOP_EQ, 1)], [(5, DOP_EQ, 1)], luts)
        # different COLUMNS never prove anything
        assert not _sig_disjoint([(5, DOP_EQ, 1)], [(6, DOP_EQ, 2)], luts)
        # overlapping LUTs ({0,1} vs {1,2})
        assert not _sig_disjoint([(7, DOP_LUT, 0)], [(7, DOP_LUT, 2)],
                                 luts)
        # EQ value inside the LUT's set
        assert not _sig_disjoint([(7, DOP_LUT, 0)], [(7, DOP_EQ, 1)],
                                 luts)
        # NEQ / padding rows are ignored (no false proofs from them)
        assert not _sig_disjoint([(5, DOP_NEQ, 1)], [(5, DOP_NEQ, 2)],
                                 luts)
        assert not _sig_disjoint([(0, DOP_TRUE, 0)], [(0, DOP_TRUE, 0)],
                                 luts)
        # empty signatures
        assert not _sig_disjoint([], [(5, DOP_EQ, 1)], luts)

    def test_overlapping_signatures_fall_back_to_flat(self):
        """Two jobs whose CSI topologies OVERLAP must not lane-split:
        build_multi_inputs has to keep the flat sequential schedule."""
        from nomad_tpu.structs import CSIVolume, VolumeRequest
        h = Harness()
        nodes = [mock.node() for _ in range(40)]
        for n in nodes:
            n.csi_node_plugins["ebs0"] = True
        h.state.upsert_nodes(nodes)
        ids = [n.id for n in nodes]
        h.state.upsert_csi_volume(CSIVolume(
            id="vol-a", plugin_id="ebs0",
            topology_node_ids=tuple(ids[:30])))      # overlaps vol-b
        h.state.upsert_csi_volume(CSIVolume(
            id="vol-b", plugin_id="ebs0",
            topology_node_ids=tuple(ids[20:])))
        items = []
        for src in ("vol-a", "vol-b"):
            job = mock.batch_job()
            tg = job.task_groups[0]
            tg.count = 10
            tg.volumes = {"data": VolumeRequest(
                name="data", type="csi", source=src, read_only=True)}
            h.state.upsert_job(job)
            items.append(BatchItem(job=job, tg=tg, count=10))
        eng = PlacementEngine(mesh=False)
        built = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert built["cand_rows"] is None     # no disjointness proof
        assert built["n_lanes"] == 1
        # and the batch still places correctly on the flat path
        d = eng.place_batch(h.state.snapshot(), items, seed=3)
        assert sum(int((x.picks >= 0).sum()) for x in d) == 20


class TestCompactLanedKernel:
    """The compact lane-parallel multi-eval kernel (round-5: signatures
    with provably-disjoint landscapes run as concurrent lanes over
    per-signature candidate frames) must be decision- and metric-exact
    vs the flat sequential schedule.  Single-device engines: the mesh
    path keeps the flat schedule."""

    def _flat(self, fn):
        import nomad_tpu.ops.engine as em
        old = em.MAX_LANES
        em.MAX_LANES = 1          # width-1 cliques -> flat fallback path
        try:
            return fn()
        finally:
            em.MAX_LANES = old

    def test_fast_path_engages_on_zoned_batch(self):
        h, _ = build_zoned_cluster()
        items = zoned_items(h, 10, 30)
        eng = PlacementEngine(mesh=False)
        built = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert built["cand_rows"] is not None
        assert built["n_lanes"] == 5
        assert built["perm"] is not None

    def test_parity_binpack(self):
        h, _ = build_zoned_cluster()
        items = zoned_items(h, 13, 40)
        snap = h.state.snapshot()
        d_c = PlacementEngine(mesh=False).place_batch(snap, items, seed=7)
        d_f = self._flat(
            lambda: PlacementEngine(mesh=False).place_batch(
                snap, items, seed=7))
        for a, b in zip(d_c, d_f):
            assert np.array_equal(a.picks, b.picks)
            for ma, mb in zip(a.metrics, b.metrics):
                assert ma.nodes_filtered == mb.nodes_filtered
                assert ma.nodes_exhausted == mb.nodes_exhausted
                assert ma.dimension_exhausted == mb.dimension_exhausted
                assert ([s.node_id for s in ma.score_meta_data]
                        == [s.node_id for s in mb.score_meta_data])

    def test_parity_spread_overflow(self):
        """Spread algorithm fans a round over more distinct nodes than
        the FILL_K small-buffer prefix: the collect path must detect the
        overflow and fall back to the device-resident full fills."""
        from nomad_tpu.ops.select import FILL_K
        from nomad_tpu.structs import (
            SCHED_ALGO_SPREAD, SchedulerConfiguration)
        h, _ = build_zoned_cluster()
        h.state.set_scheduler_config(SchedulerConfiguration(
            scheduler_algorithm=SCHED_ALGO_SPREAD))
        snap = h.state.snapshot()
        items = zoned_items(h, 6, FILL_K + 26)
        d_c = PlacementEngine(mesh=False).place_batch(snap, items, seed=5)
        d_f = self._flat(
            lambda: PlacementEngine(mesh=False).place_batch(
                snap, items, seed=5))
        for a, b in zip(d_c, d_f):
            assert np.array_equal(a.picks, b.picks)
        # the spread cap really did fan past the small prefix
        distinct = {p for a in d_c for p in a.picks.tolist() if p >= 0}
        assert len(distinct) > FILL_K

    def test_mesh_compact_parity(self):
        """The laned fast path composes with node-axis sharding: the
        8-virtual-device mesh engine must take the compact path on a
        zoned batch and decide exactly like the single-device engine
        (sorted picks per item — the two-stage top-k resolves ties in
        mesh order, so pick ORDER may differ within a round)."""
        h, _ = build_zoned_cluster(512)     # mesh-multiple node count
        items = zoned_items(h, 10, 30)
        snap = h.state.snapshot()
        mesh_eng = PlacementEngine()        # auto-mesh (8 devices)
        assert mesh_eng.mesh is not None
        built = mesh_eng.build_multi_inputs(snap, items, seed=9)
        assert built["cand_rows"] is not None, "mesh compact not engaged"
        assert built["cand_rows"].ndim == 3      # [S, L, Nc_loc]
        d_mesh = mesh_eng.place_batch(snap, items, seed=9)
        d_one = PlacementEngine(mesh=False).place_batch(snap, items,
                                                        seed=9)
        for a, b in zip(d_mesh, d_one):
            assert np.array_equal(np.sort(a.picks), np.sort(b.picks))
            for ma, mb in zip(a.metrics, b.metrics):
                assert ma.nodes_filtered == mb.nodes_filtered
                assert ma.nodes_exhausted == mb.nodes_exhausted

    def test_single_eval_bulk_overflow_fallback(self):
        """A solo water-fill eval whose round fills more distinct nodes
        than the compact kernel's FILL_K prefix (tiny nodes force ~2
        allocs each) places every allocation, within capacity."""
        from nomad_tpu.ops.select import FILL_K

        h = Harness()
        nodes = []
        for _ in range(FILL_K * 2):
            n = mock.node()
            # mock nodes reserve cpu=100/mem=256: usable = 200/200,
            # exactly 2 of the 100/100 asks
            n.resources.cpu = 300
            n.resources.memory_mb = 456
            nodes.append(n)
        h.state.upsert_nodes(nodes)
        job = mock.batch_job()
        tg = job.task_groups[0]
        count = FILL_K * 3                 # > FILL_K distinct fills
        tg.count = count
        tg.tasks[0].resources.cpu = 100
        tg.tasks[0].resources.memory_mb = 100
        h.state.upsert_job(job)
        snap = h.state.snapshot()

        bd = PlacementEngine(mesh=False).place(
            snap, job, job.task_groups, None, seed=5,
            block=(tg.name, count))
        placed = bd.picks[bd.picks >= 0]
        assert len(placed) == count
        assert len(np.unique(placed)) > FILL_K
        counts = np.bincount(placed)
        assert counts.max() <= 2                   # capacity respected

    def test_job_count_seeds_respected(self):
        """A job with live allocs placing again through the compact path
        must see its existing per-node counts (anti-affinity seeds) —
        the compact [J', Nc] seed table gathers them onto the frame."""
        h, nodes = build_zoned_cluster(60, n_zones=2)
        items = zoned_items(h, 2, 8, n_zones=2)
        snap = h.state.snapshot()
        eng = PlacementEngine(mesh=False)
        first = eng.place_batch(snap, items, seed=3)
        from nomad_tpu.structs import Resources
        allocs = []
        for bd, it in zip(first, items):
            for p in bd.picks.tolist():
                if p >= 0:
                    allocs.append(mock.alloc(
                        job=it.job, node_id=bd.node_ids[p],
                        task_group=it.tg.name,
                        resources=Resources(cpu=10, memory_mb=10),
                        client_status="running"))
        h.state.upsert_allocs(allocs)
        snap2 = h.state.snapshot()
        d_c = PlacementEngine(mesh=False).place_batch(snap2, items, seed=4)
        d_f = self._flat(
            lambda: PlacementEngine(mesh=False).place_batch(
                snap2, items, seed=4))
        for a, b in zip(d_c, d_f):
            assert np.array_equal(a.picks, b.picks)


class TestPortSafetyInBatch:
    """Port asks must never ride the coupled-batch skip-fit path: each
    batched scheduler assigns ports from a private NetworkIndex over the
    same shared snapshot, so two batch-mates on one node pick identical
    dynamic ports — only the applier's AllocsFit port check catches it
    (reference: plan_apply.go evaluateNodePlan)."""

    def test_prepare_batch_accepts_port_asks(self):
        """Round-5 verdict #6: networked groups RIDE the batch (the
        worker's shared NetworkIndex keeps batch-mates' ports disjoint;
        round 4 excluded them entirely)."""
        from nomad_tpu.scheduler.generic import GenericScheduler
        from nomad_tpu.structs import NetworkResource, Port

        h, _ = build_cluster(20)
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 8
        tg.tasks[0].resources.networks = [NetworkResource(
            dynamic_ports=[Port(label="http")])]
        h.state.upsert_job(job)
        e = mock.eval(job_id=job.id, type=job.type)
        h.state.upsert_evals([e])
        sched = GenericScheduler(h.state.snapshot(), h, is_batch=True,
                                 now=NOW)
        assert sched.prepare_batch(e) is not None

    def test_batched_networked_jobs_get_disjoint_ports(self):
        """Several networked evals share one batch on a TINY cluster so
        batch-mates pile onto the same nodes: every committed (node,
        port) pair must be unique — the shared per-batch NetworkIndex is
        what prevents the identical-pick collision the old exclusion
        guarded against."""
        from nomad_tpu.structs import NetworkResource, Port

        s = Server(dev_mode=True, eval_batch=64)
        s.establish_leadership()
        for _ in range(3):
            s.register_node(mock.node(), now=NOW)
        jobs = []
        for _ in range(4):
            job = mock.batch_job()
            tg = job.task_groups[0]
            tg.count = 6
            tg.tasks[0].resources.cpu = 10
            tg.tasks[0].resources.memory_mb = 10
            tg.tasks[0].resources.networks = [NetworkResource(
                dynamic_ports=[Port(label="http"),
                               Port(label="admin")])]
            jobs.append(job)
            s.register_job(job, now=NOW)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        seen = set()
        live = 0
        for job in jobs:
            for a in snap.allocs_by_job(job.namespace, job.id):
                if a.terminal_status():
                    continue
                live += 1
                for label, port in a.allocated_ports.items():
                    key = (a.node_id, port)
                    assert key not in seen, (
                        f"port collision on {key} ({label})")
                    seen.add(key)
        assert live == 24          # every placement committed
        assert len(seen) == 48     # two unique ports per alloc

    def test_skip_fit_still_refutes_port_collision(self):
        """Defense at the serialization point: even a fenced coupled plan
        whose allocs carry port assignments must run the fit check — a
        static-port collision behind an intact fence is refuted, not
        committed."""
        from nomad_tpu.core import PlanApplier, PlanQueue
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs import (NetworkResource, Plan, Port,
                                       Resources)

        state = StateStore()
        q = PlanQueue()
        q.set_enabled(True)
        applier = PlanApplier(state, q)
        node = mock.node()
        state.upsert_node(node)
        job = mock.job()
        state.upsert_job(job)

        def mkplan(eid, bid, seq0):
            a = mock.alloc(job=job, node_id=node.id)
            a.resources = Resources(
                cpu=50, memory_mb=32,
                networks=[NetworkResource(
                    reserved_ports=[Port(label="http", value=8080)])])
            a.allocated_ports = {"http": 8080}
            p = Plan(eval_id=eid, job=job, coupled_batch=(bid, seq0))
            p.append_alloc(a)
            return p

        seq0 = state.placement_seq()
        p1 = q.enqueue(mkplan("e1", "batch1", seq0))
        applier.apply_one(p1)
        r1, err1 = p1.wait(1)
        assert err1 is None and not r1.refuted_nodes

        # same static port, same node, same (still-intact) chain fence
        p2 = q.enqueue(mkplan("e2", "batch1", seq0))
        applier.apply_one(p2)
        r2, err2 = p2.wait(1)
        assert err2 is None
        assert r2.refuted_nodes == [node.id]
        # the collision never reached state
        ports = [a.allocated_ports for a in
                 state.snapshot().allocs_by_node(node.id)
                 if not a.terminal_status()]
        assert ports == [{"http": 8080}]

    def test_static_port_job_places_end_to_end(self):
        """A static-port alloc carries its port in BOTH allocated_ports
        and its resources ask; the applier must not read that as a
        self-collision (regression: allocs_fit double-counted it)."""
        from nomad_tpu.structs import NetworkResource, Port

        s = Server(dev_mode=True, eval_batch=64)
        s.establish_leadership()
        for _ in range(4):
            s.register_node(mock.node(), now=NOW)
        job = mock.job()
        tg = job.task_groups[0]
        tg.count = 3
        tg.tasks[0].resources.networks = [NetworkResource(
            reserved_ports=[Port(label="http", value=8080)])]
        s.register_job(job, now=NOW)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        assert len(live) == 3
        # static port -> three distinct nodes, each alloc owns 8080
        assert len({a.node_id for a in live}) == 3
        assert all(a.allocated_ports.get("http") == 8080 for a in live)


class TestMultiWorkerSafety:
    """Per-node fencing, delivery-token gating, and partitioned dequeue —
    the machinery that lets num_schedulers-style concurrent workers
    coexist with the coupled-batch fast path (reference contrast:
    nomad/worker.go workers dequeue blindly and resolve every collision
    at plan apply; here disjoint workers never even collide)."""

    def test_per_node_fence_tolerates_own_chain(self):
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs import Plan

        state = StateStore()
        n1, n2 = mock.node(), mock.node()
        state.upsert_node(n1)
        state.upsert_node(n2)
        job = mock.job()
        state.upsert_job(job)
        seq0 = state.placement_seq()
        # chain A commits on n1
        a = mock.alloc(job=job, node_id=n1.id)
        plan = Plan(eval_id="e1", job=job, coupled_batch=("chainA", seq0))
        plan.append_alloc(a)
        from nomad_tpu.structs import PlanResult
        state.upsert_plan_results(plan, PlanResult(
            node_allocation=plan.node_allocation))
        # chain A's own write on n1 is tolerated; a foreign view is not
        assert state.nodes_unchanged_since([n1.id], seq0, "chainA")
        assert not state.nodes_unchanged_since([n1.id], seq0, "chainB")
        # n2 untouched: everyone passes
        assert state.nodes_unchanged_since([n2.id], seq0, "chainB")

    def test_stale_delivery_token_rejected_at_applier(self):
        """An eval redelivered while worker A sat in a device compile:
        worker A's plan must be rejected, not double-committed
        (reference: the EvalToken check at plan submission)."""
        s = Server(dev_mode=True)
        s.establish_leadership()
        s.register_node(mock.node(), now=NOW)
        job = mock.job()
        job.task_groups[0].count = 1
        ev = s.register_job(job, now=NOW)

        # worker A dequeues; then the delivery expires and B gets it
        e1, tok_a = s.eval_broker.dequeue(["service"], now=NOW)
        assert e1.id == ev.id
        s.eval_broker.tick(NOW + 10_000)          # expire A's delivery
        e2, tok_b = s.eval_broker.dequeue(["service"], now=NOW + 10_000)
        assert e2.id == ev.id and tok_b != tok_a

        from nomad_tpu.core.plan_apply import PendingPlan, StaleDeliveryError
        from nomad_tpu.structs import Plan
        stale = Plan(eval_id=ev.id, eval_token=tok_a, job=job)
        stale.append_alloc(mock.alloc(job=job,
                                      node_id=s.state.snapshot().nodes()[0].id))
        p = PendingPlan(stale)
        s.plan_applier.apply_one(p)
        result, err = p.wait(1)
        assert result is None and isinstance(err, StaleDeliveryError)
        assert s.plan_applier.stats["stale_token"] == 1
        # the CURRENT delivery's plan commits fine
        fresh = Plan(eval_id=ev.id, eval_token=tok_b, job=job)
        fresh.append_alloc(mock.alloc(job=job,
                                      node_id=s.state.snapshot().nodes()[0].id))
        p2 = PendingPlan(fresh)
        s.plan_applier.apply_one(p2)
        result2, err2 = p2.wait(1)
        assert err2 is None and not result2.refuted_nodes

    def test_partitioned_dequeue_single_key_batches(self):
        """With partition_of set (num_workers > 1), each batch carries a
        single placement-domain signature; other signatures stay queued
        for the next worker."""
        from nomad_tpu.structs import VolumeRequest

        s = Server(dev_mode=True, num_workers=2)
        s.establish_leadership()
        for _ in range(4):
            n = mock.node()
            n.csi_node_plugins["ebs0"] = True
            s.register_node(n, now=NOW)
        from nomad_tpu.structs import CSIVolume
        for z in ("a", "b"):
            s.state.upsert_csi_volume(CSIVolume(id=f"vol-{z}",
                                                plugin_id="ebs0"))
        jobs = []
        for i in range(6):
            job = mock.batch_job()
            job.task_groups[0].count = 1
            job.task_groups[0].volumes = {
                "d": VolumeRequest(name="d", type="csi",
                                   source=f"vol-{'a' if i % 2 else 'b'}",
                                   read_only=True)}
            s.register_job(job, now=NOW)
            jobs.append(job)
        batch1 = s.eval_broker.dequeue_batch(
            ["service", "batch"], 16, now=NOW)
        batch2 = s.eval_broker.dequeue_batch(
            ["service", "batch"], 16, now=NOW)
        assert len(batch1) == 3 and len(batch2) == 3
        key1 = {s._eval_partition(ev) for ev, _ in batch1}
        key2 = {s._eval_partition(ev) for ev, _ in batch2}
        assert len(key1) == 1 and len(key2) == 1 and key1 != key2
