"""Deployment lifecycle: rolling updates, canaries, promotion, auto-revert,
progress deadline (reference: nomad/deploymentwatcher/ +
scheduler/reconcile.go canary/rolling semantics)."""

import copy

from nomad_tpu import mock
from nomad_tpu.core import Server
from nomad_tpu.structs import (
    DEPLOYMENT_STATUS_FAILED,
    DEPLOYMENT_STATUS_RUNNING,
    DEPLOYMENT_STATUS_SUCCESSFUL,
    UpdateStrategy,
)

NOW = 1000.0


def _service_job(count=4, **update_kw):
    j = mock.job()
    j.task_groups[0].count = count
    j.update = UpdateStrategy(max_parallel=1, progress_deadline_s=600.0,
                              **update_kw)
    return j


def _mutate(job):
    """New version of `job` requiring destructive updates."""
    j2 = copy.deepcopy(job)
    j2.task_groups[0].tasks[0].config = {"command": "/bin/sleep"}
    return j2


def _live(server, job):
    return [a for a in server.state.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


def _set_health(server, allocs, healthy=True):
    ups = []
    for a in allocs:
        u = a.copy_skip_job()
        u.client_status = "running"
        u.deployment_status = {"healthy": healthy, "ts": NOW}
        ups.append(u)
    server.state.update_allocs_from_client(ups)


def _drive_to_completion(s, job, now=NOW, rounds=30):
    """process evals + mark new-version allocs healthy + tick, until the
    active deployment leaves the running state."""
    for i in range(rounds):
        s.process_all(now=now + i)
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        if dep is None or dep.status != DEPLOYMENT_STATUS_RUNNING:
            return dep
        fresh = [a for a in _live(s, job)
                 if a.deployment_id == dep.id
                 and not (a.deployment_status or {}).get("healthy")]
        _set_health(s, fresh, healthy=True)
        s.deployments.tick(now=now + i)
    return s.state.latest_deployment_by_job(job.namespace, job.id)


def _stable_v0(s, job):
    """Initial registration driven to a successful deployment."""
    s.register_job(job, now=NOW)
    dep = _drive_to_completion(s, job)
    assert dep is not None and dep.status == DEPLOYMENT_STATUS_SUCCESSFUL
    assert s.state.job_by_id(job.namespace, job.id).stable
    return dep


class TestRollingUpdate:
    def test_initial_deploy_completes_and_marks_stable(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)
        assert len(_live(s, job)) == 4

    def test_rolling_is_health_gated_by_max_parallel(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)

        v1 = _mutate(job)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        new = [a for a in _live(s, v1) if a.job_version == 1]
        assert len(new) == 1, "first wave must respect max_parallel=1"

        # a second eval without health progress must NOT widen the wave
        s.apply_eval_update([mock.eval(job_id=v1.id, type=v1.type)],
                            now=NOW + 101)
        s.process_all(now=NOW + 101)
        assert len([a for a in _live(s, v1) if a.job_version == 1]) == 1, \
            "unhealthy in-flight wave consumes the max_parallel budget"

        dep = _drive_to_completion(s, v1, now=NOW + 110)
        assert dep.status == DEPLOYMENT_STATUS_SUCCESSFUL
        final = _live(s, v1)
        assert len(final) == 4
        assert all(a.job_version == dep.job_version for a in final)

    def test_unhealthy_alloc_fails_deployment(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)

        v1 = _mutate(job)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        new = [a for a in _live(s, v1) if a.deployment_id == dep.id]
        _set_health(s, new, healthy=False)
        s.deployments.tick(now=NOW + 101)
        dep = s.state.deployment_by_id(dep.id)
        assert dep.status == DEPLOYMENT_STATUS_FAILED
        assert "unhealthy" in dep.status_description.lower()


class TestCanaries:
    def _setup(self, auto_promote=False, auto_revert=False):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(8):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)
        v1 = _mutate(job)
        v1.update = UpdateStrategy(max_parallel=1, canary=1,
                                   auto_promote=auto_promote,
                                   auto_revert=auto_revert,
                                   progress_deadline_s=600.0)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        return s, v1

    def test_canary_placed_alongside_old_version(self):
        s, v1 = self._setup()
        live = _live(s, v1)
        old = [a for a in live if a.job_version == 0]
        new = [a for a in live if a.job_version == 1]
        assert len(old) == 4, "old version must keep running"
        assert len(new) == 1, "exactly `canary` new-version allocs"
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        st = dep.task_groups["web"]
        assert st.desired_canaries == 1
        assert st.placed_canaries == [new[0].id]
        assert not st.promoted

    def test_unpromoted_deployment_does_not_roll(self):
        s, v1 = self._setup()
        canaries = [a for a in _live(s, v1) if a.job_version == 1]
        _set_health(s, canaries, healthy=True)
        s.deployments.tick(now=NOW + 101)
        s.process_all(now=NOW + 101)
        live = _live(s, v1)
        assert len([a for a in live if a.job_version == 1]) == 1, \
            "no rollout before promotion"

    def test_manual_promote_then_rollout(self):
        s, v1 = self._setup()
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        canaries = [a for a in _live(s, v1) if a.job_version == 1]

        err = s.deployments.promote(dep.id, now=NOW + 101)
        assert err == "canaries are not healthy"

        _set_health(s, canaries, healthy=True)
        err = s.deployments.promote(dep.id, now=NOW + 102)
        assert err is None
        dep = s.state.deployment_by_id(dep.id)
        assert dep.task_groups["web"].promoted

        final_dep = _drive_to_completion(s, v1, now=NOW + 110)
        assert final_dep.status == DEPLOYMENT_STATUS_SUCCESSFUL
        live = _live(s, v1)
        assert len(live) == 4
        assert all(a.job_version == dep.job_version for a in live)

    def test_auto_promote(self):
        s, v1 = self._setup(auto_promote=True)
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        canaries = [a for a in _live(s, v1) if a.job_version == 1]
        _set_health(s, canaries, healthy=True)
        s.deployments.tick(now=NOW + 101)
        dep = s.state.deployment_by_id(dep.id)
        assert dep.task_groups["web"].promoted


class TestAutoRevert:
    def test_unhealthy_reverts_to_stable_version(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)
        v0_cmd = job.task_groups[0].tasks[0].config["command"]

        v1 = _mutate(job)
        v1.update = UpdateStrategy(max_parallel=1, auto_revert=True,
                                   progress_deadline_s=600.0)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        new = [a for a in _live(s, v1) if a.deployment_id == dep.id]
        _set_health(s, new, healthy=False)
        s.deployments.tick(now=NOW + 101)

        dep = s.state.deployment_by_id(dep.id)
        assert dep.status == DEPLOYMENT_STATUS_FAILED
        assert "rolling back to job version 0" in dep.status_description

        cur = s.state.job_by_id(v1.namespace, v1.id)
        assert cur.version == 2, "revert mints a new version"
        assert cur.task_groups[0].tasks[0].config["command"] == v0_cmd
        # the revert eval reconciles the cluster back to the old spec
        s.process_all(now=NOW + 102)
        live = _live(s, v1)
        assert all(a.job is not None and
                   a.job.task_groups[0].tasks[0].config["command"] == v0_cmd
                   for a in live if a.job_version == 2)


class TestSupersededDeployment:
    def test_new_version_cancels_running_deployment(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)

        v1 = _mutate(job)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        dep_v1 = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        assert dep_v1.status == DEPLOYMENT_STATUS_RUNNING

        v2 = _mutate(v1)
        v2.task_groups[0].tasks[0].config = {"command": "/bin/true"}
        s.register_job(v2, now=NOW + 110)
        s.process_all(now=NOW + 110)
        old = s.state.deployment_by_id(dep_v1.id)
        assert old.status == "cancelled"
        cur = s.state.latest_deployment_by_job(v2.namespace, v2.id)
        assert cur.id != dep_v1.id
        assert cur.status == DEPLOYMENT_STATUS_RUNNING


class TestReviewRegressions:
    def test_replacement_after_success_does_not_restart_deployment(self):
        # A node failure after a successful deployment must not mint a
        # fresh deployment (whose progress deadline would later fail and
        # auto-revert a perfectly healthy job).
        s = Server(dev_mode=True)
        s.establish_leadership()
        nodes = [mock.node() for _ in range(6)]
        for n in nodes:
            s.register_node(n, now=NOW)
        job = _service_job(auto_revert=True)
        dep0 = _stable_v0(s, job)

        victim = _live(s, job)[0]
        s.update_node_status(victim.node_id, "down", now=NOW + 50)
        s.process_all(now=NOW + 50)
        assert len(_live(s, job)) == 4, "replacement placed"
        cur = s.state.latest_deployment_by_job(job.namespace, job.id)
        assert cur.id == dep0.id and cur.status == DEPLOYMENT_STATUS_SUCCESSFUL
        # far-future tick: nothing to deadline-fail, job not reverted
        s.deployments.tick(now=NOW + 10000)
        assert s.state.job_by_id(job.namespace, job.id).version == 0

    def test_failed_canary_is_refilled_not_replaced(self):
        # A failed canary must be replaced by a NEW canary, not stop a
        # healthy old-version alloc / mint an untagged new-version alloc.
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(8):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)
        v1 = _mutate(job)
        v1.update = UpdateStrategy(max_parallel=1, canary=1,
                                   progress_deadline_s=600.0)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        canary = [a for a in _live(s, v1) if a.job_version == 1][0]

        u = canary.copy_skip_job()
        u.client_status = "failed"
        s.state.update_allocs_from_client([u])
        s.apply_eval_update([mock.eval(job_id=v1.id, type=v1.type)],
                            now=NOW + 101)
        s.process_all(now=NOW + 101)

        live = _live(s, v1)
        old = [a for a in live if a.job_version == 0]
        new = [a for a in live if a.job_version == 1]
        assert len(old) == 4, "old version untouched by canary failure"
        assert len(new) == 1, "exactly one replacement canary"
        assert new[0].id != canary.id
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        assert new[0].id in dep.task_groups["web"].placed_canaries

    def test_superseded_deployment_cancelled_without_successor(self):
        # Dropping the update stanza must still cancel the running
        # deployment (cancellation is unconditional, not tied to the
        # successor creating its own deployment).
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)
        v1 = _mutate(job)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        dep_v1 = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        assert dep_v1.status == DEPLOYMENT_STATUS_RUNNING

        v2 = _mutate(v1)
        v2.task_groups[0].tasks[0].config = {"command": "/bin/true"}
        v2.update = None
        v2.task_groups[0].update = None
        s.register_job(v2, now=NOW + 110)
        s.process_all(now=NOW + 110)
        assert s.state.deployment_by_id(dep_v1.id).status == "cancelled"


class TestProgressDeadline:
    def test_no_progress_fails_deployment(self):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(6):
            s.register_node(mock.node(), now=NOW)
        job = _service_job()
        _stable_v0(s, job)

        v1 = _mutate(job)
        v1.update = UpdateStrategy(max_parallel=1, progress_deadline_s=10.0)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        s.deployments.tick(now=NOW + 101)    # arms the deadline
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        assert dep.status == DEPLOYMENT_STATUS_RUNNING
        s.deployments.tick(now=NOW + 120)    # past deadline, no health
        dep = s.state.deployment_by_id(dep.id)
        assert dep.status == DEPLOYMENT_STATUS_FAILED
        assert "progress deadline" in dep.status_description.lower()


class TestBlockCommittedDeployment:
    """ISSUE 35: a deployment no longer bars the columnar commit.  A
    service job with an update stanza, 80 fresh placements and no spread
    rides the water-fill and commits as ONE AllocBlock whose template
    carries the deployment's id; the lifecycle runs as it did on rows."""

    def _server(self, n_nodes=12):
        s = Server(dev_mode=True)
        s.establish_leadership()
        for _ in range(n_nodes):
            s.register_node(mock.node(), now=NOW)
        return s

    def _job(self, **update_kw):
        j = _service_job(count=80, **update_kw)
        j.task_groups[0].tasks[0].resources.cpu = 20
        j.task_groups[0].tasks[0].resources.memory_mb = 16
        return j

    def _registered(self, **update_kw):
        s = self._server()
        job = self._job(**update_kw)
        s.register_job(job, now=NOW)
        s.process_all(now=NOW)
        return s, job

    def test_commits_as_one_block_with_the_deployments_id(self):
        s, job = self._registered()
        (block,) = s.state._alloc_blocks.values()
        assert block.count == 80 and block.row_metrics is None
        assert block.metrics, "the water-fill's per-round metrics"
        assert not s.state._allocs_by_job.get((job.namespace, job.id))
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        assert dep.status == DEPLOYMENT_STATUS_RUNNING
        assert dep.task_groups["web"].desired_total == 80
        s.deployments.tick(now=NOW + 1)
        dep = s.state.deployment_by_id(dep.id)
        assert dep.task_groups["web"].placed_allocs == 80
        # the tick counted the block off its columns: no row was built
        assert block._rows is None and s.state._alloc_blocks
        rows = _live(s, job)
        assert len(rows) == 80
        assert {a.deployment_id for a in rows} == {dep.id}

    def test_completes_and_marks_stable(self):
        s = self._server()
        job = self._job()
        dep = _stable_v0(s, job)
        assert dep.task_groups["web"].healthy_allocs == 80
        assert len(_live(s, job)) == 80

    def test_unhealthy_row_fails_the_deployment(self):
        s, job = self._registered()
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        rows = _live(s, job)
        _set_health(s, rows[:1], healthy=False)
        s.deployments.tick(now=NOW + 1)
        dep = s.state.deployment_by_id(dep.id)
        assert dep.status == DEPLOYMENT_STATUS_FAILED
        assert "unhealthy" in dep.status_description.lower()

    def test_manual_fail_and_pause(self):
        s, job = self._registered()
        dep = s.state.latest_deployment_by_job(job.namespace, job.id)
        assert s.deployments.pause(dep.id, True) is None
        assert s.state.deployment_by_id(dep.id).status == "paused"
        assert s.deployments.pause(dep.id, False, now=NOW + 1) is None
        assert s.deployments.fail(dep.id, now=NOW + 2) is None
        assert s.state.deployment_by_id(dep.id).status == \
            DEPLOYMENT_STATUS_FAILED

    def test_canaries_beside_a_block_promote_and_roll(self):
        s = self._server()
        job = self._job()
        _stable_v0(s, job)
        v1 = _mutate(job)
        v1.update = UpdateStrategy(max_parallel=40, canary=2,
                                   progress_deadline_s=600.0)
        s.register_job(v1, now=NOW + 100)
        s.process_all(now=NOW + 100)
        dep = s.state.latest_deployment_by_job(v1.namespace, v1.id)
        canaries = [a for a in _live(s, v1) if a.job_version == 1]
        assert len(canaries) == 2
        assert sorted(dep.task_groups["web"].placed_canaries) == \
            sorted(a.id for a in canaries)
        assert s.deployments.promote(dep.id, now=NOW + 101) == \
            "canaries are not healthy"
        _set_health(s, canaries, healthy=True)
        assert s.deployments.promote(dep.id, now=NOW + 102) is None
        final = _drive_to_completion(s, v1, now=NOW + 110)
        assert final.status == DEPLOYMENT_STATUS_SUCCESSFUL
        live = _live(s, v1)
        assert len(live) == 80
        assert all(a.job_version == final.job_version for a in live)
