"""Allocation reconciler (reference: scheduler/reconcile.go, reconcile_util.go).

Diffs desired state (the job) against actual state (existing allocations +
node health) and emits the action sets the scheduler turns into a plan:
place / stop / ignore / in-place update / destructive update / migrate /
reschedule-now / reschedule-later, plus deployment bookkeeping and the
per-task-group DesiredUpdates annotation counts.

This is deliberately host-side Python (SURVEY.md §7 P4): it is control-flow
heavy, data-light, and feeds the batched device placement kernel with one
flat list of placement requests per eval.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from nomad_tpu.structs import (
    ALLOC_CLIENT_FAILED,
    ALLOC_CLIENT_LOST,
    Allocation,
    DEPLOYMENT_STATUS_CANCELLED,
    DEPLOYMENT_STATUS_FAILED,
    Deployment,
    DeploymentState,
    DeploymentStatusUpdate,
    DesiredUpdates,
    JOB_TYPE_BATCH,
    Job,
    Node,
    TaskGroup,
)

from .util import (
    ALLOC_LOST,
    ALLOC_MIGRATING,
    ALLOC_NOT_NEEDED,
    RESCHEDULE_LATER,
    RESCHEDULE_NOW,
    free_indexes,
    should_reschedule,
    tasks_updated,
)


@dataclass
class PlaceRequest:
    """One placement the scheduler must make."""
    tg: TaskGroup
    name: str
    index: int
    previous_alloc: Optional[Allocation] = None   # reschedule/migrate source
    reschedule: bool = False
    migrate: bool = False
    canary: bool = False


@dataclass
class StopRequest:
    alloc: Allocation
    status_description: str
    client_status: str = ""          # e.g. "lost" for down nodes


@dataclass
class PlaceBlock:
    """Compact form of a homogeneous run of FRESH placements for one task
    group (no previous alloc, not canaries): carrying one object + an
    index list instead of N PlaceRequests.  At bench scale (100k
    placements) the per-request objects and name strings alone cost more
    than the device work, so the common batch-job shape stays compact all
    the way into the water-fill kernel."""
    tg: TaskGroup
    indexes: List[int]


@dataclass
class ReconcileResults:
    place: List[PlaceRequest] = field(default_factory=list)
    place_blocks: List[PlaceBlock] = field(default_factory=list)
    stop: List[StopRequest] = field(default_factory=list)
    inplace_update: List[Allocation] = field(default_factory=list)
    destructive_update: List[Allocation] = field(default_factory=list)
    ignore: List[Allocation] = field(default_factory=list)
    # (alloc, ready_time): follow-up eval needed at ready_time
    reschedule_later: List[tuple] = field(default_factory=list)
    desired_tg_updates: Dict[str, DesiredUpdates] = field(default_factory=dict)
    deployment: Optional[Deployment] = None
    deployment_updates: List[DeploymentStatusUpdate] = field(default_factory=list)

    def empty(self) -> bool:
        return not (self.place or self.place_blocks or self.stop
                    or self.inplace_update
                    or self.destructive_update or self.reschedule_later)


def reconcile(job: Optional[Job],
              job_stopped: bool,
              allocs: List[Allocation],
              tainted: Dict[str, Optional[Node]],
              now: float,
              existing_deployment: Optional[Deployment] = None,
              ) -> ReconcileResults:
    """Compute the action sets for one eval.

    reference: allocReconciler.Compute.  Semantics preserved:
      - stopped/deregistered job ⇒ stop everything non-terminal
      - batch jobs don't replace successfully-completed allocs
      - allocs on down nodes are lost (stop w/ client_status=lost) and
        replaced; draining nodes migrate (stop + place with migrate flag)
      - failed allocs follow the task group ReschedulePolicy (now / later
        with follow-up eval / never)
      - job-version changes split into in-place vs destructive updates via
        tasks_updated; destructive updates are throttled by
        update.max_parallel when an update stanza is present
      - excess allocs (count shrink) stop highest name-indexes first
    """
    r = ReconcileResults()

    # a still-active deployment that no longer matches the job (version
    # superseded, job stopped/deregistered) is cancelled unconditionally —
    # not only when the successor creates its own deployment (reference:
    # reconcile.go cancelUnneededDeployments)
    if (existing_deployment is not None and existing_deployment.active()
            and (job is None or job_stopped
                 or existing_deployment.job_version != job.version)):
        r.deployment_updates.append(DeploymentStatusUpdate(
            deployment_id=existing_deployment.id,
            status=DEPLOYMENT_STATUS_CANCELLED,
            status_description=(
                "cancelled because job is no longer the same version"
                if job is not None and not job_stopped
                else "cancelled because job is stopped"),
        ))

    live = [a for a in allocs if not a.terminal_status()]
    if job is None or job_stopped:
        for a in live:
            r.stop.append(StopRequest(a, ALLOC_NOT_NEEDED))
        return r

    is_batch = job.type == JOB_TYPE_BATCH
    by_tg: Dict[str, List[Allocation]] = {}
    for a in allocs:
        by_tg.setdefault(a.task_group, []).append(a)

    # allocs for task groups that no longer exist
    known = {tg.name for tg in job.task_groups}
    for tg_name, tg_allocs in by_tg.items():
        if tg_name not in known:
            for a in tg_allocs:
                if not a.terminal_status():
                    r.stop.append(StopRequest(a, ALLOC_NOT_NEEDED))

    for tg in job.task_groups:
        _reconcile_group(r, job, tg, by_tg.get(tg.name, []), tainted, now,
                         is_batch, existing_deployment)
    return r


def _reconcile_group(r: ReconcileResults, job: Job, tg: TaskGroup,
                     allocs: List[Allocation],
                     tainted: Dict[str, Optional[Node]], now: float,
                     is_batch: bool,
                     deployment: Optional[Deployment]) -> None:
    du = DesiredUpdates()
    r.desired_tg_updates[tg.name] = du

    # ---- deployment context for this job version / group ----
    update = tg.update or job.update
    dstate = None
    dep_failed_version = False
    dep_concluded_version = False
    if (deployment is not None and deployment.job_version == job.version
            and job.type == "service"):
        if deployment.active():
            dstate = deployment.task_groups.get(tg.name)
        else:
            # this version's deployment already concluded — replacements
            # and reschedules must not mint a fresh one (a node failure
            # would otherwise restart deployment tracking and, worse,
            # progress-deadline-fail + auto-revert a healthy job)
            dep_concluded_version = True
            if deployment.status == DEPLOYMENT_STATUS_FAILED:
                # failed additionally halts further rollout; recovery is
                # job revert / new version (reference: reconcile.go
                # deploymentFailed handling)
                dep_failed_version = True
    promoted = dstate.promoted if dstate is not None else False
    canary_ids = set(dstate.placed_canaries) if dstate is not None else set()

    # unpromoted canaries are supernumerary: they run ALONGSIDE the old
    # version and stay out of ALL slot-count math (including the
    # lost/failed buckets) until promotion; dead/lost canaries are
    # refilled by the canary placement below, not by regular replacement
    canaries_live: List[Allocation] = []
    if canary_ids and not promoted:
        remaining: List[Allocation] = []
        for a in allocs:
            if a.id not in canary_ids:
                remaining.append(a)
                continue
            if a.desired_status != "run" or a.client_terminal_status():
                continue
            if a.node_id in tainted:
                node = tainted[a.node_id]
                if node is None or node.status in ("down", "disconnected"):
                    du.stop += 1
                    r.stop.append(StopRequest(
                        a, ALLOC_LOST, client_status=ALLOC_CLIENT_LOST))
                elif a.desired_transition.migrate:
                    # draining canaries follow the same drainer-flagged
                    # batching as regular allocs
                    du.migrate += 1
                    r.stop.append(StopRequest(a, ALLOC_MIGRATING))
                else:
                    canaries_live.append(a)
                continue
            if a.client_status == ALLOC_CLIENT_FAILED:
                continue
            canaries_live.append(a)
        allocs = remaining

    untainted: List[Allocation] = []
    migrate: List[Allocation] = []
    lost: List[Allocation] = []
    failed: List[Allocation] = []
    done_batch: List[Allocation] = []   # batch allocs that ran successfully

    for a in allocs:
        if a.desired_status != "run":
            continue  # already stopping/evicting
        if a.node_id in tainted:
            node = tainted[a.node_id]
            if node is None or node.status in ("down", "disconnected"):
                if a.client_terminal_status():
                    continue
                lost.append(a)
            else:  # draining
                if a.client_terminal_status():
                    continue
                if a.desired_transition.migrate:
                    migrate.append(a)
                else:
                    # the drainer releases allocs in migrate.max_parallel
                    # batches by flagging DesiredTransition.migrate; until
                    # then the alloc keeps running on the draining node
                    untainted.append(a)
            continue
        if a.client_status == ALLOC_CLIENT_FAILED:
            failed.append(a)
            continue
        if a.client_terminal_status():
            # complete: batch jobs treat success as done — the slot is
            # filled forever, never replaced
            if is_batch and a.ran_successfully():
                du.ignore += 1
                r.ignore.append(a)
                done_batch.append(a)
            continue
        untainted.append(a)

    # ---- lost: stop w/ lost status + replace ----
    for a in lost:
        du.stop += 1
        r.stop.append(StopRequest(a, ALLOC_LOST, client_status=ALLOC_CLIENT_LOST))

    # ---- migrate (drain): stop + replacement placement ----
    for a in migrate:
        du.migrate += 1
        r.stop.append(StopRequest(a, ALLOC_MIGRATING))

    # ---- failed: reschedule policy ----
    # Failed allocs NOT rescheduled right now still hold their slot (the
    # reference keeps them in the untainted set): a reschedule-later alloc
    # is replaced only when its follow-up eval fires; a
    # reschedule-exhausted alloc is never replaced.
    reschedule_now: List[Allocation] = []
    failed_holding_slot: List[Allocation] = []
    for a in failed:
        policy = tg.reschedule_policy
        verdict, ready_at = should_reschedule(a, policy, now)
        if verdict == RESCHEDULE_NOW:
            reschedule_now.append(a)
            du.reschedule_now += 1
        elif verdict == RESCHEDULE_LATER:
            r.reschedule_later.append((a, ready_at))
            failed_holding_slot.append(a)
            du.reschedule_later += 1
        else:
            r.ignore.append(a)
            failed_holding_slot.append(a)

    # ---- count management: stop excess BEFORE the update split, so a
    # count decrease can shed old-version allocs too.  Old-version allocs
    # stop first (that is the post-promotion rollover), then highest
    # name-indexes ----
    n_replacements = len(lost) + len(migrate) + len(reschedule_now)
    needed = (tg.count - len(untainted) - len(done_batch)
              - len(failed_holding_slot) - n_replacements)
    if needed < 0:
        excess = sorted(untainted, key=lambda a: (
            a.job is not None and a.job_version != job.version, a.index()),
            reverse=True)
        to_stop = excess[:-needed]
        for a in to_stop:
            du.stop += 1
            r.stop.append(StopRequest(a, ALLOC_NOT_NEEDED))
        stop_ids = {a.id for a in to_stop}
        untainted = [a for a in untainted if a.id not in stop_ids]
        needed = 0

    # ---- updates: in-place vs destructive for old-version allocs ----
    inplace: List[Allocation] = []
    destructive: List[Allocation] = []
    current: List[Allocation] = []
    for a in untainted:
        if a.job is not None and a.job_version != job.version:
            if tasks_updated(a.job, job, tg.name):
                destructive.append(a)
            else:
                inplace.append(a)
        else:
            current.append(a)

    canaries_desired = (update.canary
                        if (update is not None and not is_batch
                            and job.type == "service") else 0)
    canarying = (canaries_desired > 0 and bool(destructive) and not promoted
                 and not dep_failed_version)

    limit = len(destructive)
    if update is not None and update.max_parallel > 0 and not is_batch:
        limit = min(limit, update.max_parallel)
        if dstate is not None and deployment is not None:
            # health-gated rolling: new-version allocs placed by this
            # deployment but not yet healthy consume max_parallel slots,
            # so the next wave waits for the previous one's health
            inflight = sum(
                1 for a in current
                if a.deployment_id == deployment.id
                and not (a.deployment_status or {}).get("healthy"))
            limit = max(0, limit - inflight)
    if canarying or dep_failed_version:
        # while canarying (or after this version's deployment failed) the
        # old version keeps running untouched
        limit = 0
    for a in destructive[:limit]:
        du.destructive_update += 1
        r.destructive_update.append(a)
    for a in destructive[limit:]:
        du.ignore += 1
        r.ignore.append(a)
    for a in inplace:
        du.in_place_update += 1
        r.inplace_update.append(a)

    # allocs that keep their slot (current, updated in place, or updated
    # destructively — the destructive replacement reuses the name/index)
    keep = current + inplace + destructive

    # ---- place: replacements first (carry prev alloc), then new slots,
    # then canaries — ONE shared index sequence so a replacement and a
    # canary minted in the same reconcile can't collide on a name ----
    n_canary_place = (max(0, canaries_desired - len(canaries_live))
                      if canarying else 0)
    indexes = free_indexes(
        keep + done_batch + failed_holding_slot + canaries_live, tg.count,
        extra=n_replacements + max(needed, 0) + n_canary_place)
    ptr = 0

    for a in lost + migrate:
        r.place.append(PlaceRequest(
            tg=tg, name=_name(job, tg, indexes[ptr]), index=indexes[ptr],
            previous_alloc=a, migrate=a in migrate))
        ptr += 1
        du.place += 1
    for a in reschedule_now:
        r.place.append(PlaceRequest(
            tg=tg, name=_name(job, tg, indexes[ptr]), index=indexes[ptr],
            previous_alloc=a, reschedule=True))
        ptr += 1
        du.place += 1
    n_fresh = max(needed, 0)
    if (n_fresh >= 64 and not lost and not migrate and not reschedule_now
            and n_canary_place == 0):
        # compact: one PlaceBlock instead of n_fresh PlaceRequests
        r.place_blocks.append(PlaceBlock(
            tg=tg, indexes=indexes[ptr:ptr + n_fresh]))
        ptr += n_fresh
        du.place += n_fresh
    else:
        for _ in range(n_fresh):
            r.place.append(PlaceRequest(
                tg=tg, name=_name(job, tg, indexes[ptr]),
                index=indexes[ptr]))
            ptr += 1
            du.place += 1

    # missing canaries ride alongside the old version until promotion
    for _ in range(n_canary_place):
        r.place.append(PlaceRequest(
            tg=tg, name=_name(job, tg, indexes[ptr]), index=indexes[ptr],
            canary=True))
        ptr += 1
        du.canary += 1

    # kept-current allocs are untouched
    du.ignore += len(current) + len(canaries_live)
    r.ignore.extend(current)
    r.ignore.extend(canaries_live)

    # ---- deployment bookkeeping (service jobs with update stanza) ----
    # Accumulate onto the deployment the previous task group created this
    # reconcile, so multi-group jobs share one deployment object.
    if (not is_batch and update is not None and not dep_failed_version
            and (r.place or r.place_blocks or r.destructive_update
                 or canarying)
            and job.type == "service"):
        dep = r.deployment
        if dep is None:
            dep = deployment
            if (dep is None or dep.job_version != job.version
                    or not dep.active()):
                if dep_concluded_version or job.stable:
                    # this version already concluded a deployment (or was
                    # marked stable by one): replacements/reschedules do
                    # not restart deployment tracking
                    return
                dep = Deployment(
                    namespace=job.namespace, job_id=job.id,
                    job_version=job.version,
                    job_modify_index=job.job_modify_index)
            else:
                dep = dep.copy()
        state = dep.task_groups.get(tg.name) or DeploymentState(
            auto_revert=update.auto_revert,
            auto_promote=update.auto_promote,
            progress_deadline_s=update.progress_deadline_s)
        state.desired_total = tg.count
        state.desired_canaries = canaries_desired
        dep.task_groups[tg.name] = state
        r.deployment = dep


def _name(job: Job, tg: TaskGroup, idx: int) -> str:
    return f"{job.id}.{tg.name}[{idx}]"
