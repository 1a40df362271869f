"""System / sysbatch scheduler (reference: scheduler/system_sched.go,
scheduler/scheduler_sysbatch.go).

One alloc per eligible feasible node (daemonset-style).  Selection is
trivial (each feasible node hosts one alloc), so no scan is needed: one
`place_system` launch (ops/feasibility.py) evaluates feasibility AND the
capacity fit of every node x every task group, and the placements leave
as one columnar AllocBlock per task group.  Nodes that already hold the
job, and groups that ask for devices or ports, take the per-node walk on
the host.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from nomad_tpu.chaos.clock import SystemClock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import PlacementEngine
from nomad_tpu.ops.feasibility import (
    SYS_DIMENSIONS,
    SYS_FILTERED,
    SYS_PLACED,
    SYS_VERDICTS,
    feasible_mask,
)
from nomad_tpu.structs import (
    ALLOC_CLIENT_LOST,
    AllocBlock,
    Allocation,
    AllocMetric,
    EVAL_STATUS_COMPLETE,
    Evaluation,
    Job,
    Plan,
    allocs_fit,
    new_id,
    new_ids,
)

from .base import Planner, Scheduler
from .device import InUseIndex, assign_devices, tg_device_requests
from .generic import _engine
from .util import ALLOC_LOST, ALLOC_NOT_NEEDED, tainted_nodes, tasks_updated

# wall fallback when the driver passes no `now` (one-shot CLI paths);
# server paths always inject now from the bound chaos Clock
_WALL = SystemClock()

MAX_SYSTEM_ATTEMPTS = 5

# Device placement (ISSUE 26): when True, an eval whose task groups ask
# for no devices and no ports places every node that holds nothing of
# the job with ONE `place_system` launch and one AllocBlock per task
# group; False sends every node through the per-node walk -- the PARITY
# REFERENCE tests/test_system_device_path.py compares against (the
# pattern of generic.PORT_BATCHED).
SYSTEM_BATCHED = True


class SystemScheduler(Scheduler):
    """reference: scheduler.SystemScheduler"""

    def __init__(self, state, planner: Planner, sysbatch: bool = False,
                 engine: Optional[PlacementEngine] = None,
                 now: Optional[float] = None) -> None:
        self.state = state
        self.planner = planner
        self.sysbatch = sysbatch
        self.engine = _engine(engine, state)
        self.now = now if now is not None else _WALL.time()
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        # decision-record capture (core/explain.py)
        self._tg_stats: Dict[str, dict] = {}

    def process(self, evaluation: Evaluation) -> Optional[Exception]:
        state = self.state
        job = state.job_by_id(evaluation.namespace, evaluation.job_id)
        allocs = state.allocs_by_job(evaluation.namespace, evaluation.job_id)
        tainted = tainted_nodes(state, allocs)
        stopped = job is None or job.stopped()

        plan = Plan(eval_id=evaluation.id, priority=evaluation.priority,
                    job=job)
        self.failed_tg_allocs = {}
        self._tg_stats = {}

        live = [a for a in allocs if not a.terminal_status()]
        if stopped:
            for a in live:
                plan.append_stopped_alloc(a, ALLOC_NOT_NEEDED)
            return self._submit(plan, evaluation)

        # nodes the job can run in: ready + right dc/pool; restrict to a
        # single node for node-update triggered evals
        all_nodes = state.ready_nodes_in_pool(job.datacenters, job.node_pool)
        nodes = all_nodes
        if evaluation.node_id:
            nodes = [n for n in all_nodes if n.id == evaluation.node_id]

        # existing allocs per (node, tg)
        by_node_tg: Dict[tuple, Allocation] = {}
        for a in live:
            by_node_tg[(a.node_id, a.task_group)] = a

        # stops: allocs on tainted/ineligible nodes or for removed TGs
        all_eligible = {n.id for n in all_nodes}
        known_tgs = {tg.name for tg in job.task_groups}
        for a in live:
            if a.task_group not in known_tgs:
                plan.append_stopped_alloc(a, ALLOC_NOT_NEEDED)
                continue
            if a.node_id in tainted:
                node = tainted[a.node_id]
                if node is None or node.status in ("down", "disconnected"):
                    plan.append_stopped_alloc(a, ALLOC_LOST,
                                              client_status=ALLOC_CLIENT_LOST)
                elif a.desired_transition.migrate:
                    # draining system allocs wait for the drainer to flag
                    # them (they drain LAST, after the node's service
                    # allocs are gone)
                    plan.append_stopped_alloc(a, ALLOC_NOT_NEEDED)
                continue
            if a.node_id not in all_eligible:
                # stop only when the node left the job's placement domain;
                # a merely-ineligible node (drain finished with
                # ignore_system_jobs, manual eligibility -disable) keeps
                # its system allocs running
                node = state.node_by_id(a.node_id)
                if node is None:
                    plan.append_stopped_alloc(a, ALLOC_LOST,
                                              client_status=ALLOC_CLIENT_LOST)
                elif (node.datacenter not in job.datacenters
                        or node.node_pool != job.node_pool):
                    plan.append_stopped_alloc(a, ALLOC_NOT_NEEDED)

        # device feasibility over all nodes x TGs
        if nodes:
            self._place(plan, job, nodes, by_node_tg, evaluation)

        return self._submit(plan, evaluation)

    # ------------------------------------------------------------ placing

    def _place(self, plan: Plan, job: Job, nodes, by_node_tg, evaluation):
        tgs = job.task_groups
        asks = [tg.combined_resources() for tg in tgs]
        metrics = [AllocMetric(nodes_evaluated=len(nodes)) for _ in tgs]
        placed = [0] * len(tgs)          # fresh placements
        kept = [0] * len(tgs)            # existing allocs kept or updated
        # the device path's admission, in the spirit of prepare_batch's:
        # device instances and ports are assigned on the host, per node
        batched = SYSTEM_BATCHED and not any(
            tg_device_requests(tg) or ask.networks
            for tg, ask in zip(tgs, asks))
        if batched:
            # a node that holds a live alloc of the job is kept, updated
            # or stopped by the host walk, as ever: few on a
            # registration, all of them on a no-op re-eval
            held = {nid for nid, _ in by_node_tg}
            host_nodes = [n for n in nodes if n.id in held]
            fresh = [n.id for n in nodes if n.id not in held]
            t, rows, verdicts = self.engine.place_system(
                self.state, job, tgs, fresh)
            with self._stage("materialize"):
                for gi, tg in enumerate(tgs):
                    placed[gi] = self._place_block(
                        plan, job, tg, asks[gi], metrics[gi], evaluation,
                        fresh, rows, verdicts[gi])
            feasible = verdicts != SYS_FILTERED
        else:
            host_nodes = nodes
            packer = self.engine.packer
            t = packer.update(self.state)
            tgt = packer.lower_task_groups(job, tgs, snapshot=self.state)
            ctx = packer.job_context(job, self.state, t)
            feasible = np.asarray(feasible_mask(
                jnp.asarray(t.attrs), jnp.asarray(t.elig),
                jnp.asarray(ctx.dc_mask), jnp.asarray(ctx.pool_mask),
                jnp.asarray(tgt.con), jnp.asarray(tgt.luts)))   # [G, N]
        host_fit = 0
        for gi, tg in enumerate(tgs):
            if host_nodes:
                n_placed, n_kept, n_fit = self._place_on_host(
                    plan, job, tg, asks[gi], metrics[gi], evaluation,
                    host_nodes, by_node_tg, t.id_to_row, feasible[gi])
                placed[gi] += n_placed
                kept[gi] += n_kept
                host_fit += n_fit
            metric = metrics[gi]
            placed_or_kept = placed[gi] + kept[gi]
            if metric.nodes_exhausted or (placed_or_kept == 0
                                          and metric.nodes_filtered == len(nodes)):
                self.failed_tg_allocs[tg.name] = metric
            if placed_or_kept:
                # decision record: a system group's "desired" is its
                # eligible-node count; selection is trivial so there is
                # no top-k table, just the rollup
                self._tg_stats[tg.name] = {
                    "placed": placed_or_kept, "desired": len(nodes),
                    "metric": metric}
        REGISTRY.inc("nomad.system.nodes_evaluated", len(nodes) * len(tgs))
        REGISTRY.inc("nomad.system.placed", sum(placed))
        REGISTRY.inc("nomad.system.host_fit_nodes", host_fit)

    def _stage(self, name: str):
        """A core/wavepipe.py stage of the worker's thread, timed
        through the planner where it offers stages (the Worker; not the
        test Harness)."""
        stage = getattr(self.planner, "stage", None)
        return stage(name) if stage else contextlib.nullcontext()

    def _place_block(self, plan: Plan, job: Job, tg, ask, metric,
                     evaluation, node_ids, rows, verdicts) -> int:
        """One task group's share of a `place_system` launch as ONE
        columnar AllocBlock (the form generic._materialize_bulk gave
        batch jobs): every placed node once in `node_table`, `picks` the
        identity over it, every row named `<job>.<group>[0]`.  Rolls the
        kernel's verdicts over `node_ids` up into `metric` as the host
        walk counts them, and returns the number placed."""
        v = np.where(rows >= 0, verdicts[rows], SYS_FILTERED)
        counts = np.bincount(v, minlength=SYS_VERDICTS).tolist()
        if counts[SYS_FILTERED]:
            metric.nodes_filtered += counts[SYS_FILTERED]
            metric.constraint_filtered["feasibility"] = (
                metric.constraint_filtered.get("feasibility", 0)
                + counts[SYS_FILTERED])
        for code, dim in SYS_DIMENSIONS.items():
            if counts[code]:
                metric.nodes_exhausted += counts[code]
                metric.dimension_exhausted[dim] = (
                    metric.dimension_exhausted.get(dim, 0) + counts[code])
        k = counts[SYS_PLACED]
        if not k:
            return 0
        table = [node_ids[i] for i in
                 np.flatnonzero(v == SYS_PLACED).tolist()]
        plan.alloc_blocks.append(AllocBlock(
            id=new_id(),
            template=Allocation(
                namespace=job.namespace,
                eval_id=evaluation.id,
                job_id=job.id,
                job=job,
                task_group=tg.name,
                resources=ask,
                desired_status="run",
                client_status="pending",
                job_version=job.version,
                create_time=self.now,
                modify_time=self.now,
            ),
            ids=new_ids(k),
            name_prefix=f"{job.id}.{tg.name}[",
            indexes=[0] * k,
            picks=np.arange(k, dtype=np.int32),
            node_table=table,
            metrics=[metric],
            round_size=k,
        ))
        return k

    def _place_on_host(self, plan: Plan, job: Job, tg, ask, metric,
                       evaluation, nodes, by_node_tg, id_to_row, feasible):
        """The per-node walk for one task group: keep, update or stop
        what a node holds, and fit-check a fresh placement with
        `allocs_fit` against the proposed view.  Every node's with
        SYSTEM_BATCHED off (the parity reference) and for groups that
        ask for devices or ports; else only the nodes that already hold
        the job.  Returns (placed, kept, fit-checked)."""
        placed = kept = fit_checked = 0
        wants_devices = bool(tg_device_requests(tg))
        for n in nodes:
            row = id_to_row.get(n.id)
            existing = by_node_tg.get((n.id, tg.name))
            if existing is not None:
                # update-in-place/destructive if job version changed
                if existing.job is not None and existing.job_version != job.version:
                    if tasks_updated(existing.job, job, tg.name):
                        plan.append_stopped_alloc(
                            existing,
                            "alloc is being updated due to job update")
                    else:
                        upd = existing.copy_skip_job()
                        upd.job = job
                        upd.job_version = job.version
                        plan.append_alloc(upd)
                        kept += 1
                        continue
                else:
                    kept += 1
                    continue
            if row is None or not feasible[row]:
                metric.filter_node("feasibility")
                continue
            # proposed view: state allocs minus this plan's stops,
            # overlaid with this plan's placements/updates (same-id
            # in-place updates replace, not double-count)
            proposed = {a.id: a
                        for a in self.state.allocs_by_node(n.id)
                        if not a.terminal_status()}
            for a in plan.node_update.get(n.id, []):
                proposed.pop(a.id, None)
            for a in plan.node_allocation.get(n.id, []):
                proposed[a.id] = a
            probe = Allocation(resources=ask)
            fit_checked += 1
            ok, dim, _ = allocs_fit(n, list(proposed.values()) + [probe])
            if not ok:
                metric.exhausted_node(dim)
                continue
            # device instance assignment (scheduler/device.py): the
            # proposed view's assignments are visible via the index
            assigned = []
            if wants_devices:
                idx = InUseIndex()
                for a in proposed.values():
                    idx.add_alloc(n.id, a)
                assigned, _why = assign_devices(n, tg, idx)
                if assigned is None:
                    metric.exhausted_node("devices")
                    continue
            alloc = Allocation(
                namespace=job.namespace,
                eval_id=evaluation.id,
                name=f"{job.id}.{tg.name}[0]",
                node_id=n.id,
                job_id=job.id,
                job=job,
                task_group=tg.name,
                resources=ask,
                allocated_devices=assigned,
                desired_status="run",
                client_status="pending",
                job_version=job.version,
                metrics=metric,
                create_time=self.now,
                modify_time=self.now,
            )
            plan.append_alloc(alloc)
            placed += 1
        return placed, kept, fit_checked

    def _submit(self, plan: Plan, evaluation: Evaluation):
        if not plan.is_no_op():
            # chain-of-1 fence tag (see generic._process_once): this
            # scheduler ran allocs_fit per node itself against this
            # snapshot, so the applier's re-fit is redundant while the
            # fence holds
            fence = getattr(self.state, "placement_fence", None)
            if fence is not None:
                plan.coupled_batch = (evaluation.id, fence)
            _, _, err = self.planner.submit_plan(plan)
            if err is not None:
                self._update_eval(evaluation, "failed", str(err))
                return err
        self._update_eval(evaluation, EVAL_STATUS_COMPLETE, "")
        return None

    def _update_eval(self, evaluation, status, desc):
        e = evaluation.copy()
        e.status = status
        e.status_description = desc
        e.failed_tg_allocs = dict(self.failed_tg_allocs)
        self.planner.update_eval(e)
        from nomad_tpu.core.explain import record_decision
        record_decision(self.planner, e, self._tg_stats, now=self.now,
                        snapshot_index=getattr(self.state, "index", 0))


def new_system_scheduler(state, planner, **kwargs) -> SystemScheduler:
    return SystemScheduler(state, planner, sysbatch=False, **kwargs)


def new_sysbatch_scheduler(state, planner, **kwargs) -> SystemScheduler:
    return SystemScheduler(state, planner, sysbatch=True, **kwargs)
