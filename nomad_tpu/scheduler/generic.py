"""Generic (service/batch) scheduler (reference: scheduler/generic_sched.go).

`process(eval)` = snapshot → reconcile → batched device placement → plan →
submit, with the reference's retry-on-partial-commit loop, blocked-eval
creation for failed placements, and follow-up evals for delayed reschedules.

The hot-loop difference vs the reference: computePlacements there walks
candidates one placement at a time through the iterator stack; here ALL
placements of the eval go to the TPU kernel as one batch
(nomad_tpu.ops.PlacementEngine) and come back as node picks + AllocMetrics
in a single device round-trip.
"""

from __future__ import annotations

import contextlib
import zlib
from typing import Dict, List, Optional, Tuple

from nomad_tpu.chaos.clock import SystemClock
from nomad_tpu.ops import PlacementEngine, PlacementRequest
from nomad_tpu.ops.engine import DEVICE_GROUP_SLOTS, BulkDecisions
from nomad_tpu.pack.spread import job_spreads
from nomad_tpu.structs import (
    Allocation,
    AllocMetric,
    EVAL_STATUS_COMPLETE,
    Evaluation,
    Job,
    NetworkIndex,
    Plan,
    PlanAnnotations,
    TRIGGER_PLAN_REFUTE,
    TRIGGER_QUEUED_ALLOCS,
    new_id,
    new_ids,
)

from .base import Planner, Scheduler
from .reconcile import PlaceRequest as RPlace
from .reconcile import ReconcileResults, _name, reconcile
from .util import ALLOC_RESCHEDULED, tainted_nodes

# wall fallback when the driver passes no `now` (one-shot CLI paths);
# server paths always inject now from the bound chaos Clock
_WALL = SystemClock()

# reference: maxServiceScheduleAttempts / maxBatchScheduleAttempts
MAX_SERVICE_ATTEMPTS = 5
MAX_BATCH_ATTEMPTS = 2

# Batched port assignment (ISSUE 8): when True, a networked eval's fresh
# rows ride the columnar path with a per-node bulk port carve, whatever
# their count (ISSUE 38: a small eval's rows too, and a static port
# beside the dynamic ones); False forces the sequential per-alloc
# NetworkIndex loop — the PARITY ORACLE the bench gate and tests compare
# against (bit-for-bit (node, port) equality is the promotion contract,
# like PR 7's sharded-vs-single).
PORT_BATCHED = True

# The most static port values ONE group may ask and still ride the wave:
# each is a slot of the launch's port state (ops/engine.py _lower_ports),
# whose ladder of shapes a group of dozens would climb alone.  A size the
# mechanism needs, not a switch: nothing sets it.
PORT_WAVE_MAX_STATIC = 8

# Batched device path (ISSUE 30): when True, a group whose ONE device
# request has no affinities, that asks for no ports, on a fleet whose
# nodes advertise at most DEVICE_GROUP_SLOTS device groups each, rides
# the wave: the kernel accounts free instances a group slot and charges
# each placement to one group, and `_materialize_bulk` carves the ids
# from that group as columns of the AllocBlock.  False sends every
# device-asking eval down the solo path
# (the exact scan, then `_assign_devices`): the PARITY REFERENCE
# tests/test_device_batched.py compares against.  A module constant that
# only tests set; no server option reaches it.
DEVICE_BATCHED = True

# The largest eval with a spread stanza that rides the wave.  The flat
# multi-eval kernel gives such an eval one round a placement (the boost
# moves with every commit: ops/engine.py build_multi_inputs), so its
# count is its round count; 64 is the smallest round bucket.  A larger
# eval would be a program shape of its own doing the device work the
# exact scan already does, and keeps the scan.  A size the mechanism
# needs, not a switch: nothing sets it.
SPREAD_WAVE_MAX = 64

# Several task groups of one eval ride the wave: one launch
# item a group, the eval's groups sharing its job-count row, one plan an
# eval.  False sends every eval whose placements name two or more groups
# down the solo path (rule `off`): the reference tests/test_groups_wave.py
# compares against.  A module constant that only tests set.
MULTI_GROUP_WAVE = True

# Shared engines so packed node tensors + jit caches persist across evals
# of one in-process scheduler session (the worker wires its own).  Keyed
# by the backing store's identity: two Harness/Server instances in one
# process must never share packed tensors — an engine caching one store's
# rows would serve the other stale state (ADVICE r2 #4 pattern).  Bounded
# LRU-ish: old stores' engines are dropped, not leaked.
_engines: Dict[str, PlacementEngine] = {}


def asks_ports(tg) -> bool:
    """The group carries a `network` block: its own or a task's."""
    return bool(tg.networks) or any(t.resources.networks for t in tg.tasks)


def _engine(explicit: Optional[PlacementEngine],
            state) -> PlacementEngine:
    if explicit is not None:
        return explicit
    key = getattr(state, "store_id", "") or "<unkeyed>"
    eng = _engines.get(key)
    if eng is None:
        if len(_engines) > 8:
            for old in list(_engines)[:4]:
                _engines.pop(old, None)
        _engines[key] = eng = PlacementEngine()
    return eng


class GenericScheduler(Scheduler):
    """reference: scheduler.GenericScheduler"""

    def __init__(self, state, planner: Planner, is_batch: bool = False,
                 engine: Optional[PlacementEngine] = None,
                 now: Optional[float] = None) -> None:
        self.state = state
        self.planner = planner
        self.is_batch = is_batch
        self.engine = _engine(engine, state)
        self.now = now if now is not None else _WALL.time()
        self.max_attempts = (MAX_BATCH_ATTEMPTS if is_batch
                             else MAX_SERVICE_ATTEMPTS)
        # replica-fed planners (pool worker processes) see the head
        # later than a thread worker reading the shared store, so their
        # optimistic-concurrency retries need more headroom
        self.max_attempts += getattr(planner, "schedule_attempt_boost", 0)
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}
        # decision-record capture (core/explain.py): per-TG placed
        # counts, the winning metric/top-k, and preemption choices —
        # all host-resident already, so capture costs dict writes only
        self._tg_stats: Dict[str, dict] = {}
        # rows whose ports the last _materialize_bulk carved COLUMNAR
        # (the worker mirrors it into the wave pipeline's stats)
        self.last_port_carve = 0
        # the batched device path: why prepare_batch kept a device-asking
        # eval off the wave ("" = it did not; None = the solo path has
        # counted the eval), the carve's open ledger record, and the rows
        # whose node was short at carve time
        self._device_solo_rule = ""
        self._carve = None            # (ledger, record)
        self._carve_short = (0, ())   # (rows dropped, their node ids)
        # the state `_net_index` reads a node's ports from where the
        # caller has a fresher one than `self.state` (submit_batched)
        self._port_view = None

    # ------------------------------------------------------------- process

    def process(self, evaluation: Evaluation) -> Optional[Exception]:
        for attempt in range(self.max_attempts):
            # per-(eval, attempt) tie-break seed: concurrent workers (and
            # their refutation retries) must diverge on equal-score nodes
            # or they re-collide every attempt (see select._tiebreak_noise)
            self._seed = ((zlib.crc32(evaluation.id.encode())
                           + attempt * 0x9E3779B9) & 0xFFFFFFFF) or 1
            done, err = self._process_once(evaluation)
            if err is not None:
                self._update_eval_status(evaluation, "failed", str(err))
                return err
            if done:
                break
        else:
            self._update_eval_status(
                evaluation, "failed",
                f"maximum attempts reached ({self.max_attempts})")
            return None
        self._finalize(evaluation)
        return None

    def _finalize(self, evaluation: Evaluation) -> None:
        # blocked eval for unplaced allocs (reference: ensureBlockedEval)
        if self.failed_tg_allocs and evaluation.triggered_by != TRIGGER_QUEUED_ALLOCS:
            blocked = evaluation.create_blocked_eval(
                class_eligibility={}, escaped=True,
                failed_tg_allocs=self.failed_tg_allocs)
            # the state index this scheduling pass saw: the blocked-evals
            # tracker re-enqueues instead of parking when capacity
            # changed after it (block-time race guard)
            blocked.snapshot_index = getattr(self.state, "index", 0)
            self.planner.create_eval(blocked)
            evaluation.blocked_eval = blocked.id
        self._update_eval_status(evaluation, EVAL_STATUS_COMPLETE, "")

    def _update_eval_status(self, evaluation: Evaluation, status: str,
                            desc: str) -> None:
        e = evaluation.copy()
        e.status = status
        e.status_description = desc
        e.queued_allocations = dict(self.queued_allocs)
        e.failed_tg_allocs = dict(self.failed_tg_allocs)
        self.planner.update_eval(e)
        if status in (EVAL_STATUS_COMPLETE, "failed"):
            from nomad_tpu.core.explain import record_decision
            record_decision(self.planner, e, self._tg_stats, now=self.now,
                            snapshot_index=getattr(self.state, "index", 0))

    def _note_placed(self, tg_name: str, metric: AllocMetric, n: int = 1,
                     evictions=()) -> None:
        """Decision-record capture for successful placements: counts,
        the first (representative) metric + its interned top-k table,
        and a bounded sample of preemption victims."""
        st = self._tg_stats.get(tg_name)
        if st is None:
            self._tg_stats[tg_name] = st = {
                "placed": 0, "preempted": 0, "preempted_ids": [],
                "metric": None, "score_meta": ()}
        st["placed"] += n
        if st["metric"] is None:
            st["metric"] = metric
            st["score_meta"] = metric.score_meta_data
        if evictions:
            st["preempted"] += len(evictions)
            ids = st["preempted_ids"]
            if len(ids) < 16:
                ids.extend(v.id for v in evictions[:16 - len(ids)])

    # ------------------------------------------------------- batched path

    class BatchPart:
        """One task group of a batch-eligible eval: `count` fresh
        placements of `tg` — either a compact PlaceBlock (count >= 64)
        or a list of fresh PlaceRequests (small groups, THE case the
        multi-eval launch amortizes)."""
        __slots__ = ("tg", "count", "block", "places")

        def __init__(self, tg, count, block, places):
            self.tg = tg
            self.count = count
            self.block = block
            self.places = places

    class BatchPrep:
        """One batch-eligible eval's reconcile output: its fresh
        placements as one BatchPart a task group, in the job's group
        order (one item each of the wave's launch)."""
        __slots__ = ("job", "parts", "results")

        def __init__(self, job, parts, results):
            self.job = job
            self.parts = parts
            self.results = results

    def prepare_batch(self, evaluation: Evaluation):
        """Phase 1 of the multi-eval batched path (reference contrast:
        nomad/worker.go runs one eval per goroutine; here compatible
        evals share ONE device launch): run the reconcile phase only and
        decide whether this eval is the batchable shape — ONLY fresh
        placements, of one task group or several, and nothing else (no
        stops, updates, reschedules, deployment activity) and no
        distinct_property (the exact scan kernel's per-placement state).
        A device ask rides under `_device_batch_refusal`'s rules, a
        spread stanza under `_spread_batch_refusal`'s, a port ask under
        `_port_batch_refusal`'s; an eval of several groups with any of
        the three stays solo (rule `groups_state`).  Returns a BatchPrep
        or None (caller processes the eval through the normal path); a
        job with a spread stanza that is refused counts itself, by rule,
        in `nomad.spread.evals_solo`, one that asks ports in
        `nomad.ports.evals_solo`, one of several groups in
        `nomad.groups.evals_solo`."""
        if evaluation.annotate_plan:
            return None          # dry-run diffs ride the normal path
        state = self.state
        job = state.job_by_id(evaluation.namespace, evaluation.job_id)
        if job is None or job.stopped():
            return None
        prep, rule = self._prepare_batch(evaluation, job)
        if prep is None:
            from nomad_tpu.core.telemetry import REGISTRY
            if job_spreads(job):
                REGISTRY.inc("nomad.spread.evals_solo", rule=rule)
            if any(asks_ports(tg) for tg in job.task_groups):
                REGISTRY.inc("nomad.ports.evals_solo", rule=rule)
            if len(job.task_groups) > 1:
                REGISTRY.inc("nomad.groups.evals_solo", rule=rule)
        return prep

    def _prepare_batch(self, evaluation: Evaluation, job: Job):
        """(BatchPrep, "") or (None, the rule that refused)."""
        state = self.state
        allocs = state.allocs_by_job(evaluation.namespace, evaluation.job_id)
        tainted = tainted_nodes(state, allocs)
        deployment = state.latest_deployment_by_job(
            evaluation.namespace, evaluation.job_id)
        results = reconcile(job, False, allocs, tainted, self.now,
                            existing_deployment=deployment)
        if (results.stop or results.inplace_update
                or results.destructive_update or results.reschedule_later):
            return None, "reconcile"
        if results.deployment is not None or results.deployment_updates:
            return None, "deployment"
        # one part a task group: its PlaceBlock or its fresh rows
        # (reconcile gives a group one form or the other), in the job's
        # group order
        by_tg: Dict[int, GenericScheduler.BatchPart] = {}
        for block in results.place_blocks:
            if id(block.tg) in by_tg:
                return None, "shape"
            by_tg[id(block.tg)] = self.BatchPart(
                block.tg, len(block.indexes), block, None)
        for p in results.place:
            if p.previous_alloc is not None or p.canary:
                return None, "shape"   # reschedules/canaries: exact path
            part = by_tg.get(id(p.tg))
            if part is None:
                by_tg[id(p.tg)] = part = self.BatchPart(p.tg, 0, None, [])
            elif part.block is not None:
                return None, "shape"
            part.places.append(p)
            part.count += 1
        if not by_tg:
            return None, "shape"
        order = {id(tg): i for i, tg in enumerate(job.task_groups)}
        parts = sorted(by_tg.values(),
                       key=lambda part: order.get(id(part.tg), len(order)))
        from nomad_tpu.structs import OP_DISTINCT_PROPERTY
        from .device import tg_device_requests
        for part in parts:
            tg = part.tg
            cons = (list(job.constraints) + list(tg.constraints)
                    + [c for task in tg.tasks for c in task.constraints])
            if any(c.operand == OP_DISTINCT_PROPERTY for c in cons):
                return None, "distinct_property"
        if len(parts) > 1 and not MULTI_GROUP_WAVE:
            return None, "off"
        if len(parts) > 1 and (job_spreads(job) or any(
                tg_device_requests(p.tg) or asks_ports(p.tg)
                for p in parts)):
            # what the wave carries from one round to the next beside
            # `used` and the job's count row (spread counts, static-port
            # holders, carved instances) is not carried across the
            # groups of one eval
            return None, "groups_state"
        # from here on one part, or several that carry none of it
        tg = parts[0].tg
        rule = self._spread_batch_refusal(job, parts[0].count)
        if rule:
            return None, rule
        dev_reqs = tg_device_requests(tg)
        if dev_reqs:
            # a device-asking group rides the wave where the kernel's
            # device dimension says all there is to say (see
            # DEVICE_BATCHED); anything else takes the solo path, which
            # counts itself under the rule that refused (_assign_devices)
            self._device_solo_rule = self._device_batch_refusal(tg, dev_reqs)
            if self._device_solo_rule:
                return None, "device"
        # Networked groups RIDE the batch (round-5 verdict #6), and
        # since ISSUE 8 they ride the COLUMNAR block path too: the
        # worker threads ONE NetworkIndex cache through every batch
        # mate's materialize pass (materialization is sequential in the
        # worker thread), and each mate's ports are carved in a
        # single batched per-node pass (_carve_ports_batch) that lands
        # as port columns on the AllocBlock — batch-mates landing on one
        # node commit disjoint ports without per-alloc index round
        # trips.  A STATIC port is a feasibility rule of the wave's
        # kernel (ISSUE 38, ops/engine.py STATIC_PORT_FEASIBILITY): a
        # mate asking the value a mate took passes the node by.  Safety
        # net: port-carrying plans are demoted from the applier's
        # skip-fit to the full re-check, which audits block ports per
        # node (plan_apply._carries_host_assigned / _eval_blocks).
        rule = self._port_batch_refusal(tg)
        if rule:
            return None, rule
        return self.BatchPrep(job, parts, results), ""

    def _port_batch_refusal(self, tg) -> str:
        """The admission rule that keeps a group with a STATIC port ask
        off the wave, or "" when it rides (dynamic ports alone always
        do): at most PORT_WAVE_MAX_STATIC values, and an engine that is
        not sharded (the mesh's wave kernel carries no port state)."""
        from nomad_tpu.pack.packer import tg_static_ports
        static = tg_static_ports(tg)
        if not static:
            return ""
        if len(static) > PORT_WAVE_MAX_STATIC:
            return "static_count"
        if self.engine.mesh is not None:
            return "mesh"
        return ""

    def _spread_batch_refusal(self, job: Job, count: int) -> str:
        """The admission rule that keeps an eval with a spread stanza
        off the wave, or "" when it rides (or has none): every stanza
        with explicit targets (a target-less one lowers to an even split
        over the values observed, which the wave does not stand on), at
        most SPREAD_WAVE_MAX placements (a round each), and an engine
        that is not sharded (the mesh's wave kernel carries no spread
        state)."""
        spreads = job_spreads(job)
        if not spreads:
            return ""
        if not all(sp.targets for sp in spreads):
            return "targets"
        if count > SPREAD_WAVE_MAX:
            return "count"
        if self.engine.mesh is not None:
            return "mesh"
        return ""

    def _device_batch_refusal(self, tg, dev_reqs) -> str:
        """The admission rule that keeps a device-asking group off the
        wave, or "" when it rides: ONE request, without affinities (and no
        port ask beside it: one host-assigned column set a block), on a
        fleet where no node advertises more device groups than the node
        tensors have slots (ops/engine.py DEVICE_GROUP_SLOTS), and, where
        a node advertises several, on an engine that is not sharded (the
        mesh's wave kernel carries no slot state).  Then the instances
        free in each group a node has are numbers the tensors carry, the
        request's name and constraints are a static accept table by slot
        (ops/engine.py device_accept_table), the kernel charges each
        placement to the first accepted group with room, as
        `assign_devices` picks one, and the carve takes that group's
        first free ids."""
        if not DEVICE_BATCHED:
            return "off"
        if len(dev_reqs) != 1:
            return "requests"
        if dev_reqs[0][1].affinities:
            return "affinity"
        if asks_ports(tg):
            return "ports"
        engine = self.engine
        most = engine.fleet_device_groups(engine.packer.update(self.state))
        if most > DEVICE_GROUP_SLOTS:
            return "slots"
        if most > 1 and engine.mesh is not None:
            return "mesh"
        return ""

    def submit_batched(self, evaluation: Evaluation, prep, bds,
                       coupled_batch=None, net_index_cache=None,
                       device_ledger=None, port_view=None):
        """Phase 2a of the batched path: materialize + ENQUEUE the plan
        without waiting for the applier — the worker submits a whole
        coupled chain first, so plan apply overlaps the next plan's
        materialization.  `bds`: the wave's decisions for the eval, one
        a part of `prep`; ONE plan takes every group, each in the form
        its size gives it (a block at 64 or more, rows below).  Returns
        an opaque handle for finalize_batched, or None when the eval
        needs the solo path (no decisions, or preemption could still
        place failed picks — the batch kernel never preempts).
        `port_view`: the state the shared `net_index_cache` is built
        from where it is fresher than this scheduler's own snapshot (the
        worker's, taken as the wave's materialize begins)."""
        self._port_view = port_view
        from nomad_tpu.ops.preempt import preemption_enabled
        job, results = prep.job, prep.results
        if any(bd is None for bd in bds):
            return None
        if (any((bd.picks < 0).any() for bd in bds)
                and preemption_enabled(self.state.scheduler_config(),
                                       job.type)):
            return None
        self.failed_tg_allocs = {}
        self.queued_allocs = {tg.name: 0 for tg in job.task_groups}
        self._tg_stats = {}
        plan = Plan(eval_id=evaluation.id, priority=evaluation.priority,
                    job=job, coupled_batch=coupled_batch)
        try:
            for part, bd in zip(prep.parts, bds):
                self._materialize_bulk(plan, job, part.places, bd,
                                       evaluation, results, block=part.block,
                                       net_idx=net_index_cache,
                                       device_ledger=device_ledger)
        except BaseException:
            self._settle_carve(None)
            raise
        from nomad_tpu.core.telemetry import REGISTRY
        if asks_ports(prep.parts[0].tg):
            REGISTRY.inc("nomad.ports.evals_batched")
        if len(job.task_groups) > 1:
            REGISTRY.inc("nomad.groups.evals_batched")
        if plan.is_no_op():
            self._settle_carve(None)
            short, nodes = self._carve_short
            if short:
                # every row's node was short of instances at carve time
                # (a device ask: one group)
                self._repair_refuted(evaluation, plan, nodes,
                                     {prep.parts[0].tg.name: short}, None)
                return ("done", None)
            self._finalize(evaluation)
            return ("done", None)
        submit = getattr(self.planner, "submit_plan_async", None)
        if submit is None:          # planner without the async surface
            result, refreshed, err = self.planner.submit_plan(plan)
            return ("sync", (plan, result, refreshed, err))
        return ("pending", (plan, submit(plan)))

    def finalize_batched(self, evaluation: Evaluation, handle,
                         pipeline=None) -> Optional[Exception]:
        """Phase 2b: collect the applier's verdict and finish the eval.
        On partial commit, the wavepipe refute-repair path
        (_repair_refuted) masks the refuted nodes into the pipeline and
        re-queues ONLY the refuted rows as a fresh eval for a later wave
        — the committed remainder stays committed and the wave is never
        re-run.  Without a pipeline (solo/sync callers) the original
        full process() retry loop runs instead."""
        kind, payload = handle
        if kind == "done":
            return None
        if kind == "sync":
            plan, result, refreshed_state, err = payload
        else:
            plan, pending = payload
            # through the planner where it names the wait (the worker's
            # "plan_wait" stage, core/wavepipe.py)
            wait = getattr(self.planner, "wait_plan", None)
            result, err = wait(pending) if wait else pending.wait()
            refreshed_state = None
        # the "finalize" stage (core/wavepipe.py): from the verdict in
        # hand to the eval done, named through the planner as the wait
        # is.  The retry loop runs outside it: its stages are the solo
        # path's own and must not nest in this one
        stage = getattr(self.planner, "stage", None)
        with stage("finalize") if stage else contextlib.nullcontext():
            retry, err = self._after_verdict(evaluation, plan, result, err,
                                             refreshed_state, pipeline)
        return self.process(evaluation) if retry else err

    def _after_verdict(self, evaluation: Evaluation, plan: Plan, result,
                       err, refreshed_state, pipeline
                       ) -> Tuple[bool, Optional[Exception]]:
        """finalize_batched past its wait.  Returns (retry, error):
        `retry` asks the caller for the normal retry loop on the state
        this left in `self.state`."""
        if err is not None:
            self._settle_carve(None)
            self._update_eval_status(evaluation, "failed", str(err))
            return False, err
        refuted = list(result.refuted_nodes) if result is not None else []
        self._settle_carve(result.alloc_index if result is not None
                           else None, refuted)
        # rows whose node was short of device instances at carve time
        # never entered the plan: they re-enter as the refuted rows do
        short, short_nodes = self._carve_short
        if result is not None:
            full, _, _ = result.full_commit(plan)
            if not full or short:
                if (pipeline is not None and (refuted or short)
                        and plan.alloc_blocks
                        and not plan.node_allocation
                        and evaluation.triggered_by != TRIGGER_PLAN_REFUTE):
                    missing = result.missing_by_group(plan)
                    if short:
                        # a device ask's rows: one group, its block
                        tg = plan.alloc_blocks[0].template.task_group
                        missing[tg] = missing.get(tg, 0) + short
                    return False, self._repair_refuted(
                        evaluation, plan, refuted + list(short_nodes),
                        missing, pipeline)
                # partial commit: some nodes were refuted against newer
                # state — re-run the normal retry loop, which reconciles
                # the committed remainder on a fresh snapshot
                if refreshed_state is None:
                    refresh = getattr(self.planner, "refreshed_snapshot",
                                      None)
                    refreshed_state = refresh() if refresh else None
                if refreshed_state is not None:
                    self.state = refreshed_state
                return True, None
        self._finalize(evaluation)
        return False, None

    def _settle_carve(self, commit_index, refuted_nodes=()) -> None:
        """Close this eval's record in the carve ledger: committed at
        `commit_index` less the refuted nodes, or (None) never."""
        if self._carve is not None:
            ledger, record = self._carve
            self._carve = None
            ledger.settle(record, commit_index, refuted_nodes)

    def _repair_refuted(self, evaluation: Evaluation, plan: Plan,
                        refuted_nodes, missing: Dict[str, int], pipeline
                        ) -> Optional[Exception]:
        """Refute-repair (core/wavepipe.py): the applier refuted rows of
        this eval's blocks against newer state.  Instead of re-running
        the whole device launch, (1) the refuted nodes join the
        pipeline's mask so subsequent CHAINED dispatches — whose usage
        buffers predate the refuting write — cannot re-pick them, and
        (2) a fresh pending eval re-places only the `missing` rows
        ({task group: rows}, queued a group) in a later wave (its
        reconcile counts the committed remainder, so nothing
        double-commits).  Repair evals that refute AGAIN fall back to the
        normal retry loop (the TRIGGER_PLAN_REFUTE guard in
        finalize_batched) — the repair never recurses."""
        if pipeline is not None:
            pipeline.note_refuted(refuted_nodes)
        for tg_name, n in missing.items():
            self.queued_allocs[tg_name] = (
                self.queued_allocs.get(tg_name, 0) + n)
        follow = Evaluation(
            namespace=evaluation.namespace,
            priority=evaluation.priority,
            type=evaluation.type,
            triggered_by=TRIGGER_PLAN_REFUTE,
            job_id=evaluation.job_id,
            previous_eval=evaluation.id,
        )
        self.planner.create_eval(follow)
        self._update_eval_status(
            evaluation, EVAL_STATUS_COMPLETE,
            f"{sum(missing.values())} refuted placement(s) re-queued as "
            f"{follow.id}")
        return None

    def process_batched(self, evaluation: Evaluation, prep, bds,
                        coupled_batch=None) -> Optional[Exception]:
        """Phase 2, synchronous form: submit + finalize in one call."""
        handle = self.submit_batched(evaluation, prep, bds,
                                     coupled_batch=coupled_batch)
        if handle is None:
            return self.process(evaluation)
        return self.finalize_batched(evaluation, handle)

    # -------------------------------------------------------- single pass

    def _process_once(self, evaluation: Evaluation):
        state = self.state
        job = state.job_by_id(evaluation.namespace, evaluation.job_id)
        allocs = state.allocs_by_job(evaluation.namespace, evaluation.job_id)
        tainted = tainted_nodes(state, allocs)
        stopped = job is None or job.stopped()
        deployment = (state.latest_deployment_by_job(
            evaluation.namespace, evaluation.job_id) if job else None)

        results = reconcile(job, stopped, allocs, tainted, self.now,
                            existing_deployment=deployment)

        plan = Plan(eval_id=evaluation.id, priority=evaluation.priority,
                    job=job)
        if evaluation.annotate_plan:
            plan.annotations = PlanAnnotations(
                desired_tg_updates=results.desired_tg_updates)

        self.failed_tg_allocs = {}
        self.queued_allocs = {tg.name: 0 for tg in job.task_groups} if job else {}
        self._tg_stats = {}

        # ---- stops ----
        for s in results.stop:
            plan.append_stopped_alloc(s.alloc, s.status_description,
                                      client_status=s.client_status)

        # ---- in-place updates ----
        for a in results.inplace_update:
            upd = a.copy_skip_job()
            upd.job = job
            upd.job_version = job.version
            if results.deployment is not None:
                # a running alloc updated in place (count/meta change)
                # joins the deployment already healthy — its tasks never
                # restarted, so there is nothing to re-check, and without
                # this the deployment's desired_total can never be met
                upd.deployment_id = results.deployment.id
                upd.deployment_status = {"healthy": True, "ts": self.now}
            plan.append_alloc(upd)

        # ---- destructive updates: stop old + place new ----
        destructive_places: List[RPlace] = []
        for a in results.destructive_update:
            plan.append_stopped_alloc(
                a, "alloc is being updated due to job update")
            tg = job.lookup_task_group(a.task_group)
            destructive_places.append(RPlace(
                tg=tg, name=a.name, index=a.index(), previous_alloc=a))

        # ---- reschedule-later: follow-up evals + alloc annotations ----
        if results.reschedule_later:
            by_time: Dict[float, List[Allocation]] = {}
            for a, ready_at in results.reschedule_later:
                by_time.setdefault(ready_at, []).append(a)
            for ready_at, late_allocs in sorted(by_time.items()):
                follow = evaluation.create_failed_follow_up_eval(ready_at)
                self.planner.create_eval(follow)
                for a in late_allocs:
                    upd = a.copy_skip_job()
                    upd.job = job
                    upd.followup_eval_id = follow.id
                    plan.append_alloc(upd)

        # ---- placements: one batched device call for the whole eval ----
        all_places = results.place + destructive_places
        blocks = results.place_blocks
        if blocks and (all_places or len(blocks) > 1):
            # mixed placement kinds in one eval: expand the compact blocks
            # so capacity stays coupled in a SINGLE engine call (two calls
            # would each see only state usage, not each other's picks)
            for b in blocks:
                all_places.extend(
                    RPlace(tg=b.tg, name=_name(job, b.tg, ix), index=ix)
                    for ix in b.indexes)
            blocks = []
        if all_places and job is not None:
            self._compute_placements(plan, job, all_places, evaluation,
                                     results)
        elif blocks and job is not None:
            self._compute_placements_block(plan, job, blocks[0],
                                           evaluation, results)

        plan.deployment = results.deployment
        plan.deployment_updates = results.deployment_updates

        if plan.is_no_op():
            return True, None

        # fence-tag from THE snapshot this pass computed against (a chain
        # of length 1): while the applier's placement fence proves no
        # foreign write intervened, its per-node re-fit is provably
        # redundant — the kernels enforced capacity against this exact
        # state.  The re-check exists for optimistic concurrency, which
        # the fence detects precisely.
        fence = getattr(self.state, "placement_fence", None)
        if fence is not None and not plan.host_redirected:
            plan.coupled_batch = (evaluation.id, fence)
        result, refreshed_state, err = self.planner.submit_plan(plan)
        if err is not None:
            return False, err
        if result is not None:
            full, expected, actual = result.full_commit(plan)
            if not full:
                if refreshed_state is not None:
                    self.state = refreshed_state
                return False, None
        return True, None

    # ---------------------------------------------------------- placement

    def _compute_placements(self, plan: Plan, job: Job,
                            places: List[RPlace],
                            evaluation: Evaluation,
                            results: ReconcileResults) -> None:
        tgs = job.task_groups
        reqs = []
        for p in places:
            prev_node = ""
            if p.previous_alloc is not None and p.reschedule:
                prev_node = p.previous_alloc.node_id
            reqs.append(PlacementRequest(tg_name=p.tg.name,
                                         prev_node_id=prev_node))
        # allocs this plan is stopping free their capacity for placement
        stopped = [a for allocs in plan.node_update.values() for a in allocs]
        decisions = self.engine.place(self.state, job, tgs, reqs,
                                      stopped_allocs=stopped,
                                      seed=getattr(self, "_seed", 0))
        with self._materialize_stage():
            if isinstance(decisions, BulkDecisions):
                self._materialize_bulk(plan, job, places, decisions,
                                       evaluation, results)
                return
            self._materialize_decisions(plan, job, places, reqs, decisions,
                                        evaluation, results, stopped)

    def _materialize_stage(self):
        """The solo path's "materialize" stage (core/wavepipe.py), timed
        through the planner where it offers stages (the Worker; not the
        test Harness).  At the call that follows `engine.place`, not in
        `_materialize_bulk`: the batched path runs that under the
        worker's own per-wave materialize."""
        stage = getattr(self.planner, "stage", None)
        return stage("materialize") if stage else contextlib.nullcontext()

    def _materialize_decisions(self, plan: Plan, job: Job,
                               places: List[RPlace], reqs,
                               decisions, evaluation: Evaluation,
                               results: ReconcileResults,
                               stopped) -> None:
        """Per-decision alloc construction (ports, devices, reschedule
        trackers) — the tail of `_compute_placements`, shared with the
        block fallback path."""
        tgs = job.task_groups
        # concrete device-instance assignment for groups that ask for
        # devices (reference: scheduler/device.go AllocateDevice); may
        # re-place a subset when a node's instances run out mid-plan
        dev_assign = self._assign_devices(job, tgs, places, reqs,
                                          decisions, stopped)

        # host-side port assignment per chosen node (reference: AllocsFit's
        # NetworkIndex, kept off-device per SURVEY §7 P1).  Preemption
        # victims' ports are freed: exclude them from the index.
        net_idx: Dict[str, NetworkIndex] = {}
        victim_ids = {v.id for d in decisions for v in d.evictions}

        # one combined-resources template per task group.  When the group
        # asks for no ports the template is shared by every alloc of the
        # group (immutable once inserted, the store's ownership convention);
        # with networks each alloc gets a copy carrying its port assignment.
        ask_templates: Dict[str, object] = {}
        # alloc construction is the host-side hot path at bench scale
        # (100k placements/plan): build one fully-initialized template
        # alloc per task group and clone via dict copy instead of running
        # the 40-field dataclass constructor per placement.
        alloc_templates: Dict[str, Allocation] = {}

        placed_n = 0
        for i, (p, d) in enumerate(zip(places, decisions)):
            tg = p.tg
            if d.node_id is None:
                self._record_failure(tg.name, d.metric)
                continue
            ports = None
            ask = ask_templates.get(tg.name)
            if ask is None:
                ask_templates[tg.name] = ask = tg.combined_resources()
            has_net = bool(ask.networks)
            if has_net:
                ask = ask.copy()
            if ask.networks:
                ni = self._net_index(d.node_id, net_idx, victim_ids)
                ports, fail = ni.assign_ports(ask.networks)
                if ports is None:
                    # stock moves to the NEXT candidate when the picked
                    # node can't satisfy the ask (rank.go iterator pull);
                    # the kernel returned its runner-ups in the metric's
                    # top-k — retry them before declaring failure.
                    # Never redirect a placement bound to its node by
                    # evictions or device instances.
                    alt_ports = alt = None
                    if not d.evictions and i not in dev_assign:
                        alt_ports, alt = self._ports_from_runner_up(
                            plan, d.node_id, d.metric.score_meta_data,
                            ask, net_idx, victim_ids, job, tg)
                    if alt_ports is None:
                        d.metric.exhausted_node(fail)
                        self._record_failure(tg.name, d.metric)
                        continue
                    ports = alt_ports
                    d.node_id = alt
                else:
                    ni.commit(ports)

            tmpl = alloc_templates.get(tg.name)
            if tmpl is None:
                alloc_templates[tg.name] = tmpl = Allocation(
                    namespace=job.namespace,
                    eval_id=evaluation.id,
                    job_id=job.id,
                    job=job,
                    task_group=tg.name,
                    desired_status="run",
                    client_status="pending",
                    job_version=job.version,
                    create_time=self.now,
                    modify_time=self.now,
                )
            alloc = Allocation.__new__(Allocation)
            ad = dict(tmpl.__dict__)
            alloc.__dict__ = ad
            ad["id"] = new_id()
            ad["name"] = p.name
            ad["node_id"] = d.node_id
            ad["resources"] = ask
            ad["allocated_ports"] = ports or {}
            ad["allocated_devices"] = dev_assign.get(i, [])
            ad["metrics"] = d.metric
            # per-alloc mutable state: runners write task_states in place
            ad["task_states"] = {}
            if d.evictions:
                for victim in d.evictions:
                    plan.append_preempted_alloc(victim, alloc.id)
                alloc.preempted_allocations = [v.id for v in d.evictions]
            if results.deployment is not None:
                alloc.deployment_id = results.deployment.id
                if p.canary:
                    dstate = results.deployment.task_groups.get(tg.name)
                    if dstate is not None:
                        dstate.placed_canaries.append(alloc.id)
            if p.previous_alloc is not None:
                alloc.previous_allocation = p.previous_alloc.id
                if p.reschedule:
                    from .util import append_reschedule_tracker
                    append_reschedule_tracker(alloc, p.previous_alloc, self.now)
                    alloc.desired_description = ALLOC_RESCHEDULED
            plan.append_alloc(alloc)
            placed_n += 1
            self._note_placed(tg.name, d.metric, evictions=d.evictions)
        self._count_materialized("rows", placed_n)

    @staticmethod
    def _count_materialized(form: str, n: int) -> None:
        """`nomad.materialize.placements{form}`: placements materialized
        by representation, "block" (one columnar AllocBlock) or "rows"
        (an Allocation a placement); once a materialize call."""
        from nomad_tpu.core.telemetry import REGISTRY
        REGISTRY.inc("nomad.materialize.placements", n, form=form)

    @staticmethod
    def _net_columnar_labels(ask):
        """The batched-carve-eligible network shape: ONE network whose
        ports, static first and then dynamic as `assign_ports` takes
        them, all carry a label of their own.  Returns (labels, the
        static ones' values) or None: several networks, unlabeled or
        duplicate labels ride the sequential per-alloc path, which
        doubles as the parity oracle (ISSUE 8)."""
        if len(ask.networks) != 1:
            return None
        net = ask.networks[0]
        labels = [p.label for p in net.reserved_ports + net.dynamic_ports]
        if (not labels or not all(labels)
                or len(set(labels)) != len(labels)
                or not all(p.value for p in net.reserved_ports)):
            return None
        return labels, [p.value for p in net.reserved_ports]

    def _carve_ports_batch(self, picks_ok, node_ids, labels, static,
                           net_idx, victim_ids):
        """Vectorized per-node offset scheme (ISSUE 8): group the wave's
        placements by node, pre-check every node against its cumulative
        demand (its free dynamic pool, and each static value free and
        asked by one row), then carve each node's ports in ONE cursor
        pass and scatter them back to rows in row order.
        Bit-for-bit the sequential per-alloc result — mates landing on
        one node take ascending first-fit ports in row order, exactly as
        N ordered assign_ports calls would — without the N sequential
        index round-trips.  `static` are the values of the first
        len(static) labels; the rest are dynamic.  Returns an [n_ok,
        len(labels)] int32 array, or None when any node is short or
        holds a static value (NOTHING committed — the feasibility
        pass runs before any claim, so a mid-wave shortfall cannot leak
        partial claims into the batch-shared index)."""
        import numpy as np

        from nomad_tpu.structs import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT
        n_dyn = len(labels) - len(static)
        # (a static value inside the dynamic range takes a port of the
        # pool with it)
        in_pool = sum(MIN_DYNAMIC_PORT <= v <= MAX_DYNAMIC_PORT
                      for v in static)
        uniq, inv = np.unique(picks_ok, return_inverse=True)
        counts = np.bincount(inv, minlength=len(uniq)).tolist()
        indexes = []
        for r, k in zip(uniq.tolist(), counts):
            ni = self._net_index(node_ids[int(r)], net_idx, victim_ids)
            if static and (k > 1 or not ni.used_ports.isdisjoint(static)):
                return None
            if ni.dyn_free_count() < k * n_dyn + in_pool:
                return None
            indexes.append(ni)
        out = np.empty((len(picks_ok), len(labels)), np.int32)
        out[:, :len(static)] = static
        held = dict(zip(labels, static))
        order = np.argsort(inv, kind="stable")
        pos = 0
        for ni, k in zip(indexes, counts):
            if static:
                ni.commit(held)
            got = ni.claim_dynamic_block(k * n_dyn)
            out[order[pos:pos + k], len(static):] = np.asarray(
                got, np.int32).reshape(k, n_dyn)
            pos += k
        return out

    def _net_index(self, node_id: str, cache: Dict[str, NetworkIndex],
                   victim_ids) -> NetworkIndex:
        """Per-node port bookkeeping for this plan, built lazily
        (preemption victims' ports count as free)."""
        ni = cache.get(node_id)
        if ni is None:
            ni = NetworkIndex()
            state = self._port_view or self.state
            node = state.node_by_id(node_id)
            if node is not None:
                ni.set_node(node)
            ni.add_allocs(a for a in state.allocs_by_node(node_id)
                          if a.id not in victim_ids)
            cache[node_id] = ni
        return ni

    def _ports_from_runner_up(self, plan: Plan, picked_node: str,
                              score_meta, ask, net_idx, victim_ids,
                              job, tg):
        """Port exhaustion on the picked node: try the top-k runner-up
        rows (reference: the rank iterator simply pulls the next
        candidate).  Returns (ports, runner_up_node_id) or (None, None).
        On success the PLAN loses its fence — the kernel's capacity
        accounting assumed the original pick, so the applier must run
        the full AllocsFit re-check; the caller moves the placement.
        The candidate must also pass a host-side capacity check against
        existing + in-plan allocs (the kernel verified the ORIGINAL
        node, not this one).

        Callers must NOT redirect placements that carry preemption
        victims or device-instance assignments: both are bound to the
        ORIGINAL node (victims evicted there; instances exist there) and
        would be orphaned by the move.  distinct_hosts groups never
        redirect either — the kernel enforced the one-per-node limit for
        the original pick only."""
        from nomad_tpu.structs import (OP_DISTINCT_HOSTS,
                                       OP_DISTINCT_PROPERTY)
        cons = (list(job.constraints) + list(tg.constraints)
                + [c for task in tg.tasks for c in task.constraints])
        if any(c.operand in (OP_DISTINCT_HOSTS, OP_DISTINCT_PROPERTY)
               for c in cons):
            # the kernel enforced per-node/per-property limits for the
            # ORIGINAL pick only; a host-side move could violate them
            # invisibly (allocs_fit checks neither)
            return None, None
        # ALL top-k entries are candidates: for round-shared bulk
        # metrics, entry 0 is the round's best node, not necessarily
        # this placement's pick (the picked-node filter below covers
        # the per-decision case where entry 0 IS the pick)
        for meta in score_meta:
            alt = meta.node_id
            if not alt or alt == picked_node:
                continue
            if not self._alt_fits(plan, alt, ask):
                continue
            ni = self._net_index(alt, net_idx, victim_ids)
            ports, _ = ni.assign_ports(ask.networks)
            if ports is None:
                continue
            ni.commit(ports)
            # host-side redirection invalidates the device's coupled
            # capacity view (the flag also blocks the fence-tag step)
            plan.coupled_batch = None
            plan.host_redirected = True
            from nomad_tpu.core.telemetry import REGISTRY
            REGISTRY.inc("nomad.ports.runner_up_redirects")
            return ports, alt
        return None, None

    def _alt_fits(self, plan: Plan, node_id: str, ask) -> bool:
        """Capacity check for a redirect candidate: existing live allocs
        + this plan's placements on the node + the ask must fit (the
        applier re-checks too — this avoids redirecting into a
        guaranteed refute)."""
        node = self.state.node_by_id(node_id)
        if node is None or node.status == "down":
            return False
        cpu = mem = disk = 0
        for a in self.state.allocs_by_node(node_id):
            if a.terminal_status():
                continue
            cpu += a.resources.cpu
            mem += a.resources.memory_mb
            disk += a.resources.disk_mb
        for a in plan.node_allocation.get(node_id, ()):
            cpu += a.resources.cpu
            mem += a.resources.memory_mb
            disk += a.resources.disk_mb
        # columnar blocks bypass node_allocation: count their load too
        for block in plan.alloc_blocks:
            i = block.node_table.index(node_id) \
                if node_id in block.node_table else -1
            if i >= 0:
                k = int(block.node_counts()[i])
                r = block.template.resources
                cpu += k * r.cpu
                mem += k * r.memory_mb
                disk += k * r.disk_mb
        return (cpu + ask.cpu <= node.resources.cpu - node.reserved.cpu
                and mem + ask.memory_mb
                <= node.resources.memory_mb - node.reserved.memory_mb
                and disk + ask.disk_mb
                <= node.resources.disk_mb - node.reserved.disk_mb)

    def _compute_placements_block(self, plan: Plan, job: Job, block,
                                  evaluation: Evaluation,
                                  results: ReconcileResults) -> None:
        """Compact twin of `_compute_placements` for one PlaceBlock: no
        per-placement request objects anywhere — the engine gets
        (task group, count) and the bulk decisions materialize with names
        derived from the block's index list."""
        stopped = [a for allocs in plan.node_update.values() for a in allocs]
        decisions = self.engine.place(
            self.state, job, job.task_groups, None,
            stopped_allocs=stopped, seed=getattr(self, "_seed", 0),
            block=(block.tg.name, len(block.indexes)))
        with self._materialize_stage():
            if isinstance(decisions, BulkDecisions):
                self._materialize_bulk(plan, job, None, decisions,
                                       evaluation, results, block=block)
                return
            # a device ask (the engine answers it a decision a placement:
            # `_assign_devices` binds instances one by one): expand and
            # run the general path with the decisions it already computed
            places = [RPlace(tg=block.tg, name=_name(job, block.tg, ix),
                             index=ix) for ix in block.indexes]
            reqs = [PlacementRequest(tg_name=block.tg.name)] * len(places)
            self._materialize_decisions(plan, job, places, reqs, decisions,
                                        evaluation, results, stopped)

    def _assign_devices(self, job, tgs, places, reqs, decisions, stopped):
        """Pick concrete device instances for every placement whose task
        group requests devices (reference: scheduler/device.go
        AllocateDevice called from BinPackIterator).

        The scan accounts instances as a capacity dimension, so one
        request on single-group nodes is assigned in the first round.  A
        group with several requests, or a node with several groups, is
        held to a mask computed against the snapshot and to the
        instances of the node TOGETHER, so it can still run out of a
        group mid-plan.  Failed assignments are re-placed through the
        engine with the in-plan usage overlay visible (up to 3 rounds —
        the host-side twin of the kernel's sequential-capacity scan);
        still-failing placements become normal placement failures with the
        exhausted dimension recorded.  Mutates `decisions` in place and
        returns {placement_index: [AllocatedDeviceResource]}."""
        from .device import InUseIndex, assign_devices, tg_device_requests

        tg_has_dev = {tg.name: bool(tg_device_requests(tg)) for tg in tgs}
        if not any(tg_has_dev.values()):
            return {}
        # a device-asking eval on the solo path, under the admission rule
        # that refused it (prepare_batch) or, where none did, "unbatched":
        # no mate to share a wave with, or a retry after a refute
        if self._device_solo_rule is not None:
            from nomad_tpu.core.telemetry import REGISTRY
            REGISTRY.inc("nomad.device.evals_solo",
                         rule=self._device_solo_rule or "unbatched")
            self._device_solo_rule = None       # once an eval
        dev_assign: Dict[int, list] = {}
        stopped_ids = {a.id for a in stopped}
        dev_index = InUseIndex()
        seeded = set()

        def seed(node_id: str) -> None:
            # preemption victims are conservatively NOT excluded: their
            # instances stay unavailable within this plan
            if node_id in seeded:
                return
            seeded.add(node_id)
            for a in self.state.allocs_by_node(node_id):
                if a.terminal_status() or a.id in stopped_ids:
                    continue
                dev_index.add_alloc(node_id, a)

        pending = [i for i, p in enumerate(places)
                   if tg_has_dev[p.tg.name]]
        for round_no in range(3):
            failed = []
            for i in pending:
                d = decisions[i]
                if d.node_id is None:
                    continue
                node = self.state.node_by_id(d.node_id)
                if node is None:
                    failed.append(i)
                    continue
                seed(d.node_id)
                assigned, why = assign_devices(node, places[i].tg, dev_index)
                if assigned is None:
                    failed.append(i)
                else:
                    dev_assign[i] = assigned
            if not failed:
                return dev_assign
            if round_no == 2:
                break
            redo = self.engine.place(
                self.state, job, tgs, [reqs[i] for i in failed],
                stopped_allocs=stopped, seed=getattr(self, "_seed", 0),
                device_in_use=dev_index)
            for i, d_new in zip(failed, redo):
                if d_new.node_id is None:
                    # the first pass found a device node; the re-place
                    # lost it to in-plan instance consumption — that is
                    # exhaustion, not filtering (reference: AllocMetric
                    # DimensionExhausted["devices"])
                    d_new.metric.exhausted_node("devices")
                decisions[i] = d_new
            pending = failed
        for i in failed:
            d = decisions[i]
            if d.node_id is not None:
                d.metric.exhausted_node("devices")
                d.node_id = None
                d.evictions = []
        return dev_assign

    def _materialize_bulk(self, plan: Plan, job: Job,
                          places: Optional[List[RPlace]], bd,
                          evaluation: Evaluation,
                          results: ReconcileResults,
                          block=None, net_idx=None,
                          device_ledger=None) -> None:
        """Materialize allocations straight from a BulkDecisions array —
        the per-placement twin loop of `_compute_placements`, with every
        per-alloc object cost stripped: template-dict clones, batched ids,
        a shared per-round AllocMetric (or, off the exact scan, a metric
        a placement kept as columns: `bd.rows`), and a shared resources
        object when the group asks for no ports.  With `block` (compact
        path) names come straight from the index list — no RPlace
        objects exist.

        A device-asking group reaches this only off a wave
        (prepare_batch admitted it: ONE request; the solo path's scan
        never returns BulkDecisions for one).  Its rows stay columnar
        whatever their count, and their instance ids are carved per node
        against `device_ledger` (scheduler/device.py CarveLedger, the
        worker's: shared by the wave's mates and the cycle's waves)."""
        from .device import CarveLedger, carve_block, tg_device_requests
        tg = block.tg if block is not None else places[0].tg
        ask = tg.combined_resources()
        has_net = bool(ask.networks)
        dev_reqs = tg_device_requests(tg)
        dev_task, dev_req = dev_reqs[0] if dev_reqs else ("", None)
        self._carve_short = (0, ())
        tmpl = Allocation(
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
        )
        if results.deployment is not None:
            tmpl.deployment_id = results.deployment.id
        tmpl_d = tmpl.__dict__
        count = len(block.indexes) if block is not None else len(places)
        ids = new_ids(count)
        node_ids = bd.node_ids
        victim_ids = {v.id for vs in bd.evictions.values() for v in vs}
        # `net_idx` may be the BATCH-SHARED port cache (see prepare_batch:
        # batch mates materialize sequentially and must see each other's
        # in-plan port commitments); coupled batches never carry
        # evictions, so the victim set is empty whenever the cache is
        # shared and the per-plan victim semantics cannot diverge
        if net_idx is None:
            net_idx = {}
        # fresh rows named from an index list: a PlaceBlock's, or (device
        # and networked groups, whose small evals must not fall to
        # per-alloc objects) the fresh PlaceRequests of a small eval
        net_cols = (self._net_columnar_labels(ask)
                    if has_net and PORT_BATCHED else None)
        rows_fresh = block is not None or (
            (dev_req is not None or net_cols is not None)
            and all(p.previous_alloc is None and not p.canary
                    for p in places))
        if rows_fresh:
            prefix = f"{job.id}.{tg.name}["     # matches reconcile._name
            indexes = (block.indexes if block is not None
                       else [p.index for p in places])
        net_labels, net_static = net_cols if net_cols is not None \
            else (None, ())
        if (rows_fresh and not bd.evictions
                and (not has_net or net_labels is not None)):
            # hottest shape (the bench/batch pattern): fresh block, no
            # preemptions — stays COLUMNAR end-to-end: the picks array +
            # shared template become one AllocBlock on the plan;
            # per-alloc objects never exist on this path (the store
            # materializes them lazily on first read).  Networked groups
            # now ride it too (ISSUE 8): dynamic ports are carved per
            # node in ONE batched pass (bit-for-bit the sequential
            # result) and land as port COLUMNS on the block.  A
            # deployment does not bar it (ISSUE 35): the template carries
            # its id, fresh rows hold no canary, and the watcher counts a
            # block's rows off its columns.
            import numpy as np

            from nomad_tpu.structs import AllocBlock
            picks = bd.picks
            ok_mask = picks >= 0
            n_ok = int(ok_mask.sum())
            n_fail = count - n_ok
            picks_ok = (picks[ok_mask] if n_fail else picks) if n_ok \
                else picks[:0]
            ports_arr = None
            if has_net and n_ok:
                # carve BEFORE any failure accounting: a short node
                # falls the whole eval back to the sequential per-alloc
                # oracle below, which keeps its own failure counters
                with self._port_assign_stage():
                    ports_arr = self._carve_ports_batch(
                        picks_ok, node_ids, net_labels, net_static,
                        net_idx, victim_ids)
            dev_ids = dev_groups = dev_group_of = None
            slots_ok = (None if bd.slots is None
                        else bd.slots[ok_mask] if n_fail else bd.slots)
            n_short = 0
            if dev_req is not None and n_ok:
                # (no ports to carve: prepare_batch keeps a group that
                # asks for both off the wave)
                ledger = (device_ledger if device_ledger is not None
                          else CarveLedger())
                record = ledger.open(getattr(self.state, "index", 0))
                self._carve = (ledger, record)
                stage = getattr(self.planner, "stage", None)
                with (stage("device_carve") if stage
                      else contextlib.nullcontext()):
                    dev_ids, dev_groups, short = carve_block(
                        self.state, ledger, record, dev_req, picks_ok,
                        node_ids, slots=slots_ok)
                from nomad_tpu.core.telemetry import REGISTRY
                REGISTRY.inc("nomad.device.evals_batched")
                if short:
                    # a foreign write took these nodes' instances after
                    # the kernel looked: their rows leave the block whole
                    # (never a partial claim) and re-enter through the
                    # repair eval (finalize_batched)
                    REGISTRY.inc("nomad.device.carve_short", len(short))
                    gone = np.isin(picks, np.asarray(short, picks.dtype))
                    n_short = int(gone.sum())
                    self._carve_short = (
                        n_short, tuple(node_ids[r] for r in short))
                    keep_ok = ~(gone[ok_mask] if n_fail else gone)
                    dev_ids = dev_ids[keep_ok]
                    picks_ok = picks_ok[keep_ok]
                    if slots_ok is not None:
                        slots_ok = slots_ok[keep_ok]
                    ok_mask = ok_mask & ~gone
                    n_ok -= n_short
                REGISTRY.inc("nomad.device.instances_carved",
                             int(dev_ids.size))
                # the block's table of groups and each row's entry in it
                keys = (picks_ok.tolist() if slots_ok is None
                        else zip(picks_ok.tolist(), slots_ok.tolist()))
                index: Dict[tuple, int] = {}
                dev_group_of = np.fromiter(
                    (index.setdefault(dev_groups[k], len(index))
                     for k in keys), np.int32, len(picks_ok))
                dev_groups = list(index)
            if not has_net or n_ok == 0 or ports_arr is not None:
                if n_fail:
                    # aggregate failure accounting: one stored metric
                    # (the first failing round's), coalesced + queued
                    # counters match the per-pick loop's totals
                    tg_name = tg.name
                    m = bd.metric_at(int(np.argmax(picks < 0)))
                    self._record_failure_shared(tg_name, m)
                    if n_fail > 1:
                        self.failed_tg_allocs[tg_name].coalesced_failures \
                            += n_fail - 1
                        self.queued_allocs[tg_name] = \
                            self.queued_allocs.get(tg_name, 0) + n_fail - 1
                if n_ok == 0:
                    return
                if n_fail or n_short:
                    import itertools
                    sel = ok_mask.tolist()
                    ids_ok = list(itertools.compress(ids, sel))
                    idx_ok = list(itertools.compress(indexes, sel))
                else:
                    ids_ok = ids
                    idx_ok = list(indexes)
                # (no numpy call where every pick placed: one more
                # release of the interpreter lock a plan, with the
                # applier waiting for it, doubled a wave's materialize)
                self._note_placed(
                    tg.name, bd.metric_at(int(np.argmax(ok_mask))
                                          if n_fail or n_short else 0),
                    n=n_ok)
                self._count_materialized("block", n_ok)
                if ports_arr is not None:
                    self.last_port_carve = n_ok
                    from nomad_tpu.core.telemetry import REGISTRY
                    REGISTRY.inc("nomad.ports.batched_rows", n_ok)
                # block-local node table: unique picked rows only
                # (hundreds), never the full cluster table
                uniq, inv = np.unique(picks_ok, return_inverse=True)
                plan.alloc_blocks.append(AllocBlock(
                    id=new_id(),
                    template=tmpl,
                    ids=ids_ok,
                    name_prefix=prefix,
                    indexes=idx_ok,
                    picks=inv.astype(np.int32),
                    node_table=[node_ids[int(r)] for r in uniq],
                    metrics=list(bd.metrics),
                    round_size=bd.round_size,
                    row_metrics=(bd.rows.take(ok_mask)
                                 if bd.rows is not None
                                 and (n_fail or n_short) else bd.rows),
                    port_labels=(list(net_labels)
                                 if ports_arr is not None else []),
                    ports=ports_arr,
                    device_task=dev_task if dev_ids is not None else "",
                    device_groups=dev_groups or [],
                    device_group_of=dev_group_of,
                    device_ids=dev_ids,
                ))
                return
            # a node's dynamic pool was short of the wave's demand, or
            # a node held a static value (a launch without the kernels'
            # port state): sequential per-alloc oracle below (runner-up
            # redirects, per-port exhaustion dimensions)

        if dev_req is not None:
            # the loop below assigns no instance: rather nack the eval
            # than commit device-asking allocations that hold none
            raise RuntimeError(
                f"{job.id}.{tg.name}: a device-asking group left the "
                "columnar path (evictions, or rows that are not fresh)")
        with (self._port_assign_stage() if has_net
              else contextlib.nullcontext()):
            self._materialize_rows(plan, job, places, bd, results, block,
                                   tg, ask, tmpl_d, ids, net_idx,
                                   victim_ids,
                                   prefix if rows_fresh else "",
                                   indexes if rows_fresh else ())

    def _port_assign_stage(self):
        """The "port_assign" stage (core/wavepipe.py): an eval's port
        work inside its materialize, timed through the planner where it
        offers stages."""
        stage = getattr(self.planner, "stage", None)
        return stage("port_assign") if stage else contextlib.nullcontext()

    def _materialize_rows(self, plan, job, places, bd, results, block, tg,
                          ask, tmpl_d, ids, net_idx, victim_ids,
                          prefix, indexes) -> None:
        """`_materialize_bulk`'s per-allocation loop: rows that are not
        fresh, preemptions, and the ports the columnar carve leaves (a
        shape it does not take, a short node, PORT_BATCHED off: the
        sequential NetworkIndex oracle, with its runner-up redirects)."""
        has_net = bool(ask.networks)
        node_ids = bd.node_ids
        node_alloc = plan.node_allocation
        last_nid = None
        last_list = None
        picks_l = bd.picks.tolist()
        # a metric a placement (the exact scan's): built once, not a row
        # at a time
        row_ms = bd.rows.materialize() if bd.rows is not None else None
        placed_n = 0          # decision-record capture, noted ONCE below
        first_m = None        # the first placed row's metric
        victims_sample: List = []
        victims_n = 0
        for i in range(len(ids)):
            p = places[i] if block is None else None
            pick = picks_l[i]
            m = row_ms[i] if row_ms is not None else bd.metric_at(i)
            if pick < 0:
                self._record_failure_shared(tg.name, m)
                continue
            nid = node_ids[pick]
            alloc = Allocation.__new__(Allocation)
            d2 = dict(tmpl_d)
            alloc.__dict__ = d2
            d2["id"] = ids[i]
            d2["name"] = (prefix + str(indexes[i]) + "]"
                          if block is not None else p.name)
            d2["node_id"] = nid
            d2["metrics"] = m
            d2["task_states"] = {}
            if has_net:
                a2 = ask.copy()
                ni = self._net_index(nid, net_idx, victim_ids)
                ports, fail = ni.assign_ports(a2.networks)
                if ports is not None:
                    ni.commit(ports)
                elif not bd.evictions.get(i):
                    # retry the round's top-k runner-ups (stock pulls the
                    # next candidate on exhaustion — rank.go iterator);
                    # eviction-backed placements stay put (victims are
                    # bound to the original node)
                    ports, alt = self._ports_from_runner_up(
                        plan, nid, m.score_meta_data, a2, net_idx,
                        victim_ids, job, tg)
                    if ports is not None:
                        nid = alt
                        d2["node_id"] = alt
                if ports is None:
                    # never mutate the round-shared metric: exhausted_node
                    # writes dimension_exhausted on a private copy
                    fm = m.copy()
                    fm.exhausted_node(fail)
                    self._record_failure_shared(tg.name, fm, copied=True)
                    continue
                d2["resources"] = a2
                d2["allocated_ports"] = ports
            ev = bd.evictions.get(i)
            if ev:
                for victim in ev:
                    plan.append_preempted_alloc(victim, alloc.id)
                d2["preempted_allocations"] = [v.id for v in ev]
                victims_n += len(ev)
                if len(victims_sample) < 16:
                    victims_sample.extend(ev[:16 - len(victims_sample)])
            if p is not None and p.canary and results.deployment is not None:
                dstate = results.deployment.task_groups.get(tg.name)
                if dstate is not None:
                    dstate.placed_canaries.append(alloc.id)
            if p is not None and p.previous_alloc is not None:
                d2["previous_allocation"] = p.previous_alloc.id
                if p.reschedule:
                    from .util import append_reschedule_tracker
                    append_reschedule_tracker(alloc, p.previous_alloc,
                                              self.now)
                    d2["desired_description"] = ALLOC_RESCHEDULED
            if nid is last_nid:
                last_list.append(alloc)
            else:
                last_nid = nid
                last_list = node_alloc.get(nid)
                if last_list is None:
                    node_alloc[nid] = last_list = []
                last_list.append(alloc)
            if not placed_n:
                first_m = m
            placed_n += 1
        self._count_materialized("rows", placed_n)
        if placed_n:
            self._note_placed(tg.name, first_m, n=placed_n,
                              evictions=victims_sample)
            if victims_n > len(victims_sample):
                self._tg_stats[tg.name]["preempted"] += (
                    victims_n - len(victims_sample))
            if has_net:
                # the sequential oracle ran (ineligible shape, pool
                # shortfall, or PORT_BATCHED off): meter it so the
                # batched-vs-sequential split is visible in /v1/metrics
                from nomad_tpu.core.telemetry import REGISTRY
                REGISTRY.inc("nomad.ports.sequential_rows", placed_n)

    def _record_failure_shared(self, tg_name: str, metric: AllocMetric,
                               copied: bool = False) -> None:
        """_record_failure for metrics shared across a bulk round: the
        stored (mutated) instance must not be the one attached to placed
        allocs, so the first failure stores a copy with its own mutable
        counter dicts."""
        if tg_name in self.failed_tg_allocs:
            # only the coalesced counter is bumped; skip the dict copies
            # (a full-cluster 100k-placement failure calls this per pick)
            self._record_failure(tg_name, metric)
        else:
            self._record_failure(
                tg_name, metric if copied else metric.copy())

    def _record_failure(self, tg_name: str, metric: AllocMetric) -> None:
        prev = self.failed_tg_allocs.get(tg_name)
        if prev is not None:
            prev.coalesced_failures += 1
        else:
            self.failed_tg_allocs[tg_name] = metric
        self.queued_allocs[tg_name] = self.queued_allocs.get(tg_name, 0) + 1


def new_service_scheduler(state, planner, **kwargs) -> GenericScheduler:
    return GenericScheduler(state, planner, is_batch=False, **kwargs)


def new_batch_scheduler(state, planner, **kwargs) -> GenericScheduler:
    return GenericScheduler(state, planner, is_batch=True, **kwargs)
