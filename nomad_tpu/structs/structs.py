"""Core data model for the TPU-native Nomad scheduler framework.

Semantics re-derived from upstream hashicorp/nomad `nomad/structs/structs.go`
(the reference fork `alexandredantas/nomad` was unavailable at survey time —
see SURVEY.md §0).  These are *host-side* control-plane objects: plain Python
dataclasses, never traced by JAX.  The device-side representation is a packed
tensor cache produced by `nomad_tpu.pack` and rebuilt from any state snapshot.

Design departures from the reference (deliberate, TPU-first):
  - No msgpack/wire tags; objects are in-process only (the Go/RPC plane stays
    in the host orchestrator per the north-star scoping).
  - Resources are flat scalars (cpu MHz shares, memory MB, disk MB) plus a
    port set, matching what the scoring kernels consume.
  - `Job` embeds no HCL; `nomad_tpu.core.jobspec` parses a dict/JSON jobspec.
"""

from __future__ import annotations

import os
import threading
import uuid
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Enumerations (string-valued to match reference wire values)
# ---------------------------------------------------------------------------

JOB_TYPE_SERVICE = "service"
JOB_TYPE_BATCH = "batch"
JOB_TYPE_SYSTEM = "system"
JOB_TYPE_SYSBATCH = "sysbatch"
JOB_TYPE_CORE = "_core"

JOB_STATUS_PENDING = "pending"
JOB_STATUS_RUNNING = "running"
JOB_STATUS_DEAD = "dead"

NODE_STATUS_INIT = "initializing"
NODE_STATUS_READY = "ready"
NODE_STATUS_DOWN = "down"
NODE_STATUS_DISCONNECTED = "disconnected"

NODE_SCHED_ELIGIBLE = "eligible"
NODE_SCHED_INELIGIBLE = "ineligible"

ALLOC_DESIRED_RUN = "run"
ALLOC_DESIRED_STOP = "stop"
ALLOC_DESIRED_EVICT = "evict"

ALLOC_CLIENT_PENDING = "pending"
ALLOC_CLIENT_RUNNING = "running"
ALLOC_CLIENT_COMPLETE = "complete"
ALLOC_CLIENT_FAILED = "failed"
ALLOC_CLIENT_LOST = "lost"
ALLOC_CLIENT_UNKNOWN = "unknown"

EVAL_STATUS_PENDING = "pending"
EVAL_STATUS_COMPLETE = "complete"
EVAL_STATUS_FAILED = "failed"
EVAL_STATUS_BLOCKED = "blocked"
EVAL_STATUS_CANCELLED = "canceled"

# Evaluation trigger reasons (reference: structs.go EvalTriggerX consts).
TRIGGER_JOB_REGISTER = "job-register"
TRIGGER_JOB_DEREGISTER = "job-deregister"
TRIGGER_PERIODIC_JOB = "periodic-job"
TRIGGER_NODE_UPDATE = "node-update"
TRIGGER_NODE_DRAIN = "node-drain"
TRIGGER_ALLOC_FAILURE = "alloc-failure"
TRIGGER_ALLOC_STOP = "alloc-stop"
TRIGGER_ROLLING_UPDATE = "rolling-update"
TRIGGER_DEPLOYMENT_WATCHER = "deployment-watcher"
TRIGGER_FAILED_FOLLOW_UP = "failed-follow-up"
TRIGGER_MAX_DISCONNECT_TIMEOUT = "max-disconnect-timeout"
TRIGGER_RECONNECT = "reconnect"
TRIGGER_QUEUED_ALLOCS = "queued-allocs"
TRIGGER_RETRY_FAILED_ALLOC = "retry-failed-alloc"
TRIGGER_SCHEDULED = "scheduled"
# wavepipe refute-repair: a fresh eval re-places rows the applier
# refuted out of an already-dispatched wave (scheduler/generic.py
# _repair_refuted) instead of re-running the wave's device launch
TRIGGER_PLAN_REFUTE = "plan-refute-repair"
TRIGGER_PREEMPTION = "preemption"

# Constraint operands (reference: structs.go ConstraintX consts).
OP_EQ = "="
OP_NEQ = "!="
OP_LT = "<"
OP_LTE = "<="
OP_GT = ">"
OP_GTE = ">="
OP_REGEX = "regexp"
OP_VERSION = "version"
OP_SEMVER = "semver"
OP_SET_CONTAINS = "set_contains"
OP_SET_CONTAINS_ALL = "set_contains_all"
OP_SET_CONTAINS_ANY = "set_contains_any"
OP_DISTINCT_HOSTS = "distinct_hosts"
OP_DISTINCT_PROPERTY = "distinct_property"
OP_IS_SET = "is_set"
OP_IS_NOT_SET = "is_not_set"

SCHED_ALGO_BINPACK = "binpack"
SCHED_ALGO_SPREAD = "spread"

DEPLOYMENT_STATUS_RUNNING = "running"
DEPLOYMENT_STATUS_PAUSED = "paused"
DEPLOYMENT_STATUS_FAILED = "failed"
DEPLOYMENT_STATUS_SUCCESSFUL = "successful"
DEPLOYMENT_STATUS_CANCELLED = "cancelled"

# Dynamic port allocation range (reference: structs.go DefaultMinDynamicPort/
# DefaultMaxDynamicPort).
MIN_DYNAMIC_PORT = 20000
MAX_DYNAMIC_PORT = 32000


def _mint_ids(count: int) -> List[str]:
    """`count` UUIDv4-shaped random ids in ONE run: one urandom syscall,
    one hex conversion, vectorized dash insertion, one decode."""
    import numpy as np
    v = np.frombuffer(os.urandom(16 * count).hex().encode(),
                      np.uint8).reshape(count, 32)
    out = np.empty((count, 36), np.uint8)
    out[:, 8] = out[:, 13] = out[:, 18] = out[:, 23] = ord("-")
    out[:, :8] = v[:, :8]
    out[:, 9:13] = v[:, 8:12]
    out[:, 14] = ord("4")                      # uuid4 version nibble
    out[:, 15:18] = v[:, 13:16]
    out[:, 19:23] = v[:, 16:20]
    out[:, 24:] = v[:, 20:]
    # ONE decode of the whole matrix + fixed-stride slicing: the per-row
    # tobytes().decode() this replaces was 300k decode calls per
    # sustained run (profiled at ~half the minting cost)
    big = out.tobytes().decode("ascii")
    return [big[i:i + 36] for i in range(0, 36 * count, 36)]


# Every id of the process comes off ONE pool, refilled ID_POOL_REFILL at a
# time by whichever caller finds it short (no thread mints ahead, so a
# drain pays for every id it uses).  One run of `_mint_ids` costs ~0.4 us
# an id at any size from 8,192 up; as sixty-four cold runs of 260 a wave
# (one an eval, since the wave pipeline) it was 0.75-0.9 ms a run on the
# worker's thread, each handing the interpreter lock to the applier inside
# os.urandom.  The size: 8,192 to 65,536 read the same mean on the chip's
# host and the refill's step, inside one eval's materialize, grows with it
# (7 to 32 ms); this is the smallest power of two that holds a full wave's
# ids (64 evals x 260), so no wave refills twice (PERF.md section 6, PR
# 31).  A count over ID_DIRECT_MIN is one amortised run already and mints
# past the pool (a system eval's 49,000).
ID_POOL_REFILL = 32768
ID_DIRECT_MIN = ID_POOL_REFILL // 8
_id_pool: List[str] = []
_id_lock = threading.Lock()    # take-and-delete is two steps: one holder
# what /v1/metrics shows as nomad.ids.* (core/telemetry.py reads these:
# structs/ imports nothing of core/); written under `_id_lock`
ID_STATS = {"pool_refills": 0, "pool_served": 0, "minted_direct": 0}


def _id_pool_after_fork() -> None:
    """A forked child shares no id with its parent, and no held lock."""
    global _id_lock
    _id_lock = threading.Lock()
    _id_pool.clear()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_id_pool_after_fork)


def _refill_id_pool() -> None:
    """The caller holds `_id_lock`."""
    _id_pool.extend(_mint_ids(ID_POOL_REFILL))
    ID_STATS["pool_refills"] += 1


def new_ids(count: int) -> List[str]:
    """`count` UUIDv4-shaped random ids, never handed out twice."""
    if count <= 0:
        return []
    if count > ID_DIRECT_MIN:
        ids = _mint_ids(count)
        with _id_lock:
            ID_STATS["minted_direct"] += count
        return ids
    with _id_lock:
        if len(_id_pool) < count:
            _refill_id_pool()
        ids = _id_pool[-count:]
        del _id_pool[-count:]
        ID_STATS["pool_served"] += count
    return ids


def new_id() -> str:
    """One id off the same pool (plan ids, block ids, delivery tokens,
    the solo path's per-alloc ids, every default factory below)."""
    with _id_lock:
        if not _id_pool:
            _refill_id_pool()
        ID_STATS["pool_served"] += 1
        return _id_pool.pop()


# ---------------------------------------------------------------------------
# Resources
# ---------------------------------------------------------------------------


@dataclass
class Port:
    label: str = ""
    value: int = 0          # static port number; 0 => dynamic
    to: int = 0
    host_network: str = "default"


@dataclass
class NetworkResource:
    mode: str = "host"
    device: str = ""
    ip: str = ""
    mbits: int = 0
    reserved_ports: List[Port] = field(default_factory=list)
    dynamic_ports: List[Port] = field(default_factory=list)

    def port_labels(self) -> Dict[str, int]:
        out = {}
        for p in self.reserved_ports:
            out[p.label] = p.value
        for p in self.dynamic_ports:
            out[p.label] = p.value
        return out


@dataclass
class Resources:
    """Task-level resource ask (reference: structs.Resources)."""

    cpu: int = 100            # MHz shares
    memory_mb: int = 300
    memory_max_mb: int = 0    # oversubscription ceiling; 0 = disabled
    disk_mb: int = 0
    networks: List[NetworkResource] = field(default_factory=list)
    devices: List["RequestedDevice"] = field(default_factory=list)

    def add(self, other: "Resources") -> None:
        self.cpu += other.cpu
        self.memory_mb += other.memory_mb
        self.disk_mb += other.disk_mb
        self.networks.extend(other.networks)

    def copy(self) -> "Resources":
        return Resources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            memory_max_mb=self.memory_max_mb,
            disk_mb=self.disk_mb,
            networks=[replace(n,
                             reserved_ports=[replace(p) for p in n.reserved_ports],
                             dynamic_ports=[replace(p) for p in n.dynamic_ports])
                      for n in self.networks],
            devices=[replace(d, constraints=list(d.constraints),
                             affinities=list(d.affinities))
                     for d in self.devices],
        )


# The capacity dimensions a node has and an allocation uses, in the
# order of the packed tensors' last axis (pack/packer.py `cap` / `used`,
# the kernels' `req`): the ONE place their number is written.  Device
# instances are a count: which instance an allocation holds is host
# state (scheduler/device.py), how many are free is a capacity.
RES_NAMES = ("cpu", "memory", "disk", "devices")
RES_DIMS = len(RES_NAMES)


@dataclass
class RequestedDevice:
    name: str = ""            # e.g. "gpu", "nvidia/gpu", "nvidia/gpu/1080ti"
    count: int = 1
    constraints: List["Constraint"] = field(default_factory=list)
    affinities: List["Affinity"] = field(default_factory=list)


@dataclass
class NodeDeviceResource:
    vendor: str = ""
    type: str = ""
    name: str = ""
    instance_ids: List[str] = field(default_factory=list)
    attributes: Dict[str, str] = field(default_factory=dict)

    def id(self) -> str:
        return f"{self.vendor}/{self.type}/{self.name}"


@dataclass
class AllocatedDeviceResource:
    """Concrete device instances assigned to one task of an allocation
    (reference: structs.AllocatedDeviceResource)."""
    task: str = ""
    vendor: str = ""
    type: str = ""
    name: str = ""
    device_ids: List[str] = field(default_factory=list)

    def group_id(self) -> str:
        return f"{self.vendor}/{self.type}/{self.name}"


@dataclass
class NodeResources:
    """Node capacity (reference: structs.NodeResources + legacy Resources)."""

    cpu: int = 4000
    memory_mb: int = 8192
    disk_mb: int = 100 * 1024
    networks: List[NetworkResource] = field(default_factory=list)
    devices: List[NodeDeviceResource] = field(default_factory=list)


@dataclass
class NodeReservedResources:
    cpu: int = 0
    memory_mb: int = 0
    disk_mb: int = 0
    reserved_ports: List[int] = field(default_factory=list)


# ---------------------------------------------------------------------------
# Constraints / affinities / spread
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Constraint:
    ltarget: str = ""         # e.g. "${attr.kernel.name}"
    operand: str = OP_EQ
    rtarget: str = ""

    def __str__(self) -> str:
        return f"{self.ltarget} {self.operand} {self.rtarget}"


@dataclass(frozen=True)
class Affinity:
    ltarget: str = ""
    operand: str = OP_EQ
    rtarget: str = ""
    weight: int = 50          # [-100, 100]; negative = anti-affinity


@dataclass(frozen=True)
class SpreadTarget:
    value: str = ""
    percent: int = 0


@dataclass(frozen=True)
class Spread:
    attribute: str = ""       # e.g. "${node.datacenter}"
    weight: int = 50          # (0, 100]
    targets: Tuple[SpreadTarget, ...] = ()


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------


@dataclass
class DrainStrategy:
    deadline_s: float = 0.0       # <=0: no deadline ("-1" force semantics host-side)
    ignore_system_jobs: bool = False
    force_deadline: float = 0.0


@dataclass
class Node:
    id: str = field(default_factory=new_id)
    name: str = ""
    datacenter: str = "dc1"
    region: str = "global"      # the registering server's region
    node_pool: str = "default"
    node_class: str = ""
    attributes: Dict[str, str] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    resources: NodeResources = field(default_factory=NodeResources)
    reserved: NodeReservedResources = field(default_factory=NodeReservedResources)
    status: str = NODE_STATUS_READY
    scheduling_eligibility: str = NODE_SCHED_ELIGIBLE
    drain: Optional[DrainStrategy] = None
    drivers: Dict[str, bool] = field(default_factory=dict)   # driver -> healthy
    host_volumes: Dict[str, str] = field(default_factory=dict)  # name -> path
    csi_node_plugins: Dict[str, bool] = field(default_factory=dict)  # plugin id -> healthy
    create_index: int = 0
    modify_index: int = 0
    # cached computed class (see node_class.py)
    computed_class: str = ""

    def ready(self) -> bool:
        """reference: Node.Ready()"""
        return (self.status == NODE_STATUS_READY
                and self.drain is None
                and self.scheduling_eligibility == NODE_SCHED_ELIGIBLE)

    def capacity(self) -> Tuple[int, ...]:
        """What the node offers allocations, one int per capacity
        dimension (RES_NAMES): resources net of reserved, and the
        instances of its device groups together."""
        res, rsv = self.resources, self.reserved
        return (res.cpu - rsv.cpu, res.memory_mb - rsv.memory_mb,
                res.disk_mb - rsv.disk_mb,
                sum(len(d.instance_ids) for d in res.devices))

    def copy(self) -> "Node":
        import copy as _copy
        return _copy.deepcopy(self)


# ---------------------------------------------------------------------------
# Job
# ---------------------------------------------------------------------------


@dataclass
class RestartPolicy:
    attempts: int = 2
    interval_s: float = 1800.0
    delay_s: float = 15.0
    mode: str = "fail"        # "fail" | "delay"


@dataclass
class ReschedulePolicy:
    """reference: structs.ReschedulePolicy."""
    attempts: int = 0
    interval_s: float = 0.0
    delay_s: float = 30.0
    delay_function: str = "exponential"   # constant | exponential | fibonacci
    max_delay_s: float = 3600.0
    unlimited: bool = True


@dataclass
class MigrateStrategy:
    max_parallel: int = 1
    health_check: str = "checks"
    min_healthy_time_s: float = 10.0
    healthy_deadline_s: float = 300.0


@dataclass
class UpdateStrategy:
    """Rolling-update config (reference: structs.UpdateStrategy)."""
    stagger_s: float = 30.0
    max_parallel: int = 1
    health_check: str = "checks"
    min_healthy_time_s: float = 10.0
    healthy_deadline_s: float = 300.0
    progress_deadline_s: float = 600.0
    auto_revert: bool = False
    auto_promote: bool = False
    canary: int = 0

    def rolling(self) -> bool:
        return self.max_parallel > 0


@dataclass
class EphemeralDisk:
    size_mb: int = 300
    sticky: bool = False
    migrate: bool = False


@dataclass
class VolumeRequest:
    name: str = ""
    type: str = "host"        # "host" | "csi"
    source: str = ""
    read_only: bool = False
    access_mode: str = ""
    attachment_mode: str = ""
    per_alloc: bool = False


@dataclass
class Service:
    name: str = ""
    port_label: str = ""
    provider: str = "consul"
    tags: List[str] = field(default_factory=list)
    checks: List[Dict[str, Any]] = field(default_factory=list)


@dataclass
class Task:
    name: str = "task"
    driver: str = "exec"
    config: Dict[str, Any] = field(default_factory=dict)
    env: Dict[str, str] = field(default_factory=dict)
    resources: Resources = field(default_factory=Resources)
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    services: List[Service] = field(default_factory=list)
    leader: bool = False
    kill_timeout_s: float = 5.0
    artifacts: List[Dict[str, Any]] = field(default_factory=list)
    templates: List[Dict[str, Any]] = field(default_factory=list)
    vault: Optional[Dict[str, Any]] = None
    lifecycle: Optional[Dict[str, Any]] = None
    dispatch_payload_file: str = ""


@dataclass
class TaskGroup:
    name: str = "group"
    count: int = 1
    tasks: List[Task] = field(default_factory=list)
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    spreads: List[Spread] = field(default_factory=list)
    restart_policy: RestartPolicy = field(default_factory=RestartPolicy)
    reschedule_policy: Optional[ReschedulePolicy] = None
    migrate: MigrateStrategy = field(default_factory=MigrateStrategy)
    update: Optional[UpdateStrategy] = None
    ephemeral_disk: EphemeralDisk = field(default_factory=EphemeralDisk)
    networks: List[NetworkResource] = field(default_factory=list)
    volumes: Dict[str, VolumeRequest] = field(default_factory=dict)
    services: List[Service] = field(default_factory=list)
    max_client_disconnect_s: Optional[float] = None

    def combined_resources(self) -> Resources:
        """Sum of task resources + ephemeral disk, the unit the scheduler
        places (reference: structs.AllocatedResources flattening)."""
        total = Resources(cpu=0, memory_mb=0, disk_mb=self.ephemeral_disk.size_mb)
        for t in self.tasks:
            total.cpu += t.resources.cpu
            total.memory_mb += t.resources.memory_mb
            total.networks.extend([n for n in t.resources.networks])
        total.networks.extend(self.networks)
        return total


@dataclass
class PeriodicConfig:
    enabled: bool = True
    spec: str = ""            # cron spec
    spec_type: str = "cron"
    prohibit_overlap: bool = False
    timezone: str = "UTC"


@dataclass
class ParameterizedJobConfig:
    payload: str = "optional"
    meta_required: List[str] = field(default_factory=list)
    meta_optional: List[str] = field(default_factory=list)


@dataclass
class Multiregion:
    strategy: Dict[str, Any] = field(default_factory=dict)
    regions: List[Dict[str, Any]] = field(default_factory=list)


@dataclass
class Job:
    id: str = ""
    name: str = ""
    namespace: str = "default"
    region: str = "global"
    type: str = JOB_TYPE_SERVICE
    priority: int = 50
    all_at_once: bool = False
    datacenters: List[str] = field(default_factory=lambda: ["dc1"])
    node_pool: str = "default"
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    spreads: List[Spread] = field(default_factory=list)
    task_groups: List[TaskGroup] = field(default_factory=list)
    update: Optional[UpdateStrategy] = None
    periodic: Optional[PeriodicConfig] = None
    parameterized: Optional[ParameterizedJobConfig] = None
    multiregion: Optional[Multiregion] = None
    meta: Dict[str, str] = field(default_factory=dict)
    status: str = JOB_STATUS_PENDING
    stop: bool = False
    stable: bool = False     # this version completed a successful deployment
    version: int = 0
    create_index: int = 0
    modify_index: int = 0
    job_modify_index: int = 0
    parent_id: str = ""
    payload: bytes = b""
    dispatched: bool = False

    def __post_init__(self):
        if not self.id:
            self.id = new_id()
        if not self.name:
            self.name = self.id

    def lookup_task_group(self, name: str) -> Optional[TaskGroup]:
        for tg in self.task_groups:
            if tg.name == name:
                return tg
        return None

    def stopped(self) -> bool:
        """reference: Job.Stopped (nil-job case handled by callers)."""
        return self.stop

    def copy(self) -> "Job":
        import copy as _copy
        return _copy.deepcopy(self)

    def ns_id(self) -> Tuple[str, str]:
        return (self.namespace, self.id)


# ---------------------------------------------------------------------------
# Allocation
# ---------------------------------------------------------------------------


@dataclass
class NodeScoreMeta:
    """Per-candidate score breakdown (reference: structs.NodeScoreMeta)."""
    node_id: str = ""
    scores: Dict[str, float] = field(default_factory=dict)
    norm_score: float = 0.0


@dataclass
class AllocMetric:
    """Scheduler decision introspection attached to every allocation
    (reference: structs.AllocMetric) — the de-facto scheduler output
    contract per SURVEY.md §4.5."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_in_pool: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)   # per-dc
    class_filtered: Dict[str, int] = field(default_factory=dict)
    constraint_filtered: Dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: Dict[str, int] = field(default_factory=dict)
    dimension_exhausted: Dict[str, int] = field(default_factory=dict)
    quota_exhausted: List[str] = field(default_factory=list)
    score_meta_data: List[NodeScoreMeta] = field(default_factory=list)
    allocation_time_ns: int = 0
    coalesced_failures: int = 0

    def copy(self) -> "AllocMetric":
        """The ONE metric copy path (alloc cloning, bulk-round failure
        accounting): every mutable container gets its own instance so
        later in-place writes never bleed across shared metrics."""
        nm = AllocMetric.__new__(AllocMetric)
        nm.__dict__ = dict(self.__dict__)
        nm.nodes_available = dict(self.nodes_available)
        nm.class_filtered = dict(self.class_filtered)
        nm.constraint_filtered = dict(self.constraint_filtered)
        nm.class_exhausted = dict(self.class_exhausted)
        nm.dimension_exhausted = dict(self.dimension_exhausted)
        nm.quota_exhausted = list(self.quota_exhausted)
        nm.score_meta_data = list(self.score_meta_data)
        return nm

    def exhausted_node(self, dimension: str) -> None:
        self.nodes_exhausted += 1
        if dimension:
            self.dimension_exhausted[dimension] = (
                self.dimension_exhausted.get(dimension, 0) + 1)

    def filter_node(self, reason: str) -> None:
        self.nodes_filtered += 1
        if reason:
            self.constraint_filtered[reason] = (
                self.constraint_filtered.get(reason, 0) + 1)


@dataclass
class RescheduleEvent:
    reschedule_time: float = 0.0
    prev_alloc_id: str = ""
    prev_node_id: str = ""
    delay_s: float = 0.0


@dataclass
class RescheduleTracker:
    events: List[RescheduleEvent] = field(default_factory=list)


@dataclass
class DesiredTransition:
    migrate: bool = False
    reschedule: bool = False
    force_reschedule: bool = False
    no_shutdown_delay: bool = False


@dataclass
class NetworkAllocation:
    ip: str = ""
    ports: Dict[str, int] = field(default_factory=dict)   # label -> host port


TASK_STATE_PENDING = "pending"
TASK_STATE_RUNNING = "running"
TASK_STATE_DEAD = "dead"

# Task event types (reference: structs.go TaskEvent consts).
TASK_RECEIVED = "Received"
TASK_SETUP = "Task Setup"
TASK_STARTED = "Started"
TASK_TERMINATED = "Terminated"
TASK_RESTARTING = "Restarting"
TASK_NOT_RESTARTING = "Not Restarting"
TASK_KILLING = "Killing"
TASK_KILLED = "Killed"
TASK_DRIVER_FAILURE = "Driver Failure"
TASK_FAILED_ARTIFACT = "Failed Artifact Download"
TASK_SIBLING_FAILED = "Sibling Task Failed"
TASK_LEADER_DEAD = "Leader Task Dead"


@dataclass
class TaskEvent:
    """reference: structs.TaskEvent"""
    type: str = ""
    time: float = 0.0
    message: str = ""
    exit_code: Optional[int] = None
    signal: Optional[int] = None
    restart_reason: str = ""


@dataclass
class TaskState:
    """reference: structs.TaskState"""
    state: str = TASK_STATE_PENDING
    failed: bool = False
    restarts: int = 0
    last_restart: float = 0.0
    started_at: float = 0.0
    finished_at: float = 0.0
    events: List[TaskEvent] = field(default_factory=list)

    def successful(self) -> bool:
        return self.state == TASK_STATE_DEAD and not self.failed


@dataclass
class Allocation:
    id: str = field(default_factory=new_id)
    namespace: str = "default"
    eval_id: str = ""
    # eval-lifecycle trace this alloc belongs to (core/telemetry.py):
    # stamped by the plan applier at commit so the client's alloc runner
    # can close the span tree with the alloc-start span
    trace_id: str = ""
    name: str = ""            # job.name[index]
    node_id: str = ""
    node_name: str = ""
    job_id: str = ""
    job: Optional[Job] = None
    task_group: str = ""
    resources: Resources = field(default_factory=Resources)
    allocated_ports: Dict[str, int] = field(default_factory=dict)
    allocated_devices: List[AllocatedDeviceResource] = field(default_factory=list)
    desired_status: str = ALLOC_DESIRED_RUN
    desired_description: str = ""
    desired_transition: DesiredTransition = field(default_factory=DesiredTransition)
    client_status: str = ALLOC_CLIENT_PENDING
    client_description: str = ""
    task_states: Dict[str, TaskState] = field(default_factory=dict)
    previous_allocation: str = ""
    next_allocation: str = ""
    deployment_id: str = ""
    deployment_status: Optional[Dict[str, Any]] = None   # {healthy: bool, ts: float}
    reschedule_tracker: Optional[RescheduleTracker] = None
    reschedule_policy: Optional[ReschedulePolicy] = None
    followup_eval_id: str = ""
    preempted_by_allocation: str = ""
    preempted_allocations: List[str] = field(default_factory=list)
    metrics: AllocMetric = field(default_factory=AllocMetric)
    job_version: int = 0
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0
    create_time: float = 0.0
    modify_time: float = 0.0

    # -- status helpers (reference: structs.Allocation.TerminalStatus etc.) --

    def terminal_status(self) -> bool:
        """True when the *desired* or *client* status is terminal."""
        if self.desired_status in (ALLOC_DESIRED_STOP, ALLOC_DESIRED_EVICT):
            return True
        return self.client_terminal_status()

    def usage(self) -> Tuple[int, ...]:
        """What this allocation counts against its node, one int per
        capacity dimension (RES_NAMES): cpu, memory, disk, and the
        device instances it holds."""
        r = self.resources
        devs = self.allocated_devices
        return (r.cpu, r.memory_mb, r.disk_mb,
                sum(len(d.device_ids) for d in devs) if devs else 0)

    def client_terminal_status(self) -> bool:
        return self.client_status in (
            ALLOC_CLIENT_COMPLETE, ALLOC_CLIENT_FAILED, ALLOC_CLIENT_LOST)

    def migrate_disk(self) -> bool:
        if self.job is None:
            return False
        tg = self.job.lookup_task_group(self.task_group)
        return bool(tg and tg.ephemeral_disk.migrate)

    def index(self) -> int:
        """Alloc name index: `job.name[idx]` (reference: AllocIndexFromName)."""
        l, r = self.name.rfind("["), self.name.rfind("]")
        if l == -1 or r == -1:
            return -1
        try:
            return int(self.name[l + 1:r])
        except ValueError:
            return -1

    def ran_successfully(self) -> bool:
        return self.client_status == ALLOC_CLIENT_COMPLETE

    def copy(self) -> "Allocation":
        out = self.copy_skip_job()
        if self.job is not None:
            out.job = self.job.copy()
        return out

    def copy_skip_job(self) -> "Allocation":
        """Structured copy sharing the embedded job pointer (reference:
        Allocation.CopySkipJob).  Hand-rolled rather than deepcopy: alloc
        inserts are the state store's hot path and deepcopy dominates plan
        apply at bench scale.  NodeScoreMeta/TaskEvent/RescheduleEvent
        entries are treated as immutable records and shared."""
        cls = type(self)
        out = cls.__new__(cls)
        d = dict(self.__dict__)
        out.__dict__ = d
        d["resources"] = self.resources.copy()
        d["allocated_ports"] = dict(self.allocated_ports)
        dt = self.desired_transition
        d["desired_transition"] = DesiredTransition(
            migrate=dt.migrate, reschedule=dt.reschedule,
            force_reschedule=dt.force_reschedule,
            no_shutdown_delay=dt.no_shutdown_delay)
        states = {}
        for k, v in self.task_states.items():
            ts = TaskState.__new__(TaskState)
            ts.__dict__ = dict(v.__dict__)
            ts.events = list(v.events)
            states[k] = ts
        d["task_states"] = states
        if self.deployment_status is not None:
            d["deployment_status"] = dict(self.deployment_status)
        if self.reschedule_tracker is not None:
            d["reschedule_tracker"] = RescheduleTracker(
                events=list(self.reschedule_tracker.events))
        d["preempted_allocations"] = list(self.preempted_allocations)
        d["metrics"] = self.metrics.copy()
        return out


def alloc_name(job_id: str, group: str, idx: int) -> str:
    """reference: structs.AllocName"""
    return f"{job_id}.{group}[{idx}]"


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------


@dataclass
class Evaluation:
    id: str = field(default_factory=new_id)
    namespace: str = "default"
    priority: int = 50
    type: str = JOB_TYPE_SERVICE        # scheduler type
    triggered_by: str = TRIGGER_JOB_REGISTER
    job_id: str = ""
    job_modify_index: int = 0
    node_id: str = ""
    node_modify_index: int = 0
    deployment_id: str = ""
    status: str = EVAL_STATUS_PENDING
    status_description: str = ""
    wait_until: float = 0.0
    next_eval: str = ""
    previous_eval: str = ""
    blocked_eval: str = ""
    related_evals: List[str] = field(default_factory=list)
    class_eligibility: Dict[str, bool] = field(default_factory=dict)
    escaped_computed_class: bool = False
    quota_limit_reached: str = ""
    queued_allocations: Dict[str, int] = field(default_factory=dict)  # tg -> queued
    failed_tg_allocs: Dict[str, AllocMetric] = field(default_factory=dict)
    annotate_plan: bool = False
    snapshot_index: int = 0
    # cross-component trace id (core/telemetry.py): stamped once at the
    # FSM boundary (Server.apply_eval_update) and inherited by every
    # follow-up/blocked eval, plan, and alloc this eval produces
    trace_id: str = ""
    create_index: int = 0
    modify_index: int = 0
    create_time: float = 0.0
    modify_time: float = 0.0

    def terminal_status(self) -> bool:
        return self.status in (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED,
                               EVAL_STATUS_CANCELLED)

    def should_enqueue(self) -> bool:
        return self.status == EVAL_STATUS_PENDING

    def should_block(self) -> bool:
        return self.status == EVAL_STATUS_BLOCKED

    def copy(self) -> "Evaluation":
        """Shallow copy + fresh top-level containers.  Nested values
        (AllocMetric objects) are SHARED under the store convention the
        reference itself relies on: objects are immutable once inserted
        (callers mutate scalars and replace containers, never nested
        metrics in place).  The deepcopy this replaces walked ~60 nested
        objects per eval and was the single largest cost of a 384-eval
        wave's status bookkeeping."""
        import copy as _copy
        e = _copy.copy(self)
        e.related_evals = list(self.related_evals)
        e.class_eligibility = dict(self.class_eligibility)
        e.queued_allocations = dict(self.queued_allocations)
        e.failed_tg_allocs = dict(self.failed_tg_allocs)
        return e

    def create_blocked_eval(self, class_eligibility: Dict[str, bool],
                            escaped: bool, quota: str = "",
                            failed_tg_allocs: Optional[Dict[str, AllocMetric]] = None,
                            ) -> "Evaluation":
        """reference: Evaluation.CreateBlockedEval"""
        return Evaluation(
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=TRIGGER_QUEUED_ALLOCS,
            job_id=self.job_id,
            status=EVAL_STATUS_BLOCKED,
            previous_eval=self.id,
            class_eligibility=dict(class_eligibility),
            escaped_computed_class=escaped,
            quota_limit_reached=quota,
            failed_tg_allocs=dict(failed_tg_allocs or {}),
            trace_id=self.trace_id,
        )

    def create_failed_follow_up_eval(self, wait_until: float) -> "Evaluation":
        return Evaluation(
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=TRIGGER_FAILED_FOLLOW_UP,
            job_id=self.job_id,
            status=EVAL_STATUS_PENDING,
            wait_until=wait_until,
            previous_eval=self.id,
            trace_id=self.trace_id,
        )


# ---------------------------------------------------------------------------
# Eval decision records (placement explainability)
# ---------------------------------------------------------------------------


@dataclass
class TGDecision:
    """One task group's slice of an eval's placement decision: how many
    placements were attempted/placed/failed, the AllocMetric rollup that
    explains the failures (NodesEvaluated/Filtered/Exhausted with the
    per-reason breakdowns), the winning top-k score table, and the
    preemption choices made on its behalf."""

    task_group: str = ""
    desired: int = 0
    placed: int = 0
    failed: int = 0
    preempted: int = 0
    # bounded sample of evicted alloc ids (the full victim set is on the
    # preempting allocs themselves)
    preempted_allocs: List[str] = field(default_factory=list)
    # failure rollup when any placement failed, else the placed rollup
    metric: Optional[AllocMetric] = None
    # top-k score table of the WINNING launch (placed placements) —
    # kept separate from `metric` so a partially-failed group shows both
    # the winners' scores and the failures' exhaustion breakdown
    score_meta: List[NodeScoreMeta] = field(default_factory=list)


@dataclass
class EvalDecision:
    """Per-eval decision record (the explainability artifact behind
    `/v1/eval/<id>/explain` and `nomad eval explain`): everything the
    scheduler already knew at submit time about WHY it placed where it
    placed — joined from the device kernels' AllocMetric/NodeScoreMeta
    output, the blocked-eval cause, and the preemption choices.  Kept in
    a size-bounded ring in the state store; observability-only (never
    raft-replicated or snapshotted)."""

    eval_id: str = ""
    trace_id: str = ""
    namespace: str = "default"
    job_id: str = ""
    job_type: str = ""
    triggered_by: str = ""
    status: str = ""                 # final eval status
    status_description: str = ""
    blocked_eval: str = ""           # id of the blocked eval, if created
    blocked_cause: str = ""          # human summary of the blocking reason
    task_groups: Dict[str, TGDecision] = field(default_factory=dict)
    snapshot_index: int = 0
    create_time: float = 0.0


# ---------------------------------------------------------------------------
# Deployment
# ---------------------------------------------------------------------------


@dataclass
class DeploymentState:
    auto_revert: bool = False
    auto_promote: bool = False
    promoted: bool = False
    placed_canaries: List[str] = field(default_factory=list)
    desired_canaries: int = 0
    desired_total: int = 0
    placed_allocs: int = 0
    healthy_allocs: int = 0
    unhealthy_allocs: int = 0
    progress_deadline_s: float = 0.0
    require_progress_by: float = 0.0


@dataclass
class Deployment:
    id: str = field(default_factory=new_id)
    namespace: str = "default"
    job_id: str = ""
    job_version: int = 0
    job_modify_index: int = 0
    job_create_index: int = 0
    task_groups: Dict[str, DeploymentState] = field(default_factory=dict)
    status: str = DEPLOYMENT_STATUS_RUNNING
    status_description: str = ""
    create_index: int = 0
    modify_index: int = 0

    def active(self) -> bool:
        return self.status in (DEPLOYMENT_STATUS_RUNNING, DEPLOYMENT_STATUS_PAUSED)

    def requires_promotion(self) -> bool:
        return any(s.desired_canaries > 0 and not s.promoted
                   for s in self.task_groups.values())

    def copy(self) -> "Deployment":
        import copy as _copy
        return _copy.deepcopy(self)


@dataclass
class DeploymentStatusUpdate:
    deployment_id: str = ""
    status: str = ""
    status_description: str = ""


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclass
class Plan:
    """Scheduler output submitted to the plan applier
    (reference: structs.Plan)."""

    eval_id: str = ""
    eval_token: str = ""
    # trace context inherited from the eval (core/telemetry.py): the
    # applier's queue-wait/apply spans and the committed allocs join the
    # eval's span tree through it
    trace_id: str = ""
    priority: int = 50
    all_at_once: bool = False
    job: Optional[Job] = None
    # node_id -> allocs to stop/evict (desired_status already set)
    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    # node_id -> new/updated allocs to place
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    # node_id -> allocs preempted to make room
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    # columnar bulk placements (structs.block.AllocBlock): one eval's
    # homogeneous placements as picks + shared template, committed to the
    # store WITHOUT materializing per-alloc objects (the round-3 profile's
    # dominant host cost).  The applier expands a block into
    # node_allocation only when it must re-check per node (broken fence,
    # refused node) — see Plan.expand_blocks.
    alloc_blocks: List = field(default_factory=list)
    deployment: Optional[Deployment] = None
    deployment_updates: List[DeploymentStatusUpdate] = field(default_factory=list)
    annotations: Optional["PlanAnnotations"] = None
    snapshot_index: int = 0
    # (batch_id, placement_seq_at_snapshot) when this plan came from a
    # multi-eval batched launch: plans of one batch were computed against
    # shared proposed capacity and cannot refute each other, so the
    # applier may skip the per-node AllocsFit re-check while the store's
    # placement_seq proves no foreign write intervened (core/plan_apply)
    coupled_batch: Optional[Tuple[str, int]] = None
    # a host-side fallback redirected a placement off its kernel pick
    # (port exhaustion -> runner-up): the device's coupled capacity view
    # no longer matches, so the plan must never be fence-tagged
    host_redirected: bool = False

    def append_alloc(self, alloc: Allocation) -> None:
        self.node_allocation.setdefault(alloc.node_id, []).append(alloc)

    def append_stopped_alloc(self, alloc: Allocation, desired_desc: str,
                             client_status: str = "",
                             followup_eval_id: str = "") -> None:
        """reference: Plan.AppendStoppedAlloc"""
        a = alloc.copy_skip_job()
        a.desired_status = ALLOC_DESIRED_STOP
        a.desired_description = desired_desc
        if client_status:
            a.client_status = client_status
        if followup_eval_id:
            a.followup_eval_id = followup_eval_id
        self.node_update.setdefault(a.node_id, []).append(a)

    def append_preempted_alloc(self, alloc: Allocation, preempting_id: str) -> None:
        a = alloc.copy_skip_job()
        a.desired_status = ALLOC_DESIRED_EVICT
        a.desired_description = f"Preempted by alloc ID {preempting_id}"
        a.preempted_by_allocation = preempting_id
        self.node_preemptions.setdefault(a.node_id, []).append(a)

    def is_no_op(self) -> bool:
        return (not self.node_update and not self.node_allocation
                and not self.node_preemptions and not self.alloc_blocks
                and self.deployment is None
                and not self.deployment_updates)

    def expand_blocks(self) -> None:
        """Materialize every alloc block into node_allocation (the
        applier's fallback when it needs per-node granularity: broken
        fence -> AllocsFit re-check, or a refused node in a block)."""
        for block in self.alloc_blocks:
            for a in block.materialize_all():
                self.node_allocation.setdefault(a.node_id, []).append(a)
        self.alloc_blocks = []


@dataclass
class PlanResult:
    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    alloc_blocks: List = field(default_factory=list)
    deployment: Optional[Deployment] = None
    deployment_updates: List[DeploymentStatusUpdate] = field(default_factory=list)
    refuted_nodes: List[str] = field(default_factory=list)
    alloc_index: int = 0

    def full_commit(self, plan: Plan) -> Tuple[bool, int, int]:
        expected = (sum(len(v) for v in plan.node_allocation.values())
                    + sum(b.count for b in plan.alloc_blocks))
        actual = (sum(len(v) for v in self.node_allocation.values())
                  + sum(b.count for b in self.alloc_blocks))
        return actual == expected, expected, actual


@dataclass
class DesiredUpdates:
    """Per-taskgroup annotation counts (reference: structs.DesiredUpdates)."""
    ignore: int = 0
    place: int = 0
    migrate: int = 0
    stop: int = 0
    in_place_update: int = 0
    destructive_update: int = 0
    canary: int = 0
    preemptions: int = 0
    reschedule_now: int = 0
    reschedule_later: int = 0
    disconnect_updates: int = 0
    reconnect_updates: int = 0


@dataclass
class PlanAnnotations:
    desired_tg_updates: Dict[str, DesiredUpdates] = field(default_factory=dict)
    preempted_allocs: List[str] = field(default_factory=list)


# ---------------------------------------------------------------------------
# Scheduler configuration (runtime cluster config plane — SURVEY §6.6)
# ---------------------------------------------------------------------------


@dataclass
class PreemptionConfig:
    system_scheduler_enabled: bool = True
    sysbatch_scheduler_enabled: bool = False
    batch_scheduler_enabled: bool = False
    service_scheduler_enabled: bool = False


@dataclass
class SchedulerConfiguration:
    scheduler_algorithm: str = SCHED_ALGO_BINPACK
    preemption_config: PreemptionConfig = field(default_factory=PreemptionConfig)
    memory_oversubscription_enabled: bool = False
    reject_job_registration: bool = False
    pause_eval_broker: bool = False
    # TPU-backend enablement (new-framework plane-(c) flag, mirrors how
    # preemption was rolled out in the reference):
    tpu_backend_enabled: bool = True
    create_index: int = 0
    modify_index: int = 0


# ---------------------------------------------------------------------------
# Namespaces / node pools / misc cluster objects
# ---------------------------------------------------------------------------


@dataclass
class Namespace:
    name: str = "default"
    description: str = ""
    create_index: int = 0
    modify_index: int = 0


@dataclass
class NodePool:
    name: str = "default"
    description: str = ""
    scheduler_algorithm: str = ""    # "" = inherit global
    create_index: int = 0
    modify_index: int = 0

NODE_POOL_ALL = "all"
NODE_POOL_DEFAULT = "default"


# ---------------------------------------------------------------------------
# ACL (reference: structs ACLPolicy / ACLToken)
# ---------------------------------------------------------------------------

ACL_TOKEN_TYPE_CLIENT = "client"
ACL_TOKEN_TYPE_MANAGEMENT = "management"


@dataclass
class ACLPolicy:
    name: str = ""
    description: str = ""
    rules: str = ""              # HCL/JSON policy document
    create_index: int = 0
    modify_index: int = 0


@dataclass
class ACLToken:
    accessor_id: str = field(default_factory=new_id)   # public handle
    secret_id: str = field(default_factory=new_id)     # the bearer secret
    name: str = ""
    type: str = ACL_TOKEN_TYPE_CLIENT
    policies: List[str] = field(default_factory=list)
    global_: bool = False
    create_time: float = 0.0
    # epoch seconds; 0 = never expires.  Login-minted tokens carry the
    # auth method's max_token_ttl_s (reference: ExpirationTime).
    expiration_time: float = 0.0
    create_index: int = 0
    modify_index: int = 0

    def is_management(self) -> bool:
        return self.type == ACL_TOKEN_TYPE_MANAGEMENT

    def expired(self, now: float) -> bool:
        return bool(self.expiration_time) and now > self.expiration_time


@dataclass
class ACLAuthMethod:
    """SSO auth method (reference: structs.ACLAuthMethod [v1.5+] —
    `nomad acl auth-method`).  Type "JWT" validates bearer JWTs locally
    against configured keys; "OIDC" requires interactive discovery +
    egress and is declared unsupported in this build (the create path
    rejects it with the reason)."""
    name: str = ""
    type: str = "JWT"            # "JWT" (supported) | "OIDC" (rejected)
    token_locality: str = "local"
    max_token_ttl_s: float = 3600.0
    default: bool = False
    # type-specific config (reference: ACLAuthMethodConfig):
    #   JWTValidationPubKeys: [PEM RSA public keys]  (RS256)
    #   JWTValidationSecrets: [shared secrets]       (HS256; deviation —
    #       handy where no PKI exists; same claims checks apply)
    #   BoundIssuer: str, BoundAudiences: [str]
    config: Dict[str, Any] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0


@dataclass
class ACLBindingRule:
    """Maps verified claims to ACL grants (reference:
    structs.ACLBindingRule).  `selector` is a comma-ANDed list of
    `claims.<name>==<value>` terms (empty = match every login);
    `bind_name` interpolates `${claims.<name>}`."""
    id: str = field(default_factory=new_id)
    auth_method: str = ""
    selector: str = ""
    bind_type: str = "policy"    # "policy" | "management"
    bind_name: str = ""
    create_index: int = 0
    modify_index: int = 0


@dataclass
class ServiceRegistration:
    """One service instance (reference: structs.ServiceRegistration —
    Nomad-native service discovery, provider="nomad")."""
    id: str = ""                 # _nomad-task-<alloc>-<group|task>-<svc>
    service_name: str = ""
    namespace: str = "default"
    node_id: str = ""
    job_id: str = ""
    alloc_id: str = ""
    datacenter: str = ""
    tags: List[str] = field(default_factory=list)
    address: str = ""
    port: int = 0
    # aggregate check status: "passing" | "critical" | "" (no checks)
    status: str = ""
    create_index: int = 0
    modify_index: int = 0


@dataclass
class VariableItem:
    """Decrypted variable (reference: structs.VariableDecrypted)."""
    path: str = ""
    namespace: str = "default"
    items: Dict[str, str] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0


@dataclass
class CSIVolume:
    id: str = ""
    namespace: str = "default"
    plugin_id: str = ""
    access_mode: str = "multi-node-multi-writer"
    attachment_mode: str = "file-system"
    # node ids in the volume's accessible topology; empty = all
    topology_node_ids: Tuple[str, ...] = ()
    # claim model: alloc id -> node id of the claiming alloc.  The node
    # axis is what single-node access modes pin on (reference:
    # nomad/structs/csi.go access-mode semantics); legacy boolean values
    # are tolerated as "node unknown" and never pin.
    read_allocs: Dict[str, str] = field(default_factory=dict)
    write_allocs: Dict[str, str] = field(default_factory=dict)
    # COLUMNAR claims: block id -> AllocBlock whose every member holds a
    # read-only claim.  Only read-only claims on multi-node volumes ride
    # here (PlanApplier._blocks_ok demotes writers and single-node modes
    # to the per-alloc path), so block claims never pin a node and never
    # count against writer limits — which keeps a bulk commit O(1) per
    # volume instead of O(members), and keeps the claim ledger's
    # copy-on-write cost proportional to BLOCKS, not claim history.  A
    # block's claims migrate to read_allocs when it materializes
    # (StateStore._materialize_block_locked), so terminal-release and
    # snapshot serialization only ever see per-alloc claims.
    read_blocks: Dict[str, object] = field(default_factory=dict)
    schedulable: bool = True

    def n_read_claims(self) -> int:
        return (len(self.read_allocs)
                + sum(len(b.ids) for b in self.read_blocks.values()))

    def has_claims(self) -> bool:
        return bool(self.read_allocs or self.write_allocs
                    or self.read_blocks)

    def writer_limited(self) -> bool:
        """Access modes permitting at most ONE live writer (reference:
        CSIVolumeAccessModeSingleNodeWriter / MultiNodeSingleWriter)."""
        return (self.access_mode.startswith("single-node-writer")
                or self.access_mode == "multi-node-single-writer")

    def reader_only(self) -> bool:
        return self.access_mode in ("single-node-reader-only",
                                    "multi-node-reader-only")

    def single_node(self) -> bool:
        """Access modes attaching to at most ONE node — readers included
        (reference: CSIVolumeAccessModeSingleNode{Writer,ReaderOnly})."""
        return self.access_mode.startswith("single-node")

    def live_claim_nodes(self, releasing=()) -> set:
        """Node ids of live claims (read AND write), skipping `releasing`
        alloc ids and claims whose node is unrecorded.  Block claims are
        deliberately absent: they exist only on multi-node volumes, whose
        access modes never pin a node."""
        return {nd
                for claims in (self.read_allocs, self.write_allocs)
                for aid, nd in claims.items()
                if aid not in releasing and isinstance(nd, str) and nd}

    def pinned_node(self) -> str:
        """The node a single-node volume is attached to, or "" when
        unclaimed (feasibility pin — scheduler/feasible.go
        CSIVolumeChecker's node-axis check)."""
        if not self.single_node():
            return ""
        for nd in self.live_claim_nodes():
            return nd
        return ""

    def claim_ok(self, read_only: bool, releasing=(),
                 node_id: str = "") -> bool:
        """`releasing`: alloc ids whose claims are being released by the
        same plan (stops / preemptions / same-id replacements) — without
        the exemption a single-node-writer volume livelocks on job update:
        the replacement is refuted by its predecessor's claim, and the
        refute also withholds the stop that would release it.

        `node_id`: the node the new claim would attach on; single-node
        modes refuse any node other than the one live claims (readers
        included) already pin.  Empty = caller doesn't know the node
        (legacy call sites) — the pin check is skipped."""
        if not self.schedulable:
            return False
        if not read_only and self.reader_only():
            return False         # write claim against a read-only mode
        if node_id and self.single_node():
            live = self.live_claim_nodes(releasing)
            if live and node_id not in live:
                return False     # single-node modes pin ALL claims
        if read_only:
            return True
        if self.writer_limited():
            return not (set(self.write_allocs) - set(releasing))
        return True


# Explicit public surface: every class/function defined in this module plus
# the upper-case constants (keeps `from .structs import *` from leaking
# stdlib/typing names).
__all__ = [
    _n for _n, _v in list(globals().items())
    if not _n.startswith("_")
    and (getattr(_v, "__module__", None) == __name__
         or (_n.isupper() and isinstance(_v, (str, int, float, tuple))))
]
