"""Wave-pipelined commit engine — overlap device scoring with host commit.

An earlier installation's profile showed the TPU kernel deciding 2.4-3.7M
placements/s while the pipeline committed ~240-365k: ~0.15s of host
Python (plan materialization + state-store commit) per 100k-placement
wave ran SERIALLY after every device launch, so kernel dominance never
became end-to-end dominance.  This module is the pipelining layer between
the scheduler and the plan applier that removes the host commit from the
device's critical path:

  - `WavePipeline.dispatch` launches wave k+1's kernel (JAX async
    dispatch, optionally chained on wave k's device-resident proposed
    usage — see `ops.engine.dispatch_batch`) BEFORE wave k's host phase
    runs, so the ~0.15s of materialize+commit runs under an in-flight
    launch and the result fetch is paid concurrently, not serially.
    Chained launches donate the dead usage-chain buffer
    (`ops.select.place_multi_chained`).
  - `StageTimers` records per-stage WALL INTERVALS (the STAGES below),
    not just totals, so the overlap is PROVABLE: `overlap("device",
    "commit") > 0` means commit time ran under an IN-FLIGHT launch (the
    `device` interval starts where dispatch returned and ends at the
    collect, so it is no measure of device compute: on the TPU v5e the
    device idles through 98 % of it, PERF.md), and tests can assert
    wave k+1's dispatch started before wave k's commit completed.
    Every interval a thread works through also lands in the profiler's
    trace as a `nomad.<stage>` span, beside the device's events.
    Exported via /v1/metrics (agent.metrics) and printed by bench.py.
  - Refute-repair: when the serialized applier refutes rows of an
    already-dispatched wave (a foreign write invalidated a node), the
    worker reports the refuted nodes here; the NEXT chained dispatch
    masks them out of the kernel's constraint input (the chain's usage
    buffer predates the foreign write and cannot see it), and the
    refuted rows re-enter a later wave through a repair eval
    (scheduler.generic._repair_refuted) instead of re-running the wave.
    A fresh (unchained) dispatch clears the mask: its packer-synced
    usage already accounts the foreign write.

The engine half lives in `ops/engine.py` (dispatch_batch/collect_batch);
this module owns wave sequencing, timing, and the refuted-node mask.
`core/worker.py` routes every batched launch through a WavePipeline.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from nomad_tpu.core import profiling
from nomad_tpu.core.flightrec import FLIGHT
from nomad_tpu.core.telemetry import (
    REGISTRY,
    stamp_thread_cpu,
    thread_cpu_by_role,
)

# stage names, in pipeline order.  Every stage but "device" is the wall
# of work (or of a wait) on ONE thread, recorded through
# `StageTimers.time`, which also emits it to the profiler as
# `nomad.<stage>`:
#   dequeue      worker: the wait in the broker's dequeue that returned
#                work, BEFORE a pass and outside it (the head of every
#                drain; an empty poll records nothing)
#   pass         worker: one batch, from the batch in hand to return
#                (encloses the prefetched successor's prepare+dispatch);
#                the parent of every worker stage below
#   prepare      worker: wait_for_index, snapshot, scheduler + reconcile
#                per eval (Worker._start_batch up to the dispatch)
#   dispatch     worker: host input build + the kernel's async launch
#   spread_lower worker: the host lowering of a wave's spread items
#                (ops/engine.py _lower_wave_spreads: landscapes by node
#                table version, the jobs' expected and existing counts);
#                lies INSIDE that wave's dispatch
#   mesh_launch  worker: the call of a wave's node-sharded program
#                (ops/engine.py dispatch_batch, only where the engine has
#                a mesh): the wave's replicated inputs go to every device
#                and one execution a device is enqueued; lies INSIDE that
#                wave's dispatch
#   device       NOT a thread's wall: from the dispatch's return to the
#                collect's wake-up, so it spans the predecessor's host
#                phase (recorded through `record`, never emitted)
#   device_wait  worker: block_until_ready alone, the worker's own wait
#   d2h          worker: result fetch + host-side expansion
#   solo_place   worker: one PlacementEngine.place call (input build,
#                launch, wait, fetch, decision rows).  A redo that
#                `_assign_devices` makes from inside a materialize nests
#                in it (the solo device path; no cell takes it)
#   system_place worker: one PlacementEngine.place_system call, a system
#                eval's whole placement (input build, the launch, the
#                wait for it, the fetch of the verdicts)
#   materialize  worker: plan construction from picks, on every path
#   device_carve worker: the batched device path's carve of instance ids
#                for one eval's picks (scheduler/device.py carve_block);
#                lies INSIDE that eval's materialize
#   port_assign  worker: one eval's port work (scheduler/generic.py
#                _materialize_bulk): the NetworkIndex build of every node
#                its picks touch and the assignment, columnar
#                (_carve_ports_batch) or, where the carve does not take
#                the eval, the per-allocation loop it falls to, whose
#                row construction the span then holds too; lies INSIDE
#                that eval's materialize
#   plan_wait    worker: blocked on the applier's verdict for one plan
#   finalize     worker: a wave's eval from the verdict in hand to done
#                (GenericScheduler.finalize_batched after its wait: the
#                carve ledger's settle, the full-commit check, the repair
#                or retry branch, the eval's completion)
#   batch_admin  worker: a batch's bookkeeping that no other stage covers
#                (Worker._finish_batch: delivery deadlines restarted,
#                the chain's state, the prefetch's dequeue, the chain
#                parked); two or three a pass
#   eval_update  worker: the eval status write
#   ack          worker: per-eval records + broker ack/nack (Worker.
#                _settle); one per eval on every path
#   commit       applier: evaluate + state-store upsert of one plan
#   store_upsert applier: the upsert alone (inside commit)
#   stream_send  an HTTP handler: one event of /v1/event/stream encoded
#                and written to its follower (api/http_server.py), the
#                wait for the event left out
# Worker stages other than "pass" do not nest in one another, but for
# device_carve and port_assign (and the solo device path's redo), each
# inside a materialize, and spread_lower and mesh_launch, inside a
# dispatch: the unnamed part of a pass is its wall minus their UNION
# (benchmark/host_spans.py View.named), which a nested span leaves as it
# was; a per-stage sum must leave device_carve, port_assign, spread_lower
# and mesh_launch out or count them twice.
#
# Two more names reach the profiler's trace and are no stage (nothing
# records them here): `nomad.gc`, a collection of generation 1 or 2 on
# the line of the thread it struck, inside whatever stage it stretched
# (core/telemetry.py), and `nomad.cpu`, the marker below.
STAGES = ("dequeue", "pass", "prepare", "dispatch", "spread_lower",
          "mesh_launch", "device", "device_wait", "d2h", "solo_place",
          "system_place", "materialize", "device_carve", "port_assign",
          "plan_wait",
          "finalize", "batch_admin", "eval_update", "ack", "commit",
          "store_upsert", "stream_send")

_SERIES = {s: f"nomad.wavepipe.{s}_s" for s in STAGES}

# per-stage interval ring size: a bench run records a few thousand
# intervals; the ring bounds memory on long-lived servers
_RING = 4096

# process-global wave numbering: the flight recorder merges per-wave
# records by wave id, and the StageTimers + applier are shared across
# every worker's pipeline — per-pipeline numbering would collide
_WAVE_SEQ = itertools.count(1)


_trace_annotation = None     # jax.profiler.TraceAnnotation, at its first use


def _profiler():
    """jax's TraceAnnotation; jax is imported at the first use, not with
    this module."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation


def mark_cpu(wave: int = -1) -> None:
    """A `nomad.cpu` marker on the calling thread's line of the trace:
    the cumulative CPU microseconds of the process's Python threads by
    role (core/telemetry.py: each thread's own stamps, summed here) and
    of the whole process (`process_us`: every thread, XLA's and the
    profiler's too; no sum of roles can pass it).  The worker leaves one
    immediately before a pass opens and one immediately after it closes,
    so a pass's interpreter budget is the difference of its two markers:
    of its wall so much the worker's own CPU, so much the applier's, so
    much the HTTP handlers'.  Where they sum to less than the wall the
    rest is time in which no Python thread ran (the device, a socket, a
    condition wait); where to more, threads ran beside one another
    outside the interpreter lock (system calls, native code)."""
    stamp_thread_cpu(fresh=True)
    cpu = thread_cpu_by_role()
    named = {role + "_us": int(cpu.pop(role, 0.0) * 1e6)
             for role in ("worker", "applier", "http")}
    with _profiler()("nomad.cpu", wave=wave,
                     other_us=int(sum(cpu.values()) * 1e6),
                     process_us=int(time.process_time() * 1e6), **named):
        pass


def _merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of possibly-overlapping intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


class _Timed:
    """`StageTimers.time`'s context manager.  A class with slots, not a
    generator: several stages are entered once per plan or per eval, a
    few hundred times a wave."""

    __slots__ = ("_timers", "_stage", "_wave", "_t0", "_span")

    def __init__(self, timers: "StageTimers", stage: str, wave: int) -> None:
        self._timers, self._stage, self._wave = timers, stage, wave

    def __enter__(self) -> None:
        # the profiler span of the interval.  With no profiler session
        # open (every run but a traced one) none is built: the test
        # costs a twentieth of the annotation it saves
        profiler = _trace_annotation or _profiler()
        if profiler.is_enabled():
            self._span = profiler("nomad." + self._stage, wave=self._wave)
            self._t0 = time.perf_counter()
            self._span.__enter__()
        else:
            self._span = None
            self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            self._span.__exit__(*exc)
        self._timers.record(self._stage, self._t0, time.perf_counter(),
                            self._wave)


class StageTimers:
    """Thread-safe per-stage wall-interval recorder.

    Totals alone cannot prove pipelining (serial and overlapped runs sum
    identically); intervals can: `overlap(a, b)` returns the seconds both
    stages had work in flight simultaneously.  With the pipeline live,
    `overlap("device", "commit")` and `overlap("device", "materialize")`
    are the seconds of host work that ran under an in-flight launch —
    pipelining proven, not asserted; how much of that the device spent
    computing is the profiler trace's to say."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # stage -> [seconds, count, deque of (wave, t0, t1) in
        # perf_counter seconds]: one lookup an interval
        self._slots: Dict[str, list] = {}

    def record(self, stage: str, t0: float, t1: float,
               wave: int = -1) -> None:
        with self._lock:
            slot = self._slots.get(stage)
            if slot is None:
                slot = self._slots[stage] = [0.0, 0, deque(maxlen=_RING)]
            slot[0] += t1 - t0
            slot[1] += 1
            slot[2].append((wave, t0, t1))
        # an interval that carries no wave is a per-plan or per-eval
        # stage (plan_wait, ack, store_upsert: 64 of each a wave) or
        # the solo path's: its total and count above reach /v1/metrics
        # through report(), and that is all.  `commit` alone keeps the
        # histogram it had before those stages existed.
        if wave < 0 and stage != "commit":
            return
        # per-stage latency distribution on the process registry
        # (core/telemetry.py): the interval ring above keeps proving the
        # overlap; the histogram adds p50/p95/p99 to /v1/metrics.  Device
        # time additionally feeds a ROLLING window (the health plane's
        # per-wave device-time SLO view), and every stage interval of a
        # wave lands on the wave's flight record.
        series = _SERIES.get(stage) or f"nomad.wavepipe.{stage}_s"
        if stage == "device":
            REGISTRY.observe_windowed(series, t1 - t0)
        else:
            REGISTRY.observe(series, t1 - t0)
        if wave >= 0:
            FLIGHT.record_wave(wave, **{f"{stage}_s": round(t1 - t0, 9)})

    def time(self, stage: str, wave: int = -1) -> "_Timed":
        """Context manager: one interval of work on the calling thread,
        recorded here and emitted to the profiler's trace as
        `nomad.<stage>`."""
        return _Timed(self, stage, wave)

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {stage: slot[0] for stage, slot in self._slots.items()}

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {stage: slot[1] for stage, slot in self._slots.items()}

    def intervals(self, stage: str) -> List[Tuple[int, float, float]]:
        with self._lock:
            slot = self._slots.get(stage)
            return list(slot[2]) if slot is not None else []

    def overlap(self, a: str, b: str) -> float:
        """Seconds stages `a` and `b` were simultaneously in flight."""
        ma, mb = (_merged([(t0, t1) for _, t0, t1 in self.intervals(s)])
                  for s in (a, b))
        total = 0.0
        i = j = 0
        while i < len(ma) and j < len(mb):
            lo = max(ma[i][0], mb[j][0])
            hi = min(ma[i][1], mb[j][1])
            if hi > lo:
                total += hi - lo
            if ma[i][1] < mb[j][1]:
                i += 1
            else:
                j += 1
        return total

    def report(self) -> Dict:
        """JSON-safe summary for /v1/metrics and bench.py."""
        out: Dict = {"stage_s": {k: round(v, 4)
                                 for k, v in sorted(self.totals().items())},
                     "counts": self.counts()}
        out["overlap_s"] = {
            "device*commit": round(self.overlap("device", "commit"), 4),
            "device*materialize":
                round(self.overlap("device", "materialize"), 4),
        }
        return out

    def reset(self) -> None:
        with self._lock:
            self._slots.clear()


@dataclass
class WaveHandle:
    """One dispatched wave: the engine's pending launch plus timing and
    chain metadata.  `pending` is whatever `engine.dispatch_batch`
    returned (a dict for a live launch, a tuple for the empty-cluster
    sentinel, None for an empty batch)."""
    wave: int
    pending: object = None
    items: list = field(default_factory=list)
    # (dispatch start, dispatch end) perf_counter stamps: the device
    # interval starts where the dispatch returned
    t_dispatch: Tuple[float, float] = (0.0, 0.0)
    collected: bool = False

    @property
    def chainable(self) -> bool:
        return isinstance(self.pending, dict)


class WavePipeline:
    """Double-buffered wave sequencing over one DeviceExecutor.

    The worker dispatches wave k+1 (chained on wave k's device-side
    proposed usage) before wave k's host phase runs; this object assigns
    wave numbers, applies the refuted-node mask to chained dispatches,
    and records the stage timers that make the overlap observable.  Depth
    is effectively 2 (one wave collecting + one in flight) — the
    worker's prefetch slot; deeper queues would let proposed usage drift
    arbitrarily far from committed state for no wall-clock gain on one
    device.

    Waves launch through the device-executor seam (ops/executor.py),
    which keeps node state in retained device buffers.  A bare
    PlacementEngine is accepted (tests, harnesses) and wrapped in a
    DeviceExecutor."""

    def __init__(self, executor, timers: Optional[StageTimers] = None
                 ) -> None:
        from nomad_tpu.ops.executor import DeviceExecutor
        if not isinstance(executor, DeviceExecutor):
            executor = DeviceExecutor(executor)
        self.executor = executor
        self.engine = executor.engine
        self.timers = timers if timers is not None else StageTimers()
        self._lock = threading.Lock()
        self._seq = 0
        # node ids refuted by the applier since the last FRESH dispatch:
        # chained launches must not re-pick them (the chain's usage
        # buffer predates the foreign write that refuted them)
        self._masked: set = set()
        # device instance ids carved by this pipeline's plans that a
        # later wave's snapshot may not show yet (scheduler/device.py
        # CarveLedger): one in-use index for a wave's mates and for the
        # waves of a cycle.  A second worker's pipeline has its own; what
        # two workers carve alike the applier's device audit refutes
        from nomad_tpu.scheduler.device import CarveLedger
        self.device_ledger = CarveLedger()
        self.stats = {"waves": 0, "chained": 0, "masked_nodes": 0,
                      "repairs": 0,
                      # mesh launches: cumulative cross-shard collective
                      # payload of this pipeline's waves (bytes; 0 on a
                      # single device) — the per-wave figure bench.py
                      # derives is the acceptance gauge for "top-k is
                      # the only cross-shard collective"
                      "collective_bytes": 0,
                      # networked rows whose ports the batched per-node
                      # carve assigned COLUMNAR (ISSUE 8): networked
                      # waves no longer demote out of wave coupling, and
                      # this counter is the proof a wave stayed on the
                      # block path (the sequential-oracle fallback rides
                      # nomad.ports.sequential_rows instead)
                      "port_batched_rows": 0}

    # ---------------------------------------------------------- dispatch

    def dispatch(self, snapshot, items, seed=0,
                 used0_dev=None) -> WaveHandle:
        """Pack + LAUNCH one wave asynchronously (does not block on the
        kernel).  `seed` is an int or one-per-item sequence of tie-break
        seeds (engine.dispatch_batch).  `used0_dev` chains on a previous
        wave's device-side proposed usage (see engine.dispatch_batch);
        chained dispatches carry the refuted-node mask, fresh dispatches
        clear it (their packer-synced usage already accounts every
        commit)."""
        wave = next(_WAVE_SEQ)
        with self._lock:
            self._seq = wave
            if used0_dev is None:
                self._masked.clear()
            mask = frozenset(self._masked) if self._masked else None
            self.stats["waves"] += 1
            if used0_dev is not None:
                self.stats["chained"] += 1
        t0 = time.perf_counter()
        with self.timers.time("dispatch", wave):
            pending = self.executor.dispatch_batch(
                snapshot, items, seed=seed, used0_dev=used0_dev,
                masked_node_ids=mask)
        t1 = time.perf_counter()
        if isinstance(pending, dict) and pending.get("collective_bytes"):
            with self._lock:
                self.stats["collective_bytes"] += \
                    int(pending["collective_bytes"])
        # flight record (core/flightrec.py): the wave's launch shape +
        # the engine/executor gauges the dispatch already computed —
        # one merge call, nothing new measured on the hot path
        fields: Dict[str, object] = {"items": len(items),
                                     "chained": used0_dev is not None,
                                     "masked_nodes": len(mask or ())}
        if isinstance(pending, dict):
            # real rounds of the launch's schedule, and the flat
            # schedule's padding to its power of two beside them
            fields["rounds"] = int(pending.get("rounds", 0))
            fields["rounds_padded"] = int(pending.get("rounds_padded", 0))
            fields["resident"] = bool(pending.get("chained"))
            # devices the launch's node axis was sharded over (1: none)
            fields["mesh_devices"] = int(pending.get("mesh_devices", 1))
            for key in ("collective_bytes", "shard_h2d_bytes"):
                if pending.get(key):
                    fields[key] = int(pending[key])
            if pending.get("padded_fraction") is not None:
                fields["padded_row_fraction"] = round(
                    float(pending["padded_fraction"]), 6)
        FLIGHT.record_wave(wave, **fields)
        return WaveHandle(wave=wave, pending=pending, items=list(items),
                          t_dispatch=(t0, t1))

    def collect(self, handle: Optional[WaveHandle]):
        """Block on the wave's result and expand per-item decisions.
        Records the device interval (dispatch end -> kernel ready), the
        worker's own wait inside it (device_wait) and the d2h interval
        (ready -> decisions expanded) separately, so the split between
        launch in flight, wait and fetch stays visible."""
        if handle is None:
            return []
        handle.collected = True
        pending = handle.pending
        if not isinstance(pending, dict):
            return self.executor.collect_batch(pending)
        buf = pending.get("buf")
        if buf is not None:
            # the pipeline's ONE deliberate sync point: collect()
            # exists to pay this wait, after the successor wave has
            # already been dispatched.  The profiling marker pins
            # the sampler's classification — the GIL is released in
            # here, so these samples are device-wait, not host time.
            # A device fault surfaces HERE (JaxRuntimeError) and must
            # reach the worker, which nacks the batch.
            with profiling.activity("device-wait"), \
                    self.timers.time("device_wait", handle.wave):
                buf.block_until_ready()   # analyze: ok purity
            self.timers.record("device", handle.t_dispatch[1],
                               time.perf_counter(), handle.wave)
        with self.timers.time("d2h", handle.wave):
            return self.executor.collect_batch(pending)

    def chain_state(self, handle: Optional[WaveHandle]):
        """The (usage array, node version, padded n) triple a successor
        wave chains on, or None when this wave cannot seed a chain."""
        if handle is None or not handle.chainable:
            return None
        return self.executor.chain_state(handle.pending)

    # --------------------------------------------- resident chain slot

    def claim_chain(self):
        """Pop the executor-retained resident chain (the previous
        worker pass's final proposed-usage handle) and merge its masked
        nodes into this pipeline's refute mask — the retained buffer
        predates whatever writes refuted them, exactly like an in-pass
        chain.  Returns (batch_id, seq0, used_triple) or None."""
        claimed = self.executor.claim_chain()
        if claimed is None:
            return None
        batch_id, seq0, triple, masked = claimed
        if masked:
            with self._lock:
                self._masked.update(masked)
        return (batch_id, seq0, triple)

    def retain_chain(self, batch_id: str, seq0: int, used_triple) -> None:
        """Park a finished wave's chain (plus the current refute mask)
        in the executor for the next dequeued batch."""
        self.executor.retain_chain(batch_id, seq0, used_triple,
                                   masked=self.masked_nodes())

    def note_ports_batched(self, n_rows: int, wave: int = -1) -> None:
        """A materialize pass carved `n_rows` networked placements'
        ports columnar (scheduler/generic._carve_ports_batch) — the
        wave stayed on the block path end to end."""
        if n_rows:
            with self._lock:
                self.stats["port_batched_rows"] += n_rows
            FLIGHT.record_wave(wave, port_batched_rows=n_rows)

    # ------------------------------------------------------ refute repair

    def note_refuted(self, node_ids: Iterable[str]) -> None:
        """The applier refuted these nodes for a plan of an
        already-dispatched wave: mask them out of subsequent CHAINED
        dispatches (whose usage buffers predate the refuting write)."""
        node_ids = [n for n in node_ids if n]
        if not node_ids:
            return
        with self._lock:
            before = len(self._masked)
            self._masked.update(node_ids)
            self.stats["masked_nodes"] += len(self._masked) - before
            self.stats["repairs"] += 1
            last_wave = self._seq
        # the refutes belong to this pipeline's newest wave (the applier
        # refuted a plan of an already-dispatched wave)
        FLIGHT.record_wave(last_wave, refuted_nodes=len(node_ids))

    def masked_nodes(self) -> frozenset:
        with self._lock:
            return frozenset(self._masked)

    # ---------------------------------------------------------- host side

    def materialize(self, wave: int = -1):
        """Context manager timing one plan's host materialization."""
        return self.timers.time("materialize", wave)

    def commit(self, wave: int = -1):
        """Context manager timing one plan's applier evaluate + commit
        (used by tests and drivers that apply plans themselves; the
        in-process PlanApplier records this stage on its own when wired
        with the server's shared StageTimers)."""
        return self.timers.time("commit", wave)
