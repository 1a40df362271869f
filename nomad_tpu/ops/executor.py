"""Device executor seam — resident buffer handles for the worker loop.

Node tensors are uploaded ONCE into retained device buffers, every wave
executes on handles, and each wave's proposed-usage OUTPUT handle chains
into the next wave's `used0`, so steady-state scheduling never
materializes node state on the host.  This module is the contract
between the wave pipeline (core/wavepipe.py) and the kernels:

  - `DeviceExecutor` dispatches and collects a multi-eval wave through
    `PlacementEngine.dispatch_batch`, whose chained launches ride the
    `donate_argnums` jit variants (select.place_multi_chained) — XLA
    reuses the dead chain buffer in place.  It hands out a wave's chain
    state and RETAINS the final wave's proposed-usage handle across
    worker passes, so the next dequeued batch starts device-resident
    instead of re-syncing `used0` from the packer through the host.
  - `SubmissionFrontEnd` serializes the process worker plane's
    submissions into one shared executor (core/workerpool.py).

Safety of the retained chain: proposed usage is a SUPERSET of what the
chain's own plans commit, so a chained wave can under-pack but never
oversubscribe — and any write the chain cannot see demotes the
applier's fenced fast path to the full fit re-check (plan_apply), whose
refutes feed the pipeline's node mask.  The executor additionally
INVALIDATES the retained chain (dropping back to a packer-synced
re-upload, counted in `nomad.executor.invalidations`) on every
state-store write that changes node state the chain cannot observe:

  - node writes (register / delete / status / drain / eligibility),
  - snapshot restore,
  - capacity-freeing alloc writes (terminal transitions),
  - a committed plan from OUTSIDE the chain (solo/system/foreign
    worker plans — wired by the plan applier via `note_plan_commit`).

Telemetry (core/telemetry.py, exported via /v1/metrics):
  nomad.executor.uploads / upload_bytes   host->device node-state syncs
  nomad.executor.upload_bytes_by_cause    the same bytes split by cause
                                          (initial-upload / dirty-shard-
                                          patch / invalidation-replay)
  nomad.executor.d2h_bytes / d2h_s        device->host result fetches
  nomad.executor.hbm_resident_bytes       retained-handle HBM estimate
  nomad.executor.hbm_high_watermark_bytes   ... and its high watermark
  nomad.executor.resident_waves           launches that chained handles
  nomad.executor.invalidations            retained chains dropped
  nomad.executor.h2d_s                    upload latency histogram
"""

from __future__ import annotations

import threading
import time
from typing import Sequence

from nomad_tpu.core.flightrec import FLIGHT
from nomad_tpu.core.timeline import TIMELINE
from nomad_tpu.core.telemetry import REGISTRY


class DeviceExecutor:
    """The device-execution seam between the wave pipeline and the
    kernels.  One instance per Server, shared by its workers — each
    retained chain lives in a per-CLIENT slot CLAIMED atomically
    (claim_chain pops; in-process workers share the default "" slot),
    so two workers can never chain concurrently on the same
    donated/retained buffer under one chain id (which would exempt each
    other from the applier's per-node fence).

    Node tensors are device-resident in the engine's version-keyed
    caches; the executor meters every host->device sync and
    device->host fetch the engine performs."""

    name = "jax"

    def __init__(self, engine) -> None:
        self.engine = engine
        # True in every deployment.  False, which only tests set, is
        # the PARITY REFERENCE (the pattern of generic.PORT_BATCHED):
        # every wave re-syncs `used0` from the packer through the host,
        # and tests/test_wavepipe.py TestExecutorResidentParity and
        # tests/test_executor.py hold the resident chain to it
        self.chain_enabled = True
        # meter the engine's host->device node-state syncs
        # (_node_arrays full uploads + _used_device delta replays) and
        # its device->host result fetches
        engine.h2d_observer = self._observe_h2d
        engine.d2h_observer = self._observe_d2h
        self._lock = threading.Lock()
        # client -> (batch_id, seq0, (used, node_version, npad),
        # masked_nodes).  One slot per chain CLIENT: the in-process
        # worker plane uses the default "" slot (single slot, exactly
        # the pre-pool behavior); the multi-process pool
        # (core/workerpool) keys a slot per worker process so each
        # child's retained chain survives other children's waves while
        # foreign plan commits still drop every slot they invalidate.
        self._chains: dict = {}
        self.stats = {"dispatches": 0, "resident_waves": 0,
                      "invalidations": 0, "uploads": 0, "upload_bytes": 0,
                      # mesh deployments: per-launch cross-shard
                      # collective payload (engine._note_collective) —
                      # 0 forever on a single device
                      "collective_bytes": 0,
                      # device->host result fetches (the d2h twin)
                      "d2h_fetches": 0, "d2h_bytes": 0,
                      # HBM residency estimate from retained/donated
                      # handle sizes, plus its high watermark
                      "hbm_resident_bytes": 0,
                      "hbm_high_watermark_bytes": 0}
        # upload_bytes split by CAUSE (initial-upload / dirty-shard-patch
        # / invalidation-replay) — kept OUT of `stats` so existing
        # numeric delta readers (bench, perfcheck) stay shape-stable;
        # `upload_bytes` above remains the sum for continuity
        self.upload_bytes_by_cause: dict = {}

    # ------------------------------------------------------------ waves

    def dispatch_batch(self, snapshot, items: Sequence, seed=0,
                       used0_dev=None, masked_node_ids=None):
        if not self.chain_enabled:
            used0_dev = None
        pending = self.engine.dispatch_batch(
            snapshot, items, seed=seed, used0_dev=used0_dev,
            masked_node_ids=masked_node_ids)
        self._note_dispatch(pending, used0_dev is not None)
        return pending

    def collect_batch(self, pending):
        return self.engine.collect_batch(pending)

    def chain_state(self, pending):
        """The (usage, node version, padded n) triple a successor wave
        chains on, or None when `pending` cannot seed a chain; a wave
        with static-port state adds it as a fourth (ops/engine.py
        _lower_ports: the holders it left, for the successor to start
        from)."""
        if not isinstance(pending, dict):
            return None
        chain = (pending["used"], pending["node_version"], pending["npad"])
        if pending.get("ports") is not None:
            chain += (pending["ports"],)
        return chain

    def _note_dispatch(self, pending, wanted_chain: bool) -> None:
        if not isinstance(pending, dict):
            return
        chained = bool(pending.get("chained"))
        coll = int(pending.get("collective_bytes") or 0)
        with self._lock:
            self.stats["dispatches"] += 1
            self.stats["collective_bytes"] += coll
            if chained:
                self.stats["resident_waves"] += 1
        if chained:
            REGISTRY.inc("nomad.executor.resident_waves")
        elif wanted_chain:
            # the engine rejected the handed-in chain (node-table
            # rebuild remapped rows): that buffer is dead
            self._count_invalidation("stale-node-table")

    # --------------------------------------------- retained chain slot

    def retain_chain(self, batch_id: str, seq0: int, used_triple,
                     masked=None, client: str = "") -> None:
        """Park a finished wave's proposed-usage chain for the NEXT
        dequeued batch (core/worker.py calls this when a fully-coupled
        batch ends with no prefetch to hand the chain to)."""
        if not self.chain_enabled or used_triple is None or not batch_id:
            return
        with self._lock:
            self._chains[client] = (
                batch_id, seq0, used_triple, frozenset(masked or ()))

    def claim_chain(self, client: str = ""):
        """Pop the client's retained chain (single consumer per slot —
        see class doc).  Returns (batch_id, seq0, used_triple,
        masked_nodes) or None."""
        if not self.chain_enabled:
            return None
        with self._lock:
            return self._chains.pop(client, None)

    def invalidate(self, reason: str = "explicit") -> None:
        """Drop every retained chain: the next wave of each client
        re-syncs node state from the packer (re-upload counted via
        uploads/upload_bytes).  The triggers (node writes, restore,
        capacity-freeing allocs) blind ALL chains equally, so there is
        no per-client variant."""
        with self._lock:
            dropped = len(self._chains)
            self._chains.clear()
        for _ in range(dropped):
            self._count_invalidation(reason)

    def drop_client(self, client: str) -> None:
        """Forget one client's slot (pool worker exited/crashed)."""
        with self._lock:
            c = self._chains.pop(client, None)
        if c is not None:
            self._count_invalidation("client-drop")

    def _count_invalidation(self, reason: str) -> None:
        with self._lock:
            self.stats["invalidations"] += 1
        REGISTRY.inc("nomad.executor.invalidations", reason=reason)
        # the flight ring's event lane: an invalidation STORM (every wave
        # re-uploading node state) is an SLO rule, and the dump bundle
        # should show which writes caused it
        FLIGHT.record_event("executor.invalidation", reason=reason)
        # ...and the retrospective timeline's (volatile) annotation lane,
        # so `nomad report` can line storms up against breaches
        TIMELINE.annotate("executor.invalidation", reason=reason)

    # ------------------------------------------------- store coupling

    def note_plan_commit(self, origin: str) -> None:
        """The plan applier committed a plan from `origin` (chain id or
        eval id).  A foreign plan's usage is invisible to every retained
        chain EXCEPT the one that proposed it — drop the others so
        their next wave re-syncs."""
        with self._lock:
            kept = {k: c for k, c in self._chains.items()
                    if c[0] == origin}
            dropped = len(self._chains) - len(kept)
            self._chains = kept
        for _ in range(dropped):
            self._count_invalidation("foreign-plan")

    def attach_store(self, store) -> None:
        """Subscribe to state-store events that change node state the
        retained chain cannot observe (node writes, snapshot restore,
        capacity-freeing terminal allocs)."""

        def on_event(topic: str, index: int, payload) -> None:
            if topic == "Node":
                self.invalidate("node-write")
            elif topic == "Restore":
                self.invalidate("restore")
            elif topic == "Allocations":
                # placements the chain proposed are non-terminal; a
                # terminal transition FREES capacity the chain still
                # counts as used — it must re-sync or under-pack forever
                # (a blocked eval would never see the freed node)
                try:
                    freed = any(a.terminal_status() for a in payload)
                except TypeError:
                    freed = True
                if freed:
                    self.invalidate("capacity-freed")

        store.subscribe(on_event)

    # ----------------------------------------------------- telemetry

    def _observe_h2d(self, nbytes: int, seconds: float,
                     cause: str = "initial-upload") -> None:
        with self._lock:
            self.stats["uploads"] += 1
            self.stats["upload_bytes"] += int(nbytes)
            self.upload_bytes_by_cause[cause] = \
                self.upload_bytes_by_cause.get(cause, 0) + int(nbytes)
            self._update_hbm_locked()
        REGISTRY.inc("nomad.executor.uploads")
        REGISTRY.inc("nomad.executor.upload_bytes", int(nbytes))
        # the by-cause twin rides a SEPARATE counter name: labeling the
        # original would double `counter_sum("...upload_bytes")` readers
        REGISTRY.inc("nomad.executor.upload_bytes_by_cause",
                     int(nbytes), cause=cause)
        REGISTRY.observe("nomad.executor.h2d_s", seconds)

    def _observe_d2h(self, nbytes: int, seconds: float,
                     cause: str = "result-fetch") -> None:
        with self._lock:
            self.stats["d2h_fetches"] += 1
            self.stats["d2h_bytes"] += int(nbytes)
        REGISTRY.inc("nomad.executor.d2h_bytes", int(nbytes),
                     cause=cause)
        REGISTRY.observe("nomad.executor.d2h_s", seconds)

    def _update_hbm_locked(self) -> None:
        """Refresh the HBM-residency estimate (self._lock held): the
        engine's retained device caches plus the parked chain handle.
        An estimate from handle sizes, not an allocator query — the
        high watermark is the capacity-planning number."""
        total = 0
        eng = self.engine
        if eng is not None and hasattr(eng, "device_resident_bytes"):
            total += eng.device_resident_bytes()
        for c in self._chains.values():
            total += int(getattr(c[2][0], "nbytes", 0))
        self.stats["hbm_resident_bytes"] = total
        if total > self.stats["hbm_high_watermark_bytes"]:
            self.stats["hbm_high_watermark_bytes"] = total
        REGISTRY.set_gauge("nomad.executor.hbm_resident_bytes", total)
        REGISTRY.set_gauge("nomad.executor.hbm_high_watermark_bytes",
                           self.stats["hbm_high_watermark_bytes"])

    def ledger(self) -> dict:
        """The device ledger (capture bundles, /v1/operator/debug):
        compile-cache traffic, HBM residency + watermark, and h2d/d2h
        transfer attribution by cause."""
        from nomad_tpu.core.profiling import COMPILE
        with self._lock:
            self._update_hbm_locked()
            stats = dict(self.stats)
            by_cause = dict(self.upload_bytes_by_cause)
        return {
            "backend": self.name,
            "compile": COMPILE.snapshot(),
            "hbm_resident_bytes": stats["hbm_resident_bytes"],
            "hbm_high_watermark_bytes":
                stats["hbm_high_watermark_bytes"],
            "uploads": stats["uploads"],
            "upload_bytes": stats["upload_bytes"],
            "upload_bytes_by_cause": by_cause,
            "d2h_fetches": stats["d2h_fetches"],
            "d2h_bytes": stats["d2h_bytes"],
            "invalidations": stats["invalidations"],
            "resident_waves": stats["resident_waves"],
            "dispatches": stats["dispatches"],
        }

    def close(self) -> None:
        self.invalidate("close")


class SubmissionFrontEnd:
    """Thin submission queue in front of a shared DeviceExecutor.

    The multi-process worker pool (core/workerpool.py) funnels every
    child's device work through the parent-owned executor; this
    front-end serializes those submissions under ONE lock so the
    resident-buffer chain and the engine's version-keyed device caches
    keep their single-owner invariants — callers queue, they never
    interleave inside a dispatch.  Contended acquisition is metered as
    the `queue-wait` profiling bucket (the pool's analogue of the
    thread plane's gil-wait) and accumulated in `stats["queue_wait_s"]`
    for the bench JSON."""

    def __init__(self, executor: DeviceExecutor) -> None:
        self.executor = executor
        self._lock = threading.Lock()
        self.stats = {"submits": 0, "queue_wait_s": 0.0,
                      "queue_waits": 0}

    def _acquire(self) -> None:
        if self._lock.acquire(blocking=False):
            return
        from nomad_tpu.core.profiling import activity
        t0 = time.perf_counter()
        with activity("queue-wait"):
            self._lock.acquire()
        waited = time.perf_counter() - t0
        self.stats["queue_wait_s"] += waited
        self.stats["queue_waits"] += 1

    def dispatch_batch(self, snapshot, items, seed=0, used0_dev=None,
                       masked_node_ids=None):
        self._acquire()
        try:
            self.stats["submits"] += 1
            return self.executor.dispatch_batch(
                snapshot, items, seed=seed, used0_dev=used0_dev,
                masked_node_ids=masked_node_ids)
        finally:
            self._lock.release()

    def collect_batch(self, pending):
        self._acquire()
        try:
            return self.executor.collect_batch(pending)
        finally:
            self._lock.release()

    def chain_state(self, pending):
        return self.executor.chain_state(pending)

    def claim_chain(self, client: str = ""):
        return self.executor.claim_chain(client)

    def retain_chain(self, batch_id: str, seq0: int, used_triple,
                     masked=None, client: str = "") -> None:
        self.executor.retain_chain(batch_id, seq0, used_triple,
                                   masked=masked, client=client)

    def drop_client(self, client: str) -> None:
        self.executor.drop_client(client)
