"""In-memory cluster state store with MVCC snapshots.

Reference semantics: `nomad/state/state_store.go` (go-memdb immutable radix
trees).  Re-designed for this framework: plain dict tables with strict
copy-on-write discipline — write paths copy incoming objects on insert (the
embedded `Allocation.job` pointer is shared; jobs are immutable by
discipline once stored), objects already in tables are never mutated, and
every write bumps a monotonically increasing index (the Raft-log-index
analog).  `snapshot()` is O(#tables + touched buckets), returning a
`StateSnapshot` that is immutable by construction and is exactly what
schedulers read (the `scheduler.State` seam, SURVEY.md §2).

Device tensors (nomad_tpu.pack) are a cache of a snapshot at some index and
are rebuildable from here at any time (checkpoint/resume, SURVEY.md §6.4).
"""

from __future__ import annotations

import itertools
import operator
import sys
import threading
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from nomad_tpu.state.live_ledger import LiveLedger
from nomad_tpu.structs import (
    ACLAuthMethod,
    ACLBindingRule,
    ACLPolicy,
    ACLToken,
    Allocation,
    CSIVolume,
    Deployment,
    DesiredTransition,
    Evaluation,
    VariableItem,
    Job,
    JOB_STATUS_DEAD,
    JOB_STATUS_PENDING,
    Namespace,
    Node,
    NodePool,
    Plan,
    PlanResult,
    SchedulerConfiguration,
    ServiceRegistration,
    compute_class,
)


_node_status = operator.attrgetter("status")


def _all_up(nodes: Dict[str, Node], node_ids) -> bool:
    """Every one of `node_ids` is in the node table and not `down`: one
    C-level pass, no call a node (a block's node table has 49,000)."""
    try:
        return "down" not in map(_node_status,
                                 map(nodes.__getitem__, node_ids))
    except KeyError:
        return False


def _entry_cost(entry: tuple) -> int:
    """Approximate retained cost of one journal entry: the 3-tuple,
    its key payload, and deque-slot overhead.  getsizeof is C-level
    (~100ns), cheap enough for the append hot path."""
    return 64 + sys.getsizeof(entry) + sys.getsizeof(entry[2])


class StateStore:
    """All cluster state.  Thread-safe; single writer at a time."""

    def __init__(self) -> None:
        import uuid as _uuid
        self.store_id = str(_uuid.uuid4())   # distinguishes stores for caches
        # injected timebase for eval create/modify stamps; Server
        # rebinds this to its chaos Clock so virtual-time soaks stamp
        # virtual (replayable) times instead of wall times.  Imported
        # lazily: nomad_tpu.chaos's package init reaches back into
        # nomad_tpu.state via transport -> core -> plan_apply
        from nomad_tpu.chaos.clock import SystemClock
        self.clock = SystemClock()
        self._lock = threading.RLock()
        self._index_cv = threading.Condition(self._lock)
        self._index = 0
        # primary tables: id -> object
        self._nodes: Dict[str, Node] = {}
        self._jobs: Dict[Tuple[str, str], Job] = {}          # (ns, id)
        self._job_versions: Dict[Tuple[str, str], Dict[int, Job]] = {}
        self._evals: Dict[str, Evaluation] = {}
        self._allocs: Dict[str, Allocation] = {}
        self._deployments: Dict[str, Deployment] = {}
        self._namespaces: Dict[str, Namespace] = {"default": Namespace()}
        self._node_pools: Dict[str, NodePool] = {
            "default": NodePool("default"), "all": NodePool("all")}
        self._csi_volumes: Dict[Tuple[str, str], CSIVolume] = {}
        self._acl_policies: Dict[str, ACLPolicy] = {}
        self._acl_tokens: Dict[str, ACLToken] = {}       # accessor -> token
        self._acl_by_secret: Dict[str, ACLToken] = {}
        self._acl_auth_methods: Dict[str, ACLAuthMethod] = {}
        self._acl_binding_rules: Dict[str, ACLBindingRule] = {}
        self._variables: Dict[Tuple[str, str], VariableItem] = {}
        self._services: Dict[str, ServiceRegistration] = {}
        self._scheduler_config = SchedulerConfiguration()
        # cluster-wide workload-identity signing secret (reference: the
        # keyring backing workload identities); set once by the leader,
        # replicated + snapshotted like all state
        self._identity_secret = ""
        # secondary indexes (bucket dicts are copy-on-write)
        self._allocs_by_node: Dict[str, Dict[str, Allocation]] = {}
        self._allocs_by_job: Dict[Tuple[str, str], Dict[str, Allocation]] = {}
        self._evals_by_job: Dict[Tuple[str, str], Dict[str, Evaluation]] = {}
        # columnar alloc blocks (structs.AllocBlock): bulk placements kept
        # as picks + template, never materialized on the commit path.
        # Registries are COW-published dicts (every write publishes fresh
        # dicts) so snapshots capture consistent references; a write to
        # any MEMBER alloc (client update, same-id stop) first
        # materializes the whole block into the normal tables — after
        # which it behaves exactly like per-alloc state.  Blocks are
        # immutable once inserted, like every stored object.
        self._alloc_blocks: Dict[str, object] = {}
        self._blocks_by_job: Dict[Tuple[str, str], tuple] = {}
        self._blocks_by_node: Dict[str, tuple] = {}
        # amortized COW for the alloc tables: snapshot() marks them shared;
        # the NEXT write copies the outer dicts once and then mutates in
        # place until another snapshot.  Without this every plan apply paid
        # an O(cluster) outer-table copy (50k nodes -> milliseconds per
        # plan, the pipeline bottleneck at bench scale).  Bucket dicts are
        # tracked the same way: `_fresh_*` holds buckets copied since the
        # last snapshot (private to the head, safe to mutate in place).
        self._alloc_tables_shared = False
        self._block_tables_shared = False
        self._eval_tables_shared = False
        self._fresh_node_buckets: set = set()
        self._fresh_job_buckets: set = set()
        self._fresh_eval_buckets: set = set()
        # volumes whose claim dicts were copied since the last snapshot
        # (private to the head — claims mutate them in place; a busy
        # volume otherwise paid a growing dict copy per PLAN)
        self._fresh_claim_vols: set = set()
        # monotonic counter of writes that can change placement validity
        # (alloc inserts, node upserts/status, CSI volume changes) — the
        # plan applier's coupled-batch fast path compares it to prove
        # nothing placement-relevant changed since a plan's snapshot
        self._placement_seq = 0
        # per-node fence: node id -> (placement_seq of last FIT-relevant
        # write, origin chain id or None).  The applier skips a fenced
        # plan's AllocsFit re-check per NODE: a node last touched before
        # the plan's snapshot — or by the plan's own chain, whose plans
        # were co-computed on device against shared proposed capacity —
        # cannot invalidate the kernel's capacity verdict.  Disjoint
        # workers (zone-partitioned batches) therefore never demote each
        # other to full checks, unlike a global fence.
        self._node_place_seq: Dict[str, Tuple[int, Optional[str]]] = {}
        # the placement writes as RUNS by origin: every write after
        # _run_floor carries _run_origin (a chain id; None is foreign to
        # every chain).  With _placement_seq itself it answers a fence
        # read in O(1) when nothing, or nothing but the reader's own
        # chain, has written since the plan's snapshot
        # (nodes_unchanged_since); anything else falls to the walk
        self._run_floor = 0
        self._run_origin: Optional[str] = None
        # after a restore the per-node history is gone: every node is
        # treated as touched at the floor, so pre-restore fences full-check
        self._node_seq_floor = 0
        # counter of CSI volume mutations (upsert/delete/claim/release):
        # the applier captures it while its guarded claim checks run and
        # the commit refuses (-1) if it moved — closing the window where
        # a volume write lands between evaluate and commit that the
        # per-NODE fence cannot see
        self._volume_seq = 0
        # bounded ring of per-eval decision records (core/explain.py):
        # newest-wins by eval id, oldest evicted past the cap.
        # Observability only — node-local, never raft-replicated or
        # snapshotted (the failure rollups that must survive restarts
        # ride the Evaluation itself)
        from collections import OrderedDict
        self._eval_decisions: "OrderedDict[str, object]" = OrderedDict()
        self._eval_decision_cap = 512
        # incremental live-allocation ledger behind quality_summary()
        # (state/live_ledger.py): per-node sums over NON-TERMINAL allocs
        # as columns.  The WRITE path pays four int adds an alloc or one
        # list append a block; the fold and the zone/fill aggregates
        # reconcile LAZILY at quality_summary() time over the rows
        # dirtied since the last read, in numpy — a 1s scrape or
        # per-commit refresh never walks the cluster (50k in-use nodes
        # measured ~200ms per full walk; the soak budget is 2% —
        # PERF.md §11).
        self._live = LiveLedger()
        # listeners for state-change events (event broker seam, SURVEY §6.5)
        self._listeners: List[Callable[[str, int, object], None]] = []
        # dirty-key journal for worker-plane replicas (core/workerpool):
        # (index, section, key) markers appended at the _emit chokepoint.
        # export_since() resolves the keys against the LIVE tables, so a
        # replica pulls incremental upserts/tombstones keyed by modify
        # index; whenever the bounded journal cannot cover the requested
        # range it falls back to a full snapshot_save document.  The
        # floor is the newest index the journal can no longer vouch for.
        from collections import deque
        self._journal: "deque" = deque()
        self._journal_cap = 8192
        self._journal_floor = 0
        # journal footprint + coalescing ledger (ISSUE 19): byte
        # estimate maintained incrementally at append/evict, merge-by-
        # key compactions metered so the MEMLEDGER scrape can publish
        # nomad.journal.* without touching telemetry under this lock
        self._journal_bytes = 0
        self._journal_appends = 0
        self._journal_compacted_at = 0     # append count at last compact
        self._journal_compact_backoff = 0  # appends to wait before retry
        self._journal_evictions = 0        # entries lost to the floor
        self._journal_compactions = 0
        self._journal_reclaimed_bytes = 0
        self._journal_floor_fallbacks = 0  # full-snapshot exports served
        # sampled per-table row-cost cache for mem_stats (one table
        # re-sampled per call, round-robin)
        self._mem_rr = 0
        self._mem_row_cost: Dict[str, float] = {}

    # ------------------------------------------------------------- indexes

    def latest_index(self) -> int:
        with self._lock:
            return self._index

    def placement_seq(self) -> int:
        """Counter of placement-relevant writes (see __init__)."""
        with self._lock:
            return self._placement_seq

    def counts(self) -> Dict[str, int]:
        """Cheap table sizes for the metrics scrape path: a 1s
        Prometheus scrape must not pay snapshot (COW-marking) cost just
        to count nodes and jobs."""
        with self._lock:
            return {"nodes": len(self._nodes), "jobs": len(self._jobs),
                    "evals": len(self._evals)}

    # ----------------------------------------------- decisions / quality

    def record_eval_decision(self, decision) -> None:
        """Retain an EvalDecision in the bounded ring (newest wins)."""
        with self._lock:
            ring = self._eval_decisions
            ring.pop(decision.eval_id, None)
            ring[decision.eval_id] = decision
            while len(ring) > self._eval_decision_cap:
                ring.popitem(last=False)

    def eval_decision(self, eval_id: str):
        with self._lock:
            return self._eval_decisions.get(eval_id)

    def eval_decisions(self, namespace: Optional[str] = None,
                       job_id: Optional[str] = None) -> List:
        """Recent decision records, oldest first, optionally filtered."""
        with self._lock:
            out = list(self._eval_decisions.values())
        if namespace is not None:
            out = [d for d in out if d.namespace == namespace]
        if job_id is not None:
            out = [d for d in out if d.job_id == job_id]
        return out

    def quality_summary(self) -> Dict[str, float]:
        """Scheduling-quality snapshot from the incremental aggregates
        (the runtime counterpart of bench.py's `quality_nodes_used_tpu`
        and `quality_zone_balance_max_over_min`): nodes-in-use, per-zone
        alloc balance, and mean bin-pack fill per dimension over in-use
        nodes.  O(dirty nodes + zones) — cheap by construction; safe
        per commit and per scrape at any cluster size."""
        with self._lock:
            return self._live.summary()

    def _bump(self) -> int:
        self._index += 1
        self._index_cv.notify_all()
        return self._index

    def _bump_placement(self, origin: Optional[str] = None) -> int:
        """_bump for writes that can change placement validity (nodes,
        allocs, CSI volumes) — advances the applier's fast-path fence.
        `origin`: the chain whose plan this write commits."""
        if origin is None or origin != self._run_origin:
            # floor first: a lock-free reader between the two stores
            # then sees a run too short, and walks
            self._run_floor = self._placement_seq
            self._run_origin = origin
        self._placement_seq += 1
        return self._bump()

    def volume_seq(self) -> int:
        """Counter of CSI volume mutations (see __init__)."""
        with self._lock:
            return self._volume_seq

    def _touch_node(self, node_id: str, origin: Optional[str] = None
                    ) -> None:
        """Record a fit-relevant write to `node_id` (see _node_place_seq).
        Callers hold the lock and have already bumped placement_seq."""
        self._node_place_seq[node_id] = (self._placement_seq, origin)

    def nodes_unchanged_since(self, node_ids, seq0: int,
                              chain_id: Optional[str] = None,
                              own_chain_ok: bool = True,
                              tally=None) -> bool:
        """True when every node in `node_ids` had no fit-relevant write
        after placement_seq `seq0` — writes by `chain_id` itself
        tolerated when `own_chain_ok` (chain plans are co-computed).
        Answered without looking at a node when NO placement write
        landed since `seq0`, or none but the chain's own run; else by
        the walk.  `tally` (the applier's StatCounters) is told which,
        in nodes: `fence_fast` / `fence_walked`.
        Point reads; values monotone, so a stale read can only cause a
        spurious full check, never a wrong skip — and the commit re-checks
        under the lock via upsert_plan_results' expected_nodes."""
        nps = self._node_place_seq
        floor = self._node_seq_floor
        if floor > seq0:
            return False
        fast = self._placement_seq == seq0 or (
            own_chain_ok and chain_id is not None
            and chain_id == self._run_origin and self._run_floor <= seq0)
        if tally is not None:
            tally.inc("fence_fast" if fast else "fence_walked",
                      len(node_ids))
        if fast:
            return True
        for nid in node_ids:
            e = nps.get(nid)
            if e is None or e[0] <= seq0:
                continue
            if own_chain_ok and chain_id is not None and e[1] == chain_id:
                continue
            return False
        return True

    def wait_for_index(self, index: int, timeout: float = 5.0) -> bool:
        """Block until the store has applied at least `index` (the eval
        worker's waitForIndex, reference: nomad/worker.go)."""
        with self._index_cv:
            return self._index_cv.wait_for(lambda: self._index >= index,
                                           timeout=timeout)

    def subscribe(self, fn: Callable[[str, int, object], None]) -> None:
        """fn(topic, index, payload) on every commit (event stream seam).
        Listeners fire after tables are assigned, so re-entrant reads see the
        committed data; a raising listener cannot abort a commit."""
        with self._lock:
            self._listeners.append(fn)

    def _emit_locked(self, topic: str, index: int, payload: object) -> None:
        self._journal_note_locked(topic, index, payload)
        for fn in list(self._listeners):
            try:
                fn(topic, index, payload)
            except Exception:  # noqa: BLE001 - listener isolation
                pass

    # ------------------------------------------- replica export (deltas)

    def _journal_note_locked(self, topic: str, index: int, payload) -> None:
        """Record dirty keys for export_since (lock held — _emit fires
        from write paths).  Payload fidelity varies by topic (object on
        upsert, bare key on delete); the journal stores only (section,
        key) and export resolves the CURRENT object — missing means a
        tombstone, so deletes need no separate bookkeeping."""
        if topic == "Node":
            entries = [("nodes", payload if isinstance(payload, str)
                        else payload.id)]
        elif topic == "Job":
            entries = [("jobs", tuple(payload) if isinstance(
                payload, tuple) else payload.ns_id())]
        elif topic == "Evaluation":
            entries = [("evals", payload.id)]
        elif topic == "Allocations":
            entries = [("allocs", a.id) for a in payload]
        elif topic == "Deployment":
            entries = [("deployments", payload.id)]
        elif topic == "AllocBlock":
            entries = [("alloc_blocks", payload.id)]
        elif topic == "BlockMaterialized":
            # the block's rows moved into the per-alloc tables without
            # an "Allocations" event: carry the member ids so the delta
            # ships the materialized rows along with the tombstone
            entries = [("block_gone", (payload.id, tuple(payload.ids)))]
        elif topic == "CSIVolume":
            entries = [("csi_volumes", (payload.namespace, payload.id))]
        elif topic == "Restore":
            self._journal.clear()
            self._journal_floor = index
            self._journal_bytes = 0
            return
        else:
            return                      # PlanResult etc: no replica table
        j = self._journal
        for e in entries:
            entry = (index,) + e
            j.append(entry)
            self._journal_bytes += _entry_cost(entry)
        self._journal_appends += len(entries)
        if len(j) > self._journal_cap:
            # coalesce superseded (section, key) deltas before paying
            # retention: newest-wins dedupe preserves export_since for
            # EVERY since value (export resolves keys against the LIVE
            # tables, so intermediate versions were never shipped) and
            # never raises the floor.  Adaptive backoff: while
            # compaction pays (churny duplicate-heavy journals) it runs
            # on every overflow and the floor never moves; once a
            # compaction reclaims almost nothing (unique-key growth) it
            # backs off cap/8 appends so the degenerate case costs O(1)
            # eviction per append, not O(n) re-compaction.
            if (self._journal_appends - self._journal_compacted_at
                    >= self._journal_compact_backoff):
                reclaimed = self._compact_journal_locked()
                self._journal_compacted_at = self._journal_appends
                self._journal_compact_backoff = (
                    0 if reclaimed >= max(self._journal_cap // 8, 1)
                    else max(self._journal_cap // 8, 64))
            while len(j) > self._journal_cap:
                self._journal_floor = j[0][0]
                old = j.popleft()
                self._journal_bytes -= _entry_cost(old)
                self._journal_evictions += 1

    def _compact_journal_locked(self) -> int:
        """Merge-by-key journal coalescing: keep only the NEWEST entry
        per (section, key).  Exactly equivalence-preserving — for any
        `since`, every key the dropped duplicates would have dirtied is
        still dirtied by its surviving (newer) entry, and export
        resolves the same live object either way (tombstones and
        block_gone carries included; the property test in
        tests/test_memledger.py proves replica bit-identity).  The
        floor never moves, so compaction cannot cause a full-snapshot
        fallback.  Returns entries reclaimed."""
        j = self._journal
        if len(j) < 2:
            return 0
        seen: set = set()
        kept: List[tuple] = []
        for entry in reversed(j):
            k = (entry[1], entry[2])
            if k in seen:
                continue
            seen.add(k)
            kept.append(entry)
        reclaimed = len(j) - len(kept)
        if reclaimed == 0:
            return 0
        kept.reverse()
        before_bytes = self._journal_bytes
        j.clear()
        j.extend(kept)
        self._journal_bytes = sum(_entry_cost(e) for e in kept)
        self._journal_compactions += 1
        self._journal_reclaimed_bytes += max(
            before_bytes - self._journal_bytes, 0)
        return reclaimed

    def compact_journal(self) -> int:
        """On-demand compaction (tests, operator tooling)."""
        with self._lock:
            return self._compact_journal_locked()

    def journal_stats(self) -> Dict:
        """Ledger sizer for the export journal (core/memledger): the
        retained window, its byte estimate, the floor, and the
        coalescing/fallback meters.  The `gauges` sub-dict is published
        verbatim by the MEMLEDGER scrape — no telemetry work happens
        under the store lock."""
        with self._lock:
            return {
                "entries": len(self._journal),
                "bytes": self._journal_bytes,
                "cap": self._journal_cap,
                "floor": self._journal_floor,
                "evictions": self._journal_evictions,
                "compactions": self._journal_compactions,
                "bytes_reclaimed": self._journal_reclaimed_bytes,
                "floor_fallbacks": self._journal_floor_fallbacks,
                "gauges": {
                    "nomad.journal.entries": len(self._journal),
                    "nomad.journal.bytes": self._journal_bytes,
                    "nomad.journal.compactions":
                        self._journal_compactions,
                    "nomad.journal.bytes_reclaimed":
                        self._journal_reclaimed_bytes,
                    "nomad.journal.floor_fallbacks":
                        self._journal_floor_fallbacks,
                },
            }

    def mem_stats(self) -> Dict:
        """Ledger sizer for the live tables: row counts plus a SAMPLED
        byte estimate.  Cost discipline (PERF.md §21): each call
        deep-sizes a few rows of ONE table (round-robin) and caches the
        per-table mean row cost; the other tables reuse their cached
        means, so a scrape is O(sample) — never a table walk."""
        from nomad_tpu.core.memledger import approx_sizeof
        with self._lock:
            tables = {"nodes": self._nodes, "jobs": self._jobs,
                      "evals": self._evals, "allocs": self._allocs,
                      "deployments": self._deployments,
                      "alloc_blocks": self._alloc_blocks,
                      "csi_volumes": self._csi_volumes}
            table_rows = {k: len(t) for k, t in tables.items()}
            names = sorted(tables)
            pick = names[self._mem_rr % len(names)]
            self._mem_rr += 1
            rows = list(itertools.islice(tables[pick].values(), 3))
        # deep-size OUTSIDE the store lock: rows are immutable by COW
        # discipline, and the estimator must never stall writers
        if rows:
            per = sum(approx_sizeof(r) for r in rows) / len(rows)
            self._mem_row_cost[pick] = per
        total = 0
        for k, n in table_rows.items():
            total += int(n * self._mem_row_cost.get(k, 512.0))
        return {"bytes": total,
                "entries": sum(table_rows.values()),
                "cap": 0, "evictions": 0,
                "tables": table_rows}

    def export_since(self, since_index: int) -> Dict:
        """Wire-shippable state export for scheduler-worker replicas
        (core/workerpool).  Returns {"kind": "empty"|"delta"|"full", ...}
        with the head index + placement fence; a delta carries current
        objects for every key dirtied after `since_index` (newest state
        wins — intermediate versions are not replayed) plus tombstones
        for keys that no longer resolve.  The config-plane tables
        (scheduler config, namespaces, node pools) are tiny and have no
        journal topic, so every delta ships them wholesale."""
        with self._lock:
            latest = self._index
            fence = self._placement_seq
            if since_index >= latest:
                return {"kind": "empty", "index": latest, "fence": fence}
            if since_index < self._journal_floor:
                # the thrash the journal compaction exists to prevent:
                # counted here, published as nomad.journal.floor_fallbacks
                # by the MEMLEDGER scrape, gated == 0 by perfcheck
                self._journal_floor_fallbacks += 1
                return {"kind": "full", "doc": self.snapshot_save(),
                        "index": self._index, "fence": self._placement_seq}
            ups: Dict[str, list] = {}
            dels: List[tuple] = []
            seen: set = set()

            def resolve(section, key, table):
                if (section, key) in seen:
                    return
                seen.add((section, key))
                obj = table.get(key)
                if obj is None:
                    dels.append((section, key))
                else:
                    ups.setdefault(section, []).append(obj)

            tables = {"nodes": self._nodes, "jobs": self._jobs,
                      "evals": self._evals, "allocs": self._allocs,
                      "deployments": self._deployments,
                      "alloc_blocks": self._alloc_blocks,
                      "csi_volumes": self._csi_volumes}
            for idx, section, key in self._journal:
                if idx <= since_index:
                    continue
                if section == "block_gone":
                    bid, member_ids = key
                    if bid not in self._alloc_blocks:
                        if ("alloc_blocks", bid) not in seen:
                            seen.add(("alloc_blocks", bid))
                            dels.append(("alloc_blocks", bid))
                        for aid in member_ids:
                            resolve("allocs", aid, self._allocs)
                    continue
                resolve(section, key, tables[section])
            # embedded job pointers ship once via the jobs section; the
            # replica re-attaches them on apply (snapshot_restore's rule)
            if "allocs" in ups:
                slim = []
                for a in ups["allocs"]:
                    a = a.copy_skip_job()
                    a.job = None
                    slim.append(a)
                ups["allocs"] = slim
            return {"kind": "delta", "index": latest, "fence": fence,
                    "upserts": ups, "deletes": dels,
                    "scheduler_config": self._scheduler_config,
                    "namespaces": list(self._namespaces.values()),
                    "node_pools": list(self._node_pools.values())}

    def apply_export(self, export: Dict) -> None:
        """Apply an export_since document to THIS store (the replica
        side; the parent store never calls this).  Fresh outer dicts are
        published for every touched table so snapshots handed to
        schedulers stay immutable; the index and placement fence are
        set to the parent's EXACT values (plan fences computed on the
        replica must line up with the parent applier's per-node seqs)."""
        kind = export.get("kind")
        if kind == "full":
            self.snapshot_restore(export["doc"])
        elif kind == "delta":
            self._apply_delta(export)
        with self._index_cv:
            if kind == "full":
                # snapshot_restore bumps PAST the doc index (the FSM
                # restore rule); a replica must sit at the parent's exact
                # head or its next pull's `since` skips the parent's next
                # write forever (the dirtied key never re-exports)
                self._index = int(export["index"])
            else:
                self._index = max(int(export["index"]), self._index)
            self._placement_seq = int(export["fence"])
            self._run_floor = self._placement_seq
            self._run_origin = None
            self._index_cv.notify_all()

    def _apply_delta(self, export: Dict) -> None:
        with self._lock:
            ups = export.get("upserts", {})
            if ups.get("nodes"):
                self._nodes = {**self._nodes,
                               **{n.id: n for n in ups["nodes"]}}
            for j in ups.get("jobs", ()):
                self._jobs = {**self._jobs, j.ns_id(): j}
                versions = dict(self._job_versions.get(j.ns_id(), {}))
                versions[j.version] = j
                self._job_versions = {**self._job_versions,
                                      j.ns_id(): versions}
            if ups.get("evals"):
                evals = dict(self._evals)
                by_job = dict(self._evals_by_job)
                for e in ups["evals"]:
                    evals[e.id] = e
                    k = (e.namespace, e.job_id)
                    bucket = dict(by_job.get(k, {}))
                    bucket[e.id] = e
                    by_job[k] = bucket
                self._evals = evals
                self._evals_by_job = by_job
            if ups.get("allocs"):
                table = dict(self._allocs)
                by_node = dict(self._allocs_by_node)
                by_job = dict(self._allocs_by_job)
                for a in ups["allocs"]:
                    a.job = (self._job_versions.get(
                        (a.namespace, a.job_id), {}).get(a.job_version)
                        or self._jobs.get((a.namespace, a.job_id)))
                    prev = table.get(a.id)
                    if (prev is not None and prev.node_id
                            and prev.node_id != a.node_id):
                        b = dict(by_node.get(prev.node_id, {}))
                        b.pop(a.id, None)
                        by_node[prev.node_id] = b
                    table[a.id] = a
                    if a.node_id:
                        b = dict(by_node.get(a.node_id, {}))
                        b[a.id] = a
                        by_node[a.node_id] = b
                    k = (a.namespace, a.job_id)
                    b = dict(by_job.get(k, {}))
                    b[a.id] = a
                    by_job[k] = b
                self._allocs = table
                self._allocs_by_node = by_node
                self._allocs_by_job = by_job
            if ups.get("deployments"):
                self._deployments = {
                    **self._deployments,
                    **{d.id: d for d in ups["deployments"]}}
            if ups.get("csi_volumes"):
                self._csi_volumes = {
                    **self._csi_volumes,
                    **{(v.namespace, v.id): v
                       for v in ups["csi_volumes"]}}
            for b in ups.get("alloc_blocks", ()):
                self._insert_replica_block_locked(b)
            for section, key in export.get("deletes", ()):
                self._delete_replica_key_locked(section, key)
            self._scheduler_config = (export.get("scheduler_config")
                                      or self._scheduler_config)
            if export.get("namespaces"):
                self._namespaces = {n.name: n
                                    for n in export["namespaces"]}
            if export.get("node_pools"):
                self._node_pools = {p.name: p
                                    for p in export["node_pools"]}
            # handed-out snapshots saw only the replaced dicts; fresh
            # copies above mean nothing shared was mutated in place
            self._alloc_tables_shared = False
            self._block_tables_shared = False
            self._eval_tables_shared = False

    def _insert_replica_block_locked(self, b) -> None:
        self._alloc_blocks = {**self._alloc_blocks, b.id: b}
        jkey = (b.template.namespace, b.template.job_id)
        bj = dict(self._blocks_by_job)
        bj[jkey] = tuple(x for x in bj.get(jkey, ())
                         if x.id != b.id) + (b,)
        self._blocks_by_job = bj
        bn = dict(self._blocks_by_node)
        for nid in b.node_table:
            bn[nid] = tuple(x for x in bn.get(nid, ())
                            if x.id != b.id) + (b,)
        self._blocks_by_node = bn

    def _delete_replica_key_locked(self, section: str, key) -> None:
        key = tuple(key) if isinstance(key, list) else key
        if section == "nodes":
            self._nodes = {k: v for k, v in self._nodes.items()
                           if k != key}
        elif section == "jobs":
            self._jobs = {k: v for k, v in self._jobs.items()
                          if k != key}
            self._job_versions = {k: v for k, v
                                  in self._job_versions.items()
                                  if k != key}
        elif section == "evals":
            e = self._evals.get(key)
            self._evals = {k: v for k, v in self._evals.items()
                           if k != key}
            if e is not None:
                k = (e.namespace, e.job_id)
                by_job = dict(self._evals_by_job)
                bucket = dict(by_job.get(k, {}))
                bucket.pop(key, None)
                by_job[k] = bucket
                self._evals_by_job = by_job
        elif section == "allocs":
            a = self._allocs.get(key)
            self._allocs = {k: v for k, v in self._allocs.items()
                            if k != key}
            if a is not None:
                by_node = dict(self._allocs_by_node)
                if a.node_id and a.node_id in by_node:
                    b = dict(by_node[a.node_id])
                    b.pop(key, None)
                    by_node[a.node_id] = b
                    self._allocs_by_node = by_node
                by_job = dict(self._allocs_by_job)
                jk = (a.namespace, a.job_id)
                if jk in by_job:
                    b = dict(by_job[jk])
                    b.pop(key, None)
                    by_job[jk] = b
                    self._allocs_by_job = by_job
        elif section == "alloc_blocks":
            b = self._alloc_blocks.get(key)
            self._alloc_blocks = {k: v for k, v
                                  in self._alloc_blocks.items()
                                  if k != key}
            if b is not None:
                self._blocks_by_job = {
                    k: t for k, t in
                    ((k, tuple(x for x in t if x.id != key))
                     for k, t in self._blocks_by_job.items()) if t}
                self._blocks_by_node = {
                    k: t for k, t in
                    ((k, tuple(x for x in t if x.id != key))
                     for k, t in self._blocks_by_node.items()) if t}
        elif section == "deployments":
            self._deployments = {k: v for k, v
                                 in self._deployments.items()
                                 if k != key}
        elif section == "csi_volumes":
            self._csi_volumes = {k: v for k, v
                                 in self._csi_volumes.items()
                                 if k != key}

    # --------------------------------------------------------------- nodes

    def upsert_node(self, node: Node) -> int:
        with self._lock:
            idx = self._bump_placement()
            prev = self._nodes.get(node.id)
            node = node.copy()
            node.create_index = prev.create_index if prev else idx
            node.modify_index = idx
            # Always recompute: a stale class hash would poison per-class
            # feasibility caching after attribute changes.
            node.computed_class = compute_class(node)
            self._nodes = {**self._nodes, node.id: node}
            self._touch_node(node.id)
            self._live.note_nodes([node])
            self._emit_locked("Node", idx, node)
            return idx

    def upsert_nodes(self, nodes: Iterable[Node]) -> int:
        """Bulk node registration: one index bump and one table publish for
        the whole batch (per-node upsert is O(cluster) per call, which makes
        seeding a 50k-node cluster quadratic)."""
        with self._lock:
            idx = self._bump_placement()
            table = dict(self._nodes)
            inserted = []
            for node in nodes:
                prev = table.get(node.id)
                node = node.copy()
                node.create_index = prev.create_index if prev else idx
                node.modify_index = idx
                node.computed_class = compute_class(node)
                table[node.id] = node
                self._touch_node(node.id)
                inserted.append(node)
            self._nodes = table          # publish before events fire
            self._live.note_nodes(inserted)
            for node in inserted:
                self._emit_locked("Node", idx, node)
            return idx

    def delete_node(self, node_id: str) -> int:
        with self._lock:
            idx = self._bump_placement()
            nodes = dict(self._nodes)
            nodes.pop(node_id, None)
            self._nodes = nodes
            self._touch_node(node_id)
            self._live.forget_node(node_id)
            self._emit_locked("Node", idx, node_id)
            return idx

    def update_node_status(self, node_id: str, status: str) -> int:
        """No-op (returning the current index) when the node is unknown —
        a status update racing node GC must not crash the caller."""
        with self._lock:
            cur = self._nodes.get(node_id)
            if cur is None:
                return self._index
            n = cur.copy()
            n.status = status
            return self.upsert_node(n)

    def update_node_eligibility(self, node_id: str, elig: str) -> int:
        with self._lock:
            cur = self._nodes.get(node_id)
            if cur is None:
                return self._index
            n = cur.copy()
            n.scheduling_eligibility = elig
            return self.upsert_node(n)

    def update_node_drain(self, node_id: str, drain) -> int:
        with self._lock:
            cur = self._nodes.get(node_id)
            if cur is None:
                return self._index
            n = cur.copy()
            n.drain = drain
            if drain is not None:
                n.scheduling_eligibility = "ineligible"
            return self.upsert_node(n)

    # ---------------------------------------------------------------- jobs

    def upsert_job(self, job: Job, preserve_version: bool = False) -> int:
        """`preserve_version=True` updates the job in place without minting
        a new version (deployment watcher marking a version stable)."""
        with self._lock:
            idx = self._bump()
            key = job.ns_id()
            prev = self._jobs.get(key)
            job = job.copy()
            # canonicalize: a job-level update stanza applies to every task
            # group without its own (reference: jobspec canonicalization) —
            # the client health hook reads tg.update
            if job.update is not None:
                for tg in job.task_groups:
                    if tg.update is None:
                        tg.update = job.update
            job.create_index = prev.create_index if prev else idx
            job.modify_index = idx
            job.job_modify_index = idx
            if (not preserve_version and prev is not None
                    and prev.version >= job.version):
                job.version = prev.version + 1
            job.status = _job_initial_status(job)
            self._jobs = {**self._jobs, key: job}
            versions = dict(self._job_versions.get(key, {}))
            versions[job.version] = job
            self._job_versions = {**self._job_versions, key: versions}
            self._emit_locked("Job", idx, job)
            return idx

    def delete_job(self, namespace: str, job_id: str) -> int:
        with self._lock:
            idx = self._bump()
            jobs = dict(self._jobs)
            jobs.pop((namespace, job_id), None)
            self._jobs = jobs
            self._emit_locked("Job", idx, (namespace, job_id))
            return idx

    # --------------------------------------------------------------- evals

    def _writable_eval_tables(self):
        """The head eval tables, COW-copied once per snapshot cycle then
        mutated in place (same amortized discipline as the alloc/block
        tables) — a 384-eval wave's two dozen status flushes were each
        paying a copy of the ENTIRE eval table, a cost that grew with
        cluster history."""
        if self._eval_tables_shared:
            self._evals = dict(self._evals)
            self._evals_by_job = dict(self._evals_by_job)
            self._eval_tables_shared = False
            self._fresh_eval_buckets = set()
        return self._evals, self._evals_by_job

    def upsert_evals(self, evals: Iterable[Evaluation]) -> int:
        with self._lock:
            idx = self._bump()
            table, by_job = self._writable_eval_tables()
            fresh = self._fresh_eval_buckets
            inserted = []
            now = self.clock.time()
            for e in evals:
                prev = table.get(e.id)
                e = e.copy()
                e.create_index = prev.create_index if prev else idx
                e.modify_index = idx
                if e.create_time == 0.0:
                    e.create_time = prev.create_time if prev else now
                e.modify_time = now
                table[e.id] = e
                key = (e.namespace, e.job_id)
                if key not in fresh:
                    by_job[key] = dict(by_job.get(key, {}))
                    fresh.add(key)
                by_job[key][e.id] = e
                inserted.append(e)
            for e in inserted:
                self._emit_locked("Evaluation", idx, e)
            return idx

    def delete_evals(self, eval_ids: Iterable[str]) -> int:
        with self._lock:
            idx = self._bump()
            table, by_job = self._writable_eval_tables()
            fresh = self._fresh_eval_buckets
            for eid in eval_ids:
                e = table.pop(eid, None)
                if e is not None:
                    key = (e.namespace, e.job_id)
                    if key not in fresh:
                        by_job[key] = dict(by_job.get(key, {}))
                        fresh.add(key)
                    by_job[key].pop(eid, None)
            return idx

    # -------------------------------------------------------------- allocs

    def upsert_allocs(self, allocs: Iterable[Allocation]) -> int:
        with self._lock:
            idx = self._bump_placement()
            self._insert_allocs_locked(allocs, idx)
            return idx

    def _writable_alloc_tables(self):
        """The head alloc tables, COW-copied once if a snapshot may hold
        them (then mutated in place until the next snapshot)."""
        if self._alloc_tables_shared:
            self._allocs = dict(self._allocs)
            self._allocs_by_node = dict(self._allocs_by_node)
            self._allocs_by_job = dict(self._allocs_by_job)
            self._fresh_node_buckets = set()
            self._fresh_job_buckets = set()
            self._alloc_tables_shared = False
        return self._allocs, self._allocs_by_node, self._allocs_by_job

    def _writable_block_tables(self):
        """The head block registries, COW-copied once if a snapshot may
        hold them (then mutated in place until the next snapshot) — the
        same amortized discipline as the alloc tables: a 384-plan wave
        was paying a fresh copy of all three dicts PER BLOCK."""
        if self._block_tables_shared:
            self._alloc_blocks = dict(self._alloc_blocks)
            self._blocks_by_job = dict(self._blocks_by_job)
            self._blocks_by_node = dict(self._blocks_by_node)
            self._block_tables_shared = False
        return (self._alloc_blocks, self._blocks_by_job,
                self._blocks_by_node)

    def _materialize_block_locked(self, block) -> None:
        """Convert a live alloc block into ordinary per-alloc table rows
        (the cold path: a member alloc is about to be updated, or a full
        scan needs uniform rows).  Pure representation change — no index
        bump, no claims, no Allocations event; the packer migrates its
        block-unit ledger on the BlockMaterialized event."""
        rows = block.materialize_all()
        table, by_node, by_job = self._writable_alloc_tables()
        fresh_node = self._fresh_node_buckets
        fresh_job = self._fresh_job_buckets
        jkey = (block.template.namespace, block.template.job_id)
        if jkey not in fresh_job:
            by_job[jkey] = dict(by_job.get(jkey, {}))
            fresh_job.add(jkey)
        job_bucket = by_job[jkey]
        for a in rows:
            table[a.id] = a
            nid = a.node_id
            if nid not in fresh_node:
                by_node[nid] = dict(by_node.get(nid, {}))
                fresh_node.add(nid)
            by_node[nid][a.id] = a
            job_bucket[a.id] = a
        # drop from the amortized-COW registries
        blocks, bj, bn = self._writable_block_tables()
        blocks.pop(block.id, None)
        rest = tuple(b for b in bj.get(jkey, ()) if b is not block)
        if rest:
            bj[jkey] = rest
        else:
            bj.pop(jkey, None)
        for nid in block.node_table:
            restn = tuple(b for b in bn.get(nid, ()) if b is not block)
            if restn:
                bn[nid] = restn
            else:
                bn.pop(nid, None)
        # migrate the block's COLUMNAR volume claims to per-alloc claims
        # (now with real node values from the materialized rows) so the
        # terminal-release and serialization paths only ever see per-alloc
        # claims.  Same copy-once-per-cycle discipline as the claim dicts.
        tmpl = block.template
        tg = (tmpl.job.lookup_task_group(tmpl.task_group)
              if tmpl.job else None)
        if tg is not None and tg.volumes:
            vol_changed = {}
            for vreq in tg.volumes.values():
                if vreq.type != "csi" or not vreq.source:
                    continue
                key = (tmpl.namespace, vreq.source)
                # vol_changed as the accumulator: duplicate-source vreqs
                # reuse the same head-private copy; the helper itself
                # publishes any fresh copy before marking it, so the
                # continue below can never strand a snapshot-shared
                # volume behind a marked key (ADVICE r5)
                vol = self._writable_claim_vol(key, vol_changed)
                if vol is None or block.id not in vol.read_blocks:
                    continue
                vol.read_blocks.pop(block.id, None)
                vol.read_allocs.update(
                    {a.id: a.node_id for a in rows})
                vol_changed[key] = vol
        self._emit_locked("BlockMaterialized", self._index, block)

    def _resolve_block_member_locked(self, alloc_id: str,
                                     namespace: str = None,
                                     job_id: str = None) -> bool:
        """If `alloc_id` lives in a block, materialize that block so the
        caller can treat it as a table row.  Returns True on a hit."""
        if not self._alloc_blocks:
            return False
        if namespace is not None:
            candidates = self._blocks_by_job.get((namespace, job_id), ())
        else:
            candidates = self._alloc_blocks.values()
        for b in list(candidates):
            if b.contains_id(alloc_id):
                self._materialize_block_locked(b)
                return True
        return False

    def _insert_allocs_locked(self, allocs: Iterable[Allocation], idx: int,
                       copy: bool = True,
                       origin: Optional[str] = None) -> None:
        table, by_node, by_job = self._writable_alloc_tables()
        # Copy-on-first-touch per bucket: buckets possibly shared with live
        # snapshots are copied once per snapshot-write cycle, not once per
        # alloc (a 10k-alloc plan for one job would otherwise copy the job
        # bucket 10k times).
        fresh_node: set = self._fresh_node_buckets
        fresh_job: set = self._fresh_job_buckets
        fn_add = fresh_node.add
        fj_add = fresh_job.add
        table_get = table.get
        inserted = []
        ins_append = inserted.append
        dead: set = set()
        live_add = self._live.add
        for a in allocs:
            aid = a.id
            prev = table_get(aid)
            if prev is None and self._alloc_blocks:
                # the id may live in a columnar block (same-id stop or
                # client update of a bulk placement): materialize it so
                # this write sees its predecessor like any table row
                if self._resolve_block_member_locked(aid, a.namespace,
                                                     a.job_id):
                    prev = table_get(aid)
            if copy:
                a = a.copy_skip_job()   # embedded job ptr shared by design
            a.create_index = prev.create_index if prev else idx
            a.modify_index = idx
            if prev is not None and a.job is None:
                a.job = prev.job
            table[aid] = a
            nid = a.node_id
            # live-allocation ledger (quality gauges): retire the
            # predecessor's contribution, add the successor's — covers
            # terminal transitions and node moves in one delta pair
            if prev is not None and prev.node_id \
                    and not prev.terminal_status():
                live_add(prev.node_id, -1, prev.usage())
            if a.terminal_status():
                dead.add(aid)
            elif nid:
                live_add(nid, 1, a.usage())
            if prev is not None and prev.node_id and prev.node_id != nid:
                pnid = prev.node_id
                if pnid not in fresh_node:
                    by_node[pnid] = dict(by_node.get(pnid, {}))
                    fn_add(pnid)
                by_node[pnid].pop(aid, None)
                self._touch_node(pnid, origin)
            if nid:
                if nid not in fresh_node:
                    by_node[nid] = dict(by_node.get(nid, {}))
                    fn_add(nid)
                by_node[nid][aid] = a
                self._touch_node(nid, origin)
            jkey = (a.namespace, a.job_id)
            if jkey not in fresh_job:
                by_job[jkey] = dict(by_job.get(jkey, {}))
                fj_add(jkey)
            by_job[jkey][aid] = a
            ins_append(a)
        # terminal allocs lose their service registrations server-side
        # (reference: state store deletes registrations on terminal alloc
        # upserts — covers clients that died before deregistering)
        if dead and any(r.alloc_id in dead
                        for r in self._services.values()):
            self._services = {k: r for k, r in self._services.items()
                              if r.alloc_id not in dead}
        if dead:
            self._release_csi_claims_locked(dead)
        self._allocs = table
        self._allocs_by_node = by_node
        self._allocs_by_job = by_job
        # one event per transaction, not per alloc: a 100k-alloc plan fires
        # one list-payload event (subscribers loop internally, vectorized)
        if inserted:
            self._emit_locked("Allocations", idx, inserted)

    def update_allocs_from_client(self, updates: Iterable[Allocation]) -> int:
        """Client-side status updates (reference: FSM AllocClientUpdate):
        merges client_status into the stored alloc."""
        with self._lock:
            idx = self._bump_placement()
            merged = []
            for u in updates:
                cur = self._allocs.get(u.id)
                if cur is None and self._resolve_block_member_locked(
                        u.id, u.namespace, u.job_id):
                    cur = self._allocs.get(u.id)
                if cur is None:
                    continue
                a = cur.copy_skip_job()
                a.client_status = u.client_status
                a.client_description = u.client_description
                a.deployment_status = u.deployment_status
                # deep copy: the caller (in-process client) keeps mutating
                # its TaskState objects; committed state must not alias them
                import copy as _copy
                a.task_states = _copy.deepcopy(u.task_states)
                a.modify_time = u.modify_time
                merged.append(a)
            self._insert_allocs_locked(merged, idx)
            return idx

    def update_alloc_desired_transition(self, alloc_ids: Iterable[str],
                                        transition) -> int:
        """Set DesiredTransition on a batch of allocs (reference: RPC
        Alloc.UpdateDesiredTransition — the drainer's lever: the reconciler
        only migrates draining-node allocs the drainer has flagged)."""
        with self._lock:
            idx = self._bump_placement()
            merged = []
            for aid in alloc_ids:
                cur = self._allocs.get(aid)
                if cur is None and self._resolve_block_member_locked(aid):
                    cur = self._allocs.get(aid)
                if cur is None:
                    continue
                a = cur.copy_skip_job()
                a.desired_transition = DesiredTransition(
                    migrate=transition.migrate,
                    reschedule=transition.reschedule,
                    force_reschedule=transition.force_reschedule,
                    no_shutdown_delay=transition.no_shutdown_delay)
                merged.append(a)
            self._insert_allocs_locked(merged, idx, copy=False)
            return idx

    # --------------------------------------------------------- deployments

    def upsert_deployment(self, dep: Deployment) -> int:
        with self._lock:
            idx = self._bump()
            prev = self._deployments.get(dep.id)
            dep = dep.copy()
            dep.create_index = prev.create_index if prev else idx
            dep.modify_index = idx
            self._deployments = {**self._deployments, dep.id: dep}
            self._emit_locked("Deployment", idx, dep)
            return idx

    # ------------------------------------------------------- plan results

    def _refute_replayed_placements_locked(self, result) -> None:
        """Name-slot refute at the FSM boundary (same family as the
        applier's columnar re-check): a plan computed by a leader that
        was deposed mid-flight can still COMMIT from its log after the
        entries it raced — the write-failed-but-committed shape — and
        the scheduler's retry of the same eval then lands the same
        placements twice.  A placement whose (job, group, name,
        job_version) slot is already held by a live alloc this plan
        does not stop is exactly that replay: mask it.  Deterministic
        across replicas — every FSM applies the same log prefix before
        this index, so all see the same live slots.  System-family jobs
        are exempt (their allocs legitimately share name index [0]
        across nodes; their uniqueness key is the node, and the
        per-node fit re-check covers them)."""
        touched = set()
        for node_allocs in result.node_update.values():
            touched.update(a.id for a in node_allocs)
        for node_allocs in result.node_preemptions.values():
            touched.update(a.id for a in node_allocs)

        live_cache: Dict[Tuple[str, str], Dict[Tuple, str]] = {}

        def live_slots(ns: str, job_id: str) -> Dict[Tuple, str]:
            key = (ns, job_id)
            slots = live_cache.get(key)
            if slots is not None:
                return slots
            slots = {}
            for a in self._allocs_by_job.get(key, {}).values():
                if (a.id in touched or a.desired_status != "run"
                        or a.client_terminal_status()):
                    continue
                slots[(a.task_group, a.name, a.job_version)] = a.id
            for b in self._blocks_by_job.get(key, ()):
                tmpl = b.template
                for i, bid in zip(b.indexes, b.ids):
                    if bid in touched:
                        continue
                    slots[(tmpl.task_group, f"{b.name_prefix}{i}]",
                           tmpl.job_version)] = bid
            live_cache[key] = slots
            return slots

        def system_family(job) -> bool:
            return job is not None and job.type in ("system", "sysbatch")

        for nid, node_allocs in list(result.node_allocation.items()):
            keep = []
            for a in node_allocs:
                if not system_family(a.job):
                    holder = live_slots(a.namespace, a.job_id).get(
                        (a.task_group, a.name, a.job_version))
                    if holder is not None and holder != a.id:
                        continue              # replayed slot — refute
                keep.append(a)
            if len(keep) != len(node_allocs):
                result.node_allocation[nid] = keep

        if result.alloc_blocks:
            kept_blocks = []
            for block in result.alloc_blocks:
                tmpl = block.template
                if system_family(tmpl.job):
                    kept_blocks.append(block)
                    continue
                slots = live_slots(tmpl.namespace, tmpl.job_id)
                colliding = {
                    j for j, (i, bid) in enumerate(
                        zip(block.indexes, block.ids))
                    if slots.get((tmpl.task_group,
                                  f"{block.name_prefix}{i}]",
                                  tmpl.job_version)) not in (None, bid)}
                if not colliding:
                    kept_blocks.append(block)
                    continue
                if len(colliding) == len(block.ids):
                    continue                  # whole block is a replay
                # partial replay (rare): keep the surviving rows as
                # ordinary placements so claims/events stay uniform
                rows = block.materialize_all()
                for j, row in enumerate(rows):
                    if j not in colliding:
                        result.node_allocation.setdefault(
                            row.node_id, []).append(row)
            result.alloc_blocks = kept_blocks

    def upsert_plan_results(self, plan: Plan, result: PlanResult,
                            expected_placement_seq: Optional[int] = None,
                            expected_nodes: Optional[Tuple] = None
                            ) -> int:
        """Apply a committed plan (reference: FSM ApplyPlanResults →
        state.UpsertPlanResults): stops, preemption evictions, placements,
        deployment upserts — one atomic index bump.

        `expected_placement_seq`: the applier's coupled-batch fast path
        passes the fence value its skip-fit decision was based on; if a
        foreign placement write slipped in since (the decision and the
        commit are separate lock scopes), the commit is REFUSED by
        returning -1 and the applier redoes the full re-check.  Checked
        under the same lock as the commit, so the fast path is exactly as
        safe as the full path.  Deterministic across Raft replicas: all
        placement writes ride the log, so every replica's counter agrees.

        `expected_nodes`: the PER-NODE form of the same re-verify —
        (node_ids, seq0, chain_id): refuse (-1) unless every listed node
        is unchanged since seq0 except by the plan's own chain (see
        nodes_unchanged_since)."""
        with self._lock:
            if (expected_placement_seq is not None
                    and self._placement_seq != expected_placement_seq):
                return -1
            if expected_nodes is not None:
                nids, seq0, chain_id, vseq = expected_nodes
                if not self.nodes_unchanged_since(nids, seq0, chain_id):
                    return -1
                if vseq is not None and self._volume_seq != vseq:
                    # a volume mutation (claim release, schedulable flip,
                    # deletion) landed after the applier's guarded claim
                    # checks — redo them against current state
                    return -1
            self._refute_replayed_placements_locked(result)
            origin = (plan.coupled_batch[0]
                      if plan.coupled_batch is not None else None)
            idx = self._bump_placement(origin)
            allocs: List[Allocation] = []
            for node_allocs in result.node_update.values():
                allocs.extend(node_allocs)
            for node_allocs in result.node_preemptions.values():
                allocs.extend(node_allocs)
            for node_allocs in result.node_allocation.values():
                allocs.extend(node_allocs)
            # Ownership transfer, no defensive copy: every alloc in a plan
            # is freshly constructed (placements) or already a private copy
            # (stops/updates via copy_skip_job in the scheduler), and by the
            # go-memdb convention the reference itself relies on, objects
            # are immutable once inserted (state.UpsertPlanResults stores
            # the submitted pointers directly).
            self._insert_allocs_locked(allocs, idx, copy=False, origin=origin)
            # CSI claims ride the plan commit (reference: the client's
            # claim RPC; the applier's claim_ok re-check reads these).
            # Released when the alloc goes terminal.  Changed volumes
            # accumulate and merge ONCE, not per alloc.
            changed_vols: Dict[Tuple[str, str], CSIVolume] = {}
            # hoist the volumes-exist check per (job, group) — a 100k-alloc
            # plan of a volumeless group must not pay a tg lookup per alloc
            vol_tg: Dict[Tuple[int, str], bool] = {}
            for node_allocs in result.node_allocation.values():
                for a in node_allocs:
                    key = (id(a.job), a.task_group)
                    has = vol_tg.get(key)
                    if has is None:
                        tg = a.job.lookup_task_group(a.task_group) \
                            if a.job else None
                        has = bool(tg is not None and tg.volumes)
                        vol_tg[key] = has
                    if has:
                        self._claim_csi_volumes_locked(a, changed_vols)
            for block in result.alloc_blocks:
                self._commit_block_locked(block, idx, changed_vols,
                                          origin=origin)
            if changed_vols:
                self._csi_volumes = {**self._csi_volumes, **changed_vols}
            if result.deployment is not None:
                dep = result.deployment.copy()
                prev = self._deployments.get(dep.id)
                dep.create_index = prev.create_index if prev else idx
                dep.modify_index = idx
                self._deployments = {**self._deployments, dep.id: dep}
            for du in result.deployment_updates:
                cur = self._deployments.get(du.deployment_id)
                if cur is not None:
                    d = cur.copy()
                    d.status = du.status
                    d.status_description = du.status_description
                    d.modify_index = idx
                    self._deployments = {**self._deployments, d.id: d}
            self._emit_locked("PlanResult", idx, result)
            return idx

    def _commit_block_locked(self, block, idx: int, changed_vols,
                             origin: Optional[str] = None) -> None:
        """Insert a columnar alloc block: registry publishes + bulk CSI
        claims.  By columns: a constant number of Python-level calls a
        block, C-level or numpy work over its nodes — a Python step only
        for a node that already holds a block."""
        block.create_index = idx
        block.modify_index = idx
        node_table = block.node_table
        # the per-node fence, _touch_node over the whole table
        self._node_place_seq.update(
            dict.fromkeys(node_table, (self._placement_seq, origin)))
        # live-allocation ledger: the whole block as one unit (rows
        # retire per alloc later — materialization keeps liveness)
        self._live.add_block(block)
        blocks, bj, bn = self._writable_block_tables()
        blocks[block.id] = block
        tmpl = block.template
        jkey = (tmpl.namespace, tmpl.job_id)
        bj[jkey] = bj.get(jkey, ()) + (block,)
        held = dict.fromkeys(node_table, (block,))
        for nid in filter(bn.__contains__, node_table):
            held[nid] = bn[nid] + (block,)
        bn.update(held)
        # CSI claims for the whole block in one dict update per volume
        job = tmpl.job
        tg = job.lookup_task_group(tmpl.task_group) if job else None
        if tg is not None and tg.volumes:
            import dataclasses
            for vreq in tg.volumes.values():
                if vreq.type != "csi" or not vreq.source:
                    continue
                key = (tmpl.namespace, vreq.source)
                vol = self._writable_claim_vol(key, changed_vols)
                if vol is None:
                    continue
                if vreq.read_only:
                    # COLUMNAR claim: one ledger entry for the whole
                    # block — O(1) per volume per wave, where the old
                    # per-alloc dict update made every later wave pay a
                    # copy of the volume's ENTIRE claim history on the
                    # first touch of each snapshot cycle (measured: the
                    # commit path degraded ~3x over a 1M-claim session).
                    # Only read-only multi-node claims reach this branch
                    # (_blocks_ok demotes the rest), so block claims
                    # never pin nodes and never count against writers.
                    vol.read_blocks[block.id] = block
                else:
                    # defensive: a hand-built write-claiming block (the
                    # applier never admits one) keeps exact per-alloc
                    # writer accounting
                    vol.write_allocs.update(dict.fromkeys(block.ids, ""))
                changed_vols[key] = vol
        self._emit_locked("AllocBlock", idx, block)

    # ----------------------------------------------------------- csi / cfg

    def _writable_claim_vol(self, key, changed=None):
        """Claim-ledger copy-on-first-touch, the ONE definition all claim
        mutators share (code-review r5: the hand-rolled copies at four
        sites are exactly how the read_blocks-omission snapshot leak
        arose — a future ledger addition must be a one-line change
        here, not a hunt).  Returns a volume private to the head for
        this snapshot cycle (claim dicts safe to mutate in place), or
        None when the volume does not exist.  `changed`: an in-flight
        accumulator dict (plan commits) consulted before the head table;
        the caller publishes the returned volume into it / the table."""
        import dataclasses
        vol = None
        if changed is not None:
            vol = changed.get(key)
        if vol is None:
            vol = self._csi_volumes.get(key)
            if vol is None:
                return None
            if key not in self._fresh_claim_vols:
                vol = dataclasses.replace(
                    vol, read_allocs=dict(vol.read_allocs),
                    write_allocs=dict(vol.write_allocs),
                    read_blocks=dict(vol.read_blocks))
                # publish the copy NOW, before marking it fresh: a caller
                # that drops the returned copy on a continue/early-return
                # (ADVICE r5: _materialize_block_locked's
                # block-not-claimed case) would otherwise leave the
                # snapshot-shared volume at the head while later claim
                # writers skip the copy and mutate the shared dicts in
                # place — the exact snapshot-isolation leak the fresh set
                # exists to prevent.  Callers' changed_vols merges are
                # now idempotent re-publishes of the same object.
                self._csi_volumes = {**self._csi_volumes, key: vol}
                self._fresh_claim_vols.add(key)
        return vol

    def delete_deployment(self, dep_id: str) -> int:
        with self._lock:
            idx = self._bump()
            deps = dict(self._deployments)
            deps.pop(dep_id, None)
            self._deployments = deps
            return idx

    def upsert_csi_volume(self, vol: CSIVolume) -> int:
        with self._lock:
            idx = self._bump_placement()
            self._volume_seq += 1
            key = (vol.namespace, vol.id)
            prev = self._csi_volumes.get(key)
            if prev is not None:
                # re-registration (idempotent retry) must not wipe live
                # claims — they belong to running allocs, not the spec
                import dataclasses
                vol = dataclasses.replace(
                    vol, read_allocs=dict(prev.read_allocs),
                    write_allocs=dict(prev.write_allocs),
                    read_blocks=dict(prev.read_blocks))
            self._csi_volumes = {**self._csi_volumes, key: vol}
            return idx

    def delete_csi_volume(self, namespace: str,
                          vol_id: str) -> Optional[str]:
        with self._lock:
            vol = self._csi_volumes.get((namespace, vol_id))
            if vol is None:
                return "volume not found"
            if vol.has_claims():
                return "volume has active claims"
            self._bump_placement()
            self._volume_seq += 1
            vols = dict(self._csi_volumes)
            vols.pop((namespace, vol_id), None)
            self._csi_volumes = vols
            return None

    def csi_volumes(self, namespace: Optional[str] = None):
        return [v for (ns, _), v in self._csi_volumes.items()
                if namespace is None or ns == namespace]

    def csi_volume_by_id(self, namespace: str,
                         vol_id: str) -> Optional[CSIVolume]:
        return self._csi_volumes.get((namespace, vol_id))

    def locked(self):
        """The store's write lock, for short read sections that iterate
        head-state dicts mutated in place between snapshots (claim dicts,
        fresh alloc buckets).  Point reads (dict.get) don't need it."""
        return self._lock

    def _claim_csi_volumes_locked(self, alloc: Allocation,
                                  changed: Dict) -> None:
        job = alloc.job
        tg = job.lookup_task_group(alloc.task_group) if job else None
        if tg is None or not tg.volumes:
            return
        import dataclasses
        for vreq in tg.volumes.values():
            if vreq.type != "csi" or not vreq.source:
                continue
            key = (alloc.namespace, vreq.source)
            vol = self._writable_claim_vol(key, changed)
            if vol is None:
                continue
            if vreq.read_only:
                vol.read_allocs[alloc.id] = alloc.node_id
            else:
                vol.write_allocs[alloc.id] = alloc.node_id
            changed[key] = vol

    def _release_csi_claims_locked(self, dead_ids: set) -> None:
        """Volume-watcher semantics (reference: nomad/volumewatcher/):
        terminal allocs lose their claims."""
        changed = {}
        for key, vol in self._csi_volumes.items():
            if not (dead_ids & (set(vol.read_allocs)
                                | set(vol.write_allocs))):
                continue
            import dataclasses
            v = dataclasses.replace(
                vol,
                read_allocs={k: nd for k, nd in vol.read_allocs.items()
                             if k not in dead_ids},
                write_allocs={k: nd for k, nd in vol.write_allocs.items()
                              if k not in dead_ids})
            changed[key] = v
        if changed:
            self._volume_seq += 1
            self._csi_volumes = {**self._csi_volumes, **changed}

    def convert_csi_block_claim(self, namespace: str, vol_id: str,
                                block_id: str) -> int:
        """Expand a columnar block claim whose block no longer exists in
        the store into ordinary per-alloc claims (safety path — normally
        a block's claims migrate at materialization).  Conversion, not
        release: each member claim must still go through the volume
        watcher's unpublish-with-backoff before it drops, and the
        per-alloc reap retries members INDEPENDENTLY where an
        all-or-nothing block unpublish would restart from member zero on
        every failure (code-review r5)."""
        with self._lock:
            return self._convert_block_claim_locked(namespace, vol_id,
                                                    block_id)

    def _convert_block_claim_locked(self, namespace: str, vol_id: str,
                                    block_id: str) -> int:
        vol = self._csi_volumes.get((namespace, vol_id))
        if vol is None or block_id not in vol.read_blocks:
            return self._index
        idx = self._bump_placement()
        self._volume_seq += 1
        import dataclasses
        block = vol.read_blocks[block_id]
        reads = dict(vol.read_allocs)
        reads.update(dict.fromkeys(block.ids, ""))
        v = dataclasses.replace(
            vol, read_allocs=reads,
            read_blocks={k: b for k, b in vol.read_blocks.items()
                         if k != block_id})
        self._csi_volumes = {**self._csi_volumes, (namespace, vol_id): v}
        self._fresh_claim_vols.discard((namespace, vol_id))
        self._emit_locked("CSIVolume", idx, v)
        return idx

    def release_csi_claim(self, namespace: str, vol_id: str,
                          alloc_id: str) -> int:
        """Drop one alloc's claim on a volume (the volume watcher's reap
        step after a successful unpublish; reference: nomad/volumewatcher/
        volume_reap).  Placement-relevant: a freed single-writer claim
        makes the volume schedulable again."""
        with self._lock:
            vol = self._csi_volumes.get((namespace, vol_id))
            if vol is None or (alloc_id not in vol.read_allocs
                               and alloc_id not in vol.write_allocs):
                return self._index
            idx = self._bump_placement()
            self._volume_seq += 1
            import dataclasses
            v = dataclasses.replace(
                vol,
                read_allocs={k: nd for k, nd in vol.read_allocs.items()
                             if k != alloc_id},
                write_allocs={k: nd for k, nd in vol.write_allocs.items()
                              if k != alloc_id})
            self._csi_volumes = {**self._csi_volumes,
                                 (namespace, vol_id): v}
            self._emit_locked("CSIVolume", idx, v)
            return idx

    def set_scheduler_config(self, cfg: SchedulerConfiguration) -> int:
        with self._lock:
            idx = self._bump()
            cfg.modify_index = idx
            self._scheduler_config = cfg
            return idx

    def set_identity_secret(self, secret: str) -> int:
        """First writer wins: concurrent leaders racing at bootstrap must
        not rotate an already-established signing secret."""
        with self._lock:
            if self._identity_secret:
                return self._index
            idx = self._bump()
            self._identity_secret = secret
            return idx

    def identity_secret(self) -> str:
        return self._identity_secret

    def upsert_namespace(self, ns: Namespace) -> int:
        with self._lock:
            idx = self._bump()
            self._namespaces = {**self._namespaces, ns.name: ns}
            return idx

    def upsert_node_pool(self, pool: NodePool) -> int:
        with self._lock:
            idx = self._bump()
            self._node_pools = {**self._node_pools, pool.name: pool}
            return idx

    def delete_namespace(self, name: str) -> Optional[str]:
        """Returns an error string when the namespace is non-empty."""
        with self._lock:
            if name == "default":
                return "default namespace cannot be deleted"
            if any(k[0] == name and j.status != JOB_STATUS_DEAD
                   for k, j in self._jobs.items()):
                return "namespace has non-terminal jobs"
            self._bump()
            nss = dict(self._namespaces)
            nss.pop(name, None)
            self._namespaces = nss
            # variables are namespace-scoped: deleting the namespace must
            # not leave (possibly secret-bearing) entries to be resurrected
            # by a later namespace of the same name
            if any(k[0] == name for k in self._variables):
                self._variables = {k: v for k, v in self._variables.items()
                                   if k[0] != name}
            return None

    def delete_node_pool(self, name: str) -> Optional[str]:
        with self._lock:
            if name in ("default", "all"):
                return f"builtin node pool {name!r} cannot be deleted"
            if any(n.node_pool == name for n in self._nodes.values()):
                return "node pool has registered nodes"
            self._bump()
            pools = dict(self._node_pools)
            pools.pop(name, None)
            self._node_pools = pools
            return None

    # ------------------------------------------------------------------ acl

    def upsert_acl_policy(self, policy: ACLPolicy) -> int:
        with self._lock:
            idx = self._bump()
            prev = self._acl_policies.get(policy.name)
            policy.create_index = prev.create_index if prev else idx
            policy.modify_index = idx
            self._acl_policies = {**self._acl_policies,
                                  policy.name: policy}
            return idx

    def delete_acl_policy(self, name: str) -> int:
        with self._lock:
            idx = self._bump()
            pols = dict(self._acl_policies)
            pols.pop(name, None)
            self._acl_policies = pols
            return idx

    def acl_policy_by_name(self, name: str) -> Optional[ACLPolicy]:
        return self._acl_policies.get(name)

    def acl_policies(self) -> List[ACLPolicy]:
        return list(self._acl_policies.values())

    def upsert_acl_token(self, token: ACLToken) -> int:
        with self._lock:
            idx = self._bump()
            prev = self._acl_tokens.get(token.accessor_id)
            token.create_index = prev.create_index if prev else idx
            token.modify_index = idx
            self._acl_tokens = {**self._acl_tokens,
                                token.accessor_id: token}
            by_secret = dict(self._acl_by_secret)
            if prev is not None and prev.secret_id != token.secret_id:
                # rotation: the old secret must stop authenticating
                by_secret.pop(prev.secret_id, None)
            by_secret[token.secret_id] = token
            self._acl_by_secret = by_secret
            return idx

    def bootstrap_acl_token(self, token: ACLToken) -> bool:
        """Atomically insert the very first token (reference:
        ACL.Bootstrap's reset-index guard).  False when already done."""
        with self._lock:
            if self._acl_tokens:
                return False
            idx = self._bump()
            token.create_index = token.modify_index = idx
            self._acl_tokens = {token.accessor_id: token}
            self._acl_by_secret = {token.secret_id: token}
            return True

    def delete_acl_token(self, accessor_id: str) -> int:
        with self._lock:
            idx = self._bump()
            toks = dict(self._acl_tokens)
            tok = toks.pop(accessor_id, None)
            self._acl_tokens = toks
            if tok is not None:
                by_secret = dict(self._acl_by_secret)
                by_secret.pop(tok.secret_id, None)
                self._acl_by_secret = by_secret
            return idx

    def acl_token_by_accessor(self, accessor_id: str) -> Optional[ACLToken]:
        return self._acl_tokens.get(accessor_id)

    def acl_token_by_secret(self, secret_id: str) -> Optional[ACLToken]:
        return self._acl_by_secret.get(secret_id)

    def acl_tokens(self) -> List[ACLToken]:
        return list(self._acl_tokens.values())

    # ------------------------------------------------- acl auth methods

    def upsert_acl_auth_method(self, method: ACLAuthMethod) -> int:
        with self._lock:
            idx = self._bump()
            prev = self._acl_auth_methods.get(method.name)
            method.create_index = prev.create_index if prev else idx
            method.modify_index = idx
            self._acl_auth_methods = {**self._acl_auth_methods,
                                      method.name: method}
            return idx

    def delete_acl_auth_method(self, name: str) -> int:
        with self._lock:
            idx = self._bump()
            methods = dict(self._acl_auth_methods)
            methods.pop(name, None)
            self._acl_auth_methods = methods
            # a method's binding rules die with it (reference: cascade)
            if any(r.auth_method == name
                   for r in self._acl_binding_rules.values()):
                self._acl_binding_rules = {
                    k: r for k, r in self._acl_binding_rules.items()
                    if r.auth_method != name}
            return idx

    def acl_auth_method_by_name(self, name: str
                                ) -> Optional[ACLAuthMethod]:
        return self._acl_auth_methods.get(name)

    def acl_auth_methods(self) -> List[ACLAuthMethod]:
        return list(self._acl_auth_methods.values())

    def upsert_acl_binding_rule(self, rule: ACLBindingRule) -> int:
        with self._lock:
            idx = self._bump()
            prev = self._acl_binding_rules.get(rule.id)
            rule.create_index = prev.create_index if prev else idx
            rule.modify_index = idx
            self._acl_binding_rules = {**self._acl_binding_rules,
                                       rule.id: rule}
            return idx

    def delete_acl_binding_rule(self, rule_id: str) -> int:
        with self._lock:
            idx = self._bump()
            rules = dict(self._acl_binding_rules)
            rules.pop(rule_id, None)
            self._acl_binding_rules = rules
            return idx

    def acl_binding_rule_by_id(self, rule_id: str
                               ) -> Optional[ACLBindingRule]:
        return self._acl_binding_rules.get(rule_id)

    def acl_binding_rules(self, auth_method: Optional[str] = None
                          ) -> List[ACLBindingRule]:
        return [r for r in self._acl_binding_rules.values()
                if auth_method is None or r.auth_method == auth_method]

    # ----------------------------------------------------------- services

    def upsert_service_registrations(self, regs) -> int:
        """reference: UpsertServiceRegistrations (Nomad-native services).
        Copies on write like every other table — with in-process RPC the
        caller keeps mutating its objects (check runners update status)."""
        import dataclasses
        with self._lock:
            idx = self._bump()
            table = dict(self._services)
            for r in regs:
                prev = table.get(r.id)
                r = dataclasses.replace(r, tags=list(r.tags))
                r.create_index = prev.create_index if prev else idx
                r.modify_index = idx
                table[r.id] = r
            self._services = table
            return idx

    def delete_service_registrations_by_alloc(self, alloc_id: str) -> int:
        with self._lock:
            idx = self._bump()
            self._services = {k: v for k, v in self._services.items()
                              if v.alloc_id != alloc_id}
            return idx

    def service_registrations(self, namespace: Optional[str] = None,
                              name: Optional[str] = None):
        return [r for r in self._services.values()
                if (namespace is None or r.namespace == namespace)
                and (name is None or r.service_name == name)]

    # ------------------------------------------------------------ variables

    def upsert_variable(self, var: VariableItem) -> int:
        with self._lock:
            idx = self._bump()
            key = (var.namespace, var.path)
            prev = self._variables.get(key)
            var.create_index = prev.create_index if prev else idx
            var.modify_index = idx
            self._variables = {**self._variables, key: var}
            return idx

    def delete_variable(self, namespace: str, path: str) -> int:
        with self._lock:
            idx = self._bump()
            vs = dict(self._variables)
            vs.pop((namespace, path), None)
            self._variables = vs
            return idx

    def variable_by_path(self, namespace: str,
                         path: str) -> Optional[VariableItem]:
        return self._variables.get((namespace, path))

    def variables(self, namespace: Optional[str] = None,
                  prefix: str = "") -> List[VariableItem]:
        return [v for (ns, p), v in self._variables.items()
                if (namespace is None or ns == namespace)
                and p.startswith(prefix)]

    # --------------------------------------------------- persist / restore

    def snapshot_save(self) -> Dict:
        """Serialize the full cluster state to one JSON-safe document
        (reference: FSM Snapshot + `nomad operator snapshot save`).
        Embedded job pointers on allocs are stripped and re-attached on
        restore (they would otherwise duplicate every job per alloc)."""
        from nomad_tpu.structs import codec
        with self._lock:
            # columnar blocks flatten for the snapshot document (cold
            # path); the restored store starts block-free.  Flattening
            # migrates block claims to per-alloc claims, so volumes
            # serialize without block references — any LEFTOVER block
            # claim references a vanished block (the watcher's reap
            # case) and CONVERTS to per-alloc claims ON THE SERIALIZED
            # DOCUMENT ONLY rather than being dropped: the restored
            # store's volume watcher must still unpublish each member
            # before releasing (detach-before-release survives a
            # snapshot/restore cycle).  Converting on the document
            # (ADVICE r5) keeps the save read-mostly: mutating live
            # state here bumped the placement index + _volume_seq and
            # emitted CSIVolume events, which could spuriously
            # invalidate concurrent plan commits' volume_seq fences.
            for b in list(self._alloc_blocks.values()):
                self._materialize_block_locked(b)
            vols_doc = []
            for v in self._csi_volumes.values():
                if v.read_blocks:
                    import dataclasses
                    reads = dict(v.read_allocs)
                    for blk in v.read_blocks.values():
                        reads.update(dict.fromkeys(blk.ids, ""))
                    v = dataclasses.replace(v, read_allocs=reads,
                                            read_blocks={})
                vols_doc.append(codec.encode(v))
            allocs = []
            for a in self._allocs.values():
                slim = a.copy_skip_job()
                slim.job = None
                allocs.append(codec.encode(slim))
            return {
                "Index": self._index,
                # the coupled-batch fence counter MUST travel with the
                # snapshot: a Raft replica restored without it would
                # diverge from the leader and silently drop replicated
                # fenced plan commits (upsert_plan_results returns -1)
                "PlacementSeq": self._placement_seq,
                "Nodes": [codec.encode(n) for n in self._nodes.values()],
                "Jobs": [codec.encode(j) for j in self._jobs.values()],
                "JobVersions": [
                    {"Namespace": k[0], "ID": k[1],
                     "Versions": {str(v): codec.encode(j)
                                  for v, j in vs.items()}}
                    for k, vs in self._job_versions.items()],
                "Evals": [codec.encode(e) for e in self._evals.values()],
                "Allocs": allocs,
                "Deployments": [codec.encode(d)
                                for d in self._deployments.values()],
                "Namespaces": [codec.encode(n)
                               for n in self._namespaces.values()],
                "NodePools": [codec.encode(p)
                              for p in self._node_pools.values()],
                "ACLPolicies": [codec.encode(p)
                                for p in self._acl_policies.values()],
                "ACLTokens": [codec.encode(t)
                              for t in self._acl_tokens.values()],
                "ACLAuthMethods": [
                    codec.encode(m)
                    for m in self._acl_auth_methods.values()],
                "ACLBindingRules": [
                    codec.encode(r)
                    for r in self._acl_binding_rules.values()],
                "Variables": [codec.encode(v)
                              for v in self._variables.values()],
                "CSIVolumes": vols_doc,
                "Services": [codec.encode(r)
                             for r in self._services.values()],
                "SchedulerConfig": codec.encode(self._scheduler_config),
                "IdentitySecret": self._identity_secret,
            }

    def snapshot_restore(self, doc: Dict) -> None:
        """Replace ALL state with a snapshot_save document
        (reference: FSM Restore + `nomad operator snapshot restore`)."""
        from nomad_tpu.structs import (
            SchedulerConfiguration as SC, codec)
        with self._lock:
            self._nodes = {n.id: n for n in
                           (codec.decode(Node, d) for d in doc["Nodes"])}
            self._jobs = {}
            for d in doc["Jobs"]:
                j = codec.decode(Job, d)
                self._jobs[j.ns_id()] = j
            self._job_versions = {}
            for entry in doc.get("JobVersions", []):
                key = (entry["Namespace"], entry["ID"])
                self._job_versions[key] = {
                    int(v): codec.decode(Job, jd)
                    for v, jd in entry["Versions"].items()}
            self._evals = {e.id: e for e in
                           (codec.decode(Evaluation, d)
                            for d in doc["Evals"])}
            self._allocs = {}
            self._allocs_by_node = {}
            self._allocs_by_job = {}
            self._alloc_blocks = {}
            self._blocks_by_job = {}
            self._blocks_by_node = {}
            self._alloc_tables_shared = False
            self._block_tables_shared = False
            self._eval_tables_shared = False
            self._fresh_node_buckets = set()
            self._fresh_job_buckets = set()
            self._fresh_eval_buckets = set()
            self._fresh_claim_vols = set()
            self._live.reset()
            self._live.note_nodes(list(self._nodes.values()))
            for d in doc["Allocs"]:
                a = codec.decode(Allocation, d)
                a.job = self._job_versions.get(
                    (a.namespace, a.job_id), {}).get(a.job_version) \
                    or self._jobs.get((a.namespace, a.job_id))
                self._allocs[a.id] = a
                if a.node_id:
                    self._allocs_by_node.setdefault(a.node_id, {})[a.id] = a
                    if not a.terminal_status():
                        self._live.add(a.node_id, 1, a.usage())
                self._allocs_by_job.setdefault(
                    (a.namespace, a.job_id), {})[a.id] = a
            self._evals_by_job = {}
            for e in self._evals.values():
                self._evals_by_job.setdefault(
                    (e.namespace, e.job_id), {})[e.id] = e
            self._deployments = {d.id: d for d in
                                 (codec.decode(Deployment, x)
                                  for x in doc["Deployments"])}
            self._namespaces = {n.name: n for n in
                                (codec.decode(Namespace, d)
                                 for d in doc["Namespaces"])}
            self._node_pools = {p.name: p for p in
                                (codec.decode(NodePool, d)
                                 for d in doc["NodePools"])}
            self._acl_policies = {p.name: p for p in
                                  (codec.decode(ACLPolicy, d)
                                   for d in doc.get("ACLPolicies", []))}
            self._acl_tokens = {}
            self._acl_by_secret = {}
            for d in doc.get("ACLTokens", []):
                t = codec.decode(ACLToken, d)
                self._acl_tokens[t.accessor_id] = t
                self._acl_by_secret[t.secret_id] = t
            self._acl_auth_methods = {
                m.name: m for m in
                (codec.decode(ACLAuthMethod, d)
                 for d in doc.get("ACLAuthMethods", []))}
            self._acl_binding_rules = {
                r.id: r for r in
                (codec.decode(ACLBindingRule, d)
                 for d in doc.get("ACLBindingRules", []))}
            self._variables = {}
            for d in doc.get("Variables", []):
                v = codec.decode(VariableItem, d)
                self._variables[(v.namespace, v.path)] = v
            self._services = {
                r.id: r for r in
                (codec.decode(ServiceRegistration, d)
                 for d in doc.get("Services", []))}
            self._csi_volumes = {
                (v.namespace, v.id): v for v in
                (codec.decode(CSIVolume, d)
                 for d in doc.get("CSIVolumes", []))}
            self._scheduler_config = codec.decode(
                SC, doc.get("SchedulerConfig") or {})
            self._identity_secret = doc.get("IdentitySecret", "") or ""
            self._placement_seq = int(doc.get("PlacementSeq", 0))
            self._node_place_seq = {}
            self._node_seq_floor = self._placement_seq
            self._run_floor = self._placement_seq
            self._run_origin = None
            self._index = max(int(doc.get("Index", 0)), self._index) + 1
            self._index_cv.notify_all()
            self._emit_locked("Restore", self._index, None)

    # ------------------------------------------------------------ snapshot

    def snapshot_and_placement_seq(self):
        """(snapshot, placement_seq) read atomically — the worker's
        coupled-batch fence must be taken AT the snapshot: a write landing
        between separate reads would be invisible to the fence while
        missing from the snapshot (the applier would then skip the fit
        re-check against state the scheduler never saw)."""
        with self._lock:
            snap = self.snapshot()
            return snap, snap.placement_fence

    def snapshot(self) -> "StateSnapshot":
        with self._lock:
            # the handed-out tables are frozen from here on: the next
            # alloc write copies before mutating (see _insert_allocs_locked)
            self._alloc_tables_shared = True
            self._block_tables_shared = True
            self._eval_tables_shared = True
            self._fresh_node_buckets = set()
            self._fresh_job_buckets = set()
            self._fresh_eval_buckets = set()
            self._fresh_claim_vols = set()
            return StateSnapshot(
                placement_fence=self._placement_seq,
                store_id=self.store_id,
                index=self._index,
                nodes=self._nodes,
                jobs=self._jobs,
                job_versions=self._job_versions,
                evals=self._evals,
                allocs=self._allocs,
                deployments=self._deployments,
                namespaces=self._namespaces,
                node_pools=self._node_pools,
                csi_volumes=self._csi_volumes,
                scheduler_config=self._scheduler_config,
                allocs_by_node=self._allocs_by_node,
                allocs_by_job=self._allocs_by_job,
                evals_by_job=self._evals_by_job,
                alloc_blocks=self._alloc_blocks,
                blocks_by_job=self._blocks_by_job,
                blocks_by_node=self._blocks_by_node,
            )

    # convenience pass-throughs (read the live head; schedulers must use
    # snapshot() for consistency).  dict.get is atomic under the GIL, but
    # anything ITERATING a bucket must hold the lock: alloc buckets copied
    # since the last snapshot are mutated in place by _insert_allocs_locked.
    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._nodes.get(node_id)

    def nodes_up(self, node_ids) -> bool:
        """Every one of `node_ids` exists and is not `down`."""
        return _all_up(self._nodes, node_ids)

    def job_by_id(self, namespace: str, job_id: str) -> Optional[Job]:
        return self._jobs.get((namespace, job_id))

    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._evals.get(eval_id)

    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        a = self._allocs.get(alloc_id)
        if a is None and self._alloc_blocks:
            for b in list(self._alloc_blocks.values()):
                i = b.index_of(alloc_id)
                if i is not None:
                    return b.materialize_all()[i]
        return a

    def allocs_by_job(self, namespace: str, job_id: str) -> List[Allocation]:
        with self._lock:
            out = list(self._allocs_by_job.get((namespace, job_id),
                                               {}).values())
            for b in self._blocks_by_job.get((namespace, job_id), ()):
                out.extend(b.materialize_all())
            return out

    def deployment_by_id(self, dep_id: str) -> Optional[Deployment]:
        return self._deployments.get(dep_id)

    def latest_deployment_by_job(self, namespace: str, job_id: str
                                 ) -> Optional[Deployment]:
        best = None
        for d in self._deployments.values():
            if d.namespace == namespace and d.job_id == job_id:
                if best is None or d.create_index > best.create_index:
                    best = d
        return best

    def job_by_id_and_version(self, namespace: str, job_id: str,
                              version: int) -> Optional[Job]:
        return self._job_versions.get((namespace, job_id), {}).get(version)


class StateSnapshot:
    """Immutable point-in-time view — the `scheduler.State` seam.

    reference: nomad/state StateSnapshot + scheduler/scheduler.go State
    interface (Nodes, AllocsByNode, AllocsByJob, JobByID, SchedulerConfig...).
    """

    def __init__(self, index, nodes, jobs, job_versions, evals, allocs,
                 deployments, namespaces, node_pools, csi_volumes,
                 scheduler_config, allocs_by_node, allocs_by_job,
                 evals_by_job, store_id="", placement_fence=None,
                 alloc_blocks=None, blocks_by_job=None,
                 blocks_by_node=None):
        self.store_id = store_id
        self.index = index
        # the placement-write counter AT this snapshot (see StateStore
        # placement_seq): plans computed from this snapshot carry it so
        # the applier can prove its fit re-check redundant
        self.placement_fence = placement_fence
        self._nodes = nodes
        self._jobs = jobs
        self._job_versions = job_versions
        self._evals = evals
        self._allocs = allocs
        self._deployments = deployments
        self._namespaces = namespaces
        self._node_pools = node_pools
        self._csi_volumes = csi_volumes
        self._scheduler_config = scheduler_config
        self._allocs_by_node = allocs_by_node
        self._allocs_by_job = allocs_by_job
        self._evals_by_job = evals_by_job
        # columnar block registries AT snapshot time (COW-published dicts;
        # blocks immutable): reads merge block rows with bucket rows.  A
        # block and a table row for the same id can never coexist in one
        # snapshot — materialization swaps representation atomically under
        # the store lock.
        self._alloc_blocks = alloc_blocks or {}
        self._blocks_by_job = blocks_by_job or {}
        self._blocks_by_node = blocks_by_node or {}

    # --- scheduler.State interface ---

    def nodes(self) -> List[Node]:
        return list(self._nodes.values())

    def node_table(self) -> Dict[str, Node]:
        """The node table itself, id -> node, read-only.  Every node
        write publishes a new dict, so two snapshots that hold the same
        object hold the same nodes (pack/packer.py update)."""
        return self._nodes

    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._nodes.get(node_id)

    def nodes_up(self, node_ids) -> bool:
        """Every one of `node_ids` exists and is not `down`."""
        return _all_up(self._nodes, node_ids)

    def ready_nodes_in_pool(self, datacenters: List[str],
                            pool: str = "default") -> List[Node]:
        """reference: scheduler/util.go readyNodesInDCs (+ node-pool filter)"""
        dcs = set(datacenters)
        out = []
        for n in self._nodes.values():
            if not n.ready():
                continue
            if n.datacenter not in dcs:
                continue
            if pool != "all" and n.node_pool != pool:
                continue
            out.append(n)
        return out

    def job_by_id(self, namespace: str, job_id: str) -> Optional[Job]:
        return self._jobs.get((namespace, job_id))

    def job_by_id_and_version(self, namespace: str, job_id: str,
                              version: int) -> Optional[Job]:
        return self._job_versions.get((namespace, job_id), {}).get(version)

    def jobs(self) -> List[Job]:
        return list(self._jobs.values())

    def allocs_by_job(self, namespace: str, job_id: str,
                      anystate: bool = True) -> List[Allocation]:
        out = list(self._allocs_by_job.get((namespace, job_id), {}).values())
        for b in self._blocks_by_job.get((namespace, job_id), ()):
            out.extend(b.materialize_all())
        return out

    def rows_and_blocks_by_job(self, namespace: str, job_id: str):
        """A job's allocations with its columnar blocks left as they
        are: (table rows, live blocks).  A live block's rows are as
        committed (a write to any member turns the block into table
        rows first), so a reader of counts needs its template and its
        `count`, and no row built."""
        key = (namespace, job_id)
        return (list(self._allocs_by_job.get(key, {}).values()),
                self._blocks_by_job.get(key, ()))

    def allocs_by_node(self, node_id: str) -> List[Allocation]:
        out = list(self._allocs_by_node.get(node_id, {}).values())
        for b in self._blocks_by_node.get(node_id, ()):
            out.extend(b.rows_for_node(node_id))
        return out

    def allocs_by_node_terminal(self, node_id: str,
                                terminal: bool) -> List[Allocation]:
        return [a for a in self.allocs_by_node(node_id)
                if a.terminal_status() == terminal]

    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        a = self._allocs.get(alloc_id)
        if a is None and self._alloc_blocks:
            for b in self._alloc_blocks.values():
                i = b.index_of(alloc_id)
                if i is not None:
                    return b.materialize_all()[i]
        return a

    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._evals.get(eval_id)

    def evals(self) -> List[Evaluation]:
        return list(self._evals.values())

    def evals_by_job(self, namespace: str, job_id: str) -> List[Evaluation]:
        return list(self._evals_by_job.get((namespace, job_id), {}).values())

    def deployments(self) -> List[Deployment]:
        return list(self._deployments.values())

    def latest_deployment_by_job(self, namespace: str,
                                 job_id: str) -> Optional[Deployment]:
        best = None
        for d in self._deployments.values():
            if d.namespace == namespace and d.job_id == job_id:
                if best is None or d.create_index > best.create_index:
                    best = d
        return best

    def deployment_by_id(self, dep_id: str) -> Optional[Deployment]:
        return self._deployments.get(dep_id)

    def csi_volume_by_id(self, namespace: str, vol_id: str) -> Optional[CSIVolume]:
        return self._csi_volumes.get((namespace, vol_id))

    def csi_volumes(self, namespace: Optional[str] = None):
        return [v for (ns, _), v in self._csi_volumes.items()
                if namespace is None or ns == namespace]

    def node_pool_by_name(self, name: str) -> Optional[NodePool]:
        return self._node_pools.get(name)

    def node_pools(self) -> List[NodePool]:
        return list(self._node_pools.values())

    def namespaces(self) -> List[Namespace]:
        return list(self._namespaces.values())

    def scheduler_config(self) -> SchedulerConfiguration:
        return self._scheduler_config

    # --- columnar read-path surface (api list endpoints) ---

    def alloc_blocks(self) -> List:
        """Live columnar blocks AT this snapshot.  The API's columnar
        list endpoints serve straight off these arrays — pair with
        `allocs()` for full coverage WITHOUT materialize_all()."""
        return list(self._alloc_blocks.values())

    def allocs(self) -> List[Allocation]:
        """Loose per-alloc table rows only (block members excluded —
        they live in alloc_blocks() until materialized)."""
        return list(self._allocs.values())


def _job_initial_status(job: Job) -> str:
    if job.stop:
        return JOB_STATUS_DEAD
    return JOB_STATUS_PENDING
