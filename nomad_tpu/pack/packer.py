"""Snapshot packer: host-side lowering of cluster state to device tensors.

SURVEY.md §7 P1.  Nodes become packed int32/float32 rows; every string
predicate is pre-lowered so the device kernels (nomad_tpu.ops) see only:

  - `cap`   [N, RES_DIMS] int32  usable capacity (cpu MHz, memory MB, disk
                           MB, device instances: structs.RES_NAMES),
                           node reservations already subtracted
  - `used`  [N, RES_DIMS] int32  sum of non-terminal alloc usage per node
  - `attrs` [N, A] int32   interned value id per attribute column (-1 unset)
  - `elig`  [N]    bool    node.ready() (status+drain+eligibility collapsed)
  - `dc`, `pool`, `klass` [N] int32   interned ids for the hot synthetics

plus per-eval tensors from `lower_task_groups` (constraint rows, LUTs,
affinity rows, spread specs, resource asks).

Incremental sync: `attach(store)` subscribes to state-store events and marks
dirty node rows; `update(snapshot)` rebuilds only those rows.  Device upload
and caching live in nomad_tpu.ops — these are host (numpy) buffers, the
rebuildable cache of a state snapshot (never the source of truth).
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field
from itertools import repeat
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from nomad_tpu.structs import (
    Constraint,
    Job,
    Node,
    OP_DISTINCT_HOSTS,
    OP_DISTINCT_PROPERTY,
    OP_IS_NOT_SET,
    OP_IS_SET,
    OP_REGEX,
    OP_SEMVER,
    OP_SET_CONTAINS,
    OP_SET_CONTAINS_ALL,
    OP_SET_CONTAINS_ANY,
    OP_VERSION,
    RES_DIMS,
    TaskGroup,
)
from nomad_tpu.utils.version import check_constraint as check_version

from .interner import Interner, UNSET


def _registry():
    """Process metrics registry, imported lazily (ops/engine.py's reason:
    `nomad_tpu.core`'s package __init__ reaches this package)."""
    from nomad_tpu.core.telemetry import REGISTRY
    return REGISTRY


# Device-side constraint opcodes (see ops/feasibility.py):
DOP_TRUE = 0        # padding row, always satisfied
DOP_EQ = 1          # set(col) and attrs[col] == arg
DOP_NEQ = 2         # unset(col) or attrs[col] != arg
DOP_IS_SET = 3
DOP_IS_NOT_SET = 4
DOP_LUT = 5         # set(col) and luts[arg, attrs[col]]

_TARGET_RE = re.compile(r"^\$\{(.+)\}$")

# hard ceiling on the node-table height: the water-fill kernels' packed fill
# rows encode (node row, count) in one int32 as `row << 11 | count`
# (ops/select.py pack_round_buffer), leaving 20 usable row bits.  The
# kernels assert this deep in a launch; validating HERE, at table-build
# time, turns an opaque kernel abort into a clear registration-time
# error naming the cap.
PACKED_FILL_CAP = 1 << 20


def resolve_target_key(target: str) -> str:
    """Normalize a constraint l-target to a column key
    (reference: scheduler/feasible.go resolveTarget interpolation)."""
    m = _TARGET_RE.match(target.strip())
    t = m.group(1) if m else target.strip()
    if t.startswith(("attr.", "meta.", "node.", "driver.", "hostvol.", "csi.")):
        return t
    # bare names historically resolve as attributes
    return "attr." + t


def static_port_values(resources) -> Tuple[int, ...]:
    """The static port values a resource ask names (`port "lb" { static
    = 8080 }`: its networks' reserved ports), in the order asked.  What
    an allocation of it HOLDS on its node while it lives, and what the
    kernels' port state is keyed by."""
    return tuple(p.value for net in resources.networks
                 for p in net.reserved_ports if p.value)


def tg_static_ports(tg: TaskGroup) -> Tuple[int, ...]:
    """`static_port_values` of a task group's combined ask (the group's
    networks and its tasks'), each value once; () for the common group
    that asks none, without building the combined ask."""
    if not tg.networks and not any(t.resources.networks for t in tg.tasks):
        return ()
    return tuple(dict.fromkeys(
        p.value for nets in ([t.resources.networks for t in tg.tasks]
                             + [tg.networks])
        for net in nets for p in net.reserved_ports if p.value))


def node_property_map(node: Node) -> Dict[str, str]:
    """All scheduling-relevant string properties of a node, keyed by column
    key.  This is the single place node state is flattened for the device."""
    out: Dict[str, str] = {
        "node.datacenter": node.datacenter,
        "node.class": node.node_class,
        "node.pool": node.node_pool,
        "node.region": node.region or "global",
        "node.unique.name": node.name,
        "node.unique.id": node.id,
    }
    for k, v in node.attributes.items():
        out["attr." + k] = v
    for k, v in node.meta.items():
        out["meta." + k] = v
    for drv, healthy in node.drivers.items():
        if healthy:
            out["driver." + drv] = "1"
    for vol in node.host_volumes:
        out["hostvol." + vol] = "1"
    for plug, ok in node.csi_node_plugins.items():
        if ok:
            out["csi." + plug] = "1"
    return out


@dataclass
class NodeTensors:
    """Host-side packed node state (numpy; ops layer handles device upload)."""

    node_ids: List[str]
    id_to_row: Dict[str, int]
    cap: np.ndarray          # [N,RES_DIMS] int32
    used: np.ndarray         # [N,RES_DIMS] int32
    attrs: np.ndarray        # [N,A] int32
    elig: np.ndarray         # [N] bool
    dc: np.ndarray           # [N] int32
    pool: np.ndarray         # [N] int32
    klass: np.ndarray        # [N] int32  (computed-class id)
    version: int = 0         # bumped on every row change (device cache key)
    used_version: int = 0    # bumped on usage-only deltas (separate upload
                             # key: plan applies touch used, not attrs)
    # device groups a node advertises, [N] int32.  cap's device column
    # is their instances TOGETHER, which is "the instances a request can
    # take" only where a node has one group: the batched device path
    # asks (scheduler/generic.py prepare_batch)
    dev_groups: Optional[np.ndarray] = None

    @property
    def n(self) -> int:
        return len(self.node_ids)


class ClusterPacker:
    """Maintains NodeTensors for a state store / snapshots.

    Column registry and value vocabulary grow monotonically; rows are
    rebuilt incrementally from dirty-node tracking.
    """

    def __init__(self, interner: Optional[Interner] = None) -> None:
        # guards tensor mutation (update/build/_on_allocs) and the delta
        # log against concurrent readers: in threaded mode the plan-applier
        # thread fires alloc events into _on_allocs while worker threads
        # run update() and sync device copies of `used` from the log
        self.lock = threading.RLock()
        self.interner = interner or Interner()
        self.columns: Dict[str, int] = {}
        self._tensors: Optional[NodeTensors] = None
        self._dirty: Set[str] = set()
        self._all_dirty = True
        self._attached = False
        self._store = None            # set by attach()
        self._events_index = -1       # highest store index seen via events
        # the store's node table (copy-on-write: a node write publishes a
        # new dict) the tensors were last read from, while attached
        self._node_table = None
        self._seq = 0                 # monotone tensor version source
        self._last_index = -1         # state index the tensors reflect
        self._last_store = None       # store identity the tensors reflect
        # LUT cache: (operand, rtarget) -> [lut_id, vocab_size_built_to].
        # Rows are extended in place as the vocab grows, so the device LUT
        # matrix stays O(#distinct predicates), not O(#evals).
        self._lut_cache: Dict[Tuple[str, str], List[int]] = {}
        self._luts: List[np.ndarray] = []
        # CSI volume topology LUTs: membership of the node-id vocab in a
        # volume's accessible-topology set, keyed by the topology tuple
        # itself (claims replace the volume object but share the tuple; a
        # topology CHANGE mints a new row — old rows go inert, bounded by
        # volume re-registrations)
        self._topo_luts: Dict[tuple, list] = {}
        self._lut_matrix_cache = None
        # read-only per-version caches for job_context (see there)
        self._job_ctx_cache: Dict[tuple, tuple] = {}
        self._zero_count_cache: Dict[tuple, np.ndarray] = {}
        # usage accounting: which allocs are counted in `used`, and where.
        # Alloc store events apply O(1) arithmetic deltas to t.used instead
        # of rescanning a node's alloc list (the alloc list only grows —
        # terminal allocs linger until GC — so rescans get slower forever).
        self._alloc_node: Dict[str, str] = {}       # alloc id -> node id
        self._counted: Dict[str, Dict[str, Tuple[int, ...]]] = {}
        # columnar-block usage, tracked as UNITS (block id -> the block,
        # whose node table and node_counts() say where its allocs count):
        # an AllocBlock event is one vectorized scatter and ONE entry, no
        # per-alloc and no per-node ledger entries.  When the store
        # materializes a block (a member is about to be updated), the
        # BlockMaterialized event migrates its rows into the per-alloc
        # ledger with zero net usage change.
        self._block_counted: Dict[str, object] = {}
        # replay log of usage deltas for device-resident `used` tensors:
        # entries are (used_version, rows, vals) or (used_version, None,
        # None) — the sentinel marks a full/row rescan (device copies must
        # re-upload).  Bounded; consumers older than the window re-upload.
        self._delta_log: List[Tuple[int, Optional[np.ndarray],
                                    Optional[np.ndarray]]] = []
        self._used_seq = 0
        # row-dirty log for NODE-TABLE versions (t.version): entries are
        # (version, rows) where rows is the np.int64 array of node rows a
        # dirty-row refresh rewrote, or None for a full rebuild / row
        # remap.  Mesh engines use it to re-upload only the SHARDS a
        # node write touched instead of the whole padded node tensor
        # (ops/engine._node_arrays); bounded like the usage delta log.
        self._row_dirty_log: List[Tuple[int, Optional[np.ndarray]]] = []
        # used-version sentinels (rows=None in _delta_log) that came from
        # a dirty-ROW refresh carry their refreshed rows here, so a
        # device `used` copy can be healed shard-wise instead of fully
        # re-uploaded (used_sync_rows_since)
        self._used_sentinel_rows: Dict[int, Optional[np.ndarray]] = {}
        self.lut_epoch = 0
        # static-port ledger (ISSUE 38): which nodes HOLD a static port
        # value, kept by the same alloc and block events as `used`, so
        # the kernels can mask them (ops/engine.py static_port_mask).
        # value -> {node id: live holders}; an alloc's entry is (node id,
        # values), a block unit's (values, its node table); a node's own
        # reservation counts as one holder; a value's version moves with
        # every change to its holders
        self._port_holders: Dict[int, Dict[str, int]] = {}
        self._alloc_ports: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self._block_ports: Dict[str, Tuple[Tuple[int, ...], List[str]]] = {}
        self._node_ports: Dict[str, Tuple[int, ...]] = {}
        self._port_versions: Dict[int, int] = {}

    # ------------------------------------------------------------ columns

    def ensure_column(self, key: str) -> int:
        col = self.columns.get(key)
        if col is None:
            col = len(self.columns)
            self.columns[key] = col
            t = self._tensors
            if t is not None and t.attrs.shape[1] < len(self.columns):
                t.attrs = np.concatenate(
                    [t.attrs, np.full((t.attrs.shape[0], 1), UNSET, np.int32)],
                    axis=1)
        return col

    # -------------------------------------------------- static-port ledger

    def _port_hold(self, values, node_id: str, by: int) -> None:
        """`by` more (or fewer) holders of each of `values` on a node
        (the ledger's writers all run under `self.lock`, as `_fill_row`
        does)."""
        for v in values:
            held = self._port_holders.setdefault(v, {})
            left = held.get(node_id, 0) + by
            if left > 0:
                held[node_id] = left
            else:
                held.pop(node_id, None)
            self._port_versions[v] = self._port_versions.get(v, 0) + 1

    def _hold_alloc_ports(self, alloc_id: str, node_id: str,
                                 resources) -> None:
        """A live allocation on a node: it holds the static values its
        resources ask, if any."""
        if resources.networks:
            static = static_port_values(resources)
            if static:
                self._alloc_ports[alloc_id] = (node_id, static)
                self._port_hold(static, node_id, 1)

    def _forget_ports(self) -> None:
        """A rebuild re-reads every holder from the snapshot."""
        for v in self._port_holders:
            self._port_versions[v] = self._port_versions.get(v, 0) + 1
        self._port_holders.clear()
        self._alloc_ports.clear()
        self._block_ports.clear()
        self._node_ports.clear()

    def static_port_holders(self, value: int) -> Tuple[int, List[str]]:
        """(version, node ids) of the nodes where a live allocation, or
        the node's own reservation, holds static port `value`: what a
        static ask of it must avoid.  The version moves with every
        change to the holders, so a mask built from them is good for as
        long as it and the node table's version stand."""
        with self.lock:
            return (self._port_versions.get(value, 0),
                    list(self._port_holders.get(value, ())))

    def static_port_rows(self, t: "NodeTensors", value: int
                         ) -> Tuple[int, List[int]]:
        """`static_port_holders` as rows of `t`."""
        version, holders = self.static_port_holders(value)
        return version, [t.id_to_row[nid] for nid in holders
                         if nid in t.id_to_row]

    # ------------------------------------------------------- store attach

    def attach(self, store) -> None:
        """Subscribe to a StateStore for dirty-row tracking."""

        self._attached = True
        self._store = store

        def on_event(topic: str, index: int, payload) -> None:
            # every branch under self.lock: _update_locked iterates _dirty
            # and readers rely on _events_index/ledger advancing together
            with self.lock:
                self._events_index = max(self._events_index, index)
                if topic == "Node":
                    nid = payload if isinstance(payload, str) else payload.id
                    self._dirty.add(nid)
                elif topic == "Allocations":
                    self._on_allocs_locked(payload)
                elif topic == "AllocBlock":
                    self._on_block_locked(payload)
                elif topic == "BlockMaterialized":
                    self._on_block_materialized_locked(payload)
                elif topic == "Restore":
                    # full-state replacement: every tensor and the usage
                    # ledger are stale; next update() rebuilds from scratch
                    self._all_dirty = True
                    self._counted.clear()
                    self._alloc_node.clear()
                    self._block_counted.clear()
                    self._forget_ports()

        store.subscribe(on_event)

    def _on_allocs_locked(self, allocs) -> None:
        """Apply a batch of alloc upserts as usage deltas (plan applies and
        client status updates both land here).  One np.add.at scatter for
        the whole batch instead of per-alloc numpy scalar writes."""
        t = self._tensors
        if t is None:
            return                      # next build() scans from scratch
        rows: List[int] = []
        vals: List[Tuple[int, ...]] = []
        alloc_node = self._alloc_node
        counted = self._counted
        id_to_row = t.id_to_row
        # bulk plans share ONE resources object across a whole round:
        # build its usage tuple once, not per alloc
        res_cache: Dict[int, Tuple[int, ...]] = {}
        alloc_ports = self._alloc_ports
        for a in allocs:
            aid = a.id
            old_node = alloc_node.get(aid)
            if old_node is not None:
                res = counted[old_node].pop(aid, None)
                del alloc_node[aid]
                if res is not None:
                    row = id_to_row.get(old_node)
                    if row is not None:
                        rows.append(row)
                        vals.append(tuple(-v for v in res))
                held = alloc_ports.pop(aid, None)
                if held is not None:
                    self._port_hold(held[1], held[0], -1)
            nid = a.node_id
            if nid and not a.terminal_status():
                r = a.resources
                self._hold_alloc_ports(aid, nid, r)
                if a.allocated_devices:
                    res = a.usage()     # instances are the alloc's own
                else:
                    res = res_cache.get(id(r))
                    if res is None:
                        res_cache[id(r)] = res = a.usage()
                c = counted.get(nid)
                if c is None:
                    counted[nid] = c = {}
                c[aid] = res
                alloc_node[aid] = nid
                row = id_to_row.get(nid)
                if row is not None:
                    rows.append(row)
                    vals.append(res)
        if rows:
            r = np.asarray(rows, np.intp)
            v = np.asarray(vals, np.int32)
            np.add.at(t.used, r, v)
            t.used_version = self._log_delta(r, v)
        # else: the batch touched no tensor rows — leave the version alone
        # so device caches stay hits and the bounded replay window isn't
        # consumed by no-op entries

    def _on_block_locked(self, block) -> None:
        """A columnar block committed: ONE vectorized usage scatter over
        its unique nodes (the block path's whole point — no per-alloc
        python work), tracked as a unit in _block_counted."""
        self._block_counted[block.id] = block
        if block.template.resources.networks:
            static = static_port_values(block.template.resources)
            if static:
                # (every node of a block's table holds a row of it)
                self._block_ports[block.id] = (static, block.node_table)
                for nid, c in zip(block.node_table,
                                  block.node_counts().tolist()):
                    self._port_hold(static, nid, c)
        t = self._tensors
        if t is None:
            return
        node_table = block.node_table
        r = np.fromiter(map(t.id_to_row.get, node_table, repeat(-1)),
                        np.intp, len(node_table))
        v = (block.node_counts()[:, None]
             * block.resources_tuple()).astype(np.int32)
        if r.size and r.min() < 0:      # nodes the tensors do not hold
            known = r >= 0
            r, v = r[known], v[known]
        if r.size:
            np.add.at(t.used, r, v)
            t.used_version = self._log_delta(r, v)

    def _on_block_materialized_locked(self, block) -> None:
        """Representation change only (block -> table rows): migrate the
        unit entry into the per-alloc ledger with ZERO usage delta so the
        follow-up Allocations events find their predecessors.  A block
        whose unit a rebuild re-anchored away (its rows were counted per
        alloc from the snapshot) has nothing to migrate."""
        if self._block_counted.pop(block.id, None) is None:
            return
        res = block.resources_tuple()
        alloc_node = self._alloc_node
        counted = self._counted
        static = self._block_ports.pop(block.id, (None,))[0]
        for a in block.materialize_all():
            aid = a.id
            if aid in alloc_node:
                continue        # a rescan already counted it per alloc
            nid = a.node_id
            c = counted.get(nid)
            if c is None:
                counted[nid] = c = {}
            c[aid] = res
            alloc_node[aid] = nid
            if static:
                # the unit's holders become the rows' own, one for one
                self._alloc_ports[aid] = (nid, static)

    def _log_delta(self, rows, vals, refreshed_rows=None) -> int:
        """Append one used-version bump to the replay log.  `rows is None`
        marks a full/row rescan (device copies must re-upload).  Versions
        in the log are consecutive, which makes continuity provable.

        `refreshed_rows`: for a sentinel that came from a dirty-ROW
        refresh (not a full rebuild), the node rows whose usage was
        re-anchored — lets used_sync_rows_since() heal a device copy
        shard-wise instead of forcing the full re-upload."""
        self._used_seq += 1
        log = self._delta_log
        log.append((self._used_seq, rows, vals))
        if rows is None:
            self._used_sentinel_rows[self._used_seq] = refreshed_rows
        if len(log) > 256:
            dropped = log[:128]
            del log[:128]
            for v, r, _ in dropped:
                if r is None:
                    self._used_sentinel_rows.pop(v, None)
        return self._used_seq

    def used_deltas_since(self, version: int
                          ) -> Optional[List[Tuple[np.ndarray, np.ndarray]]]:
        """Usage deltas with used_version > `version`, oldest first, or
        None when a rescan intervened / the window was trimmed (the caller
        must re-upload the full tensor)."""
        if version == self._used_seq:
            return []
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        expect = version + 1
        for v, rows, vals in self._delta_log:
            if v < expect:
                continue
            if v != expect or rows is None:
                return None
            out.append((rows, vals))
            expect += 1
        if expect != self._used_seq + 1:
            return None
        return out

    def used_sync_rows_since(self, version: int) -> Optional[np.ndarray]:
        """Union of node rows whose device `used` copy at `version` may
        be stale: real-delta rows plus dirty-row-refresh sentinel rows,
        oldest entries first.  None when any entry since `version` lacks
        row information (full rebuild / trimmed window) — the caller
        must re-upload the whole tensor.  A mesh engine turns this into
        a per-SHARD patch (ops/engine._used_device)."""
        if version == self._used_seq:
            return np.empty(0, np.int64)
        parts: List[np.ndarray] = []
        expect = version + 1
        for v, rows, _ in self._delta_log:
            if v < expect:
                continue
            if v != expect:
                return None
            if rows is None:
                srows = self._used_sentinel_rows.get(v)
                if srows is None:
                    return None
                parts.append(np.asarray(srows, np.int64))
            else:
                parts.append(np.asarray(rows, np.int64))
            expect += 1
        if expect != self._used_seq + 1:
            return None
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))

    def _log_row_dirty(self, rows: Optional[np.ndarray]) -> None:
        """Record which node rows version `self._seq` rewrote (None =
        full rebuild / row remap).  Bounded like the usage delta log."""
        log = self._row_dirty_log
        log.append((self._seq, rows))
        if len(log) > 256:
            del log[:128]

    def node_rows_dirty_since(self, version: int) -> Optional[np.ndarray]:
        """Node rows rewritten by table versions > `version` (row mapping
        unchanged throughout), or None when a full rebuild / row remap
        intervened or the window was trimmed — the caller must re-upload
        every node tensor."""
        t = self._tensors
        if t is None:
            return None
        if version == t.version:
            return np.empty(0, np.int64)
        parts: List[np.ndarray] = []
        expect = version + 1
        for v, rows in self._row_dirty_log:
            if v < expect:
                continue
            if v != expect or rows is None:
                return None
            parts.append(np.asarray(rows, np.int64))
            expect += 1
        if expect != t.version + 1:
            return None
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))

    # ------------------------------------------------------------- build

    def _fresh_enough(self, snapshot) -> bool:
        return (not self._attached or self._store is None
                or getattr(snapshot, "index", -1) >= self._events_index)

    def build(self, snapshot) -> NodeTensors:
        """Full rebuild from a snapshot."""
        snapshot = self._refresh_snapshot(snapshot)
        with self.lock:
            return self._build_locked(snapshot)

    def _refresh_snapshot(self, snapshot):
        """When events have advanced the usage ledger past `snapshot`,
        swap in a fresh snapshot from the attached store: a rebuild from
        an older snapshot would reset tensors+ledger to a state whose
        missing events never re-fire (persistent ghost/lost usage).
        store.snapshot() must be called OUTSIDE self.lock — events publish
        under the store lock and then take self.lock in _on_allocs, so the
        reverse order would deadlock.  Retried because a write can land
        between snapshot() and the locked check; each retry observes a
        strictly newer index, so this converges immediately in practice."""
        for _ in range(4):
            with self.lock:
                if self._fresh_enough(snapshot):
                    return snapshot
            snapshot = self._store.snapshot()
        return snapshot

    def _build_locked(self, snapshot) -> NodeTensors:
        nodes = snapshot.nodes()
        n = len(nodes)
        if n >= PACKED_FILL_CAP:
            raise ValueError(
                f"cluster has {n} nodes; the packed-fill encoding "
                f"supports at most {PACKED_FILL_CAP - 1} "
                f"(PACKED_FILL_CAP = 2^20 rows — ops/select.py packs "
                f"node rows into 20 bits of each fill word)")
        # discover all columns first so attrs has stable width this build
        prop_maps = [node_property_map(nd) for nd in nodes]
        for pm in prop_maps:
            for k in pm:
                self.ensure_column(k)
        a = len(self.columns)
        t = NodeTensors(
            node_ids=[nd.id for nd in nodes],
            id_to_row={nd.id: i for i, nd in enumerate(nodes)},
            cap=np.zeros((n, RES_DIMS), np.int32),
            used=np.zeros((n, RES_DIMS), np.int32),
            attrs=np.full((n, a), UNSET, np.int32),
            elig=np.zeros(n, bool),
            dc=np.zeros(n, np.int32),
            pool=np.zeros(n, np.int32),
            klass=np.zeros(n, np.int32),
            dev_groups=np.zeros(n, np.int32),
        )
        self._alloc_node.clear()
        self._counted.clear()
        self._block_counted.clear()
        self._forget_ports()
        for i, nd in enumerate(nodes):
            self._fill_row(t, i, nd, snapshot, prop_maps[i])
        self._seq += 1
        t.version = self._seq
        self._log_row_dirty(None)
        t.used_version = self._log_delta(None, None)
        self._tensors = t
        self._node_table = snapshot.node_table() if self._attached else None
        self._dirty.clear()
        self._all_dirty = False
        self._last_index = getattr(snapshot, "index", -1)
        self._last_store = getattr(snapshot, "store_id", None)
        return t

    def update(self, snapshot) -> NodeTensors:
        """Incremental: rebuild only dirty rows; add/remove nodes as needed.

        Without `attach()` there is no dirty tracking, so any change of
        state index (or of the backing store identity) forces a full rebuild
        (correct, just slower); an unchanged (store, index) returns the
        cached tensors as-is."""
        snapshot = self._refresh_snapshot(snapshot)
        with self.lock:
            return self._update_locked(snapshot)

    def _update_locked(self, snapshot) -> NodeTensors:
        t = self._tensors
        if t is None or self._all_dirty:
            return self._build_locked(snapshot)
        if getattr(snapshot, "store_id", None) != self._last_store:
            return self._build_locked(snapshot)
        if not self._attached:
            if getattr(snapshot, "index", -1) == self._last_index:
                return t
            return self._build_locked(snapshot)
        # membership by event: every node add, update and delete on the
        # attached store lands in `_dirty` ("Node") and a restore sets
        # `_all_dirty`, so only a dirty id can have joined or left.  The
        # store publishes a fresh node table on every node write, so a
        # table that is not the one the tensors were last read from, with
        # no event to say why, came from a feed that emits none (the
        # replica's StateStore.apply_export): there the walk stays.
        id_to_row = t.id_to_row
        node_table = snapshot.node_table()
        if self._dirty:
            checked = len(self._dirty)
            moved = any((nid in id_to_row) != (nid in node_table)
                        for nid in self._dirty)
        elif node_table is self._node_table:
            checked, moved = 0, False
        else:
            checked = len(node_table)
            moved = (checked != len(id_to_row)
                     or not all(map(id_to_row.__contains__, node_table)))
        _registry().inc("nomad.packer.membership_checked", checked)
        if moved:
            # membership change: full rebuild keeps row mapping simple
            return self._build_locked(snapshot)
        self._node_table = node_table
        if not self._dirty:
            self._last_index = getattr(snapshot, "index", self._last_index)
            return t
        refreshed: List[int] = []
        unit_used = self._unit_usage_locked(self._dirty)
        for nid in self._dirty:
            row = t.id_to_row.get(nid)
            if row is None:
                continue
            nd = snapshot.node_by_id(nid)
            if nd is None:
                continue
            pm = node_property_map(nd)
            for k in pm:
                self.ensure_column(k)
            t.attrs[row, :] = UNSET
            self._fill_row(t, row, nd, snapshot, pm,
                           unit_used=unit_used.get(nid, (0,) * RES_DIMS))
            refreshed.append(row)
        self._seq += 1
        t.version = self._seq
        rows_arr = np.asarray(refreshed, np.int64)
        self._log_row_dirty(rows_arr)
        t.used_version = self._log_delta(None, None,
                                         refreshed_rows=rows_arr)
        self._dirty.clear()
        self._last_index = getattr(snapshot, "index", self._last_index)
        return t

    def _unit_usage_locked(self, node_ids: Set[str]
                           ) -> Dict[str, List[int]]:
        """What the block units count on each of `node_ids`: one pass
        over the units, a C-level membership test over each node table."""
        out: Dict[str, List[int]] = {}
        for block in self._block_counted.values():
            table = block.node_table
            if node_ids.isdisjoint(table):
                continue
            hit = np.flatnonzero(np.fromiter(
                map(node_ids.__contains__, table), bool, len(table)))
            res = block.resources_tuple()
            for bi, c in zip(hit.tolist(),
                             block.node_counts()[hit].tolist()):
                used = out.setdefault(table[bi], [0] * RES_DIMS)
                for d in range(RES_DIMS):
                    used[d] += res[d] * c
        return out

    def _fill_row(self, t: NodeTensors, i: int, nd: Node, snapshot, pm,
                  unit_used: Optional[Sequence[int]] = None) -> None:
        """`unit_used`: the dirty-row refill (usage from the ledger, the
        block units' share of it handed in); None: a full rescan."""
        t.cap[i] = nd.capacity()
        t.dev_groups[i] = len(nd.resources.devices)
        own = tuple(nd.reserved.reserved_ports) + tuple(
            p.value for net in nd.resources.networks
            for p in net.reserved_ports)
        was = self._node_ports.pop(nd.id, ())
        if own != was:
            self._port_hold(was, nd.id, -1)
            self._port_hold(own, nd.id, 1)
        if own:
            self._node_ports[nd.id] = own
        if unit_used is not None:
            # dirty-row refill while attached: the counted/_alloc_node
            # ledger is advanced synchronously by Allocations events and
            # may be AHEAD of the worker's snapshot — re-anchoring from
            # the snapshot would durably desync it (a terminal alloc's
            # removal event never re-fires).  Usage comes from the ledger;
            # node attrs/capacity come from the snapshot's node object.
            used = list(unit_used)
            for res in self._counted.get(nd.id, {}).values():
                for d in range(RES_DIMS):
                    used[d] += res[d]
            t.used[i] = used
        else:
            # full usage rescan for this row: re-anchor the delta accounting
            old = self._counted.get(nd.id)
            if old:
                for aid in old:
                    if self._alloc_node.get(aid) == nd.id:
                        del self._alloc_node[aid]
            # block rows come back per-alloc from the snapshot read below;
            # the block UNITS went with the rest (_build_locked, the one
            # caller of a full rescan, cleared them)
            counted: Dict[str, Tuple[int, ...]] = {}
            used = [0] * RES_DIMS
            for alc in snapshot.allocs_by_node(nd.id):
                if alc.terminal_status():
                    continue
                counted[alc.id] = res = alc.usage()
                for d in range(RES_DIMS):
                    used[d] += res[d]
                self._alloc_node[alc.id] = nd.id
                self._hold_alloc_ports(alc.id, nd.id, alc.resources)
            self._counted[nd.id] = counted
            t.used[i] = used
        t.elig[i] = nd.ready()
        t.dc[i] = self.interner.intern(nd.datacenter)
        t.pool[i] = self.interner.intern(nd.node_pool)
        t.klass[i] = self.interner.intern(nd.computed_class or nd.id)
        for k, v in pm.items():
            t.attrs[i, self.columns[k]] = self.interner.intern(v)

    # ------------------------------------------------- constraint lowering

    def lower_predicate(self, operand: str, rtarget: str) -> Tuple[int, int]:
        """Lower (operand, rtarget) to a device (op, arg) pair.  LUT-class
        predicates are evaluated over the vocab host-side and cached."""
        if operand in ("=", "==", "is"):
            return DOP_EQ, self.interner.lookup(rtarget)
        if operand in ("!=", "not"):
            return DOP_NEQ, self.interner.lookup(rtarget)
        if operand == OP_IS_SET:
            return DOP_IS_SET, 0
        if operand == OP_IS_NOT_SET:
            return DOP_IS_NOT_SET, 0
        return DOP_LUT, self._lut_id(operand, rtarget)

    def _lut_id(self, operand: str, rtarget: str) -> int:
        key = (operand, rtarget)
        v = len(self.interner)
        hit = self._lut_cache.get(key)
        if hit is not None:
            lid, built = hit
            if built < v:
                # vocab grew: evaluate only the new values, extend in place
                pred = _string_predicate(operand, rtarget)
                ext = np.fromiter(
                    (pred(self.interner.string(i)) for i in range(built, v)),
                    dtype=bool, count=v - built)
                self._luts[lid] = np.concatenate([self._luts[lid], ext])
                hit[1] = v
                self.lut_epoch += 1
            return lid
        pred = _string_predicate(operand, rtarget)
        lut = self.interner.build_lut(pred)
        lid = len(self._luts)
        self._luts.append(lut)
        self._lut_cache[key] = [lid, v]
        self.lut_epoch += 1
        return lid

    def _csi_topology_lut(self, vol) -> int:
        """LUT row: is a node-id vocab entry inside `vol`'s accessible
        topology?  Same grow-in-place discipline as _lut_id.

        Keyed by (namespace, id) with the topology TUPLE compared by
        identity-then-equality inside the entry: hashing a 10k-entry
        node-id tuple on every lookup cost ~0.2ms per eval at bench scale
        (claims replace the volume object but share the tuple, so the
        identity check almost always short-circuits).  A topology CHANGE
        still mints a new row — old rows go inert, bounded by volume
        re-registrations."""
        key = (vol.namespace, vol.id)
        v = len(self.interner)
        entries = self._topo_luts.setdefault(key, [])
        for hit in entries:           # identity-first scan: a volume has
            lid, built, topo = hit    # few distinct topologies ever, and
            # a claim update shares the tuple, so `is` usually matches —
            # an ALTERNATING topology (failover flap) reuses its old row
            # instead of minting new ones forever (code-review r5)
            if topo is vol.topology_node_ids \
                    or topo == vol.topology_node_ids:
                if built < v:
                    allowed = set(vol.topology_node_ids)
                    ext = np.fromiter(
                        (self.interner.string(i) in allowed
                         for i in range(built, v)),
                        dtype=bool, count=v - built)
                    self._luts[lid] = np.concatenate([self._luts[lid], ext])
                    hit[1] = v
                    self.lut_epoch += 1
                return lid
        allowed = set(vol.topology_node_ids)
        lut = self.interner.build_lut(lambda s: s in allowed)
        lid = len(self._luts)
        self._luts.append(lut)
        entries.append([lid, v, vol.topology_node_ids])
        self.lut_epoch += 1
        return lid

    def lut_matrix(self) -> np.ndarray:
        """[L, V] bool, padded to the current vocab size (cached per
        (epoch, vocab) — rebuilding cost ~0.1ms per eval at bench scale
        and the matrix is read-only by convention)."""
        v = len(self.interner)
        cached = self._lut_matrix_cache
        if cached is not None and cached[0] == (self.lut_epoch, v):
            return cached[1]
        if not self._luts:
            out = np.zeros((1, max(v, 1)), bool)
            self._lut_matrix_cache = ((self.lut_epoch, v), out)
            return out
        out = np.zeros((len(self._luts), max(v, 1)), bool)
        for i, lut in enumerate(self._luts):
            out[i, :len(lut)] = lut
        self._lut_matrix_cache = ((self.lut_epoch, v), out)
        return out

    # --------------------------------------------------------- TG lowering

    def lower_task_groups(self, job: Job, tgs: Sequence[TaskGroup],
                          snapshot=None) -> "TGTensors":
        """Pack the placeable unit: per-TG resource asks + constraint rows +
        affinity rows.  Job-level constraints/affinities apply to every TG;
        task-level ones are merged up (the TG is the placement unit).
        distinct_hosts / distinct_property become dynamic specs handled by
        the selection kernel, not static rows."""
        g = len(tgs)
        req = np.zeros((g, RES_DIMS), np.int32)
        dh_limit = np.zeros(g, np.int32)
        rows: List[List[Tuple[int, int, int]]] = []
        aff_rows: List[List[Tuple[int, int, int, int]]] = []
        # distinct_property specs: (col, limit, scope) where scope is None
        # for job-level (counts all job allocs) or the TG name (counts only
        # that TG's allocs) — consumed by lower_distinct.
        distinct: List[List[Tuple[int, int, Optional[str]]]] = []
        for gi, tg in enumerate(tgs):
            ask = tg.combined_resources()
            # the device column: the instances the group's requests ask
            # together (a count; which ones is the host's to assign)
            req[gi] = (ask.cpu, ask.memory_mb, ask.disk_mb,
                       sum(max(d.count, 1) for task in tg.tasks
                           for d in task.resources.devices))
            crows: List[Tuple[int, int, int]] = []
            dist: List[Tuple[int, int, Optional[str]]] = []
            for task in tg.tasks:
                if task.driver:
                    crows.append((self.ensure_column("driver." + task.driver),
                                  DOP_EQ, self.interner.intern("1")))
            # volume feasibility (reference: HostVolumeChecker /
            # CSIVolumeChecker): host volumes require the named volume on
            # the node; CSI volumes require the volume's controller plugin
            # on the node (topology/claims are re-checked at plan apply)
            for vreq in tg.volumes.values():
                if vreq.type == "host" and vreq.source:
                    crows.append((
                        self.ensure_column("hostvol." + vreq.source),
                        DOP_EQ, self.interner.intern("1")))
                elif vreq.type == "csi" and vreq.source:
                    vol = (snapshot.csi_volume_by_id(job.namespace,
                                                     vreq.source)
                           if snapshot is not None else None)
                    if vol is not None and vol.plugin_id:
                        crows.append((
                            self.ensure_column("csi." + vol.plugin_id),
                            DOP_EQ, self.interner.intern("1")))
                    if vol is not None and vol.topology_node_ids:
                        # accessible-topology feasibility (reference:
                        # CSIVolumeChecker topology segments): the volume
                        # is reachable only from its topology's nodes —
                        # a LUT row over the interned node-id column
                        crows.append((
                            self.ensure_column("node.unique.id"),
                            DOP_LUT, self._csi_topology_lut(vol)))
                    if vol is not None:
                        # single-node access modes attach to ONE node:
                        # live claims (readers included) pin feasibility
                        # to it (reference: csi.go single-node modes via
                        # CSIVolumeChecker; the applier re-checks)
                        pin = vol.pinned_node()
                        if pin:
                            crows.append((
                                self.ensure_column("node.unique.id"),
                                DOP_EQ, self.interner.intern(pin)))
            for scope, constraints in (
                    (None, job.constraints),
                    (tg.name, list(tg.constraints)
                     + [c for task in tg.tasks for c in task.constraints])):
                for c in constraints:
                    lowered = self._lower_constraint(c)
                    if lowered is not None:
                        crows.append(lowered)
                    elif c.operand == OP_DISTINCT_HOSTS:
                        dh_limit[gi] = max(_int_or(c.rtarget, 1), 1)
                    elif c.operand == OP_DISTINCT_PROPERTY:
                        dist.append((
                            self.ensure_column(resolve_target_key(c.ltarget)),
                            max(_int_or(c.rtarget, 1), 1), scope))
            arows: List[Tuple[int, int, int, int]] = []
            affinities = (list(job.affinities) + list(tg.affinities)
                          + [a for task in tg.tasks for a in task.affinities])
            for af in affinities:
                op, arg = self.lower_predicate(af.operand, af.rtarget)
                col = self.ensure_column(resolve_target_key(af.ltarget))
                arows.append((col, op, arg, int(af.weight)))
            rows.append(crows)
            aff_rows.append(arows)
            distinct.append(dist)

        c_max = max([len(r) for r in rows] + [1])
        a_max = max([len(r) for r in aff_rows] + [1])
        con = np.zeros((g, c_max, 3), np.int32)   # (col, op, arg); op 0 pad
        aff = np.zeros((g, a_max, 4), np.int32)
        for gi in range(g):
            for ci, row in enumerate(rows[gi]):
                con[gi, ci] = row
            for ai, row in enumerate(aff_rows[gi]):
                aff[gi, ai] = row
        return TGTensors(
            names=[tg.name for tg in tgs], req=req, con=con, aff=aff,
            dh_limit=dh_limit, distinct=distinct, luts=self.lut_matrix(),
        )

    def lower_distinct(self, job: Job, tgs: Sequence[TaskGroup],
                       tg_tensors: "TGTensors", tensors: NodeTensors,
                       snapshot) -> "DistinctTensors":
        """Pack distinct_property constraints into per-value count state the
        selection kernel enforces and updates as the plan grows
        (reference: scheduler/propertyset.go).  Nodes lacking the property
        are infeasible for the constraint, matching the reference."""
        n = tensors.n
        # dedupe (col, limit, scope) rows; remember which TGs they apply to
        specs: Dict[Tuple[int, int, Optional[str]], List[int]] = {}
        for gi, dist in enumerate(tg_tensors.distinct):
            for spec in dist:
                specs.setdefault(spec, []).append(gi)
        if not specs or n == 0:
            return DistinctTensors.empty(len(tgs), n)
        d = len(specs)
        nodeval = np.full((d, n), -1, np.int32)
        limit = np.zeros(d, np.int32)
        apply = np.zeros((len(tgs), d), bool)
        counts_rows: List[np.ndarray] = []
        k_max = 1
        for di, ((col, lim, scope), gis) in enumerate(specs.items()):
            col_vals = (tensors.attrs[:, col] if col < tensors.attrs.shape[1]
                        else np.full(n, UNSET, np.int32))
            uniq = [int(v) for v in np.unique(col_vals) if v != UNSET]
            local = {v: i for i, v in enumerate(uniq)}
            k = max(len(uniq), 1)
            k_max = max(k_max, k)
            remap = np.full(len(self.interner) + 1, -1, np.int32)
            for v, li in local.items():
                remap[v] = li
            nodeval[di] = np.where(col_vals == UNSET, -1, remap[col_vals])
            limit[di] = lim
            for gi in gis:
                apply[gi, di] = True
            counts = np.zeros(k, np.int32)
            for alc in snapshot.allocs_by_job(job.namespace, job.id):
                if alc.terminal_status():
                    continue
                if scope is not None and alc.task_group != scope:
                    continue
                row = tensors.id_to_row.get(alc.node_id)
                if row is not None and nodeval[di, row] >= 0:
                    counts[nodeval[di, row]] += 1
            counts_rows.append(counts)
        cnt = np.zeros((d, k_max), np.int32)
        for di, c in enumerate(counts_rows):
            cnt[di, :len(c)] = c
        return DistinctTensors(pd_nodeval=nodeval, pd_limit=limit,
                               pd_apply=apply, pd_counts0=cnt)

    def _lower_constraint(self, c: Constraint
                          ) -> Optional[Tuple[int, int, int]]:
        if c.operand in (OP_DISTINCT_HOSTS, OP_DISTINCT_PROPERTY):
            return None
        op, arg = self.lower_predicate(c.operand, c.rtarget)
        col = self.ensure_column(resolve_target_key(c.ltarget))
        return (col, op, arg)

    def job_context(self, job: Job, snapshot, tensors: NodeTensors,
                    ) -> "JobContext":
        """Per-eval dynamic vectors the kernels need beyond static state:
        dc/pool masks and the job's current per-node alloc counts (for
        anti-affinity and distinct_hosts).

        The masks and the all-zeros count vector are cached per tensor
        version and shared READ-ONLY across evals (engine callers copy
        before mutating): a 384-eval batch over identical datacenters
        paid 384 `np.isin` passes + 384 zero-fills of [N] — a third of
        the whole host build at bench scale."""
        key = (tensors.version, tuple(job.datacenters), job.node_pool)
        cached = self._job_ctx_cache.get(key)
        if cached is not None:
            dc_mask, pool_mask = cached
        else:
            dc_ids = np.array(
                [self.interner.intern(d) for d in job.datacenters],
                np.int32)
            dc_mask = np.isin(tensors.dc, dc_ids)
            if job.node_pool in ("", "all"):
                pool_mask = np.ones(tensors.n, bool)
            else:
                pool_mask = (tensors.pool
                             == self.interner.intern(job.node_pool))
            if len(self._job_ctx_cache) > 128:
                self._job_ctx_cache.clear()
            self._job_ctx_cache[key] = (dc_mask, pool_mask)
        live = [alc for alc in snapshot.allocs_by_job(job.namespace, job.id)
                if not alc.terminal_status()]
        if not live:
            zkey = (tensors.version, tensors.n)
            job_count = self._zero_count_cache.get(zkey)
            if job_count is None:
                job_count = np.zeros(tensors.n, np.int32)
                self._zero_count_cache = {zkey: job_count}
        else:
            job_count = np.zeros(tensors.n, np.int32)
            for alc in live:
                row = tensors.id_to_row.get(alc.node_id)
                if row is not None:
                    job_count[row] += 1
        return JobContext(dc_mask=dc_mask, pool_mask=pool_mask,
                          job_count=job_count)


@dataclass
class TGTensors:
    names: List[str]
    req: np.ndarray                      # [G,3] int32
    con: np.ndarray                      # [G,C,3] int32 (col, op, arg)
    aff: np.ndarray                      # [G,Af,4] int32 (col, op, arg, w)
    dh_limit: np.ndarray                 # [G] int32 distinct_hosts (0=none)
    distinct: List[List[Tuple[int, int, Optional[str]]]]
    luts: np.ndarray                     # [L,V] bool


@dataclass
class DistinctTensors:
    """distinct_property count state (reference: propertyset.go)."""
    pd_nodeval: np.ndarray               # [D,N] int32 local value idx (-1)
    pd_limit: np.ndarray                 # [D] int32 (0 = inert padding)
    pd_apply: np.ndarray                 # [G,D] bool
    pd_counts0: np.ndarray               # [D,K] int32

    @staticmethod
    def empty(g: int, n: int) -> "DistinctTensors":
        return DistinctTensors(
            pd_nodeval=np.full((1, max(n, 1)), -1, np.int32),
            pd_limit=np.zeros(1, np.int32),
            pd_apply=np.zeros((max(g, 1), 1), bool),
            pd_counts0=np.zeros((1, 1), np.int32),
        )


@dataclass
class JobContext:
    dc_mask: np.ndarray                  # [N] bool
    pool_mask: np.ndarray                # [N] bool
    job_count: np.ndarray                # [N] int32


def _int_or(s: str, default: int) -> int:
    try:
        return int(s)
    except (TypeError, ValueError):
        return default


def _split_set(s: str) -> List[str]:
    return [p.strip() for p in s.split(",") if p.strip()]


def _string_predicate(operand: str, rtarget: str):
    """Host-side evaluation of LUT-class predicates over vocab strings
    (reference: scheduler/feasible.go checkConstraint/checkLexicalOrder/
    checkVersionMatch/checkRegexpMatch/checkSetContainsAll)."""
    if operand == OP_REGEX:
        try:
            rx = re.compile(rtarget)
        except re.error:
            return lambda v: False
        return lambda v: rx.search(v) is not None
    if operand == OP_VERSION:
        return lambda v: check_version(v, rtarget, strict=False)
    if operand == OP_SEMVER:
        return lambda v: check_version(v, rtarget, strict=True)
    if operand in (OP_SET_CONTAINS, OP_SET_CONTAINS_ALL):
        want = _split_set(rtarget)
        return lambda v: set(want) <= {p.strip() for p in v.split(",")}
    if operand == OP_SET_CONTAINS_ANY:
        want = set(_split_set(rtarget))
        return lambda v: bool(want & {p.strip() for p in v.split(",")})
    if operand in ("<", "<=", ">", ">="):
        def order(v: str) -> bool:
            # numeric if both parse, else lexical (reference checkLexicalOrder)
            try:
                lv, rv = float(v), float(rtarget)
            except ValueError:
                lv, rv = v, rtarget  # type: ignore[assignment]
            if operand == "<":
                return lv < rv
            if operand == "<=":
                return lv <= rv
            if operand == ">":
                return lv > rv
            return lv >= rv
        return order
    # unknown operand: never feasible (loud is better than silently true)
    return lambda v: False
