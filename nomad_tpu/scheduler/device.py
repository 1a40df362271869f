"""Device scheduling: feasibility + instance assignment
(reference: scheduler/device.go AllocateDevice, scheduler/feasible.go
DeviceChecker).

Devices (GPUs, FPGAs, ...) are discrete, named, host-assigned resources:
a node advertises device *groups* (vendor/type/name with instance IDs and
attributes, reference: structs.NodeDeviceResource); a task asks for
`count` instances of a device matching a name pattern plus optional
constraints/affinities over device attributes (reference:
structs.RequestedDevice).

HOW MANY instances a node has free is a capacity like cpu and memory:
the packed tensors carry it as a dimension (structs.RES_NAMES, "devices")
and the kernels account it placement by placement.  WHICH instance an
allocation gets is an exact small-cardinality matching problem over
string-keyed inventories, so it stays host-side (SURVEY.md §7 P1's
"strings never reach the device" stance):

  * `request_signature` / `group_accepts` give the STATIC half of the
    DeviceChecker: whether a node carries a group a request's name and
    constraints accept.  The engine keeps it as a per-signature mask by
    node-table version (ops/engine.py device_static_mask);
  * `node_feasible` is the exact DYNAMIC check, which only a group with
    several requests or a node with several groups still needs;
  * `assign_devices` picks concrete instance IDs for a chosen node after
    the kernel has placed (the AllocateDevice analog), with affinity
    scoring across eligible device groups;
  * `carve_block` is its columnar twin for a whole eval's picks (the
    batched path, scheduler/generic.py), against a `CarveLedger` that
    the wave's mates and the cycle's earlier waves share.

Both consult an `InUseIndex` built from live allocations so instances are
never double-assigned; the plan applier re-checks via
`structs.funcs.allocs_fit(check_devices=True)` against the latest state
(optimistic concurrency, reference: plan_apply.go evaluateNodePlan).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu.structs import (
    AllocatedDeviceResource,
    Node,
    NodeDeviceResource,
    RequestedDevice,
    TaskGroup,
)
from nomad_tpu.pack.packer import _string_predicate
from nomad_tpu.structs.structs import (
    OP_EQ,
    OP_IS_NOT_SET,
    OP_IS_SET,
    OP_NEQ,
)


def id_matches(request_name: str, dev: NodeDeviceResource) -> bool:
    """Match a request name against a device group's vendor/type/name
    hierarchy (reference: structs.RequestedDevice.ID().Matches):
    "gpu" matches by type; "nvidia/gpu" by vendor+type;
    "nvidia/gpu/1080ti" by all three."""
    parts = request_name.split("/")
    if len(parts) == 1:
        return dev.type == parts[0]
    if len(parts) == 2:
        return (dev.vendor, dev.type) == (parts[0], parts[1])
    if len(parts) == 3:
        return (dev.vendor, dev.type, dev.name) == tuple(parts)
    return False


def device_attr(dev: NodeDeviceResource, target: str) -> Optional[str]:
    """Resolve a constraint/affinity LTarget against a device group
    (reference: scheduler/device.go nodeDeviceMatches attribute plumbing).
    Supported: ${device.vendor} ${device.type} ${device.model}
    ${device.ids} ${device.attr.<name>}; bare names accepted too."""
    t = target.strip()
    if t.startswith("${") and t.endswith("}"):
        t = t[2:-1]
    if t.startswith("device."):
        t = t[len("device."):]
    if t == "vendor":
        return dev.vendor
    if t == "type":
        return dev.type
    if t in ("model", "name"):
        return dev.name
    if t == "ids":
        return ",".join(dev.instance_ids)
    if t.startswith("attr."):
        return dev.attributes.get(t[len("attr."):])
    return dev.attributes.get(t)


def _check(operand: str, lval: Optional[str], rtarget: str) -> bool:
    """Host-side constraint evaluation over device attribute strings —
    the same operator table the packer lowers for node attrs
    (reference: scheduler/feasible.go checkAttributeConstraint)."""
    if operand == OP_IS_SET:
        return lval is not None
    if operand == OP_IS_NOT_SET:
        return lval is None
    if lval is None:
        # absent attribute: != passes, everything else fails (reference
        # semantics: missing attr fails the check except negative ops)
        return operand == OP_NEQ
    if operand == OP_EQ:
        return lval == rtarget
    if operand == OP_NEQ:
        return lval != rtarget
    return _string_predicate(operand, rtarget)(lval)


def group_feasible(dev: NodeDeviceResource, req: RequestedDevice) -> bool:
    """Static (usage-independent) group eligibility for a request."""
    if not id_matches(req.name, dev):
        return False
    for c in req.constraints:
        if not _check(c.operand, device_attr(dev, c.ltarget), c.rtarget):
            return False
    return True


def request_signature(req: RequestedDevice) -> tuple:
    """What decides which device groups a request accepts: its name and
    constraints, not its count (hashable; the static mask's key)."""
    return (req.name, tuple((c.ltarget, c.operand, c.rtarget)
                            for c in req.constraints))


def group_accepts(dev: NodeDeviceResource, req: RequestedDevice,
                  memo: Dict[tuple, bool]) -> bool:
    """`group_feasible` through `memo`: a fleet has a handful of group
    shapes (vendor, type, model, attributes) and one verdict each, unless
    a constraint reads the instance ids themselves."""
    if any("ids" in c.ltarget for c in req.constraints):
        return group_feasible(dev, req)
    key = (dev.vendor, dev.type, dev.name,
           tuple(sorted(dev.attributes.items())))
    hit = memo.get(key)
    if hit is None:
        memo[key] = hit = group_feasible(dev, req)
    return hit


def group_affinity_score(dev: NodeDeviceResource,
                         req: RequestedDevice) -> float:
    """Normalized [-1, 1] affinity score of a group (reference:
    scheduler/device.go deviceAllocator.AddAllocs scoring)."""
    if not req.affinities:
        return 0.0
    total = 0.0
    denom = 0.0
    for a in req.affinities:
        denom += abs(a.weight)
        if _check(a.operand, device_attr(dev, a.ltarget), a.rtarget):
            total += a.weight
    if denom == 0:
        return 0.0
    return total / denom


class InUseIndex:
    """Which device instance IDs are taken, per node per device group —
    built from live allocations' `allocated_devices`, extended in place as
    a plan assigns more (intra-plan sequential semantics, SURVEY.md §4.3).
    """

    def __init__(self) -> None:
        self._used: Dict[str, Dict[str, Set[str]]] = {}

    def used(self, node_id: str, group_id: str) -> Set[str]:
        return self._used.get(node_id, {}).get(group_id, set())

    def items(self):
        """(node_id, group_id, instance_id_set) triples."""
        for node_id, groups in self._used.items():
            for gid, ids in groups.items():
                yield node_id, gid, ids

    def groups(self, node_id: str):
        """(group_id, instance_id_set) pairs of one node."""
        return self._used.get(node_id, {}).items()

    def add(self, node_id: str, group_id: str,
            instance_ids: Iterable[str]) -> None:
        self._used.setdefault(node_id, {}).setdefault(
            group_id, set()).update(instance_ids)

    def add_alloc(self, node_id: str, alloc) -> None:
        for ad in getattr(alloc, "allocated_devices", ()) or ():
            gid = f"{ad.vendor}/{ad.type}/{ad.name}"
            self.add(node_id, gid, ad.device_ids)

    @classmethod
    def from_allocs(cls, allocs_by_node) -> "InUseIndex":
        """allocs_by_node: iterable of (node_id, allocs)."""
        idx = cls()
        for node_id, allocs in allocs_by_node:
            for a in allocs:
                if a.terminal_status():
                    continue
                idx.add_alloc(node_id, a)
        return idx


def tg_device_requests(tg: TaskGroup) -> List[Tuple[str, RequestedDevice]]:
    """(task_name, request) pairs for every device ask in the group."""
    out = []
    for t in tg.tasks:
        for d in t.resources.devices:
            out.append((t.name, d))
    return out


def node_feasible(node: Node, tg: TaskGroup, in_use: InUseIndex) -> bool:
    """DeviceChecker analog: can `node` satisfy every device request of
    `tg` simultaneously, given current instance usage?  Greedy over
    groups in request order — matches the reference's sequential
    AllocateDevice behavior within one allocation."""
    reqs = tg_device_requests(tg)
    if not reqs:
        return True
    if not node.resources.devices:
        return False
    taken: Dict[str, int] = {}
    for _task, req in reqs:
        need = max(req.count, 1)
        placed = False
        for dev in node.resources.devices:
            if not group_feasible(dev, req):
                continue
            gid = dev.id()
            free = (len(dev.instance_ids)
                    - len(in_use.used(node.id, gid))
                    - taken.get(gid, 0))
            if free >= need:
                taken[gid] = taken.get(gid, 0) + need
                placed = True
                break
        if not placed:
            return False
    return True


def assign_devices(node: Node, tg: TaskGroup, in_use: InUseIndex,
                   ) -> Tuple[Optional[List[AllocatedDeviceResource]], str]:
    """AllocateDevice analog: pick concrete instance IDs on `node` for
    every device request of `tg`.  Per request, eligible groups are
    scored by the request's affinities and the best group supplies the
    instances.  On success the assignments are recorded in `in_use`
    (so later placements in the same plan see them) and returned; on
    shortfall returns (None, reason) with nothing recorded."""
    reqs = tg_device_requests(tg)
    if not reqs:
        return [], ""
    assigned: List[AllocatedDeviceResource] = []
    staged: List[Tuple[str, str, List[str]]] = []
    taken: Dict[str, Set[str]] = {}
    for task_name, req in reqs:
        need = max(req.count, 1)
        best: Optional[NodeDeviceResource] = None
        best_ids: List[str] = []
        best_score = float("-inf")
        for dev in node.resources.devices:
            if not group_feasible(dev, req):
                continue
            gid = dev.id()
            busy = in_use.used(node.id, gid) | taken.get(gid, set())
            free = [i for i in dev.instance_ids if i not in busy]
            if len(free) < need:
                continue
            score = group_affinity_score(dev, req)
            if score > best_score:
                best, best_ids, best_score = dev, free[:need], score
        if best is None:
            return None, f"devices: {req.name}"
        gid = best.id()
        taken.setdefault(gid, set()).update(best_ids)
        staged.append((gid, task_name, best_ids))
        assigned.append(AllocatedDeviceResource(
            task=task_name, vendor=best.vendor, type=best.type,
            name=best.name, device_ids=list(best_ids)))
    for gid, _task, ids in staged:
        in_use.add(node.id, gid, ids)
    return assigned, ""


# ---------------------------------------------------------------------------
# the batched carve (scheduler/generic.py _materialize_bulk)
# ---------------------------------------------------------------------------

def held_instances(allocs) -> Set[str]:
    """The instance ids the live allocations among `allocs` hold."""
    held: Set[str] = set()
    for a in allocs:
        devs = a.allocated_devices
        if devs and not a.terminal_status():
            for ad in devs:
                held.update(ad.device_ids)
    return held


class CarveLedger:
    """Instance ids carved by plans that a later scheduler's snapshot may
    not hold yet: ONE in-use index for a wave's mates (materialized one
    after another before the first of them commits) and for the cycle's
    earlier waves (a prefetched wave's snapshot predates its
    predecessor's commits, while its usage buffer already counts them).

    A plan's carve is a record {node id: ids}, open until the plan's
    verdict: `settle` stamps it with the index its allocations committed
    at and drops the nodes the applier refuted; a plan that never
    commits is dropped whole.  A settled record is forgotten once a
    carve arrives from a snapshot at or past its index: from there the
    snapshot itself shows the instances (or shows them given back)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: List[list] = []     # [commit index | None, {..}]
        # node id -> the records that carved on it: a carve asks about
        # its own handful of nodes, not about every record
        self._by_node: Dict[str, List[list]] = {}

    def _drop(self, record: list, node_ids) -> None:
        by_node = self._by_node
        for nid in node_ids:
            recs = by_node.get(nid)
            if recs is not None:
                recs[:] = [r for r in recs if r is not record]
                if not recs:
                    del by_node[nid]

    def open(self, snapshot_index: int) -> list:
        record = [None, {}]
        with self._lock:
            keep = []
            for r in self._records:
                if r[0] is None or r[0] > snapshot_index:
                    keep.append(r)
                else:
                    self._drop(r, r[1])
            keep.append(record)
            self._records = keep
        return record

    def note(self, record: list, node_id: str, ids) -> None:
        """`record`'s plan carved `ids` on `node_id`."""
        with self._lock:
            record[1][node_id] = set(ids)
            self._by_node.setdefault(node_id, []).append(record)

    def held(self, node_id: str) -> Set[str]:
        out: Set[str] = set()
        with self._lock:
            for record in self._by_node.get(node_id, ()):
                out.update(record[1].get(node_id, ()))
        return out

    def settle(self, record: list, commit_index: Optional[int],
               refuted_nodes=()) -> None:
        with self._lock:
            if commit_index is None:
                self._records = [r for r in self._records
                                 if r is not record]
                self._drop(record, record[1])
                record[1].clear()
                return
            record[0] = commit_index
            gone = [nid for nid in refuted_nodes if nid in record[1]]
            self._drop(record, gone)
            for nid in gone:
                del record[1][nid]

    def __len__(self) -> int:
        return len(self._records)


def carve_block(snapshot, ledger: CarveLedger, record: list,
                req: RequestedDevice, picks: np.ndarray,
                node_ids: List[str]):
    """Instance ids for every row of one eval's picks, in one pass over
    its nodes: a node's rows take its group's first free ids in the
    group's own order, row after row, which is what a sequence of
    `assign_devices` calls gives them.  For ONE request on nodes of ONE
    device group (prepare_batch's admission rule).

    `picks[i]` is row i's node (an index into `node_ids`).  In use on a
    node: what its live allocations hold in `snapshot`, and what the
    ledger's open and not yet visible records carved.  Returns
    (ids [rows, count] unicode array, {pick: (vendor, type, name)},
    short): `short` is the picks whose node has too few free instances
    (a foreign write took them after the kernel looked).  Such a node
    gives NONE of its rows ids, nothing of it enters the record, and its
    rows' entries in `ids` are empty strings: the caller drops them."""
    need = max(req.count, 1)
    uniq, inv = np.unique(picks, return_inverse=True)
    counts = np.bincount(inv, minlength=len(uniq)).tolist()
    order = np.argsort(inv, kind="stable")
    rows_ids: List[Optional[List[str]]] = []
    groups: Dict[int, tuple] = {}
    short: List[int] = []
    memo: Dict[tuple, bool] = {}
    for pick, k in zip(uniq.tolist(), counts):
        nid = node_ids[pick]
        node = snapshot.node_by_id(nid)
        dev = next((d for d in (node.resources.devices if node else ())
                    if group_accepts(d, req, memo)), None)
        free: List[str] = []
        if dev is not None:
            busy = held_instances(snapshot.allocs_by_node(nid))
            busy |= ledger.held(nid)
            free = [i for i in dev.instance_ids if i not in busy]
        if len(free) < k * need:
            short.append(pick)
            rows_ids.append(None)
            continue
        take = free[:k * need]
        ledger.note(record, nid, take)
        groups[pick] = (dev.vendor, dev.type, dev.name)
        rows_ids.append(take)
    flat: List[str] = []
    for take, k in zip(rows_ids, counts):
        flat.extend(take if take is not None else [""] * (k * need))
    by_node = np.asarray(flat, np.str_).reshape(len(picks), need)
    out = np.empty_like(by_node)
    out[order] = by_node            # node-major back to the rows' order
    return out, groups, short
