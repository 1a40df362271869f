"""Scoring and capacity-check oracles.

Reference semantics: `nomad/structs/funcs.go` (`ScoreFit`, `AllocsFit`) and
`nomad/structs/network.go` (`NetworkIndex`).  These pure-Python versions are
the *golden oracles* the vectorized JAX kernels in `nomad_tpu.ops` are
property-tested against (SURVEY.md §7 P0).

ScoreFit is the Google-Borg-style "best fit v3" exponential bin-packing score:
    free_frac_d = 1 - used_d / capacity_d          (per dimension d in {cpu, mem})
    total      = sum_d 10 ** free_frac_d           (2 at full util .. 20 at empty)
    binpack    = clamp(20 - total, 0, 18)          (18 = perfectly full node)
    spread     = clamp(total - 2,  0, 18)          (18 = empty node; the
                                                    SchedulerAlgorithm="spread"
                                                    inversion)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .structs import (
    Allocation,
    MAX_DYNAMIC_PORT,
    MIN_DYNAMIC_PORT,
    NetworkResource,
    Node,
    Resources,
    SCHED_ALGO_SPREAD,
)

# Maximum per-node score magnitude from the fit function.
MAX_FIT_SCORE = 18.0

# Where fresh NetworkIndex cursors start their dynamic-port scan.  The
# scan order is a ROTATION of the ascending range (base..MAX, then
# MIN..base-1): with the default base the rotation is the identity and
# picks are bit-for-bit the historical ascending first-fit.  Pool worker
# processes (core/workerpool.py) set a per-process base carved from
# disjoint shards of the range, so two workers placing on one node
# against the same snapshot pick non-overlapping ports instead of both
# taking first-fit-from-20000 and refuting at the applier.
_DYN_SCAN_BASE = MIN_DYNAMIC_PORT
_DYN_RANGE = MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT + 1
# Rotating mode (pool children only): committed picks push the process
# base forward, so a child's NEXT batch — whose snapshot may predate
# this batch's commits (wavepipe prefetch overlap) — starts past every
# port this process already claimed instead of re-offering them.
_DYN_SCAN_ROTATE = False


def set_dynamic_port_scan_base(base: int,
                               rotate: Optional[bool] = None) -> None:
    """Set this process's dynamic-port scan start (clamped into range).
    Affects only indexes built after the call.  `rotate=True` makes
    committed picks advance the base (see _advance_scan_base)."""
    global _DYN_SCAN_BASE, _DYN_SCAN_ROTATE
    _DYN_SCAN_BASE = min(max(int(base), MIN_DYNAMIC_PORT),
                         MAX_DYNAMIC_PORT)
    if rotate is not None:
        _DYN_SCAN_ROTATE = bool(rotate)


def _advance_scan_base(ports: Iterable[int]) -> None:
    """In rotating mode, move the process scan base just past the
    furthest committed pick in current scan order.  Freed ports come
    back when the rotation wraps (a fresh index rebuilds `used_ports`
    from state), so the range is recycled, not consumed."""
    if not _DYN_SCAN_ROTATE:
        return
    base_off = _DYN_SCAN_BASE - MIN_DYNAMIC_PORT
    far = -1
    for p in ports:
        if MIN_DYNAMIC_PORT <= p <= MAX_DYNAMIC_PORT:
            far = max(far, (p - MIN_DYNAMIC_PORT - base_off) % _DYN_RANGE)
    if far >= 0:
        set_dynamic_port_scan_base(
            MIN_DYNAMIC_PORT + (base_off + far + 1) % _DYN_RANGE)


def score_fit_binpack(node_cpu: float, node_mem: float,
                      used_cpu: float, used_mem: float) -> float:
    """reference: structs.ScoreFitBinPack"""
    if node_cpu <= 0 or node_mem <= 0:
        return 0.0
    free_cpu = 1.0 - min(used_cpu / node_cpu, 1.0)
    free_mem = 1.0 - min(used_mem / node_mem, 1.0)
    total = 10.0 ** free_cpu + 10.0 ** free_mem
    return max(0.0, min(MAX_FIT_SCORE, 20.0 - total))


def score_fit_spread(node_cpu: float, node_mem: float,
                     used_cpu: float, used_mem: float) -> float:
    """reference: structs.ScoreFitSpread — inverted bin-pack used when
    SchedulerConfiguration.scheduler_algorithm == "spread"."""
    if node_cpu <= 0 or node_mem <= 0:
        return 0.0
    free_cpu = 1.0 - min(used_cpu / node_cpu, 1.0)
    free_mem = 1.0 - min(used_mem / node_mem, 1.0)
    total = 10.0 ** free_cpu + 10.0 ** free_mem
    return max(0.0, min(MAX_FIT_SCORE, total - 2.0))


def score_fit(node: Node, used: Resources, algorithm: str) -> float:
    f = score_fit_spread if algorithm == SCHED_ALGO_SPREAD else score_fit_binpack
    return f(node.resources.cpu - node.reserved.cpu,
             node.resources.memory_mb - node.reserved.memory_mb,
             used.cpu, used.memory_mb)


# ---------------------------------------------------------------------------
# NetworkIndex — per-node port bookkeeping (reference: structs/network.go)
# ---------------------------------------------------------------------------


@dataclass
class NetworkIndex:
    """Tracks port usage on one node.  Simplified to a single host network:
    a port on any host network of the node takes the value for all.  The
    host builds one of these a touched node when it assigns ports
    (scheduler/generic.py) and when the applier re-checks them
    (core/plan_apply.py).  What the kernels consume is not this index but
    the holders of each STATIC value a launch asks, one [N] row a value
    (ops/select.py port_state, built from pack/packer.py's port ledger):
    dynamic ports never reach the device.

    Dynamic picks run off a FREE CURSOR: `_vcursor` maintains the
    invariant that every port before it IN SCAN ORDER is in
    `used_ports`.  Scan order is the ascending range rotated to start at
    this process's scan base (the identity rotation by default — see
    set_dynamic_port_scan_base).  Ports are only ever claimed within an
    index's lifetime (never released — a freed port shows up in a FRESH
    index built from state), so the cursor only moves forward and
    repeated assignment on a loaded node is O(1) amortized instead of
    the O(pool) first-fit scan per port it replaces (PERF.md §6).  The
    pick sequence is bit-for-bit the linear scan's: everything the
    cursor skipped is used forever."""

    used_ports: Set[int] = field(default_factory=set)

    def __post_init__(self) -> None:
        # not dataclass fields: pick-path accelerators, reconstructible
        # from used_ports (and deliberately absent from the wire form)
        self._voff = _DYN_SCAN_BASE - MIN_DYNAMIC_PORT
        self._vcursor = 0
        self._dyn_memo: Tuple[int, int] = (-1, 0)   # (len(used), free)

    def _vport(self, v: int) -> int:
        """Virtual scan position -> port number (rotation of the range)."""
        return MIN_DYNAMIC_PORT + (self._voff + v) % _DYN_RANGE

    def set_node(self, node: Node) -> None:
        for p in node.reserved.reserved_ports:
            self.used_ports.add(p)
        for net in node.resources.networks:
            for p in net.reserved_ports:
                self.used_ports.add(p.value)

    def add_allocs(self, allocs: Iterable[Allocation]) -> None:
        for a in allocs:
            if a.terminal_status():
                continue
            for port in a.allocated_ports.values():
                self.used_ports.add(port)
            for net in a.resources.networks:
                for p in net.reserved_ports:
                    self.used_ports.add(p.value)

    def add_reserved(self, net: NetworkResource) -> None:
        for p in net.reserved_ports:
            self.used_ports.add(p.value)
        for p in net.dynamic_ports:
            if p.value:
                self.used_ports.add(p.value)

    def assign_ports(self, ask: List[NetworkResource],
                     ) -> Tuple[Optional[Dict[str, int]], str]:
        """Try to satisfy the reserved+dynamic port ask.  Returns
        (label->port, "") on success or (None, dimension) on exhaustion."""
        assigned: Dict[str, int] = {}
        newly: Set[int] = set()
        for net in ask:
            for p in net.reserved_ports:
                if p.value in self.used_ports or p.value in newly:
                    return None, f"network: reserved port collision {p.value}"
                newly.add(p.value)
                assigned[p.label or str(p.value)] = p.value
            for p in net.dynamic_ports:
                got = self._pick_dynamic(newly)
                if got is None:
                    return None, "network: dynamic port exhaustion"
                newly.add(got)
                assigned[p.label or f"dyn{got}"] = got
        return assigned, ""

    def _pick_dynamic(self, newly: Set[int]) -> Optional[int]:
        """Deterministic first-fit via the free cursor (O(1) amortized).

        The durable cursor advances past COMMITTED ports only; `newly`
        (this assign call's uncommitted picks) is skipped transiently so
        a failed, never-committed assignment cannot burn pool positions
        the linear scan would still offer."""
        used = self.used_ports
        v = self._vcursor
        while v < _DYN_RANGE and self._vport(v) in used:
            v += 1
        self._vcursor = v
        while v < _DYN_RANGE and (self._vport(v) in used
                                  or self._vport(v) in newly):
            v += 1
        return self._vport(v) if v < _DYN_RANGE else None

    def dyn_free_count(self) -> int:
        """Free ports remaining in the dynamic pool — the batched carve's
        feasibility pre-check.  Memoized on len(used_ports) (the set only
        grows), so repeated calls between mutations are O(1)."""
        n = len(self.used_ports)
        memo_n, memo_free = self._dyn_memo
        if memo_n == n:
            return memo_free
        used_dyn = sum(1 for p in self.used_ports
                       if MIN_DYNAMIC_PORT <= p <= MAX_DYNAMIC_PORT)
        free = (MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT + 1) - used_dyn
        self._dyn_memo = (n, free)
        return free

    def claim_dynamic_block(self, n_ports: int) -> Optional[List[int]]:
        """Claim-and-commit the first `n_ports` free dynamic ports in
        scan order (ascending first-fit under the default rotation) —
        ONE cursor pass for a whole node's wave demand instead of
        n_ports scans.  All-or-nothing: returns None (nothing committed)
        when the pool is short; callers gate on `dyn_free_count()` first
        so this cannot fail mid-wave."""
        if n_ports <= 0:
            return []
        used = self.used_ports
        v = self._vcursor
        out: List[int] = []
        while len(out) < n_ports and v < _DYN_RANGE:
            port = self._vport(v)
            if port not in used:
                out.append(port)
            v += 1
        if len(out) < n_ports:
            return None
        used.update(out)
        # everything before `v` in scan order is now used
        # (pre-existing or claimed)
        self._vcursor = v
        _advance_scan_base(out)
        return out

    def assign_ports_batch(self, ask: List[NetworkResource], n: int,
                           ) -> Tuple[Optional[List[Dict[str, int]]], str]:
        """`n` disjoint assignments of one all-dynamic ask — the bulk
        twin of n sequential assign_ports+commit calls, committed as one
        cursor pass.  Bit-for-bit the sequential result: mate k's labels
        take the next L free ports ascending, exactly as k ordered
        assign_ports calls would.  Static (reserved) asks are the
        sequential path's job — returns the exhaustion dimension for
        them so callers fall back."""
        labels: List[str] = []
        for net in ask:
            if net.reserved_ports:
                return None, "network: reserved ports need sequential assignment"
            for p in net.dynamic_ports:
                if not p.label:
                    # sequential keys unlabeled ports by their ASSIGNED
                    # value (`dyn{got}`) — only the oracle can do that
                    return None, ("network: unlabeled dynamic ports need "
                                  "sequential assignment")
                labels.append(p.label)
        if n <= 0 or not labels:
            return [{} for _ in range(n)], ""
        got = self.claim_dynamic_block(n * len(labels))
        if got is None:
            return None, "network: dynamic port exhaustion"
        width = len(labels)
        return [dict(zip(labels, got[k * width:(k + 1) * width]))
                for k in range(n)], ""

    def commit(self, ports: Dict[str, int]) -> None:
        self.used_ports.update(ports.values())
        _advance_scan_base(ports.values())


# ---------------------------------------------------------------------------
# AllocsFit — capacity check (reference: structs.AllocsFit)
# ---------------------------------------------------------------------------


def allocs_fit(node: Node, allocs: List[Allocation],
               net_index: Optional[NetworkIndex] = None,
               check_devices: bool = False,
               ) -> Tuple[bool, str, Resources]:
    """Check that `allocs` all fit on `node` simultaneously.

    Returns (fits, failed_dimension, used_totals).  Mirrors the reference's
    behavior: terminal allocs are skipped; reserved node resources reduce
    capacity; ports are checked via NetworkIndex.
    """
    used = Resources(cpu=0, memory_mb=0, disk_mb=0)
    ni = net_index or NetworkIndex()
    if net_index is None:
        ni.set_node(node)

    seen_ports: Set[int] = set(ni.used_ports)
    # device instance bookkeeping (reference: structs.AllocsFit's
    # devicesFit path): every assigned instance must exist in the node's
    # inventory and be assigned at most once across the alloc set
    seen_devs: Set[Tuple[str, str]] = set()
    inventory: Dict[str, Set[str]] = {}
    if check_devices:
        for d in node.resources.devices:
            inventory.setdefault(d.id(), set()).update(d.instance_ids)
    for a in allocs:
        if a.terminal_status():
            continue
        used.cpu += a.resources.cpu
        used.memory_mb += a.resources.memory_mb
        used.disk_mb += a.resources.disk_mb
        # An alloc's static port appears BOTH in its allocated_ports (the
        # assignment) and in its resources.networks reserved_ports (the
        # ask): ask + fulfillment are ONE claim, not a self-collision.
        # But two labels assigned the same value, or two asks of one
        # value (even sharing a label), ARE a real within-alloc collision
        # and must still refute — so each assignment entry absorbs AT
        # MOST ONE matching ask (assign_ports keys unlabeled ports by
        # value).
        ports = list(a.allocated_ports.values())
        ap_get = a.allocated_ports.get
        consumed: Set[str] = set()
        for net in a.resources.networks:
            for p in net.reserved_ports:
                label = p.label or str(p.value)
                if label not in consumed and ap_get(label) == p.value:
                    consumed.add(label)     # fulfilled by the assignment
                    continue
                ports.append(p.value)
        for port in ports:
            if port in seen_ports:
                return False, "network: port collision", used
            seen_ports.add(port)
        if check_devices:
            for ad in getattr(a, "allocated_devices", ()) or ():
                gid = ad.group_id()
                have = inventory.get(gid, set())
                for iid in ad.device_ids:
                    if iid not in have:
                        return False, f"devices: unknown instance {gid}[{iid}]", used
                    if (gid, iid) in seen_devs:
                        return False, f"devices: instance oversubscribed {gid}[{iid}]", used
                    seen_devs.add((gid, iid))

    cap_cpu = node.resources.cpu - node.reserved.cpu
    cap_mem = node.resources.memory_mb - node.reserved.memory_mb
    cap_disk = node.resources.disk_mb - node.reserved.disk_mb
    if used.cpu > cap_cpu:
        return False, "cpu", used
    if used.memory_mb > cap_mem:
        return False, "memory", used
    if used.disk_mb > cap_disk:
        return False, "disk", used
    return True, "", used


def comparable_used(allocs: Iterable[Allocation]) -> Resources:
    """Sum non-terminal alloc resources (reference: AllocsFit's accumulation)."""
    used = Resources(cpu=0, memory_mb=0, disk_mb=0)
    for a in allocs:
        if a.terminal_status():
            continue
        used.cpu += a.resources.cpu
        used.memory_mb += a.resources.memory_mb
        used.disk_mb += a.resources.disk_mb
    return used
