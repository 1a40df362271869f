"""Live-allocation ledger — the state store's scheduling-quality sums.

Per node, over NON-TERMINAL allocations: count, then one sum a capacity
dimension (structs.RES_NAMES: cpu, memory, disk, device instances held);
from them the quality gauges (`StateStore.quality_summary`): nodes in
use, allocations per zone, mean bin-pack fill of cpu, memory and disk,
device instances in use.

Columnar: a node id maps to a row once and keeps it; the sums, the
node's capacity and zone, and each row's STANDING contribution to the
aggregates are arrays over rows.  The WRITE path touches no array: a
columnar block is kept as a unit (`add_block`: one list append, whether
it holds two nodes or 49,000), a per-alloc write adds four ints to a
pending dict entry (`add`).  A small numpy call costs the commit path
far more than its arithmetic (it runs cold between two waves), so the
arrays are touched where they are read: the flush folds what is pending
with one scatter each, then reconciles the rows dirtied since the last
one in numpy — never a Python step a node, never a walk of the cluster.

Observability only.  Fed by the writes of the store's own log (replica
imports feed neither side); drift-tolerant where the store is (an
allocation on a node the store never saw counts in nodes-in-use only);
rebuilt exactly on snapshot restore.  Every method runs under the
store's lock.
"""

from __future__ import annotations

from itertools import chain, repeat
from typing import Dict, List

import numpy as np

from nomad_tpu.structs import RES_DIMS


class LiveLedger:

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self._row: Dict[str, int] = {}              # node id -> row
        # count, then usage by capacity dimension
        self._sum = np.zeros((0, 1 + RES_DIMS), np.int64)
        # what a fill is computed from, as of the node's last write:
        # resources - reserved, and the datacenter's id (-1: the store
        # does not hold the node)
        self._avail = np.zeros((0, 3), np.int64)
        self._zone = np.full(0, -1, np.int32)
        # standing contributions, retired before they are re-added: what
        # the row last added to _fill_sums, its count as of then (over 0:
        # the row counts in _n_in_use) and the zone that count went to
        # (-1: none, the store did not hold the node)
        self._fill = np.zeros((0, 3), np.float64)
        self._held_count = np.zeros(0, np.int64)
        self._held_zone = np.full(0, -1, np.int32)
        self._dirty = np.zeros(0, bool)
        # not yet folded into _sum: per-alloc deltas, node id -> [count,
        # cpu, mem, disk, devices], and whole blocks (with the nodes they
        # name, so the list can be kept to the order of the rows)
        self._pending: Dict[str, List[int]] = {}
        self._pending_blocks: List = []
        self._pending_nodes = 0
        self._zones: Dict[str, int] = {}            # datacenter -> zone id
        self._zone_live = np.zeros(0, np.int64)     # live allocs by zone id
        self._fill_sums = np.zeros(3, np.float64)   # clamped fill fractions
        self._n_in_use = 0

    # --------------------------------------------------------------- rows

    def _rows(self, node_ids: List[str]) -> np.ndarray:
        """The rows of `node_ids`, in order; an id not seen before gets
        the next row."""
        row = self._row
        rows = np.fromiter(map(row.get, node_ids, repeat(-1)), np.intp,
                           len(node_ids))
        if rows.size and rows.min() < 0:
            for i in np.flatnonzero(rows < 0).tolist():
                rows[i] = row.setdefault(node_ids[i], len(row))
            self._grow(len(row))
        return rows

    def _grow(self, n: int) -> None:
        cap = len(self._zone)
        if n <= cap:
            return
        pad = max(n, 2 * cap, 1024) - cap

        def more(a, fill=0):
            return np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])

        self._sum = more(self._sum)
        self._avail = more(self._avail)
        self._zone = more(self._zone, -1)
        self._fill = more(self._fill)
        self._held_count = more(self._held_count)
        self._held_zone = more(self._held_zone, -1)
        self._dirty = more(self._dirty)

    # -------------------------------------------------------- node writes

    def note_nodes(self, nodes) -> None:
        """`nodes` were written to the node table: keep what their fills
        are computed from.  Takes effect when a row next flushes, as a
        read of the node table at flush time would."""
        if not nodes:
            return
        rows = self._rows([n.id for n in nodes])
        self._avail[rows] = [
            (n.resources.cpu - n.reserved.cpu,
             n.resources.memory_mb - n.reserved.memory_mb,
             n.resources.disk_mb - n.reserved.disk_mb) for n in nodes]
        zones = self._zones
        for n in nodes:
            if n.datacenter not in zones:
                zones[n.datacenter] = len(zones)
                self._zone_live = np.append(self._zone_live, 0)
        self._zone[rows] = [zones[n.datacenter] for n in nodes]

    def forget_node(self, node_id: str) -> None:
        row = self._row.get(node_id)
        if row is not None:
            self._zone[row] = -1

    # ------------------------------------------------------- alloc writes

    def add(self, node_id: str, d: int, usage) -> None:
        """One allocation's delta: `d` is +1 or -1, `usage` its
        `Allocation.usage()`.  Int adds only: the fold and the aggregate
        math wait for the flush."""
        row = self._pending.get(node_id)
        if row is None:
            self._pending[node_id] = row = [0] * (1 + RES_DIMS)
        row[0] += d
        for k, v in enumerate(usage, 1):
            row[k] += d * v

    def add_block(self, block) -> None:
        """A columnar AllocBlock: `node_counts()[i]` allocations of its
        `resources_tuple()` on `node_table[i]`, kept as a unit until the
        next fold.  A store nobody reads the gauges of still folds once
        the units name more nodes than the ledger has rows."""
        self._pending_blocks.append(block)
        self._pending_nodes += len(block.node_table)
        if self._pending_nodes > len(self._row) + 1024:
            self._fold()

    def _fold(self) -> None:
        """What is pending into `_sum`, one scatter each."""
        pending = self._pending
        if pending:
            rows = self._rows(list(pending))        # distinct ids and rows
            self._sum[rows] += np.array(list(pending.values()), np.int64)
            self._dirty[rows] = True
            pending.clear()
        blocks = self._pending_blocks
        if blocks:
            rows = self._rows(list(chain.from_iterable(
                b.node_table for b in blocks)))
            np.add.at(self._sum, rows, np.concatenate([
                b.node_counts()[:, None]
                * np.array((1,) + b.resources_tuple(), np.int64)
                for b in blocks]))
            self._dirty[rows] = True
            blocks.clear()
            self._pending_nodes = 0

    # -------------------------------------------------------------- reads

    def flush(self) -> None:
        """Fold what is pending, then reconcile every row dirtied since
        the last flush: retire its standing contributions, re-add them
        from the current sums, empty the rows that reached zero."""
        self._fold()
        rows = np.flatnonzero(self._dirty)
        if not rows.size:
            return
        self._dirty[rows] = False
        zone_live = self._zone_live
        self._fill_sums -= self._fill[rows].sum(axis=0)
        held_count, held_zone = self._held_count[rows], self._held_zone[rows]
        np.subtract.at(zone_live, held_zone[held_zone >= 0],
                       held_count[held_zone >= 0])
        sums = self._sum[rows]
        live = sums[:, 0] > 0
        self._n_in_use += int(live.sum()) - int((held_count > 0).sum())
        gone = rows[~live]
        self._sum[gone] = 0
        self._fill[gone] = 0.0
        self._held_count[gone] = 0
        self._held_zone[gone] = -1
        rows, sums = rows[live], sums[live]
        zone = self._zone[rows]
        known = zone >= 0           # else: counted in nodes-in-use only
        avail = self._avail[rows]
        fill = np.zeros((len(rows), 3), np.float64)
        np.divide(sums[:, 1:4], avail, out=fill,
                  where=known[:, None] & (avail > 0))
        np.minimum(fill, 1.0, out=fill)
        self._fill[rows] = fill
        self._fill_sums += fill.sum(axis=0)
        self._held_count[rows] = sums[:, 0]
        self._held_zone[rows] = zone
        np.add.at(zone_live, zone[known], sums[known, 0])

    def summary(self) -> Dict[str, float]:
        self.flush()
        in_use = self._n_in_use
        zones = self._zone_live[self._zone_live > 0]
        zmax = int(zones.max()) if zones.size else 0
        zmin = int(zones.min()) if zones.size else 0
        fills = [max(float(f), 0.0) / in_use if in_use else 0.0
                 for f in self._fill_sums]
        return {
            "nodes_in_use": in_use,
            "zone_allocs_max": zmax,
            "zone_allocs_min": zmin,
            "zone_balance_max_over_min": (zmax / zmin) if zmin else 0.0,
            "fill_cpu": fills[0],
            "fill_memory": fills[1],
            "fill_disk": fills[2],
            "devices_in_use": int(self._sum[:, RES_DIMS].sum()),
        }
