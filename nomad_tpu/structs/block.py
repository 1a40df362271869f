"""Columnar allocation block — bulk placements without per-alloc objects.

The TPU placement kernels decide thousands of placements per launch; the
round-3 profile showed the pipeline then spending MORE wall-time turning
those picks into per-alloc Python dicts (materialize) and inserting them
one-by-one into the state store (commit) than the device spent deciding
them.  An `AllocBlock` keeps one eval's homogeneous placements COLUMNAR
end-to-end: one shared template alloc plus numpy pick rows, flowing
through Plan -> applier -> state store as a single object.  Individual
`Allocation` objects materialize lazily — on first read of a covered
(job, node) bucket — so the scheduling hot path never pays the per-alloc
cost and cold reads (CLI, API, client sync) see ordinary allocs.

The reference has no analog: stock materializes full Allocation structs
per placement (structs.Plan NodeAllocation; scheduler/generic_sched.go
computePlacements).  This is the TPU-native replacement for exactly that
host cost, per SURVEY §7 P1's packed-plane design stance.

Ownership/mutability: a block is IMMUTABLE once inserted into the store
(same convention as every stored object).  The lazy caches (materialized
rows, id set, per-node row map) are monotone fill-once structures shared
safely across snapshots and the head under the store lock or the GIL.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from .structs import (RES_NAMES, AllocatedDeviceResource, Allocation,
                      AllocMetric, NodeScoreMeta)


@dataclass
class RowMetrics:
    """Per-ROW placement metrics as the columns the exact scan returned
    (ISSUE 35): one row a placement, where a bulk round shares ONE
    AllocMetric among its rows.  A row's AllocMetric object (and its
    NodeScoreMeta list) is built on demand, `metric(i)` or
    `materialize()`, where it is read; the scheduling path carries
    arrays only."""

    # shared by every row
    nodes_evaluated: int = 0
    nodes_in_pool: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)
    allocation_time_ns: int = 0
    # [rows, 2 + len(dim_names)] int32: nodes_filtered | nodes_exhausted
    # | dimension_exhausted by `dim_names`: the capacity dimensions
    # (RES_NAMES) and, off a scan with static-port state, the port
    # collision's
    counts: Optional[np.ndarray] = None
    dim_names: tuple = RES_NAMES
    # the scan's best candidates a row: topk[i, j] indexes `nodes` (-1:
    # none), topk_scores[i, j] is its final score (float32)
    topk: Optional[np.ndarray] = None
    topk_scores: Optional[np.ndarray] = None
    nodes: List[str] = field(default_factory=list)

    def take(self, keep) -> "RowMetrics":
        """The rows a boolean mask (or an index array) selects."""
        return RowMetrics(
            nodes_evaluated=self.nodes_evaluated,
            nodes_in_pool=self.nodes_in_pool,
            nodes_available=self.nodes_available,
            allocation_time_ns=self.allocation_time_ns,
            counts=self.counts[keep], topk=self.topk[keep],
            topk_scores=self.topk_scores[keep], nodes=self.nodes,
            dim_names=self.dim_names)

    def metric(self, i: int) -> AllocMetric:
        return self.take(slice(i, i + 1)).materialize()[0]

    def materialize(self) -> List[AllocMetric]:
        """One AllocMetric a row.  score_meta_data repeats from row to
        row: one list per distinct top-k is shared (read-only by
        convention, like the shared job pointer)."""
        # native-python views once, not one numpy-scalar box per field
        counts = self.counts.tolist()
        topk = self.topk.tolist()
        scores = self.topk_scores.tolist()
        nodes = self.nodes
        n_eval, n_pool = self.nodes_evaluated, self.nodes_in_pool
        avail, elapsed = self.nodes_available, self.allocation_time_ns
        dim_names = self.dim_names
        smd_cache: Dict[tuple, list] = {}
        out: List[AllocMetric] = []
        for row, kr, ks in zip(counts, topk, scores):
            metric = AllocMetric(
                nodes_evaluated=n_eval,
                nodes_filtered=row[0],
                nodes_in_pool=n_pool,
                nodes_available=avail,
                nodes_exhausted=row[1],
                allocation_time_ns=elapsed,
            )
            if any(row[2:]):
                metric.dimension_exhausted = {
                    name: c for name, c in zip(dim_names, row[2:]) if c}
            key = (*kr, *ks)
            smd = smd_cache.get(key)
            if smd is None:
                smd_cache[key] = smd = [
                    NodeScoreMeta(node_id=nodes[r], scores={"final": s},
                                  norm_score=s)
                    for r, s in zip(kr, ks) if r >= 0]
            metric.score_meta_data = smd
            out.append(metric)
        return out


@dataclass
class AllocBlock:
    """`count` placements of ONE task group sharing every field except
    (id, name, node, metrics)."""

    id: str = ""
    template: Optional[Allocation] = None
    ids: List[str] = field(default_factory=list)
    # names derive from the reconciler's block form: prefix + index + "]"
    name_prefix: str = ""
    indexes: List[int] = field(default_factory=list)
    # picks[i] indexes node_table (block-local, UNIQUE nodes only)
    picks: Optional[np.ndarray] = None
    node_table: List[str] = field(default_factory=list)
    # one AllocMetric per water-fill round, shared by the round's allocs
    metrics: List[AllocMetric] = field(default_factory=list)
    round_size: int = 1024
    # OR (the exact scan's blocks, ISSUE 35) one metric a ROW, as columns:
    # row i of `row_metrics` is row i of the block; `metrics` is empty
    row_metrics: Optional[RowMetrics] = None
    # COLUMNAR port assignment (ISSUE 8): ports[i, j] is row i's value for
    # dynamic-port label port_labels[j].  None for non-networked blocks.
    # The batched carve in scheduler/generic.py fills these; rows
    # materialize with per-row allocated_ports dicts and the applier's
    # per-node port re-check reads them straight off the array
    # (plan_apply._eval_blocks) — per-alloc objects never exist on the
    # networked hot path either.
    port_labels: List[str] = field(default_factory=list)
    ports: Optional[np.ndarray] = None
    # COLUMNAR device assignment (ISSUE 30), None for a group that asks
    # for no device: device_ids[i] are the instance ids of row i, a
    # [count, k] unicode array with k the request's count, held by task
    # `device_task`, carved from the device group
    # device_groups[device_group_of[i]], a (vendor, type, name): the
    # block's table of groups, one entry each, and a group index a row
    # (two rows on one node may hold instances of two of its groups).
    # The batched carve in scheduler/generic.py fills these, rows
    # materialize with their own allocated_devices, and the applier
    # audits them per node and group off the arrays
    # (plan_apply._eval_blocks).
    device_task: str = ""
    device_groups: List[tuple] = field(default_factory=list)
    device_group_of: Optional[np.ndarray] = None
    device_ids: Optional[np.ndarray] = None
    create_index: int = 0
    modify_index: int = 0

    def __post_init__(self) -> None:
        # lazy caches — deliberately NOT dataclass fields (they must not
        # ride the wire codec or compare)
        self._rows: Optional[List[Allocation]] = None
        self._id_index: Optional[Dict[str, int]] = None
        self._rows_by_node: Optional[Dict[str, list]] = None

    # ------------------------------------------------------------- shape

    @property
    def count(self) -> int:
        return len(self.ids)

    def unique_node_ids(self) -> List[str]:
        return self.node_table

    def resources_tuple(self):
        """What ONE row counts against its node, by capacity dimension
        (structs.RES_NAMES)."""
        r = self.template.resources
        return (r.cpu, r.memory_mb, r.disk_mb,
                self.device_ids.shape[1] if self.device_ids is not None
                else 0)

    def node_counts(self) -> np.ndarray:
        """allocs per node_table row (for vectorized usage scatters)."""
        return np.bincount(self.picks, minlength=len(self.node_table))

    def demand_by_node(self) -> Dict[str, tuple]:
        """{node_id: (count, cpu, mem_mb, disk_mb)} demanded by this
        block — the plan applier's columnar fit-check input.  O(unique
        nodes) host work; no per-alloc objects exist."""
        counts = self.node_counts().tolist()
        r = self.template.resources
        return {nid: (c, c * r.cpu, c * r.memory_mb, c * r.disk_mb)
                for nid, c in zip(self.node_table, counts) if c}

    def ports_by_node(self) -> Dict[str, list]:
        """{node_id: [port, ...]} claimed by this block's rows — the
        applier's batched per-node port re-check input.  One argsort over
        the picks, no per-alloc objects."""
        if self.ports is None or not self.ports.size:
            return {}
        order = np.argsort(self.picks, kind="stable")
        grouped = self.ports[order].reshape(len(order), -1)
        counts = self.node_counts()
        out: Dict[str, list] = {}
        pos = 0
        for nid, c in zip(self.node_table, counts.tolist()):
            if c:
                out[nid] = grouped[pos:pos + c].ravel().tolist()
                pos += c
        return out

    def devices_by_node(self) -> Dict[str, Dict[tuple, list]]:
        """{node_id: {group: [instance id, ...]}} claimed by this block's
        rows, the applier's per-node device audit input: one pass over
        the rows' columns, no per-alloc objects."""
        if self.device_ids is None or not self.device_ids.size:
            return {}
        groups = [tuple(g) for g in self.device_groups]
        out: Dict[str, Dict[tuple, list]] = {}
        for p, gi, ids in zip(self.picks.tolist(),
                              self.device_group_of.tolist(),
                              self.device_ids.tolist()):
            out.setdefault(self.node_table[p], {}).setdefault(
                groups[gi], []).extend(ids)
        return out

    def device_use_by_node(self) -> Dict[str, Dict[str, int]]:
        """{node_id: {group id: instances}} this block's rows hold: what
        the packer's device group ledger counts."""
        if self.device_ids is None or not self.device_ids.size:
            return {}
        gids = ["/".join(g) for g in self.device_groups]
        k = self.device_ids.shape[1]
        out: Dict[str, Dict[str, int]] = {}
        for p, gi in zip(self.picks.tolist(),
                         self.device_group_of.tolist()):
            held = out.setdefault(self.node_table[p], {})
            held[gids[gi]] = held.get(gids[gi], 0) + k
        return out

    def without_nodes(self, bad_node_ids) -> Optional["AllocBlock"]:
        """A new block with every row placed on `bad_node_ids` dropped —
        the applier's COLUMNAR per-node refute: the surviving rows stay
        an array-form block (no materialization) while the refuted rows
        simply never commit.  Returns None when nothing survives.

        The surviving rows keep the original per-round metrics list and
        round size; after compaction a row's `i // round_size` metric
        index can shift to a neighboring round's (shared, diagnostic)
        metric — acceptable drift for the rare partial-refute path, the
        same class of sharing the round metrics already are.  Per-row
        metric columns are compacted with the rows: no drift."""
        bad_rows = np.array(
            [i for i, nid in enumerate(self.node_table)
             if nid in bad_node_ids], np.int64)
        if not bad_rows.size:
            return self
        keep = ~np.isin(self.picks, bad_rows)
        if not keep.any():
            return None
        import itertools
        sel = keep.tolist()
        uniq, inv = np.unique(self.picks[keep], return_inverse=True)
        return AllocBlock(
            id=self.id,
            template=self.template,
            ids=list(itertools.compress(self.ids, sel)),
            name_prefix=self.name_prefix,
            indexes=list(itertools.compress(self.indexes, sel)),
            picks=inv.astype(np.int32),
            node_table=[self.node_table[int(r)] for r in uniq],
            metrics=list(self.metrics),
            round_size=self.round_size,
            row_metrics=(self.row_metrics.take(keep)
                         if self.row_metrics is not None else None),
            port_labels=list(self.port_labels),
            ports=self.ports[keep] if self.ports is not None else None,
            device_task=self.device_task,
            device_groups=list(self.device_groups),
            device_group_of=(self.device_group_of[keep]
                             if self.device_group_of is not None else None),
            device_ids=(self.device_ids[keep]
                        if self.device_ids is not None else None),
        )

    def index_of(self, alloc_id: str) -> Optional[int]:
        if self._id_index is None:
            self._id_index = {aid: i for i, aid in enumerate(self.ids)}
        return self._id_index.get(alloc_id)

    def contains_id(self, alloc_id: str) -> bool:
        return self.index_of(alloc_id) is not None

    # ------------------------------------------------------ materializing

    def materialize_all(self) -> List[Allocation]:
        """All rows, built once and cached (objects immutable-once-read
        by store convention, so the cache is shared across snapshots)."""
        if self._rows is None:
            picks = self.picks.tolist()
            node_table = self.node_table
            ids = self.ids
            indexes = self.indexes
            prefix = self.name_prefix
            metrics = self.metrics
            rs = self.round_size
            if self.row_metrics is not None:
                # a metric a row: built here, on first read
                metrics = self.row_metrics.materialize()
                rs = 1
            tmpl_d = self.template.__dict__
            ci, mi = self.create_index, self.modify_index
            plabels = self.port_labels
            prows = (self.ports.tolist()
                     if self.ports is not None and plabels else None)
            drows = (self.device_ids.tolist()
                     if self.device_ids is not None else None)
            dgroups = self.device_groups
            dgroup_of = (self.device_group_of.tolist()
                         if drows is not None else None)
            dtask = self.device_task
            rows = []
            alloc_new = Allocation.__new__
            n_m = len(metrics) - 1
            for i in range(len(ids)):
                a = alloc_new(Allocation)
                d = dict(tmpl_d)
                a.__dict__ = d
                d["id"] = ids[i]
                d["name"] = prefix + str(indexes[i]) + "]"
                d["node_id"] = node_table[picks[i]]
                d["metrics"] = metrics[min(i // rs, n_m)] if metrics \
                    else None
                d["task_states"] = {}
                d["create_index"] = ci
                d["modify_index"] = mi
                if prows is not None:
                    d["allocated_ports"] = dict(zip(plabels, prows[i]))
                if drows is not None:
                    vendor, dtype, dname = dgroups[dgroup_of[i]]
                    d["allocated_devices"] = [AllocatedDeviceResource(
                        task=dtask, vendor=vendor, type=dtype, name=dname,
                        device_ids=drows[i])]
                rows.append(a)
            self._rows = rows
        return self._rows

    def rows_for_node(self, node_id: str) -> List[Allocation]:
        """Materialized rows placed on `node_id` (lazy per-node index)."""
        if self._rows_by_node is None:
            rows = self.materialize_all()
            by_node: Dict[str, list] = {nid: [] for nid in self.node_table}
            for a in rows:
                by_node[a.node_id].append(a)
            self._rows_by_node = by_node
        return self._rows_by_node.get(node_id, [])
