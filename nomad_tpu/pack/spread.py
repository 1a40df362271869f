"""Spread lowering (reference: scheduler/spread.go, propertyset.go).

A Spread stanza targets an attribute column; the device needs, per spread:
  sp_nodeval  [S, N]  each node's *local* value index for the spread
                      attribute (-1 when the node's value isn't tracked)
  sp_weight   [S]     stanza weight (0 marks padding rows)
  sp_expected [S, K]  expected alloc count per tracked value
  sp_counts0  [S, K]  current (existing, non-terminal) counts per value

Expected counts follow the reference's propertySet math: explicit targets get
`percent/100 * desired_total`; with no explicit targets the desired total is
split evenly across the values observed on feasible-eligible nodes.

The lowering has two halves.  `spread_landscape` is static for a
node-table version: which value index each node carries, a function of
the stanza's attribute and target VALUES alone, so 48 evals of a wave
that spread alike share one walk of the node table (ops/engine.py keeps
it by version).  `spread_job_rows` is the job's: expected counts, weight
and the counts its live allocations already hold, a few floats.
`lower_spreads` composes the two for the solo path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from nomad_tpu.structs import Job, Spread
from .interner import UNSET
from .packer import ClusterPacker, NodeTensors, resolve_target_key


@dataclass
class SpreadTensors:
    sp_nodeval: np.ndarray   # [S, N] int32
    sp_weight: np.ndarray    # [S] float32
    sp_expected: np.ndarray  # [S, K] float32
    sp_counts0: np.ndarray   # [S, K] float32

    @staticmethod
    def empty(n: int) -> "SpreadTensors":
        return SpreadTensors(
            sp_nodeval=np.full((1, n), -1, np.int32),
            sp_weight=np.zeros(1, np.float32),
            sp_expected=np.ones((1, 1), np.float32),
            sp_counts0=np.zeros((1, 1), np.float32),
        )


def job_spreads(job: Job) -> List[Spread]:
    """The stanzas a placement of `job` is scored under: the job's own,
    then every task group's, in order."""
    spreads = list(job.spreads)
    for tg in job.task_groups:
        spreads.extend(tg.spreads)
    return spreads


def spread_signature(spreads: Sequence[Spread]) -> Tuple:
    """What `spread_landscape` is a function of, beside the node table:
    per stanza the attribute and the target values (not their percents,
    not the weight)."""
    return tuple((sp.attribute, tuple(t.value for t in sp.targets))
                 for sp in spreads)


def spread_landscape(packer: ClusterPacker, tensors: NodeTensors,
                     sp: Spread) -> Tuple[np.ndarray, int]:
    """([N] int32 local value index a node, -1 = not tracked; number of
    tracked values) of one stanza: explicit targets first, in their
    order; a target-less stanza tracks the values observed on eligible
    nodes."""
    n = tensors.n
    col = packer.ensure_column(resolve_target_key(sp.attribute))
    col_vals = (tensors.attrs[:, col] if col < tensors.attrs.shape[1]
                else np.full(n, UNSET, np.int32))
    local: Dict[int, int] = {}
    for t in sp.targets:
        vid = packer.interner.intern(t.value)
        if vid not in local:
            local[vid] = len(local)
    if not sp.targets:
        for vid in np.unique(col_vals[tensors.elig]):
            if vid != UNSET and vid not in local:
                local[vid] = len(local)
    remap = np.full(len(packer.interner) + 1, -1, np.int32)
    for vid, li in local.items():
        remap[vid] = li
    nodeval = np.where(col_vals == UNSET, -1, remap[col_vals])
    return nodeval.astype(np.int32), max(len(local), 1)


def spread_job_rows(job: Job, spreads: Sequence[Spread],
                    landscapes: Sequence[Tuple[np.ndarray, int]],
                    tensors: NodeTensors, snapshot
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(weight [S], expected [S, K], counts0 [S, K]) of `job` under
    `spreads`, K the largest tracked-value count among `landscapes`
    (one `spread_landscape` a stanza)."""
    s = len(spreads)
    k_max = max(k for _, k in landscapes)
    desired_total = sum(tg.count for tg in job.task_groups)
    weight = np.array([float(sp.weight) for sp in spreads], np.float32)
    expected = np.zeros((s, k_max), np.float32)
    counts = np.zeros((s, k_max), np.float32)
    for i, (sp, (_, k)) in enumerate(zip(spreads, landscapes)):
        if sp.targets:
            # a value named twice keeps its first percent
            pcts: List[float] = []
            seen = set()
            for t in sp.targets:
                if t.value not in seen:
                    seen.add(t.value)
                    pcts.append(float(t.percent))
        else:
            pcts = [100.0 / k] * k
        for li, pct in enumerate(pcts):
            expected[i, li] = pct / 100.0 * desired_total
    live_rows = [tensors.id_to_row.get(alc.node_id)
                 for alc in snapshot.allocs_by_job(job.namespace, job.id)
                 if not alc.terminal_status()]
    for row in live_rows:
        if row is None:
            continue
        for i, (nodeval, _) in enumerate(landscapes):
            if nodeval[row] >= 0:
                counts[i, nodeval[row]] += 1
    return weight, expected, counts


def lower_spreads(packer: ClusterPacker, job: Job, tensors: NodeTensors,
                  snapshot) -> SpreadTensors:
    spreads = job_spreads(job)
    if not spreads:
        return SpreadTensors.empty(tensors.n)
    landscapes = [spread_landscape(packer, tensors, sp) for sp in spreads]
    weight, expected, counts = spread_job_rows(job, spreads, landscapes,
                                               tensors, snapshot)
    return SpreadTensors(
        sp_nodeval=np.stack([nv for nv, _ in landscapes]),
        sp_weight=weight,
        sp_expected=expected,
        sp_counts0=counts,
    )
