"""The least operations and bytes one launch of `place_system` needs,
from its shapes: the numerator of `place_system_roofline`.  Beside
benchmark/kernel_cost.py and counted the same way: from the algorithm as
`nomad_tpu/ops/feasibility.py` states it, not from the compiled program.

The kernel is one elementwise pass over the nodes per task group, no
scan over placements and no sort, so it is bound by HBM traffic: every
input read once, the verdicts written once.
"""

from __future__ import annotations

# feasibility.constraint_mask, per node, group and constraint row: the
# attribute's gather, is-set, the compare, the LUT lookup, the five-way
# select on the opcode (~7), and the and-reduce over the rows (1)
CONSTRAINT_OPS_PER_TERM = 8
# per node and group beyond the constraint rows: eligibility & datacenter
# & pool (2) and their and into the mask (1); 3 adds and 3 compares for
# `used + ask <= capacity - reserved` on cpu, memory and disk; the
# verdict's select chain (4); the domain's and (1); the usage update for
# the next group (3)
FIT_OPS = 17
WORD = 4


def system_launch(n_nodes: int, groups: int, terms: int) -> dict:
    """`place_system` over `n_nodes` for `groups` task groups of `terms`
    constraint rows each (the job's, the group's and the tasks'
    constraints plus one driver check a task).

    Least bytes: of the attribute table only the columns the rows test
    (a row reads one int32 a node), capacity and usage (3 int32 a node
    each), the four per-node booleans (eligibility, datacenter, pool,
    domain); written once, one int8 verdict per node and group, which
    carries the mask and the exhausted dimension."""
    ops = n_nodes * groups * (terms * CONSTRAINT_OPS_PER_TERM + FIT_OPS)
    read = n_nodes * (groups * terms * WORD + 2 * 3 * WORD + 4)
    written = n_nodes * groups
    return {"ops": float(ops), "bytes": float(read + written)}


def job_shape(job: dict) -> tuple:
    """(groups, constraint rows a group) of a wire-form job, as the
    packer lowers them: every group carries the job's constraints, its
    own, its tasks', and one driver check a task; the widest group
    counts (the rows are padded to it)."""
    shared = len(job.get("Constraints") or ())
    terms = max(
        shared + len(tg.get("Constraints") or ())
        + sum(1 + len(t.get("Constraints") or ()) for t in tg["Tasks"])
        for tg in job["TaskGroups"])
    return len(job["TaskGroups"]), terms
