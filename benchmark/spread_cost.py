"""The least operations and bytes one launch of the FLAT multi-eval
kernel needs on a wave that holds spread items
(`nomad_tpu/ops/select.py place_multi_packed`, fresh or chained, with
spread state): the numerator of `place_multi_spread_roofline`.  Beside
benchmark/multi_cost.py and counted the same way: from the algorithm,
not from the compiled program, and from the CONFIGURATION's job mix, not
from a counter of the program, so that it reads the same work whatever
implements it.

Sequential spread semantics fix the round count: the boost moves with
every commit, so an item with a stanza needs one pass over the nodes a
PLACEMENT; an item without keeps a pass a round bucket.  Padding rounds
place nothing and are not counted: they are the implementation's.
"""

from __future__ import annotations

from benchmark import multi_cost
from benchmark.kernel_cost import ROUND_OPS_PER_CANDIDATE, WORD

# ops/scoring.py spread_boost per node, stanza and round: two lookups by
# the node's value index, subtract, divide, clip, mask, weight (~6, as
# kernel_cost.SCAN_OPS_PER_NODE_STEP counts it), and its share of the
# mean's fourth component (~2)
SPREAD_OPS_PER_CANDIDATE = 8


def rounds_per_wave(job_mix: list, evals: float) -> float:
    """Real rounds of a wave of `evals` evaluations drawn evenly from
    `job_mix` ([{count, spread_weight?}, ...])."""
    per_job = [m["count"] if "spread_weight" in m
               else multi_cost.rounds_per_eval(m["count"]) for m in job_mix]
    return evals * sum(per_job) / len(per_job)


def spread_launch(n_nodes: int, rounds: float, terms: int = 1) -> dict:
    """`multi_cost.flat_launch` for one static signature of `terms`
    constraint terms, plus the spread state of ONE stanza signature with
    one stanza (spread50k's: its kinds differ in weight alone): the
    boost's few operations a candidate and round, and one int32 a node
    (the value-index landscape), read once."""
    cost = multi_cost.flat_launch(n_nodes, rounds, 1, terms)
    ops = rounds * n_nodes * (ROUND_OPS_PER_CANDIDATE
                              + SPREAD_OPS_PER_CANDIDATE)
    return {"ops": float(ops), "bytes": cost["bytes"] + float(n_nodes * WORD)}
