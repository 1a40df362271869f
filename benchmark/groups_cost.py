"""The least operations and bytes one launch of the FLAT multi-eval
kernel needs on a wave of jobs of several task groups
(`nomad_tpu/ops/select.py place_multi_packed`, fresh or chained): the
numerator of `place_multi_groups_roofline`.  Beside
benchmark/multi_cost.py and counted the same way: from the algorithm, not
from the compiled program, and from the CONFIGURATION's job mix, not from
a counter of the program, so that it reads the same work whatever
implements it.

A group is one item of the launch: ceil(count / bucket) water-fill rounds
over every node, the bucket the smallest of the ladder that holds the
mix's largest group (groups50k: every group fits one round of 256, so a
round a group).  The groups of one eval share the job's count row, which
the kernel carries from round to round as it carries `used`: no bytes of
its own for a fresh job.  Padding rounds place nothing and are not
counted: they are the implementation's.
"""

from __future__ import annotations

from benchmark import multi_cost


def round_bucket(job_mix: list) -> int:
    """The ladder's bucket for a wave that holds the mix's largest
    group."""
    biggest = max(c for m in job_mix for c in m["counts"])
    return next((b for b in multi_cost.ROUND_BUCKETS if biggest <= b),
                multi_cost.ROUND_BUCKETS[-1])


def rounds_per_wave(job_mix: list, evals: float) -> float:
    """Real rounds of a wave of `evals` evaluations drawn evenly from
    `job_mix` ([{counts: [a group's count, ...]}, ...])."""
    bucket = round_bucket(job_mix)
    per_job = [sum(-(-c // bucket) for c in m["counts"]) for m in job_mix]
    return evals * sum(per_job) / len(per_job)


def groups_launch(n_nodes: int, rounds: float, terms: int = 1) -> dict:
    """`multi_cost.flat_launch` for one static signature of `terms`
    constraint terms (every group of the mix carries the job's)."""
    return multi_cost.flat_launch(n_nodes, rounds, 1, terms)
