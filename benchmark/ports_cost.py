"""The least operations and bytes one launch of the FLAT multi-eval
kernel needs on a wave that holds static port asks
(`nomad_tpu/ops/select.py place_multi_packed`, fresh or chained, with
port state): the numerator of `place_multi_ports_roofline`.  Beside
benchmark/multi_cost.py and counted the same way: from the algorithm, not
from the compiled program, and from the CONFIGURATION's job mix, not from
a counter of the program, so that it reads the same work whatever
implements it.

A static port is a rule over (value, node): a round of a job that asks
one must know, per node, whether any value it asks is held, and a
placement makes its node a holder.  Each job of the mix is one water-fill
round (its count is at most the smallest round bucket); padding rounds
place nothing and are not counted: they are the implementation's.
"""

from __future__ import annotations

from benchmark import multi_cost
from benchmark.kernel_cost import ROUND_OPS_PER_CANDIDATE

# select.port_state + port_commit per node, round and value ASKED: test
# the holder bit, fold it into the mask, set it where the round placed
# (~3); and once a round the cap of one allocation a node (~1)
PORT_OPS_PER_CANDIDATE_VALUE = 3


def rounds_per_wave(job_mix: list, evals: float) -> float:
    """Real rounds of a wave of `evals` evaluations drawn evenly from
    `job_mix` ([{count, ...}, ...])."""
    per_job = [multi_cost.rounds_per_eval(m["count"]) for m in job_mix]
    return evals * sum(per_job) / len(per_job)


def static_rounds_share(job_mix: list) -> float:
    """The share of a wave's rounds whose job asks a static port."""
    per_job = [multi_cost.rounds_per_eval(m["count"]) for m in job_mix]
    return sum(r for r, m in zip(per_job, job_mix)
               if m.get("static")) / sum(per_job)


def ports_launch(n_nodes: int, rounds: float, static_share: float,
                 values: int, terms: int = 1) -> dict:
    """`multi_cost.flat_launch` for one static signature of `terms`
    constraint terms, plus the port state of `values` static port values
    (ports50k's four): the few operations a candidate of the rounds that
    ask one (a job asks one value), and a holder bit a node and value,
    read once and written once (a byte each as the program holds them)."""
    cost = multi_cost.flat_launch(n_nodes, rounds, 1, terms)
    ops = rounds * n_nodes * (
        ROUND_OPS_PER_CANDIDATE
        + static_share * (PORT_OPS_PER_CANDIDATE_VALUE + 1))
    return {"ops": float(ops),
            "bytes": cost["bytes"] + float(2 * values * n_nodes)}
