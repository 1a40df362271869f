"""The least operations and bytes one launch of the FLAT multi-eval
kernel needs on a wave that carries device group slots
(`nomad_tpu/ops/select.py place_multi_packed`, fresh or chained): the
numerator of `place_multi_mig_roofline`.  Beside benchmark/multi_cost.py
and counted the same way: from the algorithm, not from the compiled
program, and from the CONFIGURATION (its job mix and its nodes' groups),
not from a counter of the program, so that it reads the same work
whatever implements it.

On top of `multi_cost.flat_launch`: per candidate and round, one compare
a slot (its room for the round's ask) and one select (the charge of the
first accepted slot with room); the [N, S] slot state, instances and
usage, read once and the usage written once; one [N, S] accept table a
request signature, a byte a slot, read once.  S is the most groups a node
of the configuration advertises, and the tables its distinct requests:
the program's padding of either is its own and not counted.
"""

from __future__ import annotations

from benchmark import multi_cost
from benchmark.kernel_cost import WORD


def slots_of(cfg: dict) -> int:
    """The most device groups one node of the configuration has."""
    return max(len(s["groups"]) for s in cfg["gpu_shapes"])


def signatures_of(cfg: dict) -> int:
    """The distinct device requests (name and constraint) of the mix."""
    return len({(m["device"], repr(m.get("constraint")))
                for m in cfg["job_mix"]})


def mig_launch(n_nodes: int, rounds: float, signatures: int, terms: int,
               slots: int) -> dict:
    """`rounds` water-fill rounds over `n_nodes`, each node with `slots`
    device group slots, the wave's items of `signatures` distinct device
    requests (each its own static signature too)."""
    base = multi_cost.flat_launch(n_nodes, rounds, signatures, terms)
    ops = base["ops"] + rounds * n_nodes * (slots + 1)
    state = n_nodes * slots * (3 * WORD + signatures)
    return {"ops": float(ops), "bytes": float(base["bytes"] + state)}
