"""The least operations and bytes one launch of the FLAT multi-eval
kernel needs (`nomad_tpu/ops/select.py place_multi_packed`, fresh or
chained), from its shapes: the numerator of `place_multi_roofline`.
Beside benchmark/kernel_cost.py and counted the same way: from the
algorithm as select.py states it, not from the compiled program.

The flat kernel is what a wave launches when its signatures cannot be
proved disjoint (two device requests that an A100 node both matches):
every water-fill round scores ALL the nodes, one round after another.
"""

from __future__ import annotations

from benchmark.kernel_cost import ROUND_OPS_PER_CANDIDATE, WORD

# the node tensors' capacity dimensions: cpu, memory, disk, device
# instances (nomad_tpu/structs RES_NAMES, written out here because this
# file counts what the algorithm needs, whatever the program imports)
RES_WIDTH = 4
# engine.build_multi_inputs' round ladder: an eval of `count` placements
# takes ceil(count / bucket) rounds of the smallest bucket that holds the
# wave's biggest eval
ROUND_BUCKETS = (64, 256, 512, 1024)


def rounds_per_eval(count: int) -> int:
    bucket = next((b for b in ROUND_BUCKETS if count <= b),
                  ROUND_BUCKETS[-1])
    return -(-count // bucket)


def flat_launch(n_nodes: int, rounds: float, signatures: int = 1,
                terms: int = 1, fill_k: int = 64) -> dict:
    """`rounds` water-fill rounds, each over all `n_nodes`.

    Least bytes: the node state read once at its full width (capacity
    and usage, RES_WIDTH int32 a node each), the attribute columns the
    signatures' constraint rows test (one int32 a node and row),
    eligibility and one static mask a signature (a byte a node each);
    `used` written once; a round's packed fills and meta out."""
    ops = rounds * n_nodes * ROUND_OPS_PER_CANDIDATE
    state_in = n_nodes * (WORD * (2 * RES_WIDTH + signatures * terms)
                          + 1 + signatures)
    used_out = n_nodes * WORD * RES_WIDTH
    packed_out = rounds * (fill_k + 16) * WORD
    return {"ops": float(ops),
            "bytes": float(state_in + used_out + packed_out)}
