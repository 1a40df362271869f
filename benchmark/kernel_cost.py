"""The least operations and bytes one launch of a placement kernel needs,
from its shapes: the numerator of a roofline share.  Counted from the
algorithm as `nomad_tpu/ops/select.py` states it, not from the compiled
program, so a faster formulation of the same algorithm raises the share.

"Least bytes" assumes the per-node state stays on the chip across the
steps of one launch (it is a few megabytes at most), so every input is
read from HBM once and every output written once.  The peaks are the
chip's published bf16 matrix rate and HBM rate (benchmark/peaks.py); the
kernels are int32/float32 elementwise work in a serial scan, which the
matrix unit cannot take, so a share of a few thousandths of a percent
means "bound by the latency of dependent steps", not "badly tuned".
"""

from __future__ import annotations

# select.step_scores + the update in select.place, per node and step:
# 3 fit compares, ~20 for the two-dimension bin-pack score, 2 job
# anti-affinity, 1 affinity, ~6 spread boost, ~8 normalize over five
# components, 2 mask, 2 top-k compare/select, 4 state update
SCAN_OPS_PER_NODE_STEP = 48
# select.round_scores_g + waterfill_round + round_metrics_g, per
# candidate and round: the same scoring without spread (~36), ~16 for the
# water-fill's capacity counts, prefix sum and top-k, ~12 metrics
ROUND_OPS_PER_CANDIDATE = 64
WORD = 4


def scan_launch(n_nodes: int, steps: int, spreads: int = 1) -> dict:
    """`place_packed`: `steps` dependent placements over `n_nodes`."""
    ops = steps * n_nodes * SCAN_OPS_PER_NODE_STEP
    state_in = n_nodes * WORD * (3 + 3 + 1 + 1 + 1 + 1 + spreads)
    state_out = n_nodes * WORD * (3 + 1)
    packed_out = steps * 14 * WORD
    return {"ops": float(ops),
            "bytes": float(state_in + state_out + packed_out)}


def compact_launch(n_nodes: int, lanes: int, rounds: int,
                   fill_k: int = 32) -> dict:
    """`place_multi_compact_packed` (fresh or chained): `rounds` water-
    fill rounds, each over its lane's frame of n_nodes / lanes
    candidates."""
    frame = n_nodes / lanes
    ops = rounds * frame * ROUND_OPS_PER_CANDIDATE
    frames_in = n_nodes * WORD * (3 + 3 + 1)       # cap, used, affinity
    used_out = n_nodes * WORD * 3                  # scattered back once
    packed_out = rounds * (fill_k + 16) * WORD
    return {"ops": float(ops),
            "bytes": float(frames_in + used_out + packed_out)}


def roofline(cost: dict, peaks: dict, measured_s: float) -> dict:
    """Share of the roofline one launch reached, and which bound it is."""
    t_ops = cost["ops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    least = max(t_ops, t_bytes)
    return {"share_pct": 100.0 * least / measured_s,
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "least_s": least}
