"""Vectorized ranking kernels.

Replaces the reference RankIterator chain (scheduler/rank.go, spread.go):
BinPackIterator → JobAntiAffinityIterator → NodeReschedulingPenaltyIterator →
NodeAffinityIterator → SpreadIterator → ScoreNormalizationIterator — as dense
[G, N] (or [N]) score tensors combined by mean-normalization, matching the
reference's FinalScore = mean(component scores) contract so AllocMetric
score_meta_data stays comparable.

Score components (all bounded like the reference's):
  binpack     [0, 18]   structs.ScoreFit exponential (or inverted for spread
                        scheduler algorithm)
  job-anti-affinity  [-1, 0]   -((collisions + 1) / desired_count)
  node-reschedule-penalty  {-1, 0}  previous node of a rescheduled alloc
  node-affinity  [-1, 1]  sum(matched weights)/sum(|weights|)
  allocation-spread  [-1, 1]  per-property boost toward target percentages
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nomad_tpu.pack.interner import UNSET
from .feasibility import constraint_mask

MAX_FIT_SCORE = 18.0


def binpack_score(cap: jnp.ndarray,          # [N, 3] float32
                  used: jnp.ndarray,         # [N, 3] float32 (incl. proposed)
                  req: jnp.ndarray,          # [..., 3] float32 broadcastable
                  spread_algo: bool = False,
                  ) -> jnp.ndarray:
    """structs.ScoreFit vectorized.  `used + req` is the post-placement
    utilization; only cpu (0) and memory (1) dims contribute to the score,
    matching the reference."""
    total_used = used + req
    return fit_score(cap[..., 0], cap[..., 1], total_used[..., 0],
                     total_used[..., 1], spread_algo)


def fit_score(cap_cpu, cap_mem, total_cpu, total_mem, spread_algo=False):
    """`binpack_score` on its two dimensions given apart, each any shape
    (float32): the form the fused scan (ops/scan_fused.py) calls on its
    node planes, so both scans rank with one body."""
    free = [1.0 - jnp.minimum(total / jnp.maximum(cap, 1.0), 1.0)
            for cap, total in ((cap_cpu, total_cpu), (cap_mem, total_mem))]
    total = jnp.power(10.0, free[0]) + jnp.power(10.0, free[1])
    score = jnp.where(spread_algo, total - 2.0, 20.0 - total)
    score = jnp.clip(score, 0.0, MAX_FIT_SCORE)
    # zero-capacity nodes score 0
    ok = (cap_cpu > 0) & (cap_mem > 0)
    return jnp.where(ok, score, 0.0)


def capacity_fit(cap: jnp.ndarray,           # [N, 3] int32
                 used: jnp.ndarray,          # [N, 3] int32
                 req: jnp.ndarray,           # [..., 3] int32
                 ) -> jnp.ndarray:           # [...] bool (last dim reduced)
    """AllocsFit's dimension check (ports handled host-side at plan build)."""
    return jnp.all(used + req <= cap, axis=-1)


def job_anti_affinity(job_count: jnp.ndarray,   # [N] int32
                      desired_count: jnp.ndarray | float,
                      ) -> jnp.ndarray:          # [N] float32
    """reference: JobAntiAffinityIterator — penalize nodes already running
    allocs of the same job: -((collisions + 1) / desired_total), the
    placement being scored counted with them as the reference counts it
    (rank.go: `collisions+1`).  Callers apply it where collisions > 0."""
    d = jnp.maximum(desired_count, 1.0)
    return -((job_count.astype(jnp.float32) + 1.0) / d)


def affinity_score(attrs: jnp.ndarray,       # [N, A]
                   aff: jnp.ndarray,         # [G, Af, 4] (col, op, arg, w)
                   luts: jnp.ndarray,        # [L, V]
                   ) -> jnp.ndarray:         # [G, N] float32
    """reference: NodeAffinityIterator — normalized sum of matched affinity
    weights.  Padding rows have weight 0 and contribute nothing."""
    matched = constraint_mask_rows(attrs, aff[..., :3], luts)   # [G, Af, N]
    w = aff[..., 3].astype(jnp.float32)                          # [G, Af]
    total = jnp.sum(jnp.abs(w), axis=1, keepdims=True)           # [G, 1]
    got = jnp.einsum("gan,ga->gn", matched.astype(jnp.float32), w)
    return jnp.where(total > 0, got / jnp.maximum(total, 1.0), 0.0)


def constraint_mask_rows(attrs: jnp.ndarray, con: jnp.ndarray,
                         luts: jnp.ndarray) -> jnp.ndarray:
    """Per-row (no all-reduce) predicate evaluation: [G, C, N] bool."""
    from nomad_tpu.pack.packer import (
        DOP_EQ, DOP_IS_NOT_SET, DOP_IS_SET, DOP_LUT, DOP_NEQ)
    cols = con[..., 0]
    ops = con[..., 1][..., None]
    args = con[..., 2]
    av = jnp.moveaxis(attrs[:, cols], 0, -1)          # [G, C, N]
    is_set = av != UNSET
    arg_b = args[..., None]
    lut_rows = jnp.clip(args, 0, luts.shape[0] - 1)
    av_clip = jnp.clip(av, 0, luts.shape[1] - 1)
    lut_val = luts[lut_rows[..., None], av_clip]
    return jnp.where(
        ops == DOP_EQ, is_set & (av == arg_b),
        jnp.where(
            ops == DOP_NEQ, (~is_set) | (av != arg_b),
            jnp.where(
                ops == DOP_IS_SET, is_set,
                jnp.where(
                    ops == DOP_IS_NOT_SET, ~is_set,
                    jnp.where(ops == DOP_LUT, is_set & lut_val,
                              jnp.zeros_like(is_set))))))


def spread_boost(sp_nodeval: jnp.ndarray,    # [S, N] int32 local value idx, -1 none
                 sp_weight: jnp.ndarray,     # [S] float32 (0 = padding row)
                 sp_expected: jnp.ndarray,   # [S, K] float32 expected counts
                 sp_counts: jnp.ndarray,     # [S, K] float32 current counts
                 ) -> jnp.ndarray:           # [N] float32
    """reference: SpreadIterator/propertySet — boost toward target
    percentages.  For node n with value v on spread s:
        boost = (expected_v - (count_v + 1)) / max(expected_v, 1)
    clipped to [-1, 1]: like the reference (spread.go: `usedCount += 1`)
    the count includes the placement being scored, so a value that is
    one short of its target scores 0, not 1 / expected.  Weighted by
    sp_weight/100 and averaged over non-padding spreads.  `sp_counts`
    stays the count BEFORE this placement, as every caller carries it."""
    k = sp_counts.shape[1]
    val = jnp.clip(sp_nodeval, 0, k - 1)
    exp_n = jnp.take_along_axis(sp_expected, val, axis=1)     # [S, N]
    cnt_n = jnp.take_along_axis(sp_counts, val, axis=1)       # [S, N]
    boost = value_boost(exp_n, cnt_n)
    # nodes whose value is not a spread target get no boost
    boost = jnp.where(sp_nodeval >= 0, boost, 0.0)
    w, n_active = spread_weights(sp_weight)
    return jnp.sum(boost * w[:, None], axis=0) / n_active


def spread_weights(sp_weight):
    """(each spread's weight as a share [S], how many spreads count [])
    of `spread_boost`'s weighted mean."""
    return sp_weight / 100.0, jnp.maximum(jnp.sum(sp_weight > 0), 1.0)


def value_boost(expected, counts):
    """One spread value's boost at its expected and current counts
    (`spread_boost`), elementwise: the fused scan calls it on the [S, K]
    table of values where the XLA scan calls it on the values gathered
    a node, so both compute each node's boost with one body."""
    return jnp.clip((expected - (counts + 1.0)) / jnp.maximum(expected, 1.0),
                    -1.0, 1.0)


def normalize_scores(components: jnp.ndarray,   # [Ncomp, ...] stacked
                     active: jnp.ndarray,       # [Ncomp, ...] bool
                     ) -> jnp.ndarray:
    """reference: ScoreNormalizationIterator — FinalScore is the mean of the
    component scores that actually apply."""
    n = jnp.maximum(jnp.sum(active, axis=0), 1.0)
    return jnp.sum(jnp.where(active, components, 0.0), axis=0) / n
