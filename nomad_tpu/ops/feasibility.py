"""Feasibility mask kernel.

Replaces the reference's pull-based FeasibleIterator chain
(scheduler/feasible.go: DriverChecker, ConstraintChecker, HostVolumeChecker,
CSIVolumeChecker, NodePoolChecker, per-ComputedClass EvalCache) with one
vectorized evaluation: a `[G, N]` boolean mask over all task groups × all
nodes in a single fused XLA computation.  The reference's per-class caching
trick is unnecessary — we don't cache per class, we just score every node.

All string work happened host-side in nomad_tpu.pack: the device sees interned
ids, opcodes, and pre-evaluated LUT rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nomad_tpu.pack.interner import UNSET
from nomad_tpu.structs import RES_DIMS, RES_NAMES
from nomad_tpu.pack.packer import (
    DOP_EQ,
    DOP_IS_NOT_SET,
    DOP_IS_SET,
    DOP_LUT,
    DOP_NEQ,
)


def constraint_mask(attrs: jnp.ndarray,      # [N, A] int32
                    con: jnp.ndarray,        # [G, C, 3] int32 (col, op, arg)
                    luts: jnp.ndarray,       # [L, V] bool
                    xp=jnp,
                    ) -> jnp.ndarray:        # [G, N] bool
    """Evaluate every packed constraint row against every node.

    `xp` is the array module: `jnp` inside the kernels, `numpy` for the
    engine's host-side candidate frames — one body, so the two cannot
    drift, and the host copy needs no JAX backend at all."""
    cols = con[..., 0]                       # [G, C]
    ops = con[..., 1][..., None]             # [G, C, 1]
    args = con[..., 2]                       # [G, C]

    av = attrs[:, cols]                      # [N, G, C]
    av = xp.moveaxis(av, 0, -1)              # [G, C, N]
    is_set = av != UNSET

    arg_b = args[..., None]                  # [G, C, 1]
    lut_rows = xp.clip(args, 0, luts.shape[0] - 1)
    av_clip = xp.clip(av, 0, luts.shape[1] - 1)
    lut_val = luts[lut_rows[..., None], av_clip]   # [G, C, N]

    res = xp.where(
        ops == DOP_EQ, is_set & (av == arg_b),
        xp.where(
            ops == DOP_NEQ, (~is_set) | (av != arg_b),
            xp.where(
                ops == DOP_IS_SET, is_set,
                xp.where(
                    ops == DOP_IS_NOT_SET, ~is_set,
                    xp.where(ops == DOP_LUT, is_set & lut_val,
                             xp.ones_like(is_set))))))
    return xp.all(res, axis=1)               # [G, N]


def feasible_mask(attrs: jnp.ndarray,        # [N, A]
                  elig: jnp.ndarray,         # [N] bool
                  dc_mask: jnp.ndarray,      # [N] bool
                  pool_mask: jnp.ndarray,    # [N] bool
                  con: jnp.ndarray,          # [G, C, 3]
                  luts: jnp.ndarray,         # [L, V]
                  ) -> jnp.ndarray:          # [G, N] bool
    """Full static feasibility: node eligibility (status/drain/eligibility
    collapsed host-side), datacenter and node-pool membership, and the
    constraint rows.  Capacity fit is dynamic (depends on in-plan usage) and
    lives in the selection kernel."""
    base = elig & dc_mask & pool_mask        # [N]
    return constraint_mask(attrs, con, luts) & base[None, :]


feasible_mask_jit = jax.jit(feasible_mask)

# place_system's per-(group, node) verdict, in allocs_fit's order of
# dimensions; anything but SYS_PLACED names why the node took nothing
SYS_PLACED, SYS_FILTERED = 0, 1
SYS_VERDICTS = 2 + RES_DIMS
# verdict 2 + d: capacity dimension d (structs.RES_NAMES) is over
SYS_DIMENSIONS = {2 + d: name for d, name in enumerate(RES_NAMES)}


def place_system(attrs: jnp.ndarray,         # [N, A]
                 elig: jnp.ndarray,          # [N] bool
                 dc_mask: jnp.ndarray,       # [N] bool
                 pool_mask: jnp.ndarray,     # [N] bool
                 con: jnp.ndarray,           # [G, C, 3]
                 luts: jnp.ndarray,          # [L, V]
                 cap: jnp.ndarray,           # [N, RES_DIMS], net of reserved
                 used: jnp.ndarray,          # [N, RES_DIMS] int32
                 req: jnp.ndarray,           # [G, RES_DIMS] int32
                 domain: jnp.ndarray,        # [N] bool
                 ) -> jnp.ndarray:           # [G, N] int8
    """A system eval's whole placement: one allocation of every task
    group on every node that passes (reference: SystemScheduler's
    per-node loop).  No scan over placements and no sort, since selection
    is "every node that passes"; the groups are walked in order, because
    group g's placement on a node counts against group g+1 there.

    Returns a SYS_* verdict per (group, node): `feasible_mask`'s terms,
    then `used + ask <= cap` on every capacity dimension (cpu, memory,
    disk, device instances), the first dimension over naming the verdict
    as `allocs_fit` names it.  Only
    `domain` rows take a placement (the rest are the caller's: nodes it
    walks on the host, or outside a node-update eval), but every row's
    verdict is what it would be in the domain, so the host walk reads
    its static feasibility here too."""
    mask = feasible_mask(attrs, elig, dc_mask, pool_mask, con, luts)

    def group(used, xs):
        ok, ask = xs                         # [N] bool, [RES_DIMS]
        over = used + ask[None, :] > cap     # [N, RES_DIMS]
        # the first dimension over names the verdict
        verdict = SYS_PLACED
        for d in reversed(range(RES_DIMS)):
            verdict = jnp.where(over[:, d], 2 + d, verdict)
        verdict = jnp.where(~ok, SYS_FILTERED, verdict).astype(jnp.int8)
        take = domain & (verdict == SYS_PLACED)
        return used + take[:, None] * ask[None, :], verdict

    _, verdicts = jax.lax.scan(group, used, (mask, req))
    return verdicts


place_system_jit = jax.jit(place_system)
