"""Multi-device sharded placement (SURVEY.md §6.7/§7 P7).

The node axis — the framework's "long context" — is sharded across the
device mesh.  Per placement step, each device scores its node shard
locally; the winner is found with a two-stage top-k (local `lax.top_k`,
then a global top-k over the all-gathered shard winners riding ICI);
spread / distinct-property counts are replicated and updated identically on
every shard by psum-broadcasting the picked node's property values from the
owning shard.  This is the DP/CP mapping from SURVEY.md §3.6: eval batch ↔
data parallel, node axis ↔ context parallel; there are no weights, so
TP/PP have no analog.

Works identically on a real multi-chip TPU mesh and on the virtual
8-device CPU mesh used in tests and the driver's multichip dry-run.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu.ops.feasibility import constraint_mask
from nomad_tpu.ops.scoring import affinity_score
from nomad_tpu.structs import RES_DIMS
from nomad_tpu.ops.select import (
    NEG_INF,
    TOP_K,
    MultiEvalInputs,
    PlacementInputs,
    PlacementOutputs,
    pack_outputs,
    pack_round_buffer,
    round_metrics_g,
    round_scores_g,
    round_seeds,
    scan_statics,
    step_scores,
    tiebreak_noise,
)

AXIS = "nodes"

# The jitted programs this module builds, by the name each carries in a
# profiler trace (`jit_<name>`) and in `jax.jit(...).__name__`.  A
# placement kernel's name starts with `place_`, as the single-device
# programs' do (ops/select.py), and says `sharded`, so a trace tells the
# two deployments apart; `ops/engine.py SHARDED_KINDS` maps the engine's
# launch kinds onto the builders.
PROGRAM_NAMES = (
    "place_sharded_packed",
    "place_multi_sharded_packed",
    "place_multi_sharded_chained",
    "place_multi_compact_sharded",
    "place_multi_compact_sharded_chained",
    "scatter_add_sharded",
)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


def pad_nodes(n: int, ndev: int) -> int:
    """Global node count padded to a multiple of the mesh size."""
    return ((n + ndev - 1) // ndev) * ndev


def _place_local(inp: PlacementInputs) -> PlacementOutputs:
    """Per-shard body (runs under shard_map).  The scoring core is
    ops.select.step_scores — literally the same function the single-device
    scan runs, fed global row ids — so the two deployments cannot drift;
    only winner selection (two-stage top-k over ICI) and count-state
    updates (owner-shard psum broadcast) differ."""
    n_loc = inp.attrs.shape[0]
    offset = jax.lax.axis_index(AXIS) * n_loc
    global_rows = offset + jnp.arange(n_loc)
    k_loc = min(TOP_K, n_loc)

    # global-row-keyed statics: tie-break noise is identical for a given
    # GLOBAL row on every shard, so the two-stage top-k stays consistent
    st = scan_statics(inp, global_rows)
    static, noise = st.static, st.noise

    def step(carry, xs):
        used, job_count, sp_counts, pd_counts = carry
        g, prev, act = xs
        req_g = inp.req[g]
        stat_g = static[g]
        feas, final, _, fit, dh_ok = step_scores(inp, st, carry, g, prev)
        kd = pd_counts.shape[1]
        # selection order gets the tie-break noise; reported scores recover
        # the true value by re-hashing the chosen global rows
        masked = jnp.where(feas, final, NEG_INF) + noise

        # ---- two-stage top-k: local, then global over shard winners ----
        loc_sc, loc_rows = jax.lax.top_k(masked, k_loc)
        loc_grows = jnp.where(loc_sc > NEG_INF / 2,
                              global_rows[loc_rows], -1)
        all_sc = jax.lax.all_gather(loc_sc, AXIS).reshape(-1)
        all_rows = jax.lax.all_gather(loc_grows, AXIS).reshape(-1)
        k_glob = min(TOP_K, all_sc.shape[0])
        top_nsc, top_idx = jax.lax.top_k(all_sc, k_glob)
        top_rows = all_rows[top_idx]
        top_sc = jnp.where(
            top_nsc > NEG_INF / 2,
            top_nsc - tiebreak_noise(inp.seed, jnp.maximum(top_rows, 0)),
            NEG_INF)
        pick = top_rows[0]
        ok = act & (top_sc[0] > NEG_INF / 2)
        pick = jnp.where(ok, pick, -1)

        # ---- state update ----
        onehot = (global_rows == pick) & ok
        used = used + onehot[:, None].astype(jnp.int32) * req_g[None, :]
        job_count = job_count + onehot.astype(jnp.int32)

        # owner shard broadcasts the picked node's spread / property values
        owns = ok & (pick >= offset) & (pick < offset + n_loc)
        loc_pick = jnp.clip(pick - offset, 0, n_loc - 1)
        sval = jnp.where(owns, inp.sp_nodeval[:, loc_pick] + 1, 0)
        sval = jax.lax.psum(sval, AXIS) - 1                 # [S], -1 = none
        k_sp = sp_counts.shape[1]
        sp_hot = (jax.nn.one_hot(jnp.clip(sval, 0, k_sp - 1), k_sp)
                  * ((sval >= 0) & ok)[..., None])
        sp_counts = sp_counts + sp_hot
        pval = jnp.where(owns, inp.pd_nodeval[:, loc_pick] + 1, 0)
        pval = jax.lax.psum(pval, AXIS) - 1                 # [D]
        pd_hot = (jax.nn.one_hot(jnp.clip(pval, 0, kd - 1), kd,
                                 dtype=pd_counts.dtype)
                  * ((pval >= 0) & inp.pd_apply[g] & ok)[..., None])
        pd_counts = pd_counts + pd_hot

        # ---- metrics (global; same classification as select.place:
        # distinct_property misses count as neither filtered nor
        # exhausted there, so not here either) ----
        n_filtered = jax.lax.psum(jnp.sum(~stat_g), AXIS)
        exhausted = stat_g & (~fit | ~dh_ok)
        n_exhausted = jax.lax.psum(jnp.sum(exhausted), AXIS)
        n_feas = jax.lax.psum(jnp.sum(feas), AXIS)
        pre_used = used - onehot[:, None].astype(jnp.int32) * req_g[None, :]
        over = (pre_used + req_g[None, :]) > inp.cap
        dim_ex = jax.lax.psum(jnp.sum((stat_g & ~fit)[:, None] & over,
                                      axis=0), AXIS)

        out = (pick,
               jnp.where(ok, top_sc[0], 0.0),
               jnp.where(ok, top_rows, -1),
               jnp.where(ok, top_sc, 0.0),
               n_feas.astype(jnp.int32),
               n_filtered.astype(jnp.int32),
               n_exhausted.astype(jnp.int32),
               dim_ex.astype(jnp.int32))
        return (used, job_count, sp_counts, pd_counts), out

    # replicated carries become device-varying once updated with values
    # derived from collectives; pcast the initial values to match
    carry0 = (inp.used0, inp.job_count0,
              pcast(inp.sp_counts0, (AXIS,), to="varying"),
              pcast(inp.pd_counts0, (AXIS,), to="varying"))
    (used, job_count, _, _), outs = jax.lax.scan(
        step, carry0, (inp.tg_idx, inp.prev_row, inp.active))
    return PlacementOutputs(
        picks=outs[0], scores=outs[1], topk_rows=outs[2], topk_scores=outs[3],
        n_feasible=outs[4], n_filtered=outs[5], n_exhausted=outs[6],
        dim_exhausted=outs[7], used=used, job_count=job_count)


def place_sharded_fn(mesh: Mesh):
    """Build the jitted sharded placement step for `mesh`.  Node-axis
    arrays are sharded over the mesh; everything else is replicated; the
    per-placement outputs are replicated, final usage stays sharded."""
    spec_n = P(AXIS)
    in_specs = PlacementInputs(
        attrs=spec_n, cap=spec_n, used0=spec_n, elig=spec_n,
        dc_mask=spec_n, pool_mask=spec_n, luts=P(),
        con=P(), aff=P(), req=P(), desired=P(), dh_limit=P(),
        sp_nodeval=P(None, AXIS), sp_weight=P(), sp_expected=P(),
        sp_counts0=P(),
        pd_nodeval=P(None, AXIS), pd_limit=P(), pd_apply=P(), pd_counts0=P(),
        tg_idx=P(), prev_row=P(), active=P(), job_count0=spec_n,
        spread_algo=P(), seed=P(),
        # None when absent (empty pytree — the leaf spec prefix-broadcasts
        # to nothing); a real [G, N] mask shards along the node axis
        extra_mask=P(None, AXIS),
    )
    out_specs = PlacementOutputs(
        picks=P(), scores=P(), topk_rows=P(), topk_scores=P(),
        n_feasible=P(), n_filtered=P(), n_exhausted=P(), dim_exhausted=P(),
        used=spec_n, job_count=spec_n,
    )
    # check_vma=False: the per-placement outputs are identical on every
    # shard by construction (derived from all_gather + psum), but the
    # varying-axes checker cannot infer that through the scan.
    f = shard_map(_place_local, mesh=mesh,
                      in_specs=(in_specs,), out_specs=out_specs,
                      check_vma=False)
    return jax.jit(f)


def place_sharded_packed_fn(mesh: Mesh):
    """Sharded placement + ops.select.pack_outputs in one jit: the packed
    [P, 14] buffer is what PlacementEngine fetches (single device→host
    transfer); used/job_count stay sharded on the mesh."""
    spec_n = P(AXIS)
    in_specs = PlacementInputs(
        attrs=spec_n, cap=spec_n, used0=spec_n, elig=spec_n,
        dc_mask=spec_n, pool_mask=spec_n, luts=P(),
        con=P(), aff=P(), req=P(), desired=P(), dh_limit=P(),
        sp_nodeval=P(None, AXIS), sp_weight=P(), sp_expected=P(),
        sp_counts0=P(),
        pd_nodeval=P(None, AXIS), pd_limit=P(), pd_apply=P(), pd_counts0=P(),
        tg_idx=P(), prev_row=P(), active=P(), job_count0=spec_n,
        spread_algo=P(), seed=P(),
        extra_mask=P(None, AXIS),
    )
    out_specs = PlacementOutputs(
        picks=P(), scores=P(), topk_rows=P(), topk_scores=P(),
        n_feasible=P(), n_filtered=P(), n_exhausted=P(), dim_exhausted=P(),
        used=spec_n, job_count=spec_n,
    )
    inner = shard_map(_place_local, mesh=mesh,
                          in_specs=(in_specs,), out_specs=out_specs,
                          check_vma=False)

    def place_sharded_packed(inp):
        return pack_outputs(inner(inp))

    return jax.jit(place_sharded_packed)


# ------------------------------------------------------ water-fill kernels


def _sharded_waterfill(k_i, score, noise, static, want, spread_algo,
                       round_size: int, top_k: int, n_loc: int, offset,
                       global_rows, frame_commit: bool = False):
    """One sharded water-fill round: local candidates -> two-stage top-k
    over ICI -> replicated fill math -> owner-shard commit counts.
    Shared by the sharded multi-eval kernel (task group per round) and —
    with `frame_commit=True` — the sharded COMPACT laned kernel, where the
    local axis is a per-signature candidate FRAME rather than the node
    shard: commits then scatter back to frame slots (ownership decided
    by each winner's packed frame index + the global-row range test).
    Returns the compact fill prefix (global rows/counts/scores), local
    commit counts c_i (node rows, or frame slots), the top-k metric
    slice, and global feasible/filter counts."""
    big = jnp.int32(round_size)
    # spread algorithm: cap per-node intake so a round fans out (viable
    # counted over the WHOLE mesh)
    viable = jnp.maximum(jax.lax.psum(jnp.sum(k_i > 0), AXIS), 1)
    cap_round = jnp.where(
        spread_algo,
        jnp.maximum(want // viable + 1, 1).astype(k_i.dtype), big)
    k_round = jnp.minimum(k_i, cap_round)

    # two-stage candidate selection: each shard contributes its local
    # top min(round_size, n_loc) nodes; the union is a superset of the
    # global top round_size because every global winner is a local
    # winner on its shard
    kk_loc = min(round_size, n_loc)
    masked = jnp.where(k_round > 0, score, NEG_INF)
    loc_nsc, loc_order = jax.lax.top_k(masked + noise, kk_loc)
    loc_pack = jnp.stack([
        loc_nsc,
        jnp.where(loc_nsc > NEG_INF / 2, score[loc_order], NEG_INF),
        k_round[loc_order].astype(jnp.float32),
        global_rows[loc_order].astype(jnp.float32),
        loc_order.astype(jnp.float32),       # frame slot on owner shard
    ])                                                   # [5, kk_loc]
    allp = jax.lax.all_gather(loc_pack, AXIS, axis=1).reshape(5, -1)
    kk_glob = min(round_size, allp.shape[1])
    g_nsc, g_idx = jax.lax.top_k(allp[0], kk_glob)
    sc_k = jnp.where(g_nsc > NEG_INF / 2, allp[1][g_idx], NEG_INF)
    k_sorted = jnp.where(sc_k > NEG_INF / 2,
                         allp[2][g_idx].astype(jnp.int32), 0)
    rows_k = allp[3][g_idx].astype(jnp.int32)

    # water-fill the sorted candidates up to `want` (replicated math)
    csum = jnp.cumsum(k_sorted)
    c_sorted = jnp.clip(want - (csum - k_sorted), 0, k_sorted)
    placed_total = jnp.sum(c_sorted)

    if frame_commit:
        # ownership by each winner's ORIGIN shard: the all_gather laid
        # shards out contiguously, so winner i came from shard
        # g_idx // kk_loc; its frame slot rides in pack row 4
        src_shard = g_idx // kk_loc
        mine = src_shard == jax.lax.axis_index(AXIS)
        slots = jnp.clip(allp[4][g_idx].astype(jnp.int32), 0, n_loc - 1)
        c_i = (jnp.zeros(n_loc, jnp.int32)
               .at[slots].add(
                   jnp.where(mine, c_sorted, 0).astype(jnp.int32),
                   mode="drop"))
    else:
        # commit: each shard applies the fills for rows it owns
        mine = (rows_k >= offset) & (rows_k < offset + n_loc)
        loc_rows = jnp.clip(rows_k - offset, 0, n_loc - 1)
        c_i = (jnp.zeros(n_loc, jnp.int32)
               .at[loc_rows].add(
                   jnp.where(mine, c_sorted, 0).astype(jnp.int32),
                   mode="drop"))

    # compact fill prefix (pad when the whole cluster is smaller than a
    # round)
    pad = round_size - kk_glob
    if pad:
        rows_p = jnp.concatenate([rows_k, jnp.zeros(pad, rows_k.dtype)])
        cnt_p = jnp.concatenate(
            [c_sorted.astype(jnp.int32), jnp.zeros(pad, jnp.int32)])
        sc_p = jnp.concatenate([sc_k, jnp.full(pad, NEG_INF, sc_k.dtype)])
    else:
        rows_p, cnt_p, sc_p = rows_k, c_sorted.astype(jnp.int32), sc_k

    tk = min(top_k, kk_glob)
    top_sc = sc_p[:tk]
    top_rows = jnp.where(top_sc > NEG_INF / 2, rows_p[:tk], -1)
    top_sc = jnp.where(top_sc > NEG_INF / 2, top_sc, 0.0)
    n_feas = jax.lax.psum(jnp.sum(k_round > 0), AXIS).astype(jnp.int32)
    n_filt = jax.lax.psum(jnp.sum(~static), AXIS).astype(jnp.int32)
    return (rows_p, cnt_p, sc_p, top_rows, top_sc, n_feas, n_filt,
            c_i, placed_total.astype(jnp.int32))


def _multi_local(inp: MultiEvalInputs, round_size: int, top_k: int):
    """Per-shard body of the sharded multi-eval batch kernel: the same
    round_scores_g / round_metrics_g core as ops.select.place_multi_packed
    on the local node shard, with _sharded_waterfill's two-stage top-k
    fill decision.  job_count rows [J, n_loc] are sharded along the node
    axis like `used`."""
    n_loc = inp.attrs.shape[0]
    offset = jax.lax.axis_index(AXIS) * n_loc
    global_rows = offset + jnp.arange(n_loc)

    # deduped signature landscapes, same as ops.select.place_multi_packed
    # (per-signature [U, n_loc], NOT per task group — the per-G form's
    # LUT/attr gathers were the dominant launch cost)
    static_u = (constraint_mask(inp.attrs, inp.con, inp.luts)
                & inp.elig[None, :] & inp.base_mask[inp.u_mask])
    aff_u = affinity_score(inp.attrs, inp.aff, inp.luts)
    aff_any_u = jnp.any(inp.aff[..., 3] != 0, axis=1)
    rg = inp.round_g
    u_r = inp.g_static[rg]
    a_r = inp.g_aff[rg]
    jc_r = inp.job_count0[inp.g_job[rg]]
    req_r = inp.req[rg]
    des_r = inp.desired[rg]
    dh_r = inp.dh_limit[rg]
    jobs_r = inp.g_job[rg]
    same_r = jnp.concatenate([jnp.zeros(1, bool),
                              jobs_r[1:] == jobs_r[:-1]])
    seed_r = round_seeds(inp.seed, rg)

    def round_step(carry, xs):
        used, cur_count = carry
        (u, a, jc0_row, req, desired, dh_limit, want, same, sd) = xs
        static = static_u[u]
        # per-item noise over GLOBAL rows: identical for a given row on
        # every shard AND to the single-device launch for the same eval
        # id (wavepipe serial/pipelined parity)
        noise = tiebreak_noise(sd, global_rows)
        job_count = jnp.where(same, cur_count, jc0_row)
        k_i, score = round_scores_g(
            inp.cap, req, desired, dh_limit, static,
            aff_u[a], aff_any_u[a], used, job_count,
            inp.spread_algo, round_size)
        (rows_p, cnt_p, sc_p, top_rows, top_sc, n_feas, n_filt,
         c_i, placed) = _sharded_waterfill(
            k_i, score, noise, static, want, inp.spread_algo, round_size,
            top_k, n_loc, offset, global_rows)
        used = used + c_i[:, None] * req[None, :]
        job_count = job_count + c_i
        n_exh_l, dim_ex_l = round_metrics_g(
            inp.cap, req, dh_limit, static, used, job_count)
        n_exh = jax.lax.psum(n_exh_l, AXIS).astype(jnp.int32)
        dim_ex = jax.lax.psum(dim_ex_l, AXIS).astype(jnp.int32)
        out = (rows_p, cnt_p, sc_p, top_rows, top_sc,
               n_feas, n_filt, n_exh, dim_ex, placed)
        return (used, job_count), out

    carry0 = (inp.used0, inp.job_count0[0])
    (used, jc), outs = jax.lax.scan(
        round_step, carry0,
        (u_r, a_r, jc_r, req_r, des_r, dh_r, inp.round_want, same_r,
         seed_r))
    return outs + (used, jc)


def place_multi_sharded_packed_fn(mesh: Mesh, round_size: int,
                                  chained: bool = False):
    """Sharded multi-eval batch kernel with the same compact packed
    buffer layout as ops.select.place_multi_packed.

    `chained=True` builds the donated-chain variant (the sharded analog
    of ops.select.place_multi_chained_jit): the jit takes (used0, inp)
    with `used0` DONATED — a wave chained on the previous wave's
    sharded proposed-usage output reuses that dead buffer in place.
    The engine's cached node tensors ride `inp` and are never
    donated."""
    spec_n = P(AXIS)
    in_specs = MultiEvalInputs(
        attrs=spec_n, cap=spec_n, used0=spec_n, elig=spec_n, luts=P(),
        base_mask=P(None, AXIS),
        con=P(), u_mask=P(), aff=P(), req=P(), desired=P(),
        dh_limit=P(), g_static=P(), g_aff=P(), g_job=P(),
        job_count0=P(None, AXIS),
        spread_algo=P(), round_g=P(), round_want=P(), seed=P(),
    )
    out_specs = (P(), P(), P(), P(), P(), P(), P(), P(), P(), P(),
                 spec_n, spec_n)
    top_k = TOP_K
    inner = shard_map(
        partial(_multi_local, round_size=round_size, top_k=top_k),
        mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
        check_vma=False)

    def place_multi_sharded_packed(inp: MultiEvalInputs):
        n = inp.attrs.shape[0]
        assert n < (1 << 20), "packed fill rows support < 2^20 nodes"
        assert round_size <= 1024, "packed fill counts support rounds <= 1024"
        (rows_p, cnt_p, sc_p, top_rows, top_sc,
         n_feas, n_filt, n_exh, dim_ex, placed, used, jc) = inner(inp)
        fills, meta = pack_round_buffer(
            rows_p, cnt_p, top_rows, top_sc, n_feas, n_filt, n_exh,
            dim_ex, placed)
        buf = jnp.concatenate([fills, meta], axis=1)
        return buf, used, jc

    if not chained:
        return jax.jit(place_multi_sharded_packed)

    def place_multi_sharded_chained(used0, inp: MultiEvalInputs):
        return place_multi_sharded_packed(inp._replace(used0=used0))

    return jax.jit(place_multi_sharded_chained, donate_argnums=(0,))


def _multi_compact_local(inp: MultiEvalInputs, cand_rows, cand_valid,
                         round_size: int, n_lanes: int, top_k: int):
    """Per-shard body of the sharded COMPACT laned kernel: the same
    lane-parallel per-signature-frame design as
    ops.select.place_multi_compact_packed, with the node axis sharded —
    each shard holds ITS slice of every lane's candidate frame (the
    host splits each signature's global candidate rows by owner shard)
    and rounds resolve with the two-stage _sharded_waterfill in
    frame-commit mode.  job_count0 carries the per-shard compact seed
    table [J', Nc_loc]; cand_rows holds GLOBAL row ids (padding points
    past every shard, so it is never 'mine')."""
    cand_rows = cand_rows[0]            # [L, Nc_loc] (shard's block)
    cand_valid = cand_valid[0]
    jc_seed = inp.job_count0[0]         # [J', Nc_loc]
    n_loc = inp.attrs.shape[0]
    offset = jax.lax.axis_index(AXIS) * n_loc
    nc = cand_rows.shape[1]
    loc_idx = jnp.clip(cand_rows - offset, 0, n_loc - 1)
    cap_c = inp.cap[loc_idx]                             # [L, Nc, 3]
    used0_c = inp.used0[loc_idx]
    aff_cu = jax.vmap(
        lambda li: affinity_score(inp.attrs[li], inp.aff, inp.luts)
    )(loc_idx)                                           # [L, Ua, Nc]
    aff_any_u = jnp.any(inp.aff[..., 3] != 0, axis=1)
    rg = inp.round_g.reshape(-1, n_lanes)
    seed_r = round_seeds(inp.seed, rg)                   # [T, L]
    a_r = inp.g_aff[rg]
    jrow_r = inp.g_job[rg]
    req_r = inp.req[rg]
    des_r = inp.desired[rg]
    dh_r = inp.dh_limit[rg]
    same_r = jnp.concatenate(
        [jnp.zeros((1, n_lanes), bool), rg[1:] == rg[:-1]], axis=0)
    want_r = inp.round_want.reshape(-1, n_lanes)
    n_glob = jax.lax.psum(jnp.int32(n_loc), AXIS)
    cand_n_glob = jax.lax.psum(
        jnp.sum(cand_valid, axis=1).astype(jnp.int32), AXIS)   # [L]
    n_filt = n_glob - cand_n_glob                              # [L]

    scores_l = jax.vmap(
        partial(round_scores_g, round_size=round_size),
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None))
    def _fill_one(k_i, score, noise, static, want, spread_algo, grows):
        return _sharded_waterfill(k_i, score, noise, static, want,
                                  spread_algo, round_size, top_k, nc, 0,
                                  grows, frame_commit=True)

    fill_l = jax.vmap(_fill_one, in_axes=(0, 0, 0, 0, 0, None, 0))
    metrics_l = jax.vmap(round_metrics_g)

    def lane_step(carry, xs):
        used_c, cur_count = carry        # [L, Nc, 3], [L, Nc]
        (a, jrow, req, desired, dh_limit, want, same, sd) = xs
        jc0 = jc_seed[jrow]                              # [L, Nc]
        aff_sc = jnp.take_along_axis(
            aff_cu, a[:, None, None], axis=1)[:, 0]
        # per-item noise, global-row keyed (solo-path parity)
        noise_c = jax.vmap(tiebreak_noise)(sd, cand_rows)
        job_count = jnp.where(same[:, None], cur_count, jc0)
        k_i, score = scores_l(cap_c, req, desired, dh_limit, cand_valid,
                              aff_sc, aff_any_u[a], used_c, job_count,
                              inp.spread_algo)
        (rows_p, cnt_p, sc_p, top_rows, top_sc, n_feas, _nf,
         c_i, placed) = fill_l(k_i, score, noise_c, cand_valid, want,
                               inp.spread_algo, cand_rows)
        used_c = used_c + c_i[:, :, None] * req[:, None, :]
        job_count = job_count + c_i
        n_exh_l, dim_ex_l = metrics_l(cap_c, req, dh_limit, cand_valid,
                                      used_c, job_count)
        n_exh = jax.lax.psum(n_exh_l, AXIS).astype(jnp.int32)
        dim_ex = jax.lax.psum(dim_ex_l, AXIS).astype(jnp.int32)
        out = (rows_p, cnt_p, top_rows, top_sc, n_feas, n_filt,
               n_exh, dim_ex, placed)
        return (used_c, job_count), out

    carry0 = (used0_c, jnp.zeros((n_lanes, nc), jnp.int32))
    (used_c, _), outs = jax.lax.scan(
        lane_step, carry0,
        (a_r, jrow_r, req_r, des_r, dh_r, want_r, same_r, seed_r))
    # scatter the shard's frame slices back to ITS node rows (padding
    # and foreign rows drop out of range)
    scatter_idx = jnp.where(cand_valid, cand_rows - offset, n_loc)
    used = inp.used0.at[scatter_idx.reshape(-1)].set(
        used_c.reshape(-1, RES_DIMS), mode="drop")
    return outs + (used, jnp.zeros(n_loc, jnp.int32))


def place_multi_compact_sharded_fn(mesh: Mesh, round_size: int,
                                   n_lanes: int, chained: bool = False):
    """Sharded compact laned multi-eval kernel: same output protocol as
    ops.select.place_multi_compact_packed — (buf_small [T*L, fk+16],
    fills_full [T*L, round_size], used) — over the node-sharded mesh.
    `chained=True`: donated (used0, inp, cand_rows, cand_valid)
    signature, mirroring place_multi_compact_chained_jit (see
    place_multi_sharded_packed_fn)."""
    from nomad_tpu.ops.select import FILL_K
    spec_n = P(AXIS)
    in_specs = MultiEvalInputs(
        attrs=spec_n, cap=spec_n, used0=spec_n, elig=spec_n, luts=P(),
        base_mask=P(None, AXIS),
        con=P(), u_mask=P(), aff=P(), req=P(), desired=P(),
        dh_limit=P(), g_static=P(), g_aff=P(), g_job=P(),
        job_count0=P(AXIS, None, None),
        spread_algo=P(), round_g=P(), round_want=P(), seed=P(),
    )
    cand_spec = P(AXIS, None, None)
    out_specs = (P(), P(), P(), P(), P(), P(), P(), P(), P(),
                 spec_n, spec_n)
    inner = shard_map(
        partial(_multi_compact_local, round_size=round_size,
                n_lanes=n_lanes, top_k=TOP_K),
        mesh=mesh, in_specs=(in_specs, cand_spec, cand_spec),
        out_specs=out_specs, check_vma=False)
    fill_k = min(FILL_K, round_size)

    def place_multi_compact_sharded(inp: MultiEvalInputs, cand_rows,
                                    cand_valid):
        n = inp.attrs.shape[0]
        assert n < (1 << 20), "packed fill rows support < 2^20 nodes"
        assert round_size <= 1024, "packed fill counts support rounds <= 1024"
        (rows_p, cnt_p, top_rows, top_sc, n_feas, n_filt,
         n_exh, dim_ex, placed, used, _jc) = inner(inp, cand_rows,
                                                   cand_valid)

        def flat(x):                      # [T, L, ...] -> [T*L, ...]
            return x.reshape((-1,) + x.shape[2:])

        fills, meta = pack_round_buffer(
            flat(rows_p), flat(cnt_p), flat(top_rows), flat(top_sc),
            flat(n_feas), flat(n_filt), flat(n_exh), flat(dim_ex),
            flat(placed))
        buf_small = jnp.concatenate([fills[:, :fill_k], meta], axis=1)
        return buf_small, fills, used

    if not chained:
        return jax.jit(place_multi_compact_sharded)

    def place_multi_compact_sharded_chained(used0, inp: MultiEvalInputs,
                                            cand_rows, cand_valid):
        return place_multi_compact_sharded(inp._replace(used0=used0),
                                           cand_rows, cand_valid)

    return jax.jit(place_multi_compact_sharded_chained,
                   donate_argnums=(0,))


def scatter_add_sharded_fn(mesh: Mesh):
    """The usage deltas' replay on a node-sharded `used` (rows, values
    replicated; the result keeps the node sharding): what the engine's
    single-device `_scatter_add_jit` is to one chip."""

    def scatter_add_sharded(used, rows, vals):
        return used.at[rows].add(vals)

    return jax.jit(scatter_add_sharded,
                   out_shardings=NamedSharding(mesh, P(AXIS, None)))
