"""Placement selection kernel.

Replaces the reference's per-placement iterator walk + LimitIterator(2) +
MaxScoreIterator (scheduler/select.go) with full-cluster scoring and an
exact argmax — stock Nomad scores a 2-node random subset per placement
(power-of-two-choices); we score *every* feasible node, so placement quality
strictly dominates stock while still being faster.  In the exact scan
(`place_packed`) the argmax is one: a reduce over the nodes that returns
the maximum, its lowest row and the score there, and the reported
runners-up with it (`_top_max`); the rounds-based kernels below take the
top `round_size` by a sort, or the same reduce where a round wants one.

The subtle part (SURVEY.md §4.3): placements within one plan see each other —
capacity, job anti-affinity counts, spread counts, distinct_hosts all update
as the plan grows.  That sequential dependence is preserved exactly with a
loop over the placement axis, one dependent step a placement, which ends
after the last real one; everything inside one step is vectorized over all
N nodes (and the static feasibility/affinity tensors are computed once for
all G task groups before the loop).

Outputs per placement: chosen node row (-1 = no node), final score, top-k
candidate rows/scores (feeds AllocMetric.score_meta_data), and filter/exhaust
counts (feeds nodes_filtered / nodes_exhausted / dimension_exhausted).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.structs import RES_DIMS

from .feasibility import constraint_mask, feasible_mask
from .scoring import (
    affinity_score,
    binpack_score,
    capacity_fit,
    job_anti_affinity,
    normalize_scores,
    spread_boost,
)

NEG_INF = -1e30
TOP_K = 3


def tiebreak_noise(seed, rows):
    """Per-eval selection-order jitter over (global) node row indices,
    magnitude 1e-6 — far below any real score difference (one alloc's
    binpack delta is ~1e-3), so it only reorders exact ties.  seed 0
    disables it (test determinism).  A counter-based integer hash rather
    than a PRNG stream so a sharded kernel computes identical noise for a
    given GLOBAL row on every shard (and for any gathered row id)."""
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ seed * jnp.uint32(0x85EBCA77))
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x.astype(jnp.float32) * jnp.float32(1e-6 / 2**32)
            * (seed != jnp.uint32(0)))


class PlacementInputs(NamedTuple):
    """Device inputs for one eval's placement batch."""
    # node state
    attrs: jnp.ndarray       # [N, A] int32
    cap: jnp.ndarray         # [N, RES_DIMS] int32
    used0: jnp.ndarray       # [N, RES_DIMS] int32
    elig: jnp.ndarray        # [N] bool
    dc_mask: jnp.ndarray     # [N] bool
    pool_mask: jnp.ndarray   # [N] bool
    luts: jnp.ndarray        # [L, V] bool
    # per-task-group statics
    con: jnp.ndarray         # [G, C, 3] int32
    aff: jnp.ndarray         # [G, Af, 4] int32
    req: jnp.ndarray         # [G, RES_DIMS] int32
    desired: jnp.ndarray     # [G] int32 (tg count, anti-affinity denominator)
    dh_limit: jnp.ndarray    # [G] int32 distinct_hosts limit (0 = none)
    # job-level spread state
    sp_nodeval: jnp.ndarray  # [S, N] int32 local value idx (-1 = not a target)
    sp_weight: jnp.ndarray   # [S] float32 (0 = padding)
    sp_expected: jnp.ndarray  # [S, K] float32
    sp_counts0: jnp.ndarray  # [S, K] float32 (existing alloc counts)
    # distinct_property count state (reference: propertyset.go)
    pd_nodeval: jnp.ndarray  # [D, N] int32 local value idx (-1 = unset)
    pd_limit: jnp.ndarray    # [D] int32 (0 = inert padding row)
    pd_apply: jnp.ndarray    # [G, D] bool
    pd_counts0: jnp.ndarray  # [D, Kd] int32
    # per-placement
    tg_idx: jnp.ndarray      # [P] int32
    prev_row: jnp.ndarray    # [P] int32 (-1 = not a reschedule)
    active: jnp.ndarray      # [P] bool (padding rows False)
    # dynamic per-node
    job_count0: jnp.ndarray  # [N] int32 (existing allocs of this job)
    # config
    spread_algo: jnp.ndarray  # [] bool (SchedulerAlgorithm == "spread")
    # per-eval tie-break seed (0 = deterministic row order).  The reference
    # shuffles node order per eval (scheduler/feasible.go RandomIterator),
    # which is what keeps concurrent eval workers from colliding on the
    # same nodes; full-cluster argmax is deterministic, so equal-score
    # ties must be broken per-eval or every worker picks identical nodes
    # and optimistic plan-apply refutes all but one (livelock under load).
    seed: jnp.ndarray = jnp.uint32(0)   # [] uint32
    # host-computed per-(taskgroup, node) feasibility AND-mask, or None.
    # Carries checks whose inputs never reach the device — today the
    # DeviceChecker analog (scheduler/device.py): discrete GPU/device
    # instance availability.  None (the common case) adds nothing to the
    # traced graph; a [G, N] bool (or broadcastable) array is ANDed into
    # the static feasibility mask.
    extra_mask: jnp.ndarray = None       # [G, N] bool | None
    # static-port state, or None x 2 (`port_state`, below): which nodes
    # hold each static value some group of the eval asks, and which
    # values each group asks.  Single-device scan only: the sharded twin
    # takes the values' holders through `extra_mask`
    pt_taken0: jnp.ndarray = None        # [Kp, N] bool | None
    pt_ask: jnp.ndarray = None           # [G, Kp] bool | None


def port_state(taken, ask, static):
    """Static ports as a feasibility rule.  `taken` [Kp, N] says which
    nodes hold each static port value of the launch (a live allocation
    or the node's own reservation at the start, and every placement made
    since that asked it), `ask` [Kp] which of them this placement's
    group asks.  A node that holds a value asked is no candidate: what
    upstream's BinPackIterator finds when its NetworkIndex refuses the
    node, and names "reserved port collision".  Returns (the mask
    without those nodes, the nodes of it they were: exhausted, not
    filtered)."""
    held = jnp.any(taken & ask[:, None], axis=0)
    return static & ~held, static & held


def port_commit(taken, ask, placed):
    """`taken` after a placement on the nodes of `placed` [N] bool: they
    now hold every value the group asks."""
    return taken | (ask[:, None] & placed[None, :])


class PlacementOutputs(NamedTuple):
    picks: jnp.ndarray        # [P] int32 node row or -1
    scores: jnp.ndarray       # [P] float32 final (normalized) score of pick
    topk_rows: jnp.ndarray    # [P, K] int32
    topk_scores: jnp.ndarray  # [P, K] float32
    n_feasible: jnp.ndarray   # [P] int32 feasible candidates at this step
    n_filtered: jnp.ndarray   # [P] int32 statically filtered nodes
    n_exhausted: jnp.ndarray  # [P] int32 feasible-but-full nodes
    dim_exhausted: jnp.ndarray  # [P, RES_DIMS] per-dimension exhaustion
    used: jnp.ndarray         # [N, RES_DIMS] final proposed usage
    job_count: jnp.ndarray    # [N] final job counts


class StepStatics(NamedTuple):
    """Loop-invariant per-eval tensors, computed once before the scan.
    `rows` are GLOBAL node row ids for the slice being scored — a plain
    arange on one device, offset by the shard index under shard_map — so
    the scoring core below is byte-identical in both deployments."""
    static: jnp.ndarray   # [G, N] feasibility
    aff_sc: jnp.ndarray   # [G, N]
    aff_any: jnp.ndarray  # [G]
    sp_any: jnp.ndarray   # []
    capf: jnp.ndarray     # [N, 3] float32
    noise: jnp.ndarray    # [N]
    rows: jnp.ndarray     # [N] global row ids


def scan_statics(inp: PlacementInputs, rows) -> StepStatics:
    static = feasible_mask(inp.attrs, inp.elig, inp.dc_mask, inp.pool_mask,
                           inp.con, inp.luts)              # [G, N]
    if inp.extra_mask is not None:
        static = static & inp.extra_mask
    return StepStatics(
        static=static,
        aff_sc=affinity_score(inp.attrs, inp.aff, inp.luts),  # [G, N]
        aff_any=jnp.any(inp.aff[..., 3] != 0, axis=1),        # [G]
        sp_any=jnp.any(inp.sp_weight > 0),
        capf=inp.cap.astype(jnp.float32),
        noise=tiebreak_noise(inp.seed, rows),
        rows=rows)


def step_scores(inp: PlacementInputs, st: StepStatics, carry, g, prev):
    """Scoring core of ONE placement step — shared verbatim by the
    single-device scan (`place`) and the sharded per-shard body
    (parallel/mesh._place_local), so the two deployments cannot drift.
    Returns (feas, final, stat_g, fit, dh_ok): the feasibility verdicts
    and the normalized rank-chain score for every (local) node."""
    used, job_count, sp_counts, pd_counts = carry
    n = st.rows.shape[0]
    req_g = inp.req[g]
    stat_g = st.static[g]
    fit = capacity_fit(inp.cap, used, req_g)
    dh_ok = jnp.where(inp.dh_limit[g] > 0,
                      job_count < inp.dh_limit[g], True)
    # distinct_property: node's per-value count must stay under the limit
    kd = pd_counts.shape[1]
    pd_val = jnp.clip(inp.pd_nodeval, 0, kd - 1)             # [D, N]
    pd_cnt = jnp.take_along_axis(pd_counts, pd_val, axis=1)  # [D, N]
    pd_row_ok = (pd_cnt < inp.pd_limit[:, None]) & (inp.pd_nodeval >= 0)
    pd_applies = inp.pd_apply[g] & (inp.pd_limit > 0)        # [D]
    pd_ok = jnp.all(jnp.where(pd_applies[:, None], pd_row_ok, True),
                    axis=0)                                  # [N]
    feas = stat_g & fit & dh_ok & pd_ok

    # ---- rank chain ----
    # normalized to [0,1] like the reference (rank.go: fit/maxFitScore)
    # so binpack is comparable with the ±1-bounded affinity/spread boosts
    bp = binpack_score(st.capf, used.astype(jnp.float32),
                       req_g.astype(jnp.float32),
                       inp.spread_algo) / 18.0
    aa = job_anti_affinity(job_count, inp.desired[g])
    rp = jnp.where(st.rows == prev, -1.0, 0.0)
    af = st.aff_sc[g]
    sp = spread_boost(inp.sp_nodeval, inp.sp_weight,
                      inp.sp_expected, sp_counts)
    comps = jnp.stack([bp, aa, rp, af, sp])            # [5, N]
    act_mask = jnp.stack([
        jnp.ones(n, bool),
        job_count > 0,
        st.rows == prev,
        jnp.broadcast_to(st.aff_any[g], (n,)),
        jnp.broadcast_to(st.sp_any, (n,)),
    ])
    final = normalize_scores(comps, act_mask)
    return feas, final, stat_g, fit, dh_ok


def _top_max(x, rows, k, *payload):
    """The `k` largest of x in ONE reduce over the nodes: a list, best
    first, of (value, its row, each payload's value there); among equal
    values the lower row first, which is the order `lax.top_k` gives and
    where `argmax` starts.  The reduce's state is the ranked list itself,
    two of which merge into the first `k` of both, so the runners-up cost
    no pass of their own and no masking of the rows already taken.  The
    payloads ride the reduce so that no scalar is gathered afterwards: on
    the TPU every such gather is an op of its own, as dear as the reduce.
    `k` is at most the length of x."""
    width = 2 + len(payload)

    def ahead(a, b):
        return (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))

    def either(take_a, a, b):
        return tuple(jnp.where(take_a, u, v) for u, v in zip(a, b))

    def ranked(flat):
        return [tuple(flat[j * width:(j + 1) * width]) for j in range(k)]

    def merge(a, b):
        # k times the better of the two heads; the list it came from
        # moves up one, the other drops a tail that can no longer place
        a, b = ranked(a), ranked(b)
        out = ()
        for _ in range(k):
            take_a = ahead(a[0], b[0])
            out += either(take_a, a[0], b[0])
            a, b = ([either(take_a, u, v) for u, v in zip(a[1:], a)],
                    [either(take_a, u, v) for u, v in zip(b, b[1:])])
        return out

    none = (jnp.array(-jnp.inf, x.dtype),
            jnp.array(jnp.iinfo(rows.dtype).max, rows.dtype),
            *(jnp.zeros((), p.dtype) for p in payload))
    # a node enters as a list of one: itself, then `none` k - 1 times
    lists = (x, rows, *payload)
    for _ in range(k - 1):
        lists += tuple(jnp.full(x.shape, v) for v in none)
    return ranked(jax.lax.reduce(lists, none * k, merge, (0,)))


def pack_row(pick, score, top_rows, top_sc, counts):
    """ONE placement's outputs as a row of `11 + RES_DIMS` int32 words
    (floats bitcast), the layout of the one buffer the host fetches:
    0 pick | 1 score | 2-4 topk_rows | 5-7 topk_scores | then `counts`:
    8 n_feasible | 9 n_filtered | 10 n_exhausted | 11.. dim_exhausted
    (one column a capacity dimension, structs.RES_NAMES).  A fleet of
    fewer than TOP_K nodes leaves the rows it lacks at -1 and their
    scores at zero.  Each scalar is put on its lane by a select, not
    concatenated: one fused op a row where the concatenation of fifteen
    one-word operands cost the step two."""
    f2i = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    k = len(top_rows)
    head = [pick, f2i(score),
            *(top_rows[j] if j < k else -1 for j in range(TOP_K)),
            *(f2i(top_sc[j]) if j < k else 0 for j in range(TOP_K))]
    row = jnp.pad(counts, (len(head), 0))
    lane = jnp.arange(row.shape[0])
    for j, word in enumerate(head):
        row = jnp.where(lane == j, word, row)
    return row


def pack_outputs(out: PlacementOutputs):
    """Pack per-placement outputs into ONE int32 buffer `[P, 11 +
    RES_DIMS]`, a `pack_row` a placement, so the host pays a single
    device→host round trip instead of one per array (the engine used to
    fetch ten arrays per batch, and the fixed cost per fetch dominated
    eval latency).  The single-device scan writes its rows itself
    (`place_packed`); this is for a scan that stacks its outputs, the
    node-sharded one (parallel/mesh.place_sharded_packed_fn).
    Returns (buf, used, job_count); used/job_count are fetched lazily by
    the engine only on the preemption fallback path.
    """
    counts = jnp.concatenate([
        out.n_feasible[:, None], out.n_filtered[:, None],
        out.n_exhausted[:, None], out.dim_exhausted], axis=1)
    buf = jax.vmap(pack_row)(out.picks, out.scores, out.topk_rows,
                             out.topk_scores, counts)
    return buf, out.used, out.job_count


# The single-device scan's per-eval inputs, in the order they lie in its
# ONE input buffer (`pack_scan_inputs`); the node tensors, `used0`, the
# masks, the LUTs, `job_count0` and `extra_mask` stay arguments of their
# own: they are resident or cached on the device.  The static-port pair
# is there where the eval carries it.
SCAN_PACKED_FIELDS = ("con", "aff", "req", "desired", "dh_limit",
                      "sp_nodeval", "sp_weight", "sp_expected", "sp_counts0",
                      "pd_nodeval", "pd_limit", "pd_apply", "pd_counts0",
                      "tg_idx", "prev_row", "active", "spread_algo", "seed",
                      "pt_taken0", "pt_ask")
_BOOL, _INT32 = np.dtype(bool), np.dtype(np.int32)


def scan_layout(inp: PlacementInputs, replay_rows: int) -> tuple:
    """The input buffer's layout: (name, shape, dtype) of each field of
    SCAN_PACKED_FIELDS that `inp` carries, as JAX would take it in, then
    the usage replay's section, `replay_rows` rows and their values
    `[replay_rows, RES_DIMS]`.  A function of the shapes alone: the
    program's static argument, so one compiled program a set of shapes."""
    layout = []
    for name in SCAN_PACKED_FIELDS:
        x = getattr(inp, name)
        if x is None:
            continue
        dtype = jax.dtypes.canonicalize_dtype(np.result_type(x))
        if dtype != _BOOL and dtype.itemsize != 4:
            raise ValueError(f"{name}: {dtype} does not fit an int32 word")
        layout.append((name, np.shape(x), dtype))
    return tuple(layout) + (("replay_rows", (replay_rows,), _INT32),
                            ("replay_vals", (replay_rows, RES_DIMS), _INT32))


def pack_scan_inputs(inp: PlacementInputs, deltas, replay_rows: int):
    """(layout, buffer): the per-eval fields of `inp` (host arrays) as ONE
    int32 buffer at the offsets `scan_layout` gives, floats bitcast and
    bools one word each, and the usage `deltas` ((rows, values) pairs,
    `replay_rows` rows in all at most) in its replay section, the rows
    past them zero: they add nothing to row 0."""
    layout = scan_layout(inp, replay_rows)
    sizes = [math.prod(shape) for _, shape, _ in layout]
    buf = np.zeros(sum(sizes), np.int32)
    off = 0
    for (name, _, dtype), size in zip(layout[:-2], sizes):
        x = np.asarray(getattr(inp, name)).reshape(-1)
        if dtype != _BOOL:
            x = x.astype(dtype, copy=False).view(np.int32)
        buf[off:off + size] = x
        off += size
    rows = buf[off:off + replay_rows]
    vals = buf[off + replay_rows:].reshape(replay_rows, RES_DIMS)
    lo = 0
    for r, v in deltas:
        rows[lo:lo + len(r)] = r
        vals[lo:lo + len(r)] = v
        lo += len(r)
    return layout, buf


def unpack_scan_inputs(inp: PlacementInputs, packed, layout):
    """Inside the program: the fields of `layout` out of `packed` by
    static slices and bitcasts, each the dtype and the bits it went in
    with, and the replay added to `inp.used0`.  Returns (the inputs the
    scan takes, `used0` after the replay)."""
    fields, off = {}, 0
    for name, shape, dtype in layout:
        size = math.prod(shape)
        words = packed[off:off + size]
        off += size
        if dtype == _BOOL:
            words = words != 0
        elif dtype != _INT32:
            words = jax.lax.bitcast_convert_type(words, dtype)
        fields[name] = words.reshape(shape)
    rows, vals = fields.pop("replay_rows"), fields.pop("replay_vals")
    used0 = inp.used0.at[rows].add(vals)
    return inp._replace(used0=used0, **fields), used0


def place_packed(inp: PlacementInputs, packed=None, layout=()):
    """The exact scan on one device, (buf, used, job_count): ONE Pallas
    kernel where `scan_fused.scan_gate` says it fits (the TPU, node
    state within its VMEM budget, value tables within its `where`
    chains), else `place_packed_xla`.  Decided at trace time from the
    backend and the shapes, so one program `jit_place_packed` a shape.

    With `packed` (the engine's launch), `inp` holds only the resident
    arguments and the per-eval fields come out of that ONE buffer, laid
    out by `layout` (`pack_scan_inputs`); the usage replay it carries is
    added to `inp.used0` ahead of the scan, and the sum is a fourth
    output: the engine's resident `used` from then on."""
    from .scan_fused import place_packed_fused, scan_gate  # imports this
    used0 = None
    if packed is not None:
        inp, used0 = unpack_scan_inputs(inp, packed, layout)
    if scan_gate(inp)[0] == "fused":
        out = place_packed_fused(inp)
    else:
        out = place_packed_xla(inp)
    return out if used0 is None else (*out, used0)


def place_packed_xla(inp: PlacementInputs):
    """The exact scan on one device as XLA ops: one dependent step a
    placement, every step scoring all N nodes, its outputs written as
    the step's `pack_row` into ONE `[P, 11 + RES_DIMS]` buffer (one
    column more where the eval carries static-port state: the nodes a
    step lost to it).  Returns (buf, used, job_count).  The fused
    kernel's oracle, and the scan wherever that kernel does not fit.

    A step does what it can use.  The loop's trip count is one past the
    last ACTIVE step (the engine pads a batch to a power of two, so the
    padding is the tail and runs nothing); a step inside the trip count
    whose `active` is false scores and reports its counts but places
    nothing and changes no state.  Rows past the trip count read pick -1,
    top rows -1 and zero elsewhere.  The pick and the TOP_K reported rows
    are the arg-maxes of the masked, jittered scores from one reduce
    (`_top_max`): the order `lax.top_k` gives, equal values by lower row,
    with no sort; the reported score and the picked node's spread and
    distinct_property values ride that reduce, so nothing is gathered
    after it.  The step's counts are one stacked sum."""
    n = inp.attrs.shape[0]
    p_pad = inp.tg_idx.shape[0]
    top_k = min(TOP_K, n)
    st = scan_statics(inp, jnp.arange(n))
    static, noise, rows = st.static, st.noise, st.rows
    n_sp = inp.sp_nodeval.shape[0]
    has_ports = inp.pt_taken0 is not None

    def step(carry, g, prev, act):
        used, job_count, sp_counts, pd_counts = carry[:4]
        req_g = inp.req[g]
        stat_g = static[g]
        feas, final, _, fit, dh_ok = step_scores(inp, st, carry[:4], g, prev)
        port_rows = ()
        port_hit = False
        if has_ports:
            # a node that holds a static value the group asks: exhausted,
            # and counted in a column of its own after the dimensions
            feas, port_hit = port_state(carry[4], inp.pt_ask[g], feas)
            port_rows = (port_hit,)

        # ---- metrics: n_feasible | n_filtered | n_exhausted |
        # dim_exhausted, all of the state the step met ----
        over = (used + req_g[None, :]) > inp.cap
        counts = jnp.sum(jnp.stack([
            feas, ~stat_g, (stat_g & (~fit | ~dh_ok)) | port_hit,
            *((stat_g & ~fit)[:, None] & over).T,
            *port_rows]).astype(jnp.int32), axis=1)

        # selection order gets the tie-break noise; reported scores do not
        tops = _top_max(jnp.where(feas, final, NEG_INF) + noise, rows, top_k,
                        final, *inp.sp_nodeval, *inp.pd_nodeval)
        top_rows = [t[1] for t in tops]
        top_sc = [jnp.where(t[0] > NEG_INF / 2, t[2], NEG_INF) for t in tops]
        ok = act & (top_sc[0] > NEG_INF / 2)
        pick = jnp.where(ok, top_rows[0], -1)

        # ---- state update (no-op when not placed) ----
        onehot = (rows == pick) & ok
        used = used + onehot[:, None].astype(jnp.int32) * req_g[None, :]
        job_count = job_count + onehot.astype(jnp.int32)
        # spread counts: bump (s, value[s, pick]) for real values
        val_p = jnp.where(pick >= 0,
                          jnp.array(tops[0][3:3 + n_sp], jnp.int32),
                          -1)                               # [S]
        k = sp_counts.shape[1]
        sp_hot = (jax.nn.one_hot(jnp.clip(val_p, 0, k - 1), k)
                  * ((val_p >= 0) & ok)[..., None])
        sp_counts = sp_counts + sp_hot
        # distinct_property counts bump only for rows applying to this TG
        kd = pd_counts.shape[1]
        pd_val_p = jnp.where(pick >= 0,
                             jnp.array(tops[0][3 + n_sp:], jnp.int32),
                             -1)                            # [D]
        pd_hot = (jax.nn.one_hot(jnp.clip(pd_val_p, 0, kd - 1), kd,
                                 dtype=pd_counts.dtype)
                  * ((pd_val_p >= 0) & inp.pd_apply[g] & ok)[..., None])
        pd_counts = pd_counts + pd_hot

        row = pack_row(pick, jnp.where(ok, top_sc[0], 0.0),
                       [jnp.where(ok, r, -1) for r in top_rows],
                       [jnp.where(ok, sc, 0.0) for sc in top_sc], counts)
        carry_out = (used, job_count, sp_counts, pd_counts)
        if has_ports:
            carry_out += (port_commit(carry[4], inp.pt_ask[g], onehot),)
        return carry_out, row

    # a trip-count loop over a buffer made before it, not a `lax.cond` a
    # step: the branch costs every step, and an [N, RES_DIMS] value
    # computed inside one takes the padded row-major layout on the TPU
    idle = pack_row(jnp.int32(-1), jnp.float32(0.0), [jnp.int32(-1)] * top_k,
                    [jnp.float32(0.0)] * top_k,
                    jnp.zeros(3 + inp.cap.shape[1] + has_ports, jnp.int32))
    buf0 = jnp.broadcast_to(idle, (p_pad,) + idle.shape)
    n_run = jnp.max(jnp.where(inp.active, jnp.arange(p_pad) + 1, 0),
                    initial=0)
    # a step's three inputs as one row: one slice a step, not three
    steps = jnp.stack([inp.tg_idx, inp.prev_row,
                       inp.active.astype(jnp.int32)], axis=1)

    def body(i, state):
        carry, buf = state
        g, prev, act = jax.lax.dynamic_index_in_dim(steps, i, 0, False)
        carry, row = step(carry, g, prev, act != 0)
        return carry, jax.lax.dynamic_update_index_in_dim(buf, row, i, 0)

    carry0 = (inp.used0, inp.job_count0, inp.sp_counts0, inp.pd_counts0)
    if has_ports:
        carry0 += (inp.pt_taken0,)
    (used, job_count, *_), buf = jax.lax.fori_loop(
        0, n_run, body, (carry0, buf0))
    return buf, used, job_count


place_packed_jit = jax.jit(place_packed, static_argnames="layout")


def place(inp: PlacementInputs) -> PlacementOutputs:
    """`place_packed` with the buffer's columns as arrays (tests, and
    every caller that reads the outputs by name)."""
    buf, used, job_count = place_packed(inp)
    top_k = min(TOP_K, inp.attrs.shape[0])
    i2f = lambda x: jax.lax.bitcast_convert_type(x, jnp.float32)
    return PlacementOutputs(
        picks=buf[:, 0], scores=i2f(buf[:, 1]),
        topk_rows=buf[:, 2:2 + top_k],
        topk_scores=i2f(buf[:, 2 + TOP_K:2 + TOP_K + top_k]),
        n_feasible=buf[:, 8], n_filtered=buf[:, 9], n_exhausted=buf[:, 10],
        dim_exhausted=buf[:, 11:11 + inp.cap.shape[1]], used=used,
        job_count=job_count)


place_jit = jax.jit(place)


def round_scores_g(cap, req, desired, dh_limit, static, aff_sc, aff_any,
                   used, job_count, spread_algo, round_size: int,
                   spread=None):
    """Per-node intake capacity (k_i) and rank-chain score for one
    water-fill round at the current proposed state, parameterized on the
    round's task group values — THE shared scoring core of every
    water-fill kernel: the flat multi-eval kernel, its sharded twin
    (parallel/mesh._multi_local) and the laned compact kernels, so none
    of them can drift.

    `spread`: None, or the round's (spread_boost [N], whether its item
    has a stanza at all) — one more component of the mean, as the scan's
    step_scores has it; only the flat multi-eval kernel passes it, and
    only on a wave that holds a spread item."""
    n = cap.shape[0]
    capf = cap.astype(jnp.float32)
    big = jnp.int32(round_size)

    free = cap - used
    per_dim = jnp.where(req[None, :] > 0,
                        free // jnp.maximum(req[None, :], 1), big)
    k_i = jnp.clip(jnp.min(per_dim, axis=1), 0, big)
    # a node over capacity in ANY dimension (e.g. shrunk re-registration)
    # is infeasible even if that dimension isn't requested — matches
    # capacity_fit's all-dims check in the exact scan kernel
    k_i = jnp.where(jnp.any(free < 0, axis=1), 0, k_i)
    k_i = jnp.where(dh_limit > 0,
                    jnp.minimum(k_i, jnp.clip(dh_limit - job_count, 0, big)),
                    k_i)
    k_i = jnp.where(static, k_i, 0)

    # rank chain at the current proposed state
    bp = binpack_score(capf, used.astype(jnp.float32),
                       req.astype(jnp.float32), spread_algo) / 18.0
    aa = job_anti_affinity(job_count, desired)
    comps = [bp, aa, aff_sc]
    act_mask = [
        jnp.ones(n, bool),
        job_count > 0,
        jnp.broadcast_to(aff_any, (n,)),
    ]
    if spread is not None:
        sp, sp_any = spread
        comps.append(sp)
        act_mask.append(jnp.broadcast_to(sp_any, (n,)))
    score = normalize_scores(jnp.stack(comps), jnp.stack(act_mask))
    return k_i, score


def round_metrics_g(cap, req, dh_limit, static, used, job_count,
                    dev_fit=None):
    """Post-commit exhaustion metrics for one water-fill round,
    parameterized on the round's task group values (shared core, see
    round_scores_g; the sharded caller psums the returned local sums).
    `dev_fit` [N] bool: where a launch carries device group slots, the
    nodes with an accepted group that still holds the round's ask, which
    then decides the device dimension in place of its column's sum."""
    free2 = cap - used
    if dev_fit is None:
        fit2 = jnp.all(free2 >= req[None, :], axis=1)
    else:
        fit2 = jnp.all(free2[:, :-1] >= req[None, :-1], axis=1) & dev_fit
    fit2 = fit2 & jnp.all(free2 >= 0, axis=1)
    dh_ok2 = jnp.where(dh_limit > 0, job_count < dh_limit, True)
    exhausted2 = static & ~(fit2 & dh_ok2)
    n_exh = jnp.sum(exhausted2)
    if dev_fit is None:
        dim_ex = jnp.sum(exhausted2[:, None] & (free2 < req[None, :]),
                         axis=0)
    else:
        dim_ex = jnp.concatenate([
            jnp.sum(exhausted2[:, None] & (free2[:, :-1] < req[None, :-1]),
                    axis=0),
            jnp.sum(exhausted2 & ~dev_fit)[None]])
    return n_exh, dim_ex


def slot_room(dev_cap, dev_used, accept, need, round_size: int):
    """Per device group slot and node ([S, N], slot-major: the nodes on
    the TPU's lanes), how many more placements of a `need`-instance ask
    the slot's group takes: its free instances over `need` where the ask
    accepts the group, else 0 (an ask of no instance: 0 everywhere)."""
    free = dev_cap - dev_used
    room = jnp.where(accept, free // jnp.maximum(need, 1), 0)
    return jnp.where(need > 0, jnp.clip(room, 0, round_size), 0)


def slot_charge(room, c_i, need):
    """The slots `c_i` [N] placements of a `need`-instance ask take, one
    placement after another, each from the FIRST accepted slot with room
    in node order (scheduler/device.py assign_devices' group when the
    ask has no affinity): slot s takes what the slots before it left, up
    to its room.  Returns ([S, N] placements a slot, [S, N] instances a
    slot).  The running sum goes slot by slot over [N] rows: a cumsum
    over the slot axis makes the TPU lay the slot state out slot-minor,
    each row of S words padded to 128 lanes."""
    before = jnp.zeros_like(c_i)
    takes = []
    for s in range(room.shape[0]):
        takes.append(jnp.clip(c_i - before, 0, room[s]))
        before = before + room[s]
    take = jnp.stack(takes)
    return take, take * need


def waterfill_round(k_i, score, noise, want, spread_algo, round_size: int):
    """Water-fill one round: pick the top-scored nodes and fill each up
    to its intake k_i until `want` placements are assigned.  Returns the
    compact fill prefix (rows/counts/scores, padded to round_size), the
    per-node committed counts c_i, and the total placed — shared by the
    flat and compact multi-eval kernels (the sharded kernels' two-stage
    variant lives in parallel/mesh)."""
    n = k_i.shape[0]
    big = jnp.int32(round_size)
    # spread algorithm: cap per-node intake so a round fans out
    viable = jnp.maximum(jnp.sum(k_i > 0), 1)
    cap_round = jnp.where(
        spread_algo,
        jnp.maximum(want // viable + 1, 1).astype(k_i.dtype), big)
    k_round = jnp.minimum(k_i, cap_round)

    # water-fill the top-K nodes up to `want`.  K = round_size suffices:
    # every selected node absorbs >= 1 alloc, so at most `want` <= K nodes
    # fill.  top_k over [N] then O(K) arithmetic beats a full [N] argsort
    # per round by ~50x at 50k nodes.
    # selection order gets the tie-break noise; reported scores do not
    masked = jnp.where(k_round > 0, score, NEG_INF)
    kk = min(round_size, n)
    nsc_k, order_k = jax.lax.top_k(masked + noise, kk)
    sc_k = jnp.where(nsc_k > NEG_INF / 2, score[order_k], NEG_INF)
    k_sorted = jnp.where(sc_k > NEG_INF / 2, k_round[order_k], 0)
    csum = jnp.cumsum(k_sorted)
    c_sorted = jnp.clip(want - (csum - k_sorted), 0, k_sorted)
    placed_total = jnp.sum(c_sorted)

    c_i = (jnp.zeros(n, jnp.int32)
           .at[order_k].add(c_sorted.astype(jnp.int32), mode="drop"))

    # compact fill prefix (pad up to round_size when the cluster is small)
    pad = round_size - kk
    if pad:
        rows_p = jnp.concatenate([order_k, jnp.zeros(pad, order_k.dtype)])
        cnt_p = jnp.concatenate(
            [c_sorted.astype(jnp.int32), jnp.zeros(pad, jnp.int32)])
        sc_p = jnp.concatenate([sc_k, jnp.full(pad, NEG_INF, sc_k.dtype)])
    else:
        rows_p = order_k
        cnt_p = c_sorted.astype(jnp.int32)
        sc_p = sc_k
    return rows_p, cnt_p, sc_p, c_i, placed_total, k_round


def pick_one_round(k_i, score, noise, want, spread_algo, round_size: int):
    """waterfill_round for a round whose `want` is at most 1, without the
    top-k sort: one node takes one allocation, so the selection is the
    arg-max of the same masked, jittered scores, and the TOP_K reported
    rows are the next arg-maxes of the same vector, each by the reduce
    the exact scan's step picks with (`_top_max`, above `place_packed`)
    at a `k` of one.  `lax.top_k` orders equal values by lower index and
    the reduce returns the first maximum, so the order is the sort's.
    Same signature and return tuple; every value that reaches the packed
    buffer (the fills, the first TOP_K rows and scores, c_i,
    placed_total, k_round) is waterfill_round's bit for bit.  Slots past
    TOP_K of the prefix, which hold zero counts there too, are row 0 with
    NEG_INF."""
    n = k_i.shape[0]
    big = jnp.int32(round_size)
    # the spread algorithm's cap, want // viable + 1: with `want` at most
    # 1 and one viable node at least, 2 where both are 1 and else 1
    viable = jnp.maximum(jnp.sum(k_i > 0), 1)
    cap_round = jnp.where(
        spread_algo, jnp.where(want >= viable, 2, 1).astype(k_i.dtype), big)
    k_round = jnp.minimum(k_i, cap_round)

    rows_all = jnp.arange(n, dtype=jnp.int32)
    left = jnp.where(k_round > 0, score, NEG_INF) + noise
    tops = []
    for _ in range(min(TOP_K, n, round_size)):
        (best, row, sc, k_row), = _top_max(left, rows_all, 1, score, k_round)
        tops.append((row, jnp.where(best > NEG_INF / 2, sc, NEG_INF), k_row))
        left = jnp.where(rows_all == row, -jnp.inf, left)
    row, sc, k_row = tops[0]
    placed_total = jnp.clip(
        want, 0, jnp.where(sc > NEG_INF / 2, k_row, 0)).astype(jnp.int32)

    pad = round_size - len(tops)
    rows_p = jnp.concatenate([jnp.stack([t[0] for t in tops]),
                              jnp.zeros(pad, jnp.int32)])
    cnt_p = jnp.concatenate([placed_total[None],
                             jnp.zeros(round_size - 1, jnp.int32)])
    sc_p = jnp.concatenate([jnp.stack([t[1] for t in tops]),
                            jnp.full(pad, NEG_INF, score.dtype)])
    c_i = jnp.where(rows_all == row, placed_total, 0)
    return rows_p, cnt_p, sc_p, c_i, placed_total, k_round


def pack_round_buffer(rows_p, cnt_p, top_rows, top_sc, n_feas, n_filt,
                      n_exh, dim_ex, placed):
    """Shared per-round output assembly for every rounds-based kernel
    (multi-eval flat/compact and their sharded twins): the packed fill
    slots and the 16-word meta block.  Row layout per round r:
      [0 : round_size)     fill prefix, row*2048 + count packed
                           (count <= round_size <= 1024 < 2048; asserts
                           n < 2^20 nodes)
      [round_size : +16)   topk_rows(3) | bitcast topk_scores(3) |
                           n_feasible | n_filtered | n_exhausted |
                           dim_exhausted(cpu, memory, disk) |
                           placed_total | dim_exhausted(devices) | pad(2)
    `dim_ex` has one column a capacity dimension: the first three sit
    before `placed`, where they always have, the rest after it.  The
    host expands fills to per-placement picks with np.repeat: placements
    within a round are interchangeable (same task group, no
    per-placement state), so fill order IS the placement order.
    Returns (fills, meta)."""
    f2i = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    fills = jnp.where(cnt_p > 0, rows_p * 2048 + cnt_p, 0)
    r = top_rows.shape[0]
    tk = top_rows.shape[1]
    meta = jnp.concatenate([
        jnp.concatenate([top_rows,
                         jnp.full((r, 3 - tk), -1, jnp.int32)], axis=1),
        jnp.concatenate([f2i(top_sc),
                         jnp.zeros((r, 3 - tk), jnp.int32)], axis=1),
        n_feas[:, None], n_filt[:, None], n_exh[:, None],
        dim_ex[:, :3], placed[:, None], dim_ex[:, 3:],
        jnp.zeros((r, 6 - dim_ex.shape[1]), jnp.int32),
    ], axis=1)
    return fills, meta


class MultiEvalInputs(NamedTuple):
    """Device inputs for ONE batched multi-eval launch — the
    data-parallel-over-evals axis (SURVEY.md §3.6 row 1): G task groups
    drawn from up to J distinct jobs place in R water-fill rounds
    against a single shared capacity state.  Rounds run sequentially in
    a scan, so evals in one batch see each other's proposed usage — the
    resulting plans are mutually consistent and cannot refute each other
    at the serialized applier (the optimistic-concurrency conflicts the
    reference resolves at plan_apply simply never happen inside a batch).

    Constraint and affinity work is deduped by SIGNATURE, not per task
    group: the [U, N] static feasibility and [Ua, N] affinity landscapes
    are evaluated once per DISTINCT (constraint rows, dc∧pool mask) /
    affinity-row signature, and rounds index into them.  A uniform batch
    (the bench's 384 zone-pinned evals → 5 signatures) pays the O(N·C)
    constraint gather work 5 times, not 512 — measured 1.15s → ~20ms per
    launch at 50k nodes.  `job_count0[g_job[g]]` remains per-job (it is
    dynamic state, not a signature).

    Spread stanzas ride the same way: `sp_nodeval` holds one [S, N]
    value-index landscape per DISTINCT (attribute, target values)
    signature (row 0 inert, for the items with no stanza), and per item
    `g_spread` names its row, `sp_weight` / `sp_expected` / `sp_counts0`
    carry the job's own few floats (S and K padded to the wave's
    largest).  An item with a stanza is scheduled one round a placement
    (`round_want` 1), and the round scan carries its per-value counts as
    it carries the job's count row, so each placement sees the counts
    the one before it left: the exact scan's sequence.  All five are None
    on a wave that holds no stanza, and the program is the one it was."""
    # node state (shared across the batch)
    attrs: jnp.ndarray       # [N, A] int32
    cap: jnp.ndarray         # [N, RES_DIMS] int32
    used0: jnp.ndarray       # [N, RES_DIMS] int32
    elig: jnp.ndarray        # [N] bool
    luts: jnp.ndarray        # [L, V] bool
    base_mask: jnp.ndarray   # [M, N] bool   deduped dc∧pool masks
    # deduped static-feasibility signatures
    con: jnp.ndarray         # [U, C, 3] int32   unique constraint rows
    u_mask: jnp.ndarray      # [U] int32  -> base_mask row per signature
    aff: jnp.ndarray         # [Ua, Af, 4] int32 unique affinity rows
    # per-task-group values (G spans all evals of the batch)
    req: jnp.ndarray         # [G, RES_DIMS] int32
    desired: jnp.ndarray     # [G] int32
    dh_limit: jnp.ndarray    # [G] int32
    g_static: jnp.ndarray    # [G] int32  -> static signature row (U)
    g_aff: jnp.ndarray       # [G] int32  -> affinity signature row (Ua)
    g_job: jnp.ndarray       # [G] int32  -> job_count0 row
    job_count0: jnp.ndarray  # [J, N] int32
    spread_algo: jnp.ndarray  # [] bool
    # round schedule (host-computed: eval e with count c contributes
    # ceil(c / round_size) consecutive rounds; padding rounds want=0.
    # The flat kernel's loop ends before its padding, which costs a zero
    # row of the output and nothing else; the laned kernel still runs
    # its inert slots in full)
    round_g: jnp.ndarray     # [R] int32
    round_want: jnp.ndarray  # [R] int32
    # PER-ITEM tie-break seeds, [G] uint32 (a scalar broadcasts): each
    # eval's rounds draw the SAME noise its solo-path launch would — the
    # wave pipeline's serial/pipelined parity depends on it (a single
    # wave-wide seed made batched picks diverge from the solo path on
    # every exact score tie)
    seed: jnp.ndarray = jnp.uint32(0)
    # spread state, or None x 5 (see the docstring)
    sp_nodeval: jnp.ndarray = None   # [Us, S, N] int32 (-1 = not a target)
    g_spread: jnp.ndarray = None     # [G] int32 -> sp_nodeval row
    sp_weight: jnp.ndarray = None    # [G, S] float32 (0 = padding / none)
    sp_expected: jnp.ndarray = None  # [G, S, K] float32
    sp_counts0: jnp.ndarray = None   # [G, S, K] float32 (existing allocs)
    # static-port state, or None x 2 (`port_state`): the nodes that hold
    # each static value some item of the wave asks (from the state, or
    # from the wave this one is chained on), and the values each item
    # asks.  The round scan carries the holders as it carries `used`, so
    # a wave-mate asking the same value, and the item's own next round,
    # pass by the nodes taken earlier in the launch; an item that asks
    # any takes one allocation a node.  None on a wave that holds no
    # static ask, and the program is the one it was
    pt_taken0: jnp.ndarray = None    # [Kp, N] bool
    pt_ask: jnp.ndarray = None       # [G, Kp] bool
    # device group slot state, or None x 4: per node its first S device
    # groups in node order (ops/engine.py DEVICE_GROUP_SLOTS), their
    # instances and the instances held (from the state, or from the wave
    # this one is chained on), one table a request signature of the
    # slots it accepts, and each item's table (an item that asks no
    # device charges nothing).  A node's intake for a device item is its
    # accepted slots' room summed (`slot_room`), and a round's
    # placements on it charge the first accepted slot with room
    # (`slot_charge`); the round scan carries the usage as it carries
    # `used`.  None on a wave that holds no device ask, and the program
    # is the one it was
    dev_cap: jnp.ndarray = None      # [S, N] int32 (slot-major)
    dev_used0: jnp.ndarray = None    # [S, N] int32
    dev_accept: jnp.ndarray = None   # [Ud, S, N] bool
    g_dev: jnp.ndarray = None        # [G] int32 -> dev_accept row


def round_seeds(seed, rg):
    """Per-round seed values from the per-item [G] seed vector gathered
    by the round schedule (a scalar seed broadcasts to every round)."""
    seed = jnp.asarray(seed, jnp.uint32)
    if seed.ndim == 0:
        return jnp.broadcast_to(seed, rg.shape)
    return seed[rg]


def place_multi_packed(inp: MultiEvalInputs, round_size: int):
    """Batched multi-eval placement in water-fill rounds (SURVEY.md §7
    P3's "greedy conflict-resolution rounds" alternative to the
    per-placement scan): each round scores every node once at the
    current proposed state (round_scores_g), then fills the best nodes
    up to their remaining multi-alloc capacity until the round's `want`
    is met (waterfill_round); capacity, distinct_hosts and job
    anti-affinity are re-evaluated between rounds (round_metrics_g).
    Only the task group (and its job's count row) varies per round.  A
    solo eval of many fresh placements is a wave of one item.  Output
    is the compact per-round packed buffer (pack_round_buffer),
    `[R, round_size + 16]`, one device→host transfer for the WHOLE
    batch; the host slices rows per eval.

    A round does what its own `want` can use.  `want` 0 (the schedule's
    padding to a power of two, always its tail): the round loop ends
    before it, and its row of the buffer is zeros.  `want` 1 (every round
    of an item with a spread stanza, a plain item of one): the scores,
    then pick_one_round's arg-max; its one commit is a one-hot over the
    nodes and one spread value, not a scatter of the prefix's 64 slots.
    Any other `want`: waterfill_round's top-k and its scattered commits.
    Real rounds' rows and `used` are the same bits whichever branch ran.
    Returns (buf, used, last REAL round's job count row [N]), and on a
    wave with static-port state the holders it left, [Kp, N], for the
    wave chained on this one (the meta block's column 14 then counts the
    nodes a round lost to that state), and on a wave with device slot
    state the slot usage it left, [S, N].  A round of such a wave adds
    its charges by slot after its meta block: `round_size` x S words,
    for each fill slot how many of its placements each device group slot
    took."""
    n = inp.attrs.shape[0]
    assert n < (1 << 20), "packed fill rows support < 2^20 nodes"
    assert round_size <= 1024, "packed fill counts support rounds <= 1024"
    top_k = min(TOP_K, n)

    # Deduped batch statics: the constraint/affinity landscapes are
    # evaluated ONCE PER SIGNATURE ([U, N] / [Ua, N], typically a
    # handful), and each round gathers its small signature row in-body —
    # the per-task-group [G, N] evaluation was the dominant launch cost
    # (the LUT/attr gathers are element-wise; measured 1.15s at
    # G=512 x 50k nodes vs ~20ms for U=5).
    static_u = (constraint_mask(inp.attrs, inp.con, inp.luts)
                & inp.elig[None, :]
                & inp.base_mask[inp.u_mask])                    # [U, N]
    aff_u = affinity_score(inp.attrs, inp.aff, inp.luts)        # [Ua, N]
    aff_any_u = jnp.any(inp.aff[..., 3] != 0, axis=1)           # [Ua]
    rg = inp.round_g
    u_r = inp.g_static[rg]
    a_r = inp.g_aff[rg]
    # job count rows ride as scan xs (one [R, N] gather up front — an
    # in-body gather from [J, N] at large J read far more than one row)
    jc_r = inp.job_count0[inp.g_job[rg]]                        # [R, N]
    req_r = inp.req[rg]
    des_r = inp.desired[rg]
    dh_r = inp.dh_limit[rg]
    jobs_r = inp.g_job[rg]
    # a round continues the previous round's job iff they share it: the
    # carry then keeps the accumulated count row (fresh jobs reset from
    # their job_count0 row)
    same_r = jnp.concatenate([jnp.zeros(1, bool),
                              jobs_r[1:] == jobs_r[:-1]])
    seed_r = round_seeds(inp.seed, rg)
    rows_all = jnp.arange(n)
    xs_r = (u_r, a_r, jc_r, req_r, des_r, dh_r, inp.round_want, same_r,
            seed_r)
    carry0 = (inp.used0, inp.job_count0[0])
    has_spread = inp.sp_nodeval is not None
    if has_spread:
        # the job's own spread rows ride as scan xs, its per-value
        # counts in the carry beside the count row
        xs_r += (inp.g_spread[rg], inp.sp_weight[rg], inp.sp_expected[rg],
                 inp.sp_counts0[rg])
        carry0 += (inp.sp_counts0[0],)
    has_ports = inp.pt_taken0 is not None
    # where the port and slot state ride in the scan xs and the carry
    xi_port, ci_port = len(xs_r), len(carry0)
    if has_ports:
        # the item's asked values ride as scan xs, the holders in the
        # carry
        xs_r += (inp.pt_ask[rg],)
        carry0 += (inp.pt_taken0,)
    has_slots = inp.dev_accept is not None
    xi_dev, ci_dev = len(xs_r), len(carry0)
    if has_slots:
        # the item's accept table row rides as scan xs, the slot usage
        # in the carry
        xs_r += (inp.g_dev[rg],)
        carry0 += (inp.dev_used0,)

    def round_step(carry, xs):
        used, cur_count = carry[:2]
        (u, a, jc0_row, req, desired, dh_limit, want, same, sd) = xs[:9]
        static = static_all = static_u[u]   # [N]; U is tiny — cheap gather
        if has_ports:
            taken_in = carry[ci_port]
            static, _ = port_state(taken_in, xs[xi_port], static_all)
        aff_sc = aff_u[a]
        aff_any = aff_any_u[a]
        job_count = jnp.where(same, cur_count, jc0_row)
        spread = None
        if has_spread:
            us, sp_w, sp_exp, sp_c0 = xs[9:13]
            sp_nv = inp.sp_nodeval[us]                  # [S, N]
            sp_counts = jnp.where(same, carry[2], sp_c0)
            spread = (spread_boost(sp_nv, sp_w, sp_exp, sp_counts),
                      jnp.any(sp_w > 0))
        k_i, score = round_scores_g(
            inp.cap, req, desired, dh_limit, static,
            aff_sc, aff_any, used, job_count,
            inp.spread_algo, round_size, spread=spread)
        if has_ports:
            # a static ask is one allocation a node: the fill's top
            # `want` nodes are then the picks one placement after another
            # would make, each passing by the nodes before it
            k_i = jnp.where(jnp.any(xs[xi_port]), jnp.minimum(k_i, 1), k_i)
        if has_slots:
            # a device ask takes all of its instances from ONE group: a
            # node's intake is its accepted groups' room summed, which
            # the device column's sum can only exceed
            dev_used = carry[ci_dev]
            accept = inp.dev_accept[xs[xi_dev]]          # [S, N]
            need = req[-1]
            room = slot_room(inp.dev_cap, dev_used, accept, need,
                             round_size)
            k_i = jnp.where(need > 0,
                            jnp.minimum(k_i, jnp.sum(room, axis=0)), k_i)

        def select(select_round):
            # per-item noise (elementwise hash — no [R, N] pre-gather):
            # the round draws its EVAL's tie-break stream, as the exact
            # scan does for the same eval id
            return select_round(k_i, score, tiebreak_noise(sd, rows_all),
                                want, inp.spread_algo, round_size)

        def by_value(rows, cnt):
            # `cnt` commits on node `rows`, one-hot by its spread value
            k = sp_counts.shape[1]
            val = sp_nv[:, rows]
            return (jax.nn.one_hot(jnp.clip(val, 0, k - 1), k)
                    * (cnt * (val >= 0))[..., None])

        def fill():
            # any `want`: the top-k water-fill, its commits summed by
            # value off the fill prefix (every committed node is in it)
            sel = select(waterfill_round)
            if has_spread:
                sel += (jnp.sum(by_value(sel[0], sel[1]), axis=1),)
            return sel

        def pick_one():
            # `want` 1: the arg-max, its one commit the prefix's first slot
            sel = select(pick_one_round)
            if has_spread:
                sel += (by_value(sel[0][0], sel[1][0]),)
            return sel

        sel = jax.lax.cond(want == 1, pick_one, fill)
        rows_p, cnt_p, sc_p, c_i, placed_total, k_round, *sp_commits = sel
        # the commit stays outside the branches, which hand out [N]
        # vectors alone: an [N, RES_DIMS] tensor computed inside one takes
        # the row-major layout on the TPU, four columns padded to 128,
        # and the carried `used` with it (the barrier keeps the compiler
        # from sinking the product into them)
        c_i = jax.lax.optimization_barrier(c_i)
        used = used + c_i[:, None] * req[None, :]
        job_count = job_count + c_i
        carry = (used, job_count)
        if has_spread:
            carry += (sp_counts + sp_commits[0],)
        if has_ports:
            # the holders this round left (its own picks among them)
            taken = port_commit(taken_in, xs[xi_port], c_i > 0)
            carry += (taken,)
        dev_fit = None
        if has_slots:
            took, charged = slot_charge(room, c_i, need)
            dev_used = dev_used + charged
            carry += (dev_used,)
            # each fill slot's charges, by device group slot (a row
            # gather a slot: the slot state stays slot-major)
            takes = jnp.where(cnt_p[:, None] > 0, jnp.stack(
                [took[k][rows_p] for k in range(took.shape[0])], axis=1), 0)
            dev_fit = (need <= 0) | jnp.any(
                accept & (inp.dev_cap - dev_used >= need), axis=0)

        top_sc = sc_p[:top_k]
        top_rows = jnp.where(top_sc > NEG_INF / 2, rows_p[:top_k], -1)
        top_sc = jnp.where(top_sc > NEG_INF / 2, top_sc, 0.0)
        n_feas = jnp.sum(k_round > 0).astype(jnp.int32)
        n_filt = jnp.sum(~static_all).astype(jnp.int32)
        if has_ports:
            # the round's metrics are of the state it LEFT, the port
            # state too: the nodes that hold a value asked, this round's
            # own among them, are exhausted, and counted in a column of
            # their own after the dimensions
            static, port_hit = port_state(taken, xs[xi_port], static_all)
        n_exh, dim_ex = round_metrics_g(
            inp.cap, req, dh_limit, static, used, job_count, dev_fit)
        if has_ports:
            n_port = jnp.sum(port_hit)
            n_exh = n_exh + n_port
            dim_ex = jnp.concatenate([dim_ex, n_port[None]])
        out = (rows_p, cnt_p, top_rows, top_sc,
               n_feas, n_filt, n_exh.astype(jnp.int32),
               dim_ex.astype(jnp.int32), placed_total.astype(jnp.int32))
        if has_slots:
            out += (takes.astype(jnp.int32),)
        return carry, out

    # the schedule's padding (`want` 0) is its tail, so the loop stops one
    # past the last round that wants anything: a padding round runs
    # nothing, the carry passes it by and its rows of the outputs stay
    # zeros, which no span reads
    _, out_shapes = jax.eval_shape(
        round_step, carry0, jax.tree.map(lambda x: x[0], xs_r))
    r_pad = inp.round_want.shape[0]
    outs0 = jax.tree.map(
        lambda s: jnp.zeros((r_pad,) + s.shape, s.dtype), out_shapes)
    n_run = jnp.max(jnp.where(inp.round_want > 0, jnp.arange(r_pad) + 1, 0))

    def body(i, state):
        carry, outs = state
        carry, out = round_step(carry, jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, False), xs_r))
        return carry, jax.tree.map(
            lambda o, v: jax.lax.dynamic_update_index_in_dim(o, v, i, 0),
            outs, out)

    (used, jc, *rest), outs = jax.lax.fori_loop(
        0, n_run, body, (carry0, outs0))
    (rows_p, cnt_p, top_rows, top_sc,
     n_feas, n_filt, n_exh, dim_ex, placed) = outs[:9]
    fills, meta = pack_round_buffer(rows_p, cnt_p, top_rows, top_sc,
                                    n_feas, n_filt, n_exh, dim_ex, placed)
    parts = [fills, meta]
    if has_slots:
        parts.append(outs[9].reshape(r_pad, -1))
    buf = jnp.concatenate(parts, axis=1)
    # the port holders and the slot usage, where the wave carries them
    return (buf, used, jc, *rest[has_spread:])


place_multi_packed_jit = jax.jit(place_multi_packed, static_argnums=(1,))


# Compact-output fill prefix: rounds report their top FILL_K (node, count)
# fills in the always-fetched small buffer; the full [round_size] prefix
# stays in a device-resident companion buffer the host fetches only when a
# round overflows (placed_total > sum of the small prefix).  Water-fill
# commits in sorted-score order, so the nonzero fills ARE a prefix — a
# binpack round at bench shape fills 1-3 nodes; FILL_K=32 covers every
# non-pathological round while cutting the per-wave transfer ~16×
# (overflow pays one extra fetch).  32 was chosen against a latency- and
# bandwidth-poor link to the device; not re-measured on a directly
# attached chip.
FILL_K = 32


def place_multi_compact_packed(inp: MultiEvalInputs, cand_rows, cand_valid,
                               round_size: int, n_lanes: int):
    """Lane-parallel multi-eval placement over per-signature COMPACT
    candidate frames (round-5 verdict #2/#3: fuse the per-round tax and
    shrink the wave).

    The host scheduler (engine.build_multi_inputs) activates this kernel
    when the batch's static signatures form ONE clique of pairwise
    PROVABLY-DISJOINT landscapes (proven structurally from the lowered
    constraint rows — e.g. the bench's per-zone CSI topology LUT rows
    over disjoint node-id sets).  Each signature then owns a lane and a
    compact frame of ITS candidate rows (`cand_rows[l]`, host-computed
    with the same constraint_mask code on CPU):

      - the frame IS the static mask, so the per-launch constraint
        landscape evaluation disappears entirely;
      - every per-round tensor shrinks from [N] to [Nc] (the bench's 50k
        nodes → ~10k per zone), cutting the work term of the round cost;
      - steps run one round per lane CONCURRENTLY — disjoint frames
        cannot contend for a node, so per-lane usage slices commit
        exactly the sequential result — cutting the sequential depth
        from R to R/L.

    `inp.round_g`/`inp.round_want` are the STEP-MAJOR flattened
    `[T * n_lanes]` schedule; rounds of one eval (and one job) share a
    lane in order, preserving per-eval sequential semantics and
    job-count chaining verbatim.  Usage state is carried per lane as
    `[L, Nc, 3]` slices of `used` and scattered back once at the end.

    Returns (buf_small `[T*L, FILL_K+16]`, fills_full `[T*L,
    round_size]`, used `[N, 3]`): the host fetches buf_small always and
    fills_full only for overflowed rounds (device-resident otherwise).
    Row order is schedule order; the host reorders with its permutation."""
    n = inp.attrs.shape[0]
    assert n < (1 << 20), "packed fill rows support < 2^20 nodes"
    assert round_size <= 1024, "packed fill counts support rounds <= 1024"
    top_k = min(TOP_K, n)
    fill_k = min(FILL_K, round_size)

    # per-lane compact frames, gathered once per launch (cand_rows pads
    # with n: gathers clip to the last row, cand_valid masks it off;
    # the final scatter drops out-of-range rows)
    cap_c = inp.cap[cand_rows]                         # [L, Nc, 3]
    used0_c = inp.used0[cand_rows]                     # [L, Nc, 3]
    aff_cu = jax.vmap(
        lambda a: affinity_score(inp.attrs[a], inp.aff, inp.luts)
    )(cand_rows)                                       # [L, Ua, Nc]
    aff_any_u = jnp.any(inp.aff[..., 3] != 0, axis=1)  # [Ua]

    rg = inp.round_g.reshape(-1, n_lanes)              # [T, L]
    seed_r = round_seeds(inp.seed, rg)                 # [T, L]
    a_r = inp.g_aff[rg]
    # job-count seeds are the COMPACT [J', Nc] table the engine built
    # (row 0 = zeros for fresh jobs, one row per job with live allocs,
    # already gathered onto its lane's frame): the body gathers L tiny
    # rows per step instead of a [T, L, Nc] pre-materialization — the
    # pre-gather from the old [G, N] table was 76ms of a 101ms launch,
    # gathering mostly zeros (profiled round 5)
    jrow_r = inp.g_job[rg]                             # [T, L]
    req_r = inp.req[rg]                                # [T, L, 3]
    des_r = inp.desired[rg]
    dh_r = inp.dh_limit[rg]
    # chain identity is the ROUND's task group (one job per g in a
    # batch), NOT the seed row — fresh jobs share seed row 0 and must
    # not inherit each other's accumulated counts
    same_r = jnp.concatenate(
        [jnp.zeros((1, n_lanes), bool), rg[1:] == rg[:-1]], axis=0)
    want_r = inp.round_want.reshape(-1, n_lanes)
    cand_n = jnp.sum(cand_valid, axis=1).astype(jnp.int32)   # [L]

    scores_l = jax.vmap(
        partial(round_scores_g, round_size=round_size),
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None))
    fill_l = jax.vmap(
        partial(waterfill_round, round_size=round_size),
        in_axes=(0, 0, 0, 0, None))
    metrics_l = jax.vmap(round_metrics_g)

    def lane_step(carry, xs):
        used_c, cur_count = carry        # [L, Nc, 3], [L, Nc]
        (a, jrow, req, desired, dh_limit, want, same, sd) = xs
        jc0 = inp.job_count0[jrow]                     # [L, Nc] tiny gather
        aff_sc = jnp.take_along_axis(
            aff_cu, a[:, None, None], axis=1)[:, 0]    # [L, Nc]
        aff_any = aff_any_u[a]
        # per-item noise, global-row keyed (solo-path parity — see
        # MultiEvalInputs.seed); one elementwise hash per lane per step
        noise_c = jax.vmap(tiebreak_noise)(sd, cand_rows)   # [L, Nc]
        job_count = jnp.where(same[:, None], cur_count, jc0)
        k_i, score = scores_l(cap_c, req, desired, dh_limit, cand_valid,
                              aff_sc, aff_any, used_c, job_count,
                              inp.spread_algo)
        rows_p, cnt_p, sc_p, c_i, placed_total, k_round = fill_l(
            k_i, score, noise_c, want, inp.spread_algo)

        used_c = used_c + c_i[:, :, None] * req[:, None, :]
        job_count = job_count + c_i

        top_sc = sc_p[:, :top_k]                       # [L, k]
        # translate compact rows to GLOBAL rows for the output buffer
        top_rows_c = rows_p[:, :top_k]
        top_rows = jnp.where(
            top_sc > NEG_INF / 2,
            jnp.take_along_axis(cand_rows, top_rows_c, axis=1), -1)
        top_sc = jnp.where(top_sc > NEG_INF / 2, top_sc, 0.0)
        n_feas = jnp.sum(k_round > 0, axis=1).astype(jnp.int32)
        n_filt = (n - cand_n)                          # statically filtered
        n_exh, dim_ex = metrics_l(cap_c, req, dh_limit, cand_valid,
                                  used_c, job_count)
        rows_g = jnp.take_along_axis(cand_rows, rows_p, axis=1)
        out = (rows_g, cnt_p, top_rows, top_sc,
               n_feas, n_filt, n_exh.astype(jnp.int32),
               dim_ex.astype(jnp.int32),
               placed_total.astype(jnp.int32))
        return (used_c, job_count), out

    nc = cand_rows.shape[1]
    carry0 = (used0_c, jnp.zeros((n_lanes, nc), jnp.int32))
    (used_c, _), outs = jax.lax.scan(
        lane_step, carry0,
        (a_r, jrow_r, req_r, des_r, dh_r, want_r, same_r, seed_r))
    (rows_g, cnt_p, top_rows, top_sc,
     n_feas, n_filt, n_exh, dim_ex, placed) = outs

    # scatter the per-lane usage slices back to cluster rows (disjoint
    # frames ⇒ no collisions; padding indices == n drop out of range)
    used = inp.used0.at[cand_rows.reshape(-1)].set(
        used_c.reshape(-1, RES_DIMS), mode="drop")

    def flat(x):                          # [T, L, ...] -> [T*L, ...]
        return x.reshape((-1,) + x.shape[2:])

    rows_g, cnt_p = flat(rows_g), flat(cnt_p)
    top_rows, top_sc = flat(top_rows), flat(top_sc)
    n_feas, n_filt, n_exh = flat(n_feas), flat(n_filt), flat(n_exh)
    dim_ex, placed = flat(dim_ex), flat(placed)
    fills, meta = pack_round_buffer(rows_g, cnt_p, top_rows, top_sc,
                                    n_feas, n_filt, n_exh, dim_ex, placed)
    buf_small = jnp.concatenate([fills[:, :fill_k], meta], axis=1)
    return buf_small, fills, used


place_multi_compact_packed_jit = jax.jit(place_multi_compact_packed,
                                         static_argnums=(3, 4))


# ---------------------------------------------------------------------------
# Chained-wave launches with DONATED usage buffers (core/wavepipe.py).
#
# A wave-pipelined worker chains wave k+1's launch on wave k's
# proposed-usage OUTPUT; once consumed, wave k's buffer is dead — donating
# it lets XLA reuse the [N, 3] allocation in place instead of holding two
# usage tensors live per chained step.  The donated argument is SEPARATE
# from the input bundle (donation is per jit argument, and donating the
# whole MultiEvalInputs would invalidate the engine's cached node
# tensors); callers pass `inp` with `used0=None` so the dead buffer is
# not also referenced through the pytree.  Only the engine's chain path
# uses these — the first wave's usage comes from the engine's device
# cache, which must never be donated.
# ---------------------------------------------------------------------------

def place_multi_chained(used0, inp: MultiEvalInputs, round_size: int):
    return place_multi_packed(inp._replace(used0=used0), round_size)


place_multi_chained_jit = jax.jit(place_multi_chained,
                                  static_argnums=(2,),
                                  donate_argnums=(0,))


def place_multi_compact_chained(used0, inp: MultiEvalInputs, cand_rows,
                                cand_valid, round_size: int, n_lanes: int):
    return place_multi_compact_packed(inp._replace(used0=used0),
                                      cand_rows, cand_valid,
                                      round_size, n_lanes)


place_multi_compact_chained_jit = jax.jit(place_multi_compact_chained,
                                          static_argnums=(4, 5),
                                          donate_argnums=(0,))
