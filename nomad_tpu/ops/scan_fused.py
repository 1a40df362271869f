"""The exact scan as ONE Pallas TPU (Mosaic) kernel.

`select.place_packed_xla` runs an eval's placements as a loop of XLA ops,
one dependent step a placement; on the TPU each step is some 75 ops and
the gaps between them, for work a few hundred cycles long.
`place_packed_fused` runs the same loop inside one `pallas_call`: the
node state sits in VMEM from the first step to the last, node axis as
`[rows, 128]` planes (one plane a resource dimension, a task group, a
spread), and a step fuses feasibility, the rank chain, the top-3
selection, the counts, the state update and the packed row with no op
boundary inside it.

It is the same computation, and the tests hold it to `place_packed` bit
for bit (interpret mode, on the CPU): the statics are `scan_statics`'
own, handed in; the rank chain calls the scoring module's bodies
(`fit_score`, `job_anti_affinity`, `value_boost`); a node's spread and
distinct_property counts are read by a `where` chain over the K values
(never a gather); the pick is the maximum of the masked, jittered
scores, the lowest row among equals, and the two reported runners-up
the same over what is left, which is `select._top_max`'s order.  Its
output is `place_packed`'s: the `[P_pad, 11 + RES_DIMS (+1)]` buffer,
rows past the trip count idle, and the final `used` and `job_count`.

`scan_gate` says which of the two scans a launch takes, from what the
code can observe: the backend, the input shapes and the value widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .scoring import fit_score, job_anti_affinity, spread_weights, value_boost
from .select import NEG_INF, TOP_K, PlacementInputs, scan_statics

LANES, SUBLANES = 128, 8
INT_MAX, INT_MIN = jnp.iinfo(jnp.int32).max, jnp.iinfo(jnp.int32).min
# resident VMEM bytes the fused scan may hold (`resident_bytes`): half of
# the limit the kernel asks the compiler for, the rest is the compiler's
VMEM_BUDGET = 16 << 20
VMEM_LIMIT = 32 << 20
# the widest value table a `where` chain walks a step: spread values
# (S x K), distinct_property values (D x Kd), static port values (Kp)
MAX_VALUES = 64
# a step's own planes (scores, masks, the selection's working copy) the
# compiler may keep in VMEM beside the state
STEP_PLANES = 32


def _plane_rows(n: int) -> int:
    """Rows of 128 lanes that hold `n` nodes, a multiple of 8."""
    tile = LANES * SUBLANES
    return -(-max(n, 1) // tile) * SUBLANES


def resident_bytes(inp: PlacementInputs) -> int:
    """VMEM the fused scan holds, from the input shapes: node state,
    statics, the carried state and its copy in, the output buffer (its
    rows lane-padded) and a step's own planes."""
    g, res = inp.req.shape
    kp = 0 if inp.pt_taken0 is None else inp.pt_taken0.shape[0]
    planes = (2 * g                      # feasibility, affinity
              + 3 * res                  # cap, used in, used out
              + 4                        # job count in and out, noise, rows
              + inp.sp_nodeval.shape[0] + inp.pd_nodeval.shape[0]
              + 2 * kp + STEP_PLANES)
    plane = _plane_rows(inp.attrs.shape[0]) * LANES * 4
    p_pad = inp.tg_idx.shape[0]
    out = -(-p_pad // SUBLANES) * SUBLANES * LANES * 4
    return planes * plane + out


def scan_gate(inp: PlacementInputs) -> tuple:
    """(impl, why) of a single-device scan launch: ("fused", "fits") on
    the TPU where the kernel's state fits VMEM and its value tables the
    `where` chains; else ("xla", "backend" | "values" | "vmem")."""
    if jax.default_backend() != "tpu":
        return "xla", "backend"
    kp = 0 if inp.pt_taken0 is None else inp.pt_taken0.shape[0]
    if max(inp.sp_counts0.size, inp.pd_counts0.size, kp) > MAX_VALUES:
        return "xla", "values"
    if resident_bytes(inp) > VMEM_BUDGET:
        return "xla", "vmem"
    return "fused", "fits"


def _any_of(masks):
    out = masks[0]
    for m in masks[1:]:
        out = out | m
    return out


def _fold(x, op):
    """`op` over a whole plane, kept as a (1, 1) vector."""
    return op(op(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _gather(table, r: int, val):
    """`table[r, clip(val, 0, K - 1)]` for every node, by a `where` chain
    over the K values of the row (no gather)."""
    out = table[r:r + 1, 0:1]
    for k in range(1, table.shape[1]):
        out = jnp.where(val >= k, table[r:r + 1, k:k + 1], out)
    return jnp.broadcast_to(out, val.shape)


def place_packed_fused(inp: PlacementInputs, *, interpret: bool = False):
    """`select.place_packed` as one Pallas kernel: (buf, used, job_count),
    the same values.  `interpret` runs the kernel in Pallas' interpreter
    (the tests, on the CPU)."""
    # imported here, not with the module: Pallas' import costs every
    # process ~1 s of its start, and only a scan that takes the kernel
    # needs it
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, res = inp.cap.shape
    p_pad = inp.tg_idx.shape[0]
    top_k = min(TOP_K, n)
    rows_n = _plane_rows(n)
    span = rows_n * LANES
    n_sp, n_pd = inp.sp_nodeval.shape[0], inp.pd_nodeval.shape[0]
    has_ports = inp.pt_taken0 is not None
    n_pt = inp.pt_taken0.shape[0] if has_ports else 0
    width = 11 + res + has_ports
    st = scan_statics(inp, jnp.arange(n))

    def plane(x, fill):
        """[..., N] -> [..., rows, 128], the padding `fill`."""
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, span - n)],
                    constant_values=fill)
        return x.reshape(x.shape[:-1] + (rows_n, LANES))

    w, n_active = spread_weights(inp.sp_weight)
    i32 = lambda x: jnp.asarray(x).astype(jnp.int32).reshape(-1)
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32).reshape(-1)
    smem = {
        "steps": i32(jnp.stack([inp.tg_idx, inp.prev_row,
                                inp.active.astype(jnp.int32)], axis=1)),
        "n_run": i32(jnp.max(jnp.where(inp.active, jnp.arange(p_pad) + 1, 0),
                             initial=0)),
        "req": i32(inp.req),
        "reqf": f32(inp.req),
        "desired": f32(jnp.maximum(inp.desired, 1.0)),
        "dh": i32(inp.dh_limit),
        "aff_any": i32(st.aff_any),
        "flags": i32(jnp.stack([st.sp_any, inp.spread_algo])),
        "sp_w": f32(w),
        "sp_n": f32(n_active),
        "pd_limit": i32(inp.pd_limit),
        "pd_apply": i32(inp.pd_apply),
    }
    vmem = {
        "static": plane(st.static.astype(jnp.int32), 0),
        "aff": plane(st.aff_sc, 0.0),
        "cap": plane(inp.cap.T, 0),
        "used0": plane(inp.used0.T, 0),
        "jc0": plane(inp.job_count0, 0),
        "noise": plane(st.noise, 0.0),
        "rows": jnp.arange(span, dtype=jnp.int32).reshape(rows_n, LANES),
        "sp_val": plane(inp.sp_nodeval, -1),
        "pd_val": plane(inp.pd_nodeval, -1),
        "sp_exp": inp.sp_expected.astype(jnp.float32),
        "sp_cnt0": inp.sp_counts0.astype(jnp.float32),
        "pd_cnt0": inp.pd_counts0.astype(jnp.int32),
    }
    scratch = [pltpu.VMEM(inp.sp_counts0.shape, jnp.float32),
               pltpu.VMEM(inp.pd_counts0.shape, jnp.int32)]
    if has_ports:
        smem["pt_ask"] = i32(inp.pt_ask)
        vmem["taken0"] = plane(inp.pt_taken0.astype(jnp.int32), 0)
        scratch.append(pltpu.VMEM((n_pt, rows_n, LANES), jnp.int32))
    names = list(smem) + list(vmem)

    def kernel(*refs):
        ins = dict(zip(names, refs))
        buf, used, jc = refs[len(names):len(names) + 3]
        sp_cnt, pd_cnt, *taken = refs[len(names) + 3:]
        taken = taken[0] if taken else None
        used[...] = ins["used0"][...]
        jc[...] = ins["jc0"][...]
        sp_cnt[...] = ins["sp_cnt0"][...]
        pd_cnt[...] = ins["pd_cnt0"][...]
        if has_ports:
            taken[...] = ins["taken0"][...]
        # every row idle (pick -1, top rows -1, zero elsewhere) until its
        # step writes it
        lane = jax.lax.broadcasted_iota(jnp.int32, (p_pad, width), 1)
        buf[...] = jnp.where((lane == 0) | ((lane >= 2) & (lane < 5)), -1, 0)
        sp_any = ins["flags"][0] != 0
        algo = ins["flags"][1] != 0

        def step(i, _):
            g = ins["steps"][3 * i]
            prev = ins["steps"][3 * i + 1]
            act = ins["steps"][3 * i + 2] != 0
            rows = ins["rows"][...]
            stat = ins["static"][g] != 0
            u = [used[d] for d in range(res)]
            cap = [ins["cap"][d] for d in range(res)]
            cnt = jc[...]
            req = [ins["req"][g * res + d] for d in range(res)]
            over = [u[d] + req[d] > cap[d] for d in range(res)]
            fit = ~_any_of(over)
            dh = ins["dh"][g]
            dh_ok = (dh <= 0) | (cnt < dh)
            pd_counts = pd_cnt[...]
            pd_ok = None
            for d in range(n_pd):
                val = ins["pd_val"][d]
                row_ok = ((_gather(pd_counts, d, val) < ins["pd_limit"][d])
                          & (val >= 0))
                applies = ((ins["pd_apply"][g * n_pd + d] != 0)
                           & (ins["pd_limit"][d] > 0))
                ok_d = row_ok | ~applies
                pd_ok = ok_d if pd_ok is None else pd_ok & ok_d
            feas = stat & fit & dh_ok & pd_ok

            # ---- the rank chain, select.step_scores' terms in its order
            f = lambda x: x.astype(jnp.float32)
            bp = fit_score(f(cap[0]), f(cap[1]),
                           f(u[0]) + ins["reqf"][g * res],
                           f(u[1]) + ins["reqf"][g * res + 1], algo) / 18.0
            aa = job_anti_affinity(cnt, ins["desired"][g])
            is_prev = rows == prev
            rp = jnp.where(is_prev, -1.0, 0.0)
            af = ins["aff"][g]
            sp_counts = sp_cnt[...]
            boost = value_boost(ins["sp_exp"][...], sp_counts)     # [S, K]
            sp = None
            for s in range(n_sp):
                val = ins["sp_val"][s]
                term = (jnp.where(val >= 0, _gather(boost, s, val), 0.0)
                        * ins["sp_w"][s])
                sp = term if sp is None else sp + term
            sp = sp / ins["sp_n"][0]
            aff_any = ins["aff_any"][g] != 0
            total = 0.0 + bp
            total = total + jnp.where(cnt > 0, aa, 0.0)
            total = total + jnp.where(is_prev, rp, 0.0)
            total = total + jnp.where(aff_any, af, 0.0)
            total = total + jnp.where(sp_any, sp, 0.0)
            n_act = (1 + (cnt > 0).astype(jnp.int32)
                     + is_prev.astype(jnp.int32)
                     + aff_any.astype(jnp.int32) + sp_any.astype(jnp.int32))
            final = total / n_act.astype(jnp.float32)

            port_hit = None
            if has_ports:
                held = None
                for k in range(n_pt):
                    h = (taken[k] != 0) & (ins["pt_ask"][g * n_pt + k] != 0)
                    held = h if held is None else held | h
                feas, port_hit = feas & ~held, feas & held

            # ---- counts: n_feasible | n_filtered | n_exhausted | dims
            exhausted = stat & (~fit | ~dh_ok)
            if port_hit is not None:
                exhausted = exhausted | port_hit
            counted = [feas, (rows < n) & ~stat, exhausted,
                       *(stat & ~fit & o for o in over)]
            if port_hit is not None:
                counted.append(port_hit)
            counts = [_fold(c.astype(jnp.int32), jnp.sum) for c in counted]

            # ---- selection: the max, lowest row among equals, x top_k
            x = jnp.where(feas, final, NEG_INF) + ins["noise"][...]
            tops = []
            for j in range(top_k):
                best = _fold(x, jnp.max)
                row = _fold(jnp.where(x == best, rows, INT_MAX), jnp.min)
                hit = rows == row
                score = _fold(jnp.where(hit, final, -jnp.inf), jnp.max)
                tops.append((row, hit,
                             jnp.where(best > NEG_INF / 2, score, NEG_INF)))
                if j + 1 < top_k:
                    x = jnp.where(hit, -jnp.inf, x)
            ok = act & (tops[0][2] > NEG_INF / 2)
            pick = jnp.where(ok, tops[0][0], -1)
            onehot = tops[0][1] & ok

            # ---- state update (none when nothing is placed)
            for d in range(res):
                used[d] = u[d] + jnp.where(onehot, req[d], 0)
            jc[...] = cnt + onehot.astype(jnp.int32)

            def at_pick(planes, k):
                """Each plane's value at the pick, a column [k, 1]; -1
                where nothing was placed."""
                col = jnp.full((k, 1), -1, jnp.int32)
                idx = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
                for r in range(k):
                    v = _fold(jnp.where(tops[0][1], planes[r], INT_MIN),
                              jnp.max)
                    col = jnp.where(idx == r, jnp.where(pick >= 0, v, -1), col)
                return col

            def bumped(table, col, mask):
                k = table.shape[1]
                kio = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
                hot = (kio == jnp.clip(col, 0, k - 1)) & (col >= 0) & mask
                return table + hot.astype(table.dtype)

            sp_cnt[...] = bumped(sp_counts, at_pick(ins["sp_val"], n_sp), ok)
            pd_app = jnp.zeros((n_pd, 1), jnp.int32)
            pdi = jax.lax.broadcasted_iota(jnp.int32, (n_pd, 1), 0)
            for d in range(n_pd):
                pd_app = jnp.where(pdi == d, ins["pd_apply"][g * n_pd + d],
                                   pd_app)
            pd_cnt[...] = bumped(pd_counts, at_pick(ins["pd_val"], n_pd),
                                 (pd_app != 0) & ok)
            if has_ports:
                for k in range(n_pt):
                    ask = ins["pt_ask"][g * n_pt + k] != 0
                    taken[k] = jnp.where(onehot & ask, 1, taken[k])

            # ---- the packed row, select.pack_row's layout
            bits = lambda v: jax.lax.bitcast_convert_type(v, jnp.int32)
            words = [pick, bits(jnp.where(ok, tops[0][2], 0.0))]
            words += [jnp.where(ok, tops[j][0], -1) if j < top_k else -1
                      for j in range(TOP_K)]
            words += [bits(jnp.where(ok, tops[j][2], 0.0)) if j < top_k
                      else 0 for j in range(TOP_K)]
            words += counts
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
            out = jnp.zeros((1, width), jnp.int32)
            for j, word in enumerate(words):
                out = jnp.where(lane == j, word, out)
            buf[pl.ds(i, 1), :] = out
            return 0

        jax.lax.fori_loop(0, ins["n_run"][0], step, 0)

    specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem)
             + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(vmem))
    buf, used, jc = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((p_pad, width), jnp.int32),
                   jax.ShapeDtypeStruct((res, rows_n, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((rows_n, LANES), jnp.int32)),
        in_specs=specs,
        out_specs=tuple(pl.BlockSpec(memory_space=pltpu.VMEM)
                        for _ in range(3)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*smem.values(), *vmem.values())
    return (buf, used.reshape(res, span)[:, :n].T,
            jc.reshape(span)[:n])

