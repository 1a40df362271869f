"""The exact scan's step (ISSUE 37): a trip-count loop whose padding runs
nothing, the pick and its reported runners-up from one arg-max reduce,
one packed row out a step.

Held bit for bit to `reference_packed` below: the loop as it stood
before, a `lax.scan` over every padded step with a `lax.top_k` and a
gather a step, its eight stacked outputs concatenated afterwards.  The
scoring core (`select.scan_statics`, `select.step_scores`) is shared;
the selection, the state update, the metrics and the packing are this
file's own copy.
"""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import select
from nomad_tpu.ops.select import NEG_INF, PlacementInputs
from nomad_tpu.pack import ClusterPacker, lower_spreads
from nomad_tpu.structs import OP_DISTINCT_PROPERTY, RES_DIMS, Constraint

from test_spread_batched import fleet, harness, http_get, service

WIDTH = 11 + RES_DIMS


# ------------------------------------------------------ the reference

def reference_packed(inp: PlacementInputs):
    """(buf [P, 11 + RES_DIMS], used, job_count) of the sort form."""
    n = inp.attrs.shape[0]
    top_k = min(select.TOP_K, n)
    st = select.scan_statics(inp, jnp.arange(n))
    static, noise = st.static, st.noise

    def step(carry, xs):
        used, job_count, sp_counts, pd_counts = carry
        g, prev, act = xs
        req_g = inp.req[g]
        stat_g = static[g]
        feas, final, _, fit, dh_ok = select.step_scores(inp, st, carry, g,
                                                        prev)
        rows = st.rows
        masked = jnp.where(feas, final, NEG_INF)
        nsc, top_rows = jax.lax.top_k(masked + noise, top_k)
        top_sc = jnp.where(nsc > NEG_INF / 2, final[top_rows], NEG_INF)
        pick = top_rows[0]
        ok = act & (top_sc[0] > NEG_INF / 2)
        pick = jnp.where(ok, pick, -1)

        onehot = (rows == pick) & ok
        used = used + onehot[:, None].astype(jnp.int32) * req_g[None, :]
        job_count = job_count + onehot.astype(jnp.int32)
        val_p = jnp.where(pick >= 0,
                          inp.sp_nodeval[:, jnp.maximum(pick, 0)], -1)
        k = sp_counts.shape[1]
        sp_hot = (jax.nn.one_hot(jnp.clip(val_p, 0, k - 1), k)
                  * ((val_p >= 0) & ok)[..., None])
        sp_counts = sp_counts + sp_hot
        kd = pd_counts.shape[1]
        pd_val_p = jnp.where(pick >= 0,
                             inp.pd_nodeval[:, jnp.maximum(pick, 0)], -1)
        pd_hot = (jax.nn.one_hot(jnp.clip(pd_val_p, 0, kd - 1), kd,
                                 dtype=pd_counts.dtype)
                  * ((pd_val_p >= 0) & inp.pd_apply[g] & ok)[..., None])
        pd_counts = pd_counts + pd_hot

        n_filtered = jnp.sum(~stat_g)
        exhausted = stat_g & (~fit | ~dh_ok)
        n_exhausted = jnp.sum(exhausted)
        over = (used - onehot[:, None].astype(jnp.int32) * req_g[None, :]
                + req_g[None, :]) > inp.cap
        dim_ex = jnp.sum((stat_g & ~fit)[:, None] & over, axis=0)
        out = (pick,
               jnp.where(ok, top_sc[0], 0.0),
               jnp.where(ok, top_rows, -1),
               jnp.where(ok, top_sc, 0.0),
               jnp.sum(feas).astype(jnp.int32),
               n_filtered.astype(jnp.int32),
               n_exhausted.astype(jnp.int32),
               dim_ex.astype(jnp.int32))
        return (used, job_count, sp_counts, pd_counts), out

    carry0 = (inp.used0, inp.job_count0, inp.sp_counts0, inp.pd_counts0)
    (used, job_count, _, _), outs = jax.lax.scan(
        step, carry0, (inp.tg_idx, inp.prev_row, inp.active))
    picks, scores, topk_rows, topk_scores, n_feas, n_filt, n_exh, dim_ex = outs
    f2i = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    p = picks.shape[0]
    buf = jnp.concatenate([
        picks[:, None], f2i(scores)[:, None],
        topk_rows, jnp.full((p, 3 - top_k), -1, jnp.int32),
        f2i(topk_scores), jnp.zeros((p, 3 - top_k), jnp.int32),
        n_feas[:, None], n_filt[:, None], n_exh[:, None], dim_ex], axis=1)
    return buf, used, job_count


reference_jit = jax.jit(reference_packed)


# ----------------------------------------------------------- the inputs

def scan_inputs(nodes, job, p=None, seed=0) -> PlacementInputs:
    """One eval's scan inputs, lowered as `PlacementEngine._place` lowers
    them: `p` steps (the group's count unless given), all active."""
    h = harness(nodes)
    h.state.upsert_job(job)
    snap = h.snapshot()
    packer = ClusterPacker()
    t = packer.build(snap)
    tgt = packer.lower_task_groups(job, job.task_groups)
    ctx = packer.job_context(job, snap, t)
    sp = lower_spreads(packer, job, t, snap)
    pd = packer.lower_distinct(job, job.task_groups, tgt, t, snap)
    p = p or job.task_groups[0].count
    return PlacementInputs(
        attrs=jnp.asarray(t.attrs), cap=jnp.asarray(t.cap),
        used0=jnp.asarray(t.used), elig=jnp.asarray(t.elig.astype(bool)),
        dc_mask=jnp.asarray(ctx.dc_mask), pool_mask=jnp.asarray(ctx.pool_mask),
        luts=jnp.asarray(tgt.luts), con=jnp.asarray(tgt.con),
        aff=jnp.asarray(tgt.aff), req=jnp.asarray(tgt.req),
        desired=jnp.asarray(np.array([tg.count for tg in job.task_groups],
                                     np.int32)),
        dh_limit=jnp.asarray(tgt.dh_limit),
        sp_nodeval=jnp.asarray(sp.sp_nodeval),
        sp_weight=jnp.asarray(sp.sp_weight),
        sp_expected=jnp.asarray(sp.sp_expected),
        sp_counts0=jnp.asarray(sp.sp_counts0),
        pd_nodeval=jnp.asarray(pd.pd_nodeval),
        pd_limit=jnp.asarray(pd.pd_limit),
        pd_apply=jnp.asarray(pd.pd_apply),
        pd_counts0=jnp.asarray(pd.pd_counts0),
        tg_idx=jnp.zeros(p, jnp.int32), prev_row=jnp.full(p, -1, jnp.int32),
        active=jnp.ones(p, bool), job_count0=jnp.asarray(ctx.job_count),
        spread_algo=jnp.asarray(False),
        seed=jnp.asarray(seed, jnp.uint32))


def twins(n: int):
    """`n` identical nodes in one datacenter: every score a tie."""
    nodes = fleet(n, 11, k=1)
    for node in nodes:
        node.resources.cpu, node.resources.memory_mb = 4000, 8192
        node.attributes["platform.rack"] = "r0"
        node.meta["cell"] = "c0"
    return nodes


def only_rows(inp: PlacementInputs, rows) -> PlacementInputs:
    elig = np.zeros(inp.elig.shape[0], bool)
    elig[list(rows)] = True
    return inp._replace(elig=jnp.asarray(elig))


def step_case(name: str) -> PlacementInputs:
    if name in ("seed_0", "live_seed"):
        return scan_inputs(fleet(90, 31), service("sc-a", 40, affinity=True),
                           seed=0 if name == "seed_0" else 2147937001)
    if name in ("ties_seed_0", "ties_live_seed"):
        return scan_inputs(twins(48), service("sc-t", 30, k=1, stanzas=0),
                           seed=0 if name == "ties_seed_0" else 77)
    if name == "two_feasible":
        return only_rows(scan_inputs(fleet(60, 32), service("sc-2", 12)),
                         (7, 41))
    if name == "none_feasible":
        return only_rows(scan_inputs(fleet(60, 33), service("sc-0", 6)), ())
    if name == "two_nodes":
        return scan_inputs(fleet(2, 34), service("sc-n2", 5, stanzas=0))
    if name == "one_node":
        return scan_inputs(fleet(1, 35), service("sc-n1", 3, stanzas=0))
    if name == "spread_distinct_property":
        # three cells, at most two allocations a cell: six land, two fail
        job = service("sc-dp", 8, stanzas=2, affinity=True)
        job.constraints.append(
            Constraint("${meta.cell}", OP_DISTINCT_PROPERTY, "2"))
        return scan_inputs(fleet(75, 36), job, seed=5)
    if name == "reschedule":
        inp = scan_inputs(fleet(40, 37), service("sc-r", 16), seed=9)
        prev = np.full(16, -1, np.int32)
        prev[[0, 3, 4, 11]] = [5, 5, 17, 39]
        return inp._replace(prev_row=jnp.asarray(prev))
    if name == "fills_up":
        # room for two allocations a node, by cpu on some and by memory
        # on the others: 16 land on 8 nodes, the rest meet a full fleet
        inp = scan_inputs(fleet(8, 38), service("sc-f", 22), seed=3)
        room = np.asarray(inp.req)[0][None, :] * 2
        tight = np.asarray(inp.cap).copy()
        tight[:4, 0] = (np.asarray(inp.used0) + room)[:4, 0]
        tight[4:, 1] = (np.asarray(inp.used0) + room)[4:, 1]
        return inp._replace(cap=jnp.asarray(tight))
    raise AssertionError(name)


STEP_CASES = ["seed_0", "live_seed", "ties_seed_0", "ties_live_seed",
              "two_feasible", "none_feasible", "two_nodes", "one_node",
              "spread_distinct_property", "reschedule", "fills_up"]


def same_bits(want, got):
    for a, b in zip(want, got):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def idle_rows(buf) -> bool:
    """Rows no step wrote: pick -1, top rows -1, zero elsewhere."""
    return bool((buf[:, [0, 2, 3, 4]] == -1).all()
                and not buf[:, [1, 5, 6, 7]].any() and not buf[:, 8:].any())


# ------------------------------------------------------------ exactness

@pytest.mark.parametrize("case", STEP_CASES)
def test_every_packed_row_and_the_final_state_are_the_sort_forms(case):
    inp = step_case(case)
    want = reference_jit(inp)
    got = select.place_packed_jit(inp)
    same_bits(want, got)
    buf = np.asarray(got[0])
    assert buf.shape == (inp.tg_idx.shape[0], WIDTH)
    picks, n = buf[:, 0], inp.attrs.shape[0]
    scores = buf[:, 5:8].view(np.float32)
    if case in ("seed_0", "live_seed", "reschedule"):
        assert (picks >= 0).all() and (buf[:, 2:5] >= 0).all()
    if case.startswith("ties"):
        # the lowest row of the equal maxima, or the one the noise names
        assert (picks[0] == 0) == (case == "ties_seed_0")
        assert len(set(scores[0].tolist())) == 1
    if case == "two_feasible":
        # a third row is reported with the score of no node
        assert set(picks.tolist()) <= {7, 41}
        assert set(buf[:, 2:4].ravel().tolist()) == {7, 41}
        assert (scores[:, 2] == np.float32(NEG_INF)).all()
    if case == "none_feasible":
        assert idle_rows(buf[:, :8]) and not buf[:, 8].any()
        assert (buf[:, 9] == n).all()
        same_bits((inp.used0, inp.job_count0), got[1:])
    if case in ("two_nodes", "one_node"):
        assert (picks >= 0).all()
        assert (buf[:, 2 + n:5] == -1).all() and not buf[:, 5 + n:8].any()
    if case == "spread_distinct_property":
        assert (picks >= 0).tolist() == [True] * 6 + [False] * 2
    if case == "fills_up":
        assert (picks >= 0).tolist() == [True] * 16 + [False] * 6
        # n_exhausted climbs as nodes fill, by cpu and by memory
        assert buf[0, 10] == 0 and buf[-1, 10] == 8
        assert buf[-1, 11:].tolist() == [4, 4] + [0] * (RES_DIMS - 2)


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("values", ["distinct", "ties", "all_equal",
                                    "as_many_as_k"])
def test_top_max_is_the_sort_in_one_reduce(values, k):
    """`_top_max` against `lax.top_k` and a gather: values, rows (equal
    values by lower row) and the payloads' values at those rows."""
    rng = np.random.default_rng(37)
    n = k if values == "as_many_as_k" else 257
    x = rng.standard_normal(n).astype(np.float32)
    if values == "ties":
        x = np.round(x)
        x[[3, 77, 200]] = x.max() + 1
    if values == "all_equal":
        x[:] = NEG_INF
    rows = jnp.arange(n, dtype=jnp.int32)
    score = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    tag = jnp.asarray(rng.integers(-1, 9, n).astype(np.int32))
    want_x, want_rows = jax.lax.top_k(jnp.asarray(x), k)
    got = jax.jit(lambda *a: select._top_max(a[0], a[1], k, *a[2:]))(
        jnp.asarray(x), rows, score, tag)
    assert len(got) == k and all(len(t) == 4 for t in got)
    same_bits((want_x, want_rows, score[want_rows], tag[want_rows]),
              [jnp.stack(col) for col in zip(*got)])
    if values == "ties":
        assert [int(t[1]) for t in got] == [3, 77, 200][:k]
    if values == "all_equal":
        assert [int(t[1]) for t in got] == list(range(k))


def test_place_is_a_view_of_the_packed_loop():
    inp = step_case("two_nodes")
    buf, used, job_count = (np.asarray(x)
                            for x in select.place_packed_jit(inp))
    out = select.place_jit(inp)
    assert out.topk_rows.shape == out.topk_scores.shape == (5, 2)
    same_bits((buf[:, 0], buf[:, 1].view(np.float32), buf[:, 2:4],
               buf[:, 5:7].view(np.float32), buf[:, 8], buf[:, 9],
               buf[:, 10], buf[:, 11:], used, job_count), out)
    # the stacked form's packing (the node-sharded scan's) is the step's
    same_bits((buf, used, job_count), select.pack_outputs(out))


# -------------------------------------------------------------- padding

def cheap_ask(inp: PlacementInputs) -> PlacementInputs:
    """An ask the fleet holds thousands of."""
    req = np.zeros_like(np.asarray(inp.req))
    req[:, :2] = 1
    return inp._replace(req=jnp.asarray(req))


def padded_case(name: str):
    """(inputs, the steps the loop should run)."""
    if name == "3000_of_4096":
        inp = cheap_ask(scan_inputs(fleet(64, 41), service("pad-a", 3000),
                                    p=4096, seed=2147937002))
        active = np.arange(4096) < 3000
    elif name == "hole":
        inp = scan_inputs(fleet(50, 42), service("pad-h", 16), p=32, seed=4)
        active = np.zeros(32, bool)
        active[[0, 1, 2, 6, 7, 12]] = True
    elif name == "none_active":
        inp = scan_inputs(fleet(50, 43), service("pad-0", 8), p=16)
        active = np.zeros(16, bool)
    else:
        raise AssertionError(name)
    n_run = int(np.flatnonzero(active).max(initial=-1)) + 1
    return inp._replace(active=jnp.asarray(active)), n_run


@pytest.mark.parametrize("case", ["3000_of_4096", "hole", "none_active"])
def test_the_trip_count_changes_no_row_a_step_wrote(case):
    """Against the scan that runs every padded step masked by `active`:
    the rows inside the trip count, active or a hole, and the final state
    are its bits; the rows past it are idle."""
    inp, n_run = padded_case(case)
    want = [np.asarray(x) for x in reference_jit(inp)]
    got = [np.asarray(x) for x in select.place_packed_jit(inp)]
    same_bits((want[0][:n_run], want[1], want[2]),
              (got[0][:n_run], got[1], got[2]))
    assert got[0].shape == want[0].shape and idle_rows(got[0][n_run:])
    active = np.asarray(inp.active)
    assert (got[0][active, 0] >= 0).all()
    assert idle_rows(got[0][~active][:, :8])
    if case == "3000_of_4096":
        assert n_run == 3000 and got[2].sum() == 3000
        # a padded step of the old loop did report its counts
        assert want[0][n_run:, 8].all()
    if case == "hole":
        # a hole inside the trip count scores and counts, places nothing
        assert n_run == 13 and got[0][3:6, 8].all()
    if case == "none_active":
        same_bits((inp.used0, inp.job_count0), got[1:])


# ------------------------------------------------------ the served path

def test_served_scans_share_one_program_and_count_their_steps():
    """Two spread jobs of 3,000 and 2,500 through a served agent: both go
    solo through the scan, padded to 4,096 steps, so one program key and
    one compiled `place_packed`; the counter says what ran."""
    from nomad_tpu.agent import Agent
    from nomad_tpu.ops import engine
    nodes = fleet(67, 44)
    jobs = [service("steps-a", 3000), service("steps-b", 2500)]
    for job in jobs:
        res = job.task_groups[0].tasks[0].resources
        res.cpu, res.memory_mb = 10, 10
    seen0 = set(engine._KERNEL_SHAPES_SEEN)
    size0 = select.place_packed_jit._cache_size()
    steps0 = REGISTRY.counter_labels("nomad.engine.scan_steps")
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        srv.state.upsert_nodes([n.copy() for n in nodes])
        for job in jobs:
            srv.register_job(job)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            snap = srv.state.snapshot()
            live = [sum(1 for a in snap.allocs_by_job(j.namespace, j.id)
                        if not a.terminal_status()) for j in jobs]
            if live == [3000, 2500]:
                break
            time.sleep(0.1)
        assert live == [3000, 2500]
        metrics = json.dumps(http_get(agent, "/v1/metrics"))
    finally:
        agent.shutdown()
    # (the second eval also replays the first's usage: a `scatter`)
    assert {key for key in engine._KERNEL_SHAPES_SEEN - seen0
            if key[0] == "scan"} == {("scan", (67, 4096))}
    assert select.place_packed_jit._cache_size() - size0 == 1
    now = REGISTRY.counter_labels("nomad.engine.scan_steps")
    moved = {kind: now.get(f"kind={kind}", 0.0)
             - steps0.get(f"kind={kind}", 0.0) for kind in ("run", "padded")}
    assert moved == {"run": 5500, "padded": 2692}
    assert sorted(now) == ["kind=padded", "kind=run"]
    assert "nomad.engine.scan_steps" in metrics
