"""Spread stanzas on the wave (ISSUE 32): per-value spread counts carried
by the flat multi-eval kernel, a spread eval one round a placement.

Held to the exact scan (`select.place` through `PlacementEngine.place`
and the Harness, which always runs `process` alone) on seeded fleets of
64-300 nodes with heterogeneous capacities.  Node, job and eval ids are
pinned, so two runs of one scenario compute the same placements.

Where a comparison of picks allows a tie: the two kernels sum the same
components in means of different length (the scan's five, the round's
four), so two nodes whose scores lie closer than the tie-break noise
(1e-6) may come out in the other order.  On these fleets none did.
"""

import json
import random
import time
import urllib.request

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops.engine import BatchItem, PlacementEngine, PlacementRequest
from nomad_tpu.scheduler import Harness, generic
from nomad_tpu.structs import (
    OP_DISTINCT_PROPERTY,
    OP_EQ,
    Affinity,
    Constraint,
    Spread,
    SpreadTarget,
    UpdateStrategy,
)

NOW = 1_700_000_000.0
TIE = 1e-6
PERCENTS = {2: (60, 40), 3: (50, 30, 20), 5: (40, 25, 15, 10, 10)}


# ----------------------------------------------------------------- fleet

def fleet(n: int, seed: int, k: int = 3):
    """`n` nodes with pinned ids: node i in dc{1 + i % k}, cell
    c{(i // k) % k} (a second attribute with k values, not aligned with
    the first), rack r{i % 20}, capacities drawn from the seed."""
    rng = random.Random(f"fleet:{seed}")
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = f"n{seed:03d}-{i:04d}"
        node.name = f"node-{i}"
        node.datacenter = f"dc{1 + i % k}"
        node.attributes["platform.rack"] = f"r{i % 20}"
        node.meta["cell"] = f"c{(i // k) % k}"
        node.resources.cpu = rng.choice([4000, 8000, 16000])
        node.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(node)
    return nodes


def stanza(attribute: str, values, k: int, weight: int) -> Spread:
    return Spread(attribute=attribute, weight=weight, targets=[
        SpreadTarget(v, p) for v, p in zip(values, PERCENTS[k])])


def service(job_id: str, count: int, k: int = 3, stanzas: int = 1,
            affinity: bool = False, weight: int = 100, update=None):
    """A service job of `count` with `stanzas` spread stanzas over `k`
    values each (0: a plain job), no update stanza unless given."""
    job = mock.job()
    job.id = job.name = job_id
    job.datacenters = [f"dc{d + 1}" for d in range(k)]
    job.update = update
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = 100
    tg.tasks[0].resources.memory_mb = 128
    job.spreads = []
    if stanzas >= 1:
        job.spreads.append(stanza("${node.datacenter}",
                                  job.datacenters, k, weight))
    if stanzas >= 2:
        job.spreads.append(stanza("${meta.cell}",
                                  [f"c{v}" for v in range(k)], k, 50))
    if affinity:
        job.affinities = [Affinity("${attr.platform.rack}", OP_EQ, "r3",
                                   weight=50)]
    return job


def harness(nodes) -> Harness:
    h = Harness()
    for n in nodes:
        h.state.upsert_node(n.copy())
    return h


def solo(h: Harness, job, tag: str) -> None:
    """One eval of `job`, processed alone: the sequential reference."""
    h.state.upsert_job(job)
    ev = mock.eval(job_id=job.id, type="service")
    ev.id = f"eval-{tag}"
    assert h.process("service", ev) is None


def cluster(nodes, eval_batch=8, mesh=False) -> Server:
    s = Server(dev_mode=True, eval_batch=eval_batch, mesh=mesh)
    s.establish_leadership()
    for n in nodes:
        s.register_node(n.copy(), now=NOW)
    return s


def wave(s: Server, jobs, tags) -> None:
    """Registers `jobs` with pinned eval ids and runs the worker until
    the broker is empty: waves of `eval_batch`, in this order."""
    for job, tag in zip(jobs, tags):
        s.state.upsert_job(job)
        ev = mock.eval(job_id=job.id, type="service")
        ev.id = f"eval-{tag}"
        s.apply_eval_update([ev], now=NOW)
    s.process_all(now=NOW)


def placed(snap, job):
    """(name, node id) of the job's live allocations, by name."""
    return sorted((a.name, a.node_id)
                  for a in snap.allocs_by_job(job.namespace, job.id)
                  if not a.terminal_status())


def value_counts(snap, job, attr="datacenter"):
    out = {}
    for _, node_id in placed(snap, job):
        node = snap.node_by_id(node_id)
        v = (node.datacenter if attr == "datacenter" else node.meta["cell"])
        out[v] = out.get(v, 0) + 1
    return out


def solo_counter(rule: str) -> float:
    return REGISTRY.counter_labels("nomad.spread.evals_solo").get(
        f"rule={rule}", 0.0)


# ---------------------------------------------------------------- kernel

def scan_and_round(nodes, job, count, seed, state=None):
    """The same placements of `job` by the exact scan and by the flat
    multi-eval kernel on want-1 rounds, for the same state and seed:
    ([node id], [(best, second) scores]) of the scan, [node id] of the
    rounds."""
    h = state if state is not None else harness(nodes)
    h.state.upsert_job(job)
    snap = h.state.snapshot()
    tg = job.task_groups[0]
    eng = PlacementEngine(mesh=False)
    decisions = eng.place(snap, job, job.task_groups,
                          [PlacementRequest(tg_name=tg.name)] * count,
                          seed=seed)
    top2 = [tuple(m.norm_score for m in d.metric.score_meta_data[:2])
            for d in decisions]
    eng2 = PlacementEngine(mesh=False)
    (bd,) = eng2.place_batch(snap, [BatchItem(job=job, tg=tg, count=count)],
                             seed=[seed])
    assert bd.round_size == 1 and len(bd.metrics) == count
    rounds = [bd.node_ids[p] if p >= 0 else None for p in bd.picks.tolist()]
    return [d.node_id for d in decisions], top2, rounds


def assert_same_picks(scan, top2, rounds):
    """Equal picks, but where the scan's best two lie closer than the
    noise: from there the states may differ, so the comparison ends."""
    for i, (a, b) in enumerate(zip(scan, rounds)):
        if a != b:
            best, second = top2[i]
            assert best - second <= TIE, (
                f"placement {i}: scan {a} ({best} over {second}), "
                f"rounds {b}")
            return False
    return True


def counts_of(nodes, picks, attr):
    by_id = {n.id: n for n in nodes}
    out = {}
    for nid in picks:
        v = (by_id[nid].datacenter if attr == "datacenter"
             else by_id[nid].meta["cell"])
        out[v] = out.get(v, 0) + 1
    return out


@pytest.mark.parametrize("existing", [False, True],
                         ids=["fresh", "scale_up"])
@pytest.mark.parametrize("affinity", [False, True],
                         ids=["plain", "affinity"])
@pytest.mark.parametrize("k", [2, 3, 5])
@pytest.mark.parametrize("stanzas", [1, 2])
def test_rounds_pick_what_the_scan_picks(stanzas, k, affinity, existing):
    n = 64 + 59 * (stanzas + k + affinity + 2 * existing) % 237
    nodes = fleet(n, 100 * stanzas + 10 * k + affinity, k)
    job = service(f"svc-{stanzas}{k}{int(affinity)}", 10, k, stanzas,
                  affinity)
    h = harness(nodes)
    count = 10
    if existing:
        # a scale-up from 10 to 20: ten live allocations give the job
        # existing per-value counts and collisions
        solo(h, job, "first")
        job = job.copy()
        job.task_groups[0].count = 20
    seed = 12345 + n
    scan, top2, rounds = scan_and_round(nodes, job, count, seed, state=h)
    assert None not in scan and len(set(scan)) > 1
    whole = assert_same_picks(scan, top2, rounds)
    for attr in ("datacenter", "cell")[:stanzas]:
        a, b = counts_of(nodes, scan, attr), counts_of(nodes, rounds, attr)
        if whole:
            assert a == b
        else:       # a tie fell the other way: one allocation may move
            assert all(abs(a.get(v, 0) - b.get(v, 0)) <= 1
                       for v in set(a) | set(b))
    # the stanza did its work: the first value holds the most (an
    # affinity may pull elsewhere: with two datacenters rack r3 lies in
    # the second alone)
    dc = counts_of(nodes, rounds, "datacenter")
    assert affinity or dc.get("dc1", 0) == max(dc.values())


def test_two_spread_items_share_usage_not_counts():
    """Items of different jobs in one launch: each starts from its own
    (empty) per-value counts, and sees the usage the one before it
    proposed, as two solo evals in a row see it committed."""
    nodes = fleet(120, 7)
    a, b = service("svc-a", 10), service("svc-b", 10)
    h = harness(nodes)
    solo(h, a, "a")
    solo(h, b, "b")
    snap_h = h.state.snapshot()
    s = cluster(nodes)
    wave(s, [a, b], ["a", "b"])
    snap = s.state.snapshot()
    for job in (a, b):
        assert placed(snap, job) == placed(snap_h, job)
        assert value_counts(snap, job) == {"dc1": 5, "dc2": 3, "dc3": 2}
    # b saw a's usage: bin-packing sends it to the nodes a warmed, and
    # its own anti-affinity counts start empty, so the two jobs overlap
    assert {n for _, n in placed(snap, a)} & {n for _, n in placed(snap, b)}


def test_item_without_a_stanza_scores_bit_for_bit():
    """A plain item between two spread items scores as it does in a
    launch that holds no stanza at all (today's program), from the same
    usage: picks and the reported scores' bits."""
    nodes = fleet(150, 11)
    a, b = service("svc-a", 10), service("svc-b", 12, stanzas=2)
    plain = service("plain", 30, stanzas=0)
    h = harness(nodes)
    solo(h, a, "a")                      # a's usage, committed
    for job in (plain, b):
        h.state.upsert_job(job)
    snap_after_a = h.state.snapshot()
    eng = PlacementEngine(mesh=False)
    (alone,) = eng.place_batch(
        snap_after_a, [BatchItem(plain, plain.task_groups[0], 30)],
        seed=[77])
    built = eng.build_multi_inputs(
        snap_after_a, [BatchItem(plain, plain.task_groups[0], 30)],
        seed=[77])
    assert built["inp"].sp_nodeval is None       # the program it was

    h2 = harness(nodes)
    for job in (a, plain, b):
        h2.state.upsert_job(job)
    eng2 = PlacementEngine(mesh=False)
    items = [BatchItem(j, j.task_groups[0], j.task_groups[0].count)
             for j in (a, plain, b)]
    seed_a = (__import__("zlib").crc32(b"eval-a") & 0xFFFFFFFF) or 1
    bd_a, bd_plain, bd_b = eng2.place_batch(h2.state.snapshot(), items,
                                            seed=[seed_a, 77, 99])
    assert bd_a.round_size == 1 and bd_b.round_size == 1
    assert bd_plain.round_size == 64
    # a rode first with the seed its solo eval drew: same usage
    assert sorted(bd_a.node_ids[p] for p in bd_a.picks) == sorted(
        n for _, n in placed(snap_after_a, a))
    assert bd_plain.picks.tolist() == alone.picks.tolist()
    bits = lambda bd: [np.float32(m.norm_score).view(np.int32).item()
                       for r in bd.metrics for m in r.score_meta_data]
    assert bits(bd_plain) == bits(alone)


# ------------------------------------------ the branch on a round's `want`

def selection_case(case: str):
    """(k_i, score, seed) for one selection: 97 nodes (the prefix is the
    round's 64), or 40 (the prefix is padded) with two of them feasible."""
    n = 40 if case == "two_feasible" else 97
    rng = np.random.default_rng(len(case) * 1009 + n)
    score = rng.uniform(-1.0, 1.0, n).astype(np.float32)
    k_i = rng.integers(0, 5, n).astype(np.int32)
    seed = 0x5EED
    if case.startswith("ties"):
        # four equal maxima, one of them on a node with no intake
        score[[5, 40, 41, 90]] = np.float32(1.25)
        k_i[[5, 41, 90]] = 3
        k_i[40] = 0
        seed = 0 if case == "ties_seed_0" else 2147932103
    elif case in ("two_feasible", "one_feasible", "none_feasible"):
        keep = {"two_feasible": [7, 33], "one_feasible": [60],
                "none_feasible": []}[case]
        mask = np.zeros(n, bool)
        mask[keep] = True
        k_i = np.where(mask, 2, 0).astype(np.int32)
    return k_i, score, seed


def reaches_the_buffer(sel, round_size):
    """What round_step and pack_round_buffer make of one selection: the
    packed fills, the reported rows and the scores' bits, the commit, the
    count placed and the feasible count."""
    import jax.numpy as jnp
    from nomad_tpu.ops import select
    rows_p, cnt_p, sc_p, c_i, placed, k_round = sel
    assert rows_p.shape == cnt_p.shape == sc_p.shape == (round_size,)
    top_sc = sc_p[:select.TOP_K]
    top_rows = jnp.where(top_sc > select.NEG_INF / 2,
                         rows_p[:select.TOP_K], -1)
    top_sc = jnp.where(top_sc > select.NEG_INF / 2, top_sc, 0.0)
    zero = jnp.zeros((1,), jnp.int32)
    fills, meta = select.pack_round_buffer(
        rows_p[None], cnt_p[None], top_rows[None], top_sc[None],
        jnp.sum(k_round > 0).astype(jnp.int32)[None], zero, zero,
        jnp.zeros((1, 4), jnp.int32), placed.astype(jnp.int32)[None])
    return [np.asarray(x) for x in (fills, meta, c_i, placed, k_round)]


@pytest.mark.parametrize("spread_algo", [False, True],
                         ids=["binpack", "spread_algo"])
@pytest.mark.parametrize("case", ["random", "ties_seed_0", "ties_live_seed",
                                  "two_feasible", "one_feasible",
                                  "none_feasible"])
def test_pick_one_round_is_the_water_fill_at_want_1(case, spread_algo):
    """The arg-max selection against the top-64 sort on the same intake,
    scores and noise: every value that reaches the buffer, bit for bit."""
    import jax.numpy as jnp
    from nomad_tpu.ops import select
    k_i, score, seed = selection_case(case)
    noise = select.tiebreak_noise(jnp.uint32(seed), jnp.arange(len(k_i)))
    args = (jnp.asarray(k_i), jnp.asarray(score), noise, jnp.int32(1),
            jnp.asarray(spread_algo), 64)
    want = reaches_the_buffer(select.waterfill_round(*args), 64)
    got = reaches_the_buffer(select.pick_one_round(*args), 64)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    feasible = int((k_i > 0).sum())
    fills, meta = got[0][0], got[1][0]
    assert int(got[3]) == min(feasible, 1) == int(got[2].sum())
    assert (meta[:3] >= 0).sum() == min(feasible, 3)
    if case.startswith("ties"):
        # the first of the equal maxima that has intake, or the noise's
        assert fills[0] // 2048 in (5, 41, 90) and fills[0] % 2048 == 1
        assert seed or fills[0] // 2048 == 5
        assert sorted(meta[:3]) == [5, 41, 90]


def mixed_wave(n_nodes=150, fleet_seed=61):
    """One launch's inputs for a wave that meets every branch: two spread
    items (one with two stanzas), a plain item of ten (a water-fill
    round) and a plain item of one (an arg-max round with no stanza)."""
    nodes = fleet(n_nodes, fleet_seed)
    jobs = [service("mw-a", 10), service("mw-plain", 10, stanzas=0),
            service("mw-one", 1, stanzas=0),
            service("mw-b", 12, stanzas=2, affinity=True)]
    h = harness(nodes)
    for job in jobs:
        h.state.upsert_job(job)
    snap = h.state.snapshot()
    items = [BatchItem(j, j.task_groups[0], j.task_groups[0].count)
             for j in jobs]
    seeds = [4101, 4102, 4103, 4104]
    eng = PlacementEngine(mesh=False)
    built = eng.build_multi_inputs(snap, items, seed=seeds)
    return nodes, jobs, snap, items, seeds, built


def exact_schedule(inp, n_real):
    return inp._replace(round_g=inp.round_g[:n_real],
                        round_want=inp.round_want[:n_real])


@pytest.mark.parametrize("launch", ["exact", "chained", "chained_exact",
                                    "sort_for_arg_max"])
def test_padding_and_arg_max_leave_the_launch_bit_for_bit(launch,
                                                          monkeypatch):
    """The mixed wave padded to its power of two against the same wave at
    its exact round count, through the chained program, and with the sort
    put back where the arg-max is: real rounds' rows and `used` equal."""
    import jax
    import jax.numpy as jnp
    from nomad_tpu.ops import select
    *_, built = mixed_wave()
    inp, rs, n_real = built["inp"], built["rs"], built["rounds"]
    assert n_real == 10 + 1 + 1 + 12 and inp.round_want.shape == (32,)
    assert built["rounds_padded"] == 8
    assert sorted(set(np.asarray(inp.round_want).tolist())) == [0, 1, 10]
    buf, used, _ = select.place_multi_packed_jit(inp, rs)
    buf, used = np.asarray(buf), np.asarray(used)
    # every real round placed what it wanted; a padding round's row is 0
    assert buf[:n_real, rs + 12].tolist() == [1] * 10 + [10, 1] + [1] * 12
    assert not buf[n_real:].any()
    other = exact_schedule(inp, n_real) if "exact" in launch else inp
    if launch.startswith("chained"):
        got = select.place_multi_chained_jit(
            jnp.array(inp.used0), other._replace(used0=None), rs)
    elif launch == "sort_for_arg_max":
        monkeypatch.setattr(select, "pick_one_round", select.waterfill_round)
        got = jax.jit(select.place_multi_packed, static_argnums=1)(other, rs)
    else:
        got = select.place_multi_packed_jit(other, rs)
    assert np.asarray(got[0])[:n_real].tobytes() == buf[:n_real].tobytes()
    assert np.asarray(got[1]).tobytes() == used.tobytes()


def test_mixed_wave_rounds_pick_what_the_scan_picks():
    """What test_rounds_pick_what_the_scan_picks holds for a launch of
    one item, for the first spread item of the mixed wave; and the items
    behind it place whole."""
    nodes, jobs, snap, items, seeds, built = mixed_wave()
    eng = PlacementEngine(mesh=False)
    decisions = eng.place(
        snap, jobs[0], jobs[0].task_groups,
        [PlacementRequest(tg_name=jobs[0].task_groups[0].name)] * 10,
        seed=seeds[0])
    top2 = [tuple(m.norm_score for m in d.metric.score_meta_data[:2])
            for d in decisions]
    bds = PlacementEngine(mesh=False).place_batch(snap, items, seed=seeds)
    assert [bd.round_size for bd in bds] == [1, 64, 64, 1]
    rounds = [bds[0].node_ids[p] for p in bds[0].picks.tolist()]
    assert assert_same_picks([d.node_id for d in decisions], top2, rounds)
    assert all((bd.picks >= 0).all() for bd in bds)
    assert counts_of(nodes, rounds, "datacenter") == {
        "dc1": 5, "dc2": 3, "dc3": 2}


# ----------------------------------------------------------- served path

def mixed_jobs(tag: str, n: int):
    """Spread jobs of two kinds and plain mates.  The plain mates ask
    one allocation: a plain eval of more rides ONE water-fill round (the
    flat kernel's semantics for it, before this issue and after), which
    the Harness's scan places one at a time under its anti-affinity, so
    only at one allocation are the two the same placement."""
    jobs = []
    for i in range(n):
        kind = i % 4
        if kind in (0, 1):
            jobs.append(service(f"{tag}-{i:03d}", 10))
        elif kind == 2:
            jobs.append(service(f"{tag}-{i:03d}", 20, affinity=True,
                                weight=50))
        else:
            jobs.append(service(f"{tag}-{i:03d}", 1, stanzas=0))
    return jobs


def test_served_waves_match_the_harness_and_chain():
    """Three waves of eight through a worker: every job's picks are the
    Harness's for the same evals processed one by one in the same order,
    and the second and third launches start from the usage the one
    before proposed (place_multi_chained)."""
    nodes = fleet(300, 23)
    jobs = mixed_jobs("mix", 24)
    tags = [f"mix-{i:03d}" for i in range(24)]
    h = harness(nodes)
    for job, tag in zip(jobs, tags):
        solo(h, job, tag)
    s = cluster(nodes, eval_batch=8)
    batched0 = REGISTRY.counter_sum("nomad.spread.evals_batched")
    wave(s, jobs, tags)
    snap, snap_h = s.state.snapshot(), h.state.snapshot()
    for job in jobs:
        assert value_counts(snap, job) == value_counts(snap_h, job), job.id
        assert placed(snap, job) == placed(snap_h, job), job.id
    assert REGISTRY.counter_sum("nomad.spread.evals_batched") - batched0 == 18
    ex = s.executor.stats
    assert ex["dispatches"] == 3 and ex["resident_waves"] == 2, ex
    # most jobs land on their targets; bin-packing's warm nodes move a few
    on_target = sum(value_counts(snap, j) == {"dc1": 5, "dc2": 3, "dc3": 2}
                    for j in jobs if j.task_groups[0].count == 10)
    assert on_target >= 9


# -------------------------------------------------------------- admission

def distinct_property_job():
    job = service("svc-dp", 6)
    job.constraints = list(job.constraints) + [
        Constraint("${attr.platform.rack}", OP_DISTINCT_PROPERTY, "2")]
    return job


def targetless_job():
    job = service("svc-even", 9)
    job.spreads = [Spread(attribute="${node.datacenter}", weight=100)]
    return job


ADMISSION = {
    "count": (lambda: service("svc-65", 65), False),
    "targets": (targetless_job, False),
    "mesh": (lambda: service("svc-mesh", 10), None),
    "distinct_property": (distinct_property_job, False),
    "deployment": (lambda: service("svc-upd", 10,
                                   update=UpdateStrategy()), False),
}


@pytest.mark.parametrize("rule", sorted(ADMISSION))
def test_refused_spread_eval_goes_solo_under_its_rule(rule):
    """One case a rule: the eval is counted under the rule that kept it
    off the wave, the exact scan places it whole, and its mates ride."""
    make, mesh = ADMISSION[rule]
    nodes = fleet(90, 31)
    job = make()
    mates = [service(f"mate-{rule}-{i}", 10) for i in range(2)]
    s = cluster(nodes, mesh=mesh)
    assert (s.engine.mesh is not None) == (rule == "mesh")
    before = solo_counter(rule)
    others = REGISTRY.counter_sum("nomad.spread.evals_solo")
    batched = REGISTRY.counter_sum("nomad.spread.evals_batched")
    wave(s, [mates[0], job, mates[1]], [f"{rule}-0", f"{rule}-1",
                                        f"{rule}-2"])
    snap = s.state.snapshot()
    for j in [job] + mates:
        assert len(placed(snap, j)) == j.task_groups[0].count
    if rule == "mesh":
        # the mates carry a stanza too: all three counted, none batched
        assert solo_counter(rule) - before == 3
        assert REGISTRY.counter_sum("nomad.spread.evals_batched") == batched
    else:
        assert solo_counter(rule) - before == 1
        assert REGISTRY.counter_sum("nomad.spread.evals_solo") - others == 1
        assert (REGISTRY.counter_sum("nomad.spread.evals_batched")
                - batched == 2)
    if rule not in ("targets", "distinct_property"):
        assert value_counts(snap, job).get("dc1", 0) == max(
            value_counts(snap, job).values())


def zoned(job_id: str, zone: int, count: int = 70):
    job = service(job_id, count, stanzas=0)
    job.constraints = list(job.constraints) + [
        Constraint("${attr.platform.rack}", OP_EQ, f"r{zone}")]
    return job


def test_a_spread_item_keeps_a_zoned_wave_on_the_flat_kernel():
    """Mates pinned to disjoint racks form lanes (the compact kernel);
    one spread item among them and the wave keeps the flat schedule."""
    nodes = fleet(200, 41)
    mates = [zoned(f"zoned-{z}", z) for z in range(3)]
    spread = service("svc-flat", 10)
    h = harness(nodes)
    for job in mates + [spread]:
        h.state.upsert_job(job)
    snap = h.state.snapshot()
    item = lambda j: BatchItem(j, j.task_groups[0], j.task_groups[0].count)
    eng = PlacementEngine(mesh=False)
    laned = eng.build_multi_inputs(snap, [item(j) for j in mates])
    assert laned["cand_rows"] is not None and laned["n_lanes"] == 3
    flat = eng.build_multi_inputs(
        snap, [item(mates[0]), item(spread), item(mates[1]), item(mates[2])])
    assert flat["cand_rows"] is None and flat["n_lanes"] == 1
    assert flat["inp"].sp_nodeval is not None
    assert flat["item_rs"] == [256, 1, 256, 256]
    assert flat["rounds"] == 3 + 10
    bds = eng.collect_batch(eng.dispatch_batch(
        snap, [item(mates[0]), item(spread), item(mates[1]), item(mates[2])],
        seed=[1, 2, 3, 4]))
    by_id = {n.id: n for n in nodes}
    for z, bd in zip((0, 1, 2), (bds[0], bds[2], bds[3])):
        assert (bd.picks >= 0).all() and len(bd.picks) == 70
        assert {by_id[bd.node_ids[p]].attributes["platform.rack"]
                for p in bd.picks} == {f"r{z}"}
    assert counts_of(nodes, [bds[1].node_ids[p] for p in bds[1].picks],
                     "datacenter") == {"dc1": 5, "dc2": 3, "dc3": 2}


def test_a_sharded_engine_refuses_spread_items_at_the_launch():
    """Admission keeps them out; a caller that hands one in anyway gets
    an error, not a launch that ignores the stanza."""
    nodes = fleet(64, 43)
    job = service("svc-shard", 10)
    h = harness(nodes)
    h.state.upsert_job(job)
    eng = PlacementEngine()
    assert eng.mesh is not None
    with pytest.raises(ValueError, match="spread"):
        eng.build_multi_inputs(h.state.snapshot(), [
            BatchItem(job, job.task_groups[0], 10)])


# ------------------------------------------------- counters and the span

@pytest.fixture(scope="module")
def served():
    """An agent with its HTTP API, 64 nodes, one wave through the
    threaded worker: six spread jobs that share one stanza, two plain."""
    from nomad_tpu.agent import Agent
    nodes = fleet(64, 53)
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        srv.state.upsert_nodes([n.copy() for n in nodes])
        jobs = [service(f"http-{i}", 10 if i % 4 != 3 else 5,
                        stanzas=0 if i % 4 == 3 else 1) for i in range(8)]
        before = {name: REGISTRY.counter_sum(name) for name in (
            "nomad.spread.evals_batched", "nomad.spread.rounds",
            "nomad.spread.evals_solo",
            "nomad.engine.spread_landscapes_built",
            "nomad.engine.spread_landscapes_reused")}
        before["kinds"] = REGISTRY.counter_labels("nomad.engine.rounds")
        srv.stop_scheduling()
        for job in jobs:
            srv.register_job(job)
        srv.start_scheduling()
        deadline = time.monotonic() + 120
        want = sum(j.task_groups[0].count for j in jobs)
        while time.monotonic() < deadline:
            snap = srv.state.snapshot()
            live = sum(1 for j in jobs
                       for a in snap.allocs_by_job(j.namespace, j.id)
                       if not a.terminal_status())
            if live == want:
                break
            time.sleep(0.05)
        yield agent, jobs, before
    finally:
        agent.shutdown()


def http_get(agent, path):
    with urllib.request.urlopen(agent.address + path, timeout=60) as r:
        return json.load(r)


def test_served_wave_moves_the_spread_counters(served):
    agent, jobs, before = served
    moved = {name: REGISTRY.counter_sum(name) - v
             for name, v in before.items() if name != "kinds"}
    assert moved["nomad.spread.evals_batched"] == 6
    assert moved["nomad.spread.rounds"] == 60
    assert moved["nomad.spread.evals_solo"] == 0
    # six evals share the stanza: one walk of the node table
    assert moved["nomad.engine.spread_landscapes_built"] == 1
    assert moved["nomad.engine.spread_landscapes_reused"] == 5
    names = json.dumps(http_get(agent, "/v1/metrics"))
    for series in ("nomad.spread.evals_batched", "nomad.spread.rounds",
                   "nomad.engine.spread_landscapes_built",
                   "nomad.engine.spread_landscapes_reused"):
        assert series in names
    snap = agent.server.state.snapshot()
    for job in jobs:
        if job.spreads:
            assert value_counts(snap, job) == {"dc1": 5, "dc2": 3, "dc3": 2}


def test_spread_lower_lies_inside_dispatch(served):
    agent, _, _ = served
    timers = agent.server.stage_timers
    lowers = [(a, b) for _, a, b in timers.intervals("spread_lower")]
    dispatches = [(a, b) for _, a, b in timers.intervals("dispatch")]
    assert lowers and len(lowers) <= len(dispatches)
    assert all(any(lo <= a and b <= hi for lo, hi in dispatches)
               for a, b in lowers)


def test_wave_record_carries_its_real_rounds(served):
    from nomad_tpu.core.flightrec import FLIGHT
    waves = [w for w in FLIGHT.snapshot()["Waves"]
             if w.get("items") == 8 and "rounds" in w]
    assert any(w["rounds"] == 6 * 10 + 2 for w in waves), waves[-3:]


def test_served_wave_counts_its_rounds_by_kind(served):
    """The schedule's own counts: sixty `want`-1 rounds of the six spread
    evals, one water-fill round each of the two plain evals of five, and
    the two rounds that pad 62 to 64; the record carries the padding."""
    from nomad_tpu.core.flightrec import FLIGHT
    agent, _, before = served
    now = REGISTRY.counter_labels("nomad.engine.rounds")
    moved = {kind: now.get(f"kind={kind}", 0.0)
             - before["kinds"].get(f"kind={kind}", 0.0)
             for kind in ("pick_one", "fill", "padded")}
    assert moved == {"pick_one": 60, "fill": 2, "padded": 2}
    assert sorted(now) == ["kind=fill", "kind=padded", "kind=pick_one"]
    assert "nomad.engine.rounds" in json.dumps(http_get(agent, "/v1/metrics"))
    waves = [w for w in FLIGHT.snapshot()["Waves"]
             if w.get("items") == 8 and w.get("rounds") == 62]
    assert waves and all(w["rounds_padded"] == 2 for w in waves), waves[-3:]


def test_served_mixed_drain_launches_the_programs_it_did():
    """Three waves of eight on a fleet no other test builds: the flat
    launch is keyed as it was (round bucket, padded nodes, one lane) and
    compiles one fresh and one chained program, each once."""
    from nomad_tpu.ops import engine, select
    nodes = fleet(310, 71)
    jobs = mixed_jobs("keys", 24)
    seen0 = set(engine._KERNEL_SHAPES_SEEN)
    sizes0 = (select.place_multi_packed_jit._cache_size(),
              select.place_multi_chained_jit._cache_size())
    s = cluster(nodes, eval_batch=8)
    wave(s, jobs, [f"keys-{i:03d}" for i in range(24)])
    snap = s.state.snapshot()
    assert all(len(placed(snap, j)) == j.task_groups[0].count for j in jobs)
    assert engine._KERNEL_SHAPES_SEEN - seen0 == {
        ("multi", (64, 310, 1)), ("multi_chained", (64, 310, 1))}
    assert (select.place_multi_packed_jit._cache_size() - sizes0[0],
            select.place_multi_chained_jit._cache_size() - sizes0[1]) == (1, 1)


# ------------------------------------------------ the benchmark's own files

def spread50k():
    from benchmark.loader import load_json, load_module
    return load_json("configs", "spread50k"), load_module("configs",
                                                          "spread50k")


@pytest.fixture(scope="module")
def rehearsal():
    """A rehearsal cycle of spread50k (600 nodes, 16 jobs) through the
    Harness, one eval at a time in index order."""
    from benchmark.loader import load_json
    from nomad_tpu.structs import codec
    cfg, mod = spread50k()
    cfg = dict(cfg, **cfg["rehearse"])
    per_cycle = load_json("traffic", "drain256-mixed")["rehearse"][
        "jobs_per_cycle"]
    nodes, table = mod.build_fleet(cfg, 2147483659)
    jobs = [mod.make_job(cfg, i) for i in range(per_cycle)]
    h = harness(nodes)
    by_job = {}
    for i, wire in enumerate(jobs):
        job = codec.decode(type(mock.job()), wire)
        solo(h, job, f"reh-{i:03d}")
        by_job[wire["ID"]] = [n for _, n in placed(h.state.snapshot(), job)]
    return cfg, mod, table, jobs, by_job


def test_reference_agrees_with_the_harness(rehearsal):
    cfg, mod, table, jobs, by_job = rehearsal
    want = mod.reference_counts(cfg, table, jobs, by_job)
    assert [r is None for r in want] == [i % 4 == 3 for i in range(16)]
    for job, ref in zip(jobs, want):
        if ref is None:
            continue
        got = {}
        for nid in by_job[job["ID"]]:
            got[table[nid][1]] = got.get(table[nid][1], 0) + 1
        # equal but for a tie between two datacenters (none here)
        assert {d: got.get(d, 0) for d in ref} == ref, job["ID"]
    assert mod.check(cfg, table, jobs, by_job) == []


def controls():
    """scripts/spread_controls.py: the reference's rule with one fault
    planted, as a plain scheduler."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).parent.parent / "scripts" / (
        "spread_controls.py")
    spec = importlib.util.spec_from_file_location("spread_controls", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_free_run_is_the_harness_too(rehearsal):
    """At the rehearsal size nothing piles up, so the reference's own
    free run of the cycle places every job as the Harness did."""
    cfg, mod, table, jobs, by_job = rehearsal
    assert mod.reference_counts(cfg, table, jobs) == mod.reference_counts(
        cfg, table, jobs, by_job)


@pytest.mark.parametrize("doctor", ["one_dc", "even_split", "next_ten",
                                    "round4", "frozen", "even", "ignored"])
def test_check_fails_jobs_that_do_not_spread(rehearsal, doctor):
    """What the tolerances must catch.  Doctored placements: a whole eval
    in one datacenter (the per-job limit); every job split 4 / 3 / 3 or on
    the next ten nodes (one allocation off 5 / 3 / 2, inside the per-job
    limit: the summed shares catch it).  Faulty schedulers
    (scripts/spread_controls.py): the boost refreshed every fourth
    placement, frozen over a job, aimed at an even split, left out."""
    cfg, mod, table, jobs, by_job = rehearsal
    by_dc = {}
    for nid, v in sorted(table.items(), key=lambda kv: kv[1][0]):
        by_dc.setdefault(v[1], []).append(nid)
    in_order = [nid for dc3 in zip(*by_dc.values()) for nid in dc3]
    doctored = dict(by_job)
    tens = [j for j in jobs if j["Spreads"]
            and j["TaskGroups"][0]["Count"] == 10]
    if doctor == "one_dc":
        doctored[tens[2]["ID"]] = by_dc["dc1"][100:110]
    elif doctor in ("even_split", "next_ten"):
        for k, job in enumerate(tens):
            split = [3, 3, 3]
            split[k % 3] = 4
            doctored[job["ID"]] = (
                in_order[300 + 10 * k:310 + 10 * k] if doctor == "next_ten"
                else [nid for dc, take in zip(by_dc, split)
                      for nid in by_dc[dc][100 + 10 * k:100 + 10 * k + take]])
    else:
        doctored = controls().schedule(cfg, mod, table, jobs, doctor)
    failures = mod.check(cfg, table, jobs, doctored)
    want = ("per-datacenter counts" if doctor == "one_dc"
            else "summed per-datacenter shares")
    assert any(want in f for f in failures), failures


@pytest.mark.parametrize("path", ["scan", "wave"])
def test_the_boost_counts_the_placement_being_scored(path):
    """Where upstream's boost, (expected - (placed + 1)) / expected, and
    one that reads the counts a placement late part ways: eight dc1 nodes
    warmed to a bin-pack score ~0.4 over the rest.  Upstream's rule gives
    a job of ten a seventh allocation there (7 / 2 / 1), the late one
    stops at six (6 / 2 / 2).  The program, on the scan and on the wave,
    places as the plain reference does."""
    from nomad_tpu.structs import codec
    cfg, mod = spread50k()
    cfg = dict(cfg, **cfg["rehearse"])
    nodes, table = mod.build_fleet(cfg, 2147483659)
    warm = dict(mod.make_job(cfg, 3), ID="warm-dc1", Datacenters=["dc1"])
    warm["TaskGroups"] = [dict(warm["TaskGroups"][0], Count=8)]
    task = dict(warm["TaskGroups"][0]["Tasks"][0])
    task["Resources"] = dict(task["Resources"], CPU=1000, MemoryMB=2000)
    warm["TaskGroups"][0]["Tasks"] = [task]
    jobs = [warm, mod.make_job(cfg, 0)]
    a, b = (codec.decode(type(mock.job()), wire) for wire in jobs)
    if path == "scan":
        h = harness(nodes)
        solo(h, a, "warm")
        solo(h, b, "svc")
        snap = h.state.snapshot()
    else:
        s = cluster(nodes)
        wave(s, [a], ["warm"])
        batched0 = REGISTRY.counter_sum("nomad.spread.evals_batched")
        # a mate behind it, so that the two form a wave
        wave(s, [b, service("mate", 1, stanzas=0)], ["svc", "mate"])
        assert REGISTRY.counter_sum(
            "nomad.spread.evals_batched") == batched0 + 1
        snap = s.state.snapshot()
    by_job = {w["ID"]: [n for _, n in placed(snap, j)]
              for w, j in zip(jobs, (a, b))}
    assert len(set(by_job["warm-dc1"])) == 8
    ((gap, _, got, ref),), _ = mod.gaps(cfg, table, jobs, by_job)
    assert (gap, got, ref) == (0, [7, 2, 1], [7, 2, 1])
    late = controls().schedule(cfg, mod, table, jobs, "late")
    assert sorted(table[n][1] for n in late[jobs[1]["ID"]]) == (
        ["dc1"] * 6 + ["dc2"] * 2 + ["dc3"] * 2)


def test_the_scorers_count_the_placement_being_scored():
    """spread.go `usedCount += 1`, rank.go `collisions+1`."""
    import jax.numpy as jnp
    from nomad_tpu.ops.scoring import job_anti_affinity, spread_boost
    np.testing.assert_allclose(
        job_anti_affinity(jnp.array([1, 3], jnp.int32), 10.0), [-0.2, -0.4],
        rtol=1e-6)
    nodeval = jnp.array([[0, 1, 2, -1]], jnp.int32)
    got = spread_boost(nodeval, jnp.array([50.0]),
                       jnp.array([[5.0, 3.0, 2.0]]),
                       jnp.array([[4.0, 3.0, 0.0]]))
    # one short of its target scores 0; at it, -1 / expected; not a target
    np.testing.assert_allclose(got, [0.0, -0.5 / 3.0, 0.25, 0.0], rtol=1e-6)


def test_spread_cost_counts_a_pass_a_placement():
    from benchmark import multi_cost, spread_cost
    cfg, _ = spread50k()
    rounds = spread_cost.rounds_per_wave(cfg["job_mix"], 64)
    assert rounds == 16 * (10 + 10 + 20) + 16 == 656
    flat = multi_cost.flat_launch(50_000, rounds)
    cost = spread_cost.spread_launch(50_000, rounds)
    assert cost["bytes"] == flat["bytes"] + 50_000 * 4
    assert cost["ops"] == rounds * 50_000 * (64 + 8)
