"""Evals of several task groups on the wave.

A fresh eval whose placements name two or more groups rides the batched
device path: one launch item a group, the items of one eval consecutive
and sharing ONE job-count row, so each group's anti-affinity counts the
picks its sibling groups made earlier in the launch, as the solo scan
counts the job's; one plan an eval.  Held here to the admission rules
(counted in `nomad.groups.evals_solo`), to the configuration's plain
reference (`groups50k.reference_ok`) where capacity binds, to a numpy
replay of the flat kernel's round rule, to the single-group waves as
they were, to the applier's full re-check, and to the configuration
`groups50k` with its checker, its readers and its cell rehearsed on the
CPU.  Node, job and eval ids are pinned.
"""

import copy
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.loader import load_json, load_module  # noqa: E402
from nomad_tpu import mock  # noqa: E402
from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue  # noqa: E402
from nomad_tpu.core.server import Server  # noqa: E402
from nomad_tpu.core.telemetry import REGISTRY  # noqa: E402
from nomad_tpu.core.wavepipe import WavePipeline  # noqa: E402
from nomad_tpu.ops.engine import (BatchItem, PlacementEngine,  # noqa: E402
                                  eval_spans)
from nomad_tpu.scheduler import Harness, generic  # noqa: E402
from nomad_tpu.state import StateStore  # noqa: E402
from nomad_tpu.structs import (  # noqa: E402
    OP_DISTINCT_PROPERTY,
    AllocBlock,
    AllocMetric,
    Allocation,
    Constraint,
    NetworkResource,
    Plan,
    Port,
    RequestedDevice,
    Resources,
    Spread,
    SpreadTarget,
    codec,
)
from test_batch_evals import build_zoned_cluster, zoned_items  # noqa: E402

NOW = 1_700_000_000.0
CELL = "groups50k-drain"
# the flat kernel's tie-break noise is under 1e-6 (select.tiebreak_noise);
# two candidates closer than this may fall either way
TIE = 2e-6


# ----------------------------------------------------------------- fleet

def fleet(n: int, cpu=4000, mem=8192, tag: str = "gw"):
    """`n` mock nodes in dc1; `cpu` a number or one a node."""
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = f"{tag}-node-{i:04d}"
        node.name = f"node-{i}"
        node.resources.cpu = cpu[i] if isinstance(cpu, (list, tuple)) else cpu
        node.resources.memory_mb = mem
        nodes.append(node)
    return nodes


def grouped(job_id: str, counts, cpu: int = 100, mem: int = 64):
    """A service job without an update stanza, one group a count (g0,
    g1, ...), every group one task of the same ask."""
    job = mock.job()
    job.id = job.name = job_id
    job.update = None
    base = job.task_groups[0]
    base.tasks[0].resources.cpu = cpu
    base.tasks[0].resources.memory_mb = mem
    groups = []
    for k, count in enumerate(counts):
        tg = copy.deepcopy(base)
        tg.name = f"g{k}"
        tg.count = count
        groups.append(tg)
    job.task_groups = groups
    return job


def cluster(nodes, eval_batch=8, mesh=False) -> Server:
    s = Server(dev_mode=True, eval_batch=eval_batch, mesh=mesh)
    s.establish_leadership()
    for n in nodes:
        s.register_node(n.copy(), now=NOW)
    return s


def wave(s: Server, jobs) -> None:
    """Registers `jobs` with pinned eval ids and runs the worker until
    the broker is empty: waves of `eval_batch`, in this order."""
    for job in jobs:
        s.state.upsert_job(job)
        ev = mock.eval(job_id=job.id, type=job.type)
        ev.id = f"eval-{job.id}"
        s.apply_eval_update([ev], now=NOW)
    s.process_all(now=NOW)


def live(snap, job):
    return [a for a in snap.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


def by_group(snap, job):
    out = {}
    for a in live(snap, job):
        out[a.task_group] = out.get(a.task_group, 0) + 1
    return out


def fleet_table(nodes):
    """groups50k's fleet table: {node id: (index, dc, cpu, memory) net of
    reserved}."""
    return {n.id: (i, n.datacenter, n.resources.cpu - n.reserved.cpu,
                   n.resources.memory_mb - n.reserved.memory_mb)
            for i, n in enumerate(nodes)}


def groups50k():
    cfg = load_json("configs", "groups50k")
    return dict(cfg, **cfg["rehearse"]), load_module("configs", "groups50k")


def grew(name, before):
    after = REGISTRY.counter_labels(name)
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


# ------------------------------------------------------------- admission

def prepare(job, engine=None):
    h = Harness()
    for n in fleet(8, tag="ad"):
        h.state.upsert_node(n)
    h.state.upsert_job(job)
    sched = generic.GenericScheduler(
        h.state.snapshot(), h, is_batch=False,
        engine=engine or PlacementEngine(mesh=False), now=NOW)
    return sched.prepare_batch(mock.eval(job_id=job.id, type=job.type))


def test_a_three_group_fresh_eval_rides_a_part_a_group():
    """Rows for a group under 64, a block for one of 64 or more, in the
    job's group order; a one-group eval is a list of one part."""
    prep = prepare(grouped("ride", (3, 70, 5)))
    assert [(p.tg.name, p.count) for p in prep.parts] == [
        ("g0", 3), ("g1", 70), ("g2", 5)]
    assert [p.block is not None for p in prep.parts] == [False, True, False]
    assert [len(p.places or ()) for p in prep.parts] == [3, 0, 5]
    assert all(pl.tg is p.tg for p in prep.parts for pl in p.places or ())
    (one,) = prepare(grouped("one", (4,))).parts
    assert (one.tg.name, one.count, len(one.places)) == ("g0", 4, 4)


def _with(case):
    job = grouped(f"rf-{case}", (2, 3, 4))
    tgs = job.task_groups
    if case == "spread":
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                              targets=(SpreadTarget("dc1", 100),))]
    elif case == "device":
        tgs[1].tasks[0].resources.devices = [RequestedDevice(name="gpu")]
    elif case == "ports":
        tgs[2].networks = [NetworkResource(dynamic_ports=[Port(label="http")])]
    elif case == "distinct_property":
        tgs[0].constraints.append(Constraint(
            "${node.datacenter}", OP_DISTINCT_PROPERTY, "1"))
    return job


@pytest.mark.parametrize("case,rule", [
    ("none", ""), ("spread", "groups_state"), ("device", "groups_state"),
    ("ports", "groups_state"), ("distinct_property", "distinct_property"),
    ("off", "off")])
def test_refusals_are_named_and_counted(case, rule, monkeypatch):
    if case == "off":
        monkeypatch.setattr(generic, "MULTI_GROUP_WAVE", False)
    before = REGISTRY.counter_labels("nomad.groups.evals_solo")
    prep = prepare(_with(case))
    assert (prep is None) == bool(rule)
    assert grew("nomad.groups.evals_solo", before) == (
        {f"rule={rule}": 1} if rule else {})


def test_a_refused_eval_still_places_every_group_on_the_solo_path():
    s = cluster(fleet(12, tag="so"))
    jobs = [_with("ports"), grouped("so-a", (2, 2)), grouped("so-b", (1, 3))]
    before = REGISTRY.counter_labels("nomad.groups.evals_solo")
    batched = REGISTRY.counter_sum("nomad.groups.evals_batched")
    wave(s, jobs)
    snap = s.state.snapshot()
    assert by_group(snap, jobs[0]) == {"g0": 2, "g1": 3, "g2": 4}
    assert by_group(snap, jobs[1]) == {"g0": 2, "g1": 2}
    assert by_group(snap, jobs[2]) == {"g0": 1, "g1": 3}
    assert grew("nomad.groups.evals_solo", before) == {
        "rule=groups_state": 1}
    assert REGISTRY.counter_sum("nomad.groups.evals_batched") == batched + 2
    s.shutdown()


# -------------------------------------------------------------- coupling

@pytest.mark.parametrize("path", ["wave", "solo"])
def test_groups_of_one_eval_and_a_mate_share_capacity(path, monkeypatch):
    """Two groups of one eval and a one-group mate on 20 nodes that hold
    exactly two allocations each: every placement the plain reference
    finds room for is made, no node goes over, nothing is refuted.  The
    wave and the solo path (MULTI_GROUP_WAVE off) alike."""
    _, mod = groups50k()
    if path == "solo":
        monkeypatch.setattr(generic, "MULTI_GROUP_WAVE", False)
    nodes = fleet(20, cpu=1100, tag="cp")
    s = cluster(nodes)
    a = grouped("cp-a", (10, 10), cpu=500, mem=64)
    b = grouped("cp-b", (20,), cpu=500, mem=64)
    refuted = s.plan_applier.stats["plans_refuted"]
    batched = REGISTRY.counter_sum("nomad.groups.evals_batched")
    wave(s, [a, b])
    snap = s.state.snapshot()
    placed = {j.id: [x.node_id for x in live(snap, j)] for j in (a, b)}
    assert mod.reference_ok({}, fleet_table(nodes),
                            [codec.encode(a), codec.encode(b)], placed) == []
    assert by_group(snap, a) == {"g0": 10, "g1": 10}
    assert by_group(snap, b) == {"g0": 20}
    for j in (a, b):
        ev = snap.eval_by_id(f"eval-{j.id}")
        assert ev.status == "complete" and not ev.failed_tg_allocs
        assert not any(ev.queued_allocations.values())
    assert s.plan_applier.stats["plans_refuted"] == refuted
    assert REGISTRY.counter_sum("nomad.groups.evals_batched") == batched + (
        path == "wave")
    s.shutdown()


def test_a_group_short_of_room_fails_by_group():
    """Room for 40 allocations, 44 asked by one job's two groups: the
    first group is placed whole, the second takes the 36 left, and the
    eval names the short group alone, its queued count the rest."""
    s = cluster(fleet(10, cpu=1100, tag="sh"))
    job = grouped("sh", (4, 40), cpu=250, mem=64)
    wave(s, [job, grouped("sh-mate", (1, 1), cpu=10, mem=8)])
    snap = s.state.snapshot()
    assert by_group(snap, job) == {"g0": 4, "g1": 36}
    ev = snap.eval_by_id("eval-sh")
    assert set(ev.failed_tg_allocs) == {"g1"}
    assert ev.queued_allocations == {"g0": 0, "g1": 4}
    s.shutdown()


# ------------------------------------------------- the numpy round replay

def _noise(seed: int, rows: np.ndarray) -> np.ndarray:
    """select.tiebreak_noise in numpy."""
    m = np.uint32(0xFFFFFFFF)
    x = ((rows.astype(np.uint64) * 0x9E3779B1) & m) ^ (
        (np.uint64(seed) * 0x85EBCA77) & m)
    x = ((x ^ (x >> 16)) * 0x7FEB352D) & m
    x = ((x ^ (x >> 15)) * 0x846CA68B) & m
    x = x ^ (x >> 16)
    return (x.astype(np.float32) * np.float32(1e-6 / 2**32)).astype(
        np.float32) * np.float32(seed != 0)


def _scores(cap, used, req, jc, desired):
    """A round's score a node as select.py states it: the bin-pack fit
    of one more allocation, and the job anti-affinity -(count + 1) /
    desired where the JOB (any group) holds the node, their mean."""
    capf = cap.astype(np.float32)
    total = (used + req).astype(np.float32)
    free = [np.float32(1.0) - np.minimum(total[:, d] / np.maximum(
        capf[:, d], np.float32(1.0)), np.float32(1.0)) for d in (0, 1)]
    fit = np.float32(20.0) - (np.power(np.float32(10.0), free[0])
                              + np.power(np.float32(10.0), free[1]))
    bp = np.clip(fit, 0, 18).astype(np.float32) / np.float32(18.0)
    held = jc > 0
    aa = -(jc.astype(np.float32) + 1) / np.float32(max(desired, 1))
    return np.where(held, (bp + aa) / 2, bp).astype(np.float32)


def replay(eng, snap, items, seeds, bds, round_size=64):
    """Each round in schedule order from the state it met (the kernel's
    own earlier picks, so one tie broken the other way does not carry):
    intake and score as select.py's round rule states them, the job's
    count a node summed over its groups' earlier picks.  The kernel's
    fill is checked against the replay's node for node where no two
    candidates lie within TIE of each other, and by the scores of the
    placements it made elsewhere.  Returns the rounds checked node for
    node."""
    t = eng.packer.update(snap)
    n = t.n
    cap = t.cap[:n].astype(np.int64)
    used = t.used[:n].astype(np.int64).copy()
    counts = {}
    exact = 0
    for it, seed, bd in zip(items, seeds, bds):
        jc = counts.setdefault(it.job.id, np.zeros(n, np.int64))
        req = eng.packer.lower_task_groups(
            it.job, [it.tg], snapshot=snap).req[0].astype(np.int64)
        free = cap - used
        per_dim = np.where(req > 0, free // np.maximum(req, 1), round_size)
        k = np.clip(per_dim.min(axis=1), 0, round_size)
        k[(free < 0).any(axis=1)] = 0
        score = _scores(cap, used, req, jc, it.tg.count)
        order = [r for r in np.argsort(-(score + _noise(seed, np.arange(n))),
                                       kind="stable") if k[r] > 0]
        mine = np.zeros(n, np.int64)
        left = it.count
        for r in order:
            take = min(left, k[r])
            mine[r] = take
            left -= take
            if not left:
                break
        theirs = np.bincount(bd.picks[bd.picks >= 0], minlength=n)
        assert theirs.sum() == mine.sum(), (it.tg.name, theirs, mine)
        np.testing.assert_allclose(
            np.sort(np.repeat(score, theirs)), np.sort(np.repeat(score, mine)),
            atol=TIE, err_msg=f"{it.job.id}/{it.tg.name}: picked scores")
        top = np.sort(score[order][:int((mine > 0).sum()) + 1])
        if np.all(np.diff(top) > TIE):
            assert np.array_equal(theirs, mine), (it.tg.name, theirs, mine)
            exact += 1
        used += theirs[:, None] * req[None, :]
        jc += theirs
    return exact


def _replay_case():
    """Eight nodes of distinct sizes (so a round's candidates do not
    tie); a job of three groups and a mate of one."""
    h = Harness()
    nodes = fleet(8, cpu=[4000 + 100 * i for i in range(8)], tag="rp")
    for nd in nodes:
        h.state.upsert_node(nd)
    job = grouped("rp-job", (3, 2, 1), cpu=500, mem=256)
    mate = grouped("rp-mate", (4,), cpu=500, mem=256)
    items = [BatchItem(job=job, tg=tg, count=tg.count, eval_pos=0)
             for tg in job.task_groups]
    items.append(BatchItem(job=mate, tg=mate.task_groups[0], count=4,
                           eval_pos=1))
    return h, items, [11, 11, 11, 12]


def test_the_shared_job_row_against_a_numpy_replay():
    """The groups of one eval each avoid the nodes the groups before
    them took (their count row is the job's), where bin-packing alone
    would stack them on the fullest node; the mate, a job of its own,
    does stack there."""
    h, items, seeds = _replay_case()
    eng = PlacementEngine(mesh=False)
    snap = h.state.snapshot()
    built = eng.build_multi_inputs(snap, items, seed=seeds)
    assert built["job_rows"] == 2 and built["cand_rows"] is None
    assert np.asarray(built["inp"].g_job)[:4].tolist() == [0, 0, 0, 1]
    assert built["inp"].job_count0.shape[0] == 2
    bds = eng.collect_batch(eng.dispatch_batch(snap, items, seed=seeds))
    assert replay(eng, snap, items, seeds, bds) == 4
    nodes = [set(bd.picks.tolist()) for bd in bds]
    assert not (nodes[0] & nodes[1]) and not (nodes[2] & (nodes[0] | nodes[1]))
    assert nodes[3] & (nodes[0] | nodes[1] | nodes[2])


def test_the_shared_row_holds_on_a_sharded_engine():
    """The sharded flat kernel reads `g_job` the same way: the same wave
    on the tests' 8 virtual devices picks node for node what one device
    picks, and a multi-group eval is admitted there."""
    h, items, seeds = _replay_case()
    snap = h.state.snapshot()
    sharded = PlacementEngine()
    assert sharded.mesh is not None
    one = PlacementEngine(mesh=False).place_batch(snap, items, seed=seeds)
    many = sharded.place_batch(snap, items, seed=seeds)
    for a, b in zip(one, many):
        assert np.array_equal(a.picks, b.picks)
    assert prepare(grouped("mesh-ride", (2, 3)), engine=sharded) is not None


# -------------------------------------- single-group waves as they were

@pytest.mark.parametrize("shape", ["flat", "laned"])
def test_single_group_waves_are_the_waves_they_were(shape):
    """Items that name their evals (the worker's, now) against items
    that do not (the form every single-group wave took before): the same
    inputs bit for bit — the identity `g_job`, a `[G, N]` count table,
    the same rounds — the same rows and the same final usage."""
    if shape == "laned":
        h, _ = build_zoned_cluster()
        plain = zoned_items(h, 10, 30)
    else:
        h = Harness()
        for nd in fleet(40, tag="sg"):
            h.state.upsert_node(nd)
        jobs = [grouped(f"sg-{i}", (3 + i,)) for i in range(6)]
        plain = [BatchItem(job=j, tg=j.task_groups[0], count=3 + i)
                 for i, j in enumerate(jobs)]
    named = [BatchItem(job=it.job, tg=it.tg, count=it.count, eval_pos=i)
             for i, it in enumerate(plain)]
    seeds = [7 + i for i in range(len(plain))]
    snap = h.state.snapshot()
    eng = PlacementEngine(mesh=False)
    a = eng.build_multi_inputs(snap, plain, seed=seeds)
    b = eng.build_multi_inputs(snap, named, seed=seeds)
    g_pad = a["inp"].req.shape[0]
    assert (a["cand_rows"] is None) == (shape == "flat")
    for built in (a, b):
        assert built["job_rows"] == len(plain)
        if shape == "flat":
            assert np.array_equal(np.asarray(built["inp"].g_job),
                                  np.arange(g_pad))
    for field in a["inp"]._fields:
        x, y = getattr(a["inp"], field), getattr(b["inp"], field)
        if x is None:
            assert y is None, field
            continue
        assert np.array_equal(np.asarray(x), np.asarray(y)), field
    if shape == "flat":
        assert a["inp"].job_count0.shape == (g_pad, a["npad"])
    assert a["spans"] == b["spans"] and a["item_rs"] == b["item_rs"]
    pa = eng.dispatch_batch(snap, plain, seed=seeds)
    pb = eng.dispatch_batch(snap, named, seed=seeds)
    assert np.array_equal(np.asarray(pa["buf"]), np.asarray(pb["buf"]))
    assert np.array_equal(np.asarray(pa["used"]), np.asarray(pb["used"]))
    for x, y in zip(eng.collect_batch(pa), eng.collect_batch(pb)):
        assert np.array_equal(x.picks, y.picks)


def test_the_pipeline_folds_answers_by_eval():
    """`collect` answers an eval a list, one decision a group, where the
    items name their evals; a wave record counts evals under `items` and
    launch items under `groups`."""
    from nomad_tpu.core.flightrec import FLIGHT
    h, items, seeds = _replay_case()
    pipe = WavePipeline(PlacementEngine(mesh=False))
    assert eval_spans(items) == [(0, 3), (3, 4)]
    w = pipe.dispatch(h.state.snapshot(), items, seed=seeds)
    got = pipe.collect(w)
    assert [len(x) for x in got] == [3, 1]
    assert [bd.tg_name for bd in got[0]] == ["g0", "g1", "g2"]
    rec = next(r for r in FLIGHT.waves() if r["Wave"] == w.wave)
    assert (rec["items"], rec["groups"], rec["job_rows"]) == (2, 4, 2)
    # items that name no eval: one decision an item, as before
    plain = [BatchItem(job=it.job, tg=it.tg, count=it.count)
             for it in items]
    w2 = pipe.dispatch(h.state.snapshot(), plain, seed=seeds)
    assert len(pipe.collect(w2)) == 4
    rec2 = next(r for r in FLIGHT.waves() if r["Wave"] == w2.wave)
    assert (rec2["items"], rec2["groups"], rec2["job_rows"]) == (4, 4, 4)


# ------------------------------------------------------------- applier

def _two_groups_on_one_node(extra_cpu: int, form: str):
    """A store of one node (3,900 MHz usable) and a plan of one job's
    two groups there: group a 3 x 1,000 MHz as a block, group b one of
    `extra_cpu` as a row (`form` "rows") or as a second block."""
    node = mock.node()
    node.id = "ap-node"
    state = StateStore()
    state.upsert_nodes([node])
    job = grouped("ap", (3, 1), cpu=1000, mem=64)
    state.upsert_job(job)

    def block(tg, k, cpu):
        return AllocBlock(
            id=f"block-{tg.name}",
            template=Allocation(
                namespace=job.namespace, eval_id="eval-ap", job_id=job.id,
                job=job, task_group=tg.name,
                resources=Resources(cpu=cpu, memory_mb=64),
                desired_status="run", client_status="pending"),
            ids=[f"ap-{tg.name}-{i}" for i in range(k)],
            name_prefix=f"{job.id}.{tg.name}[", indexes=list(range(k)),
            picks=np.zeros(k, np.int32), node_table=[node.id],
            metrics=[AllocMetric()], round_size=k)

    a, b = job.task_groups
    plan = Plan(eval_id="eval-ap", job=job)
    plan.alloc_blocks.append(block(a, 3, 1000))
    if form == "rows":
        plan.append_alloc(Allocation(
            id="ap-b-0", namespace=job.namespace, eval_id="eval-ap",
            job_id=job.id, job=job, task_group=b.name, node_id=node.id,
            name=f"{job.id}.{b.name}[0]",
            resources=Resources(cpu=extra_cpu, memory_mb=64),
            desired_status="run", client_status="pending"))
    else:
        plan.alloc_blocks.append(block(b, 1, extra_cpu))
    return state, plan, node


@pytest.mark.parametrize("form", ["rows", "blocks"])
@pytest.mark.parametrize("extra,fits", [(800, True), (1000, False)])
def test_the_full_recheck_sums_both_groups_on_a_node(form, extra, fits):
    """Each group alone fits the node; together they fit only where the
    sum does.  The full re-check (a plan off the skip-fit path) sums
    them: the node is refuted whole, and each group's loss is its own."""
    state, plan, node = _two_groups_on_one_node(extra, form)
    result = PlanApplier(state, PlanQueue()).evaluate_plan(
        plan, skip_fit=False)
    full, expected, actual = result.full_commit(plan)
    assert expected == 4
    if fits:
        assert full and not result.refuted_nodes
        assert result.missing_by_group(plan) == {}
    else:
        assert result.refuted_nodes == [node.id] and actual == 0
        assert result.missing_by_group(plan) == {"g0": 3, "g1": 1}


# ------------------------------------------------------ the configuration

def test_configuration_states_its_mix():
    cfg, mod = groups50k()
    full = load_json("configs", "groups50k")
    sums = [sum(m["counts"]) for m in full["job_mix"]]
    assert sums == [14, 21, 108, 7]
    assert sum(sums) / len(sums) == full["count_per_job"] == 37.5
    traffic = load_json("traffic", "drain256-purge")
    assert traffic["jobs_per_cycle"] * full["count_per_job"] == 9600
    assert traffic["rehearse"]["jobs_per_cycle"] * full["count_per_job"] == 600
    jobs = [mod.make_job(cfg, i) for i in range(8)]
    assert [[tg["Name"] for tg in j["TaskGroups"]] for j in jobs] == [
        ["web", "api", "worker"]] * 8
    assert [[tg["Count"] for tg in j["TaskGroups"]] for j in jobs[:4]] == [
        [2, 4, 8], [3, 6, 12], [4, 8, 96], [1, 2, 4]]
    assert [mod.job_ask(j) for j in jobs[:4]] == [
        (250, 256), (100, 128), (500, 512), (1000, 1024)]
    assert all(not j.get("Update") and j["Type"] == "service" for j in jobs)
    assert not any(tg.get("Networks") or any(
        t["Resources"].get("Devices") or t["Resources"].get("Networks")
        for t in tg["Tasks"]) for j in jobs for tg in j["TaskGroups"])
    # every group fits one round of the largest group's bucket
    from benchmark import groups_cost
    assert groups_cost.round_bucket(full["job_mix"]) == 256
    assert groups_cost.rounds_per_wave(full["job_mix"], 64) == 192
    base = load_json("configs", "csi50k")
    twin = load_module("configs", "csi50k")
    a = mod.build_fleet(cfg, 2147483683)
    b = twin.build_fleet(dict(base, **cfg["rehearse"]), 2147483683)
    assert [n.id for n in a[0]] == [n.id for n in b[0]] and a[1] == b[1]


def test_a_program_without_the_wave_for_groups_is_refused_at_load(
        monkeypatch, capsys):
    monkeypatch.delattr(generic, "MULTI_GROUP_WAVE")
    with pytest.raises(SystemExit) as e:
        load_module("configs", "groups50k")
    assert e.value.code == 5
    assert "MULTI_GROUP_WAVE" in capsys.readouterr().err


def _sound(nodes, jobs):
    """Every job's allocations spread over the fleet, none crowded."""
    ids = [n.id for n in nodes]
    out, at = {}, 0
    for job in jobs:
        k = sum(tg["Count"] for tg in job["TaskGroups"])
        out[job["ID"]] = [ids[(at + j) % len(ids)] for j in range(k)]
        at += k
    return out


@pytest.mark.parametrize("fault", ["none", "missing", "over", "dc",
                                   "stray"])
def test_check_names_a_planted_fault(fault, capsys):
    cfg, mod = groups50k()
    nodes, table = mod.build_fleet(cfg, 2147483683)
    jobs = [mod.make_job(cfg, i) for i in range(16)]
    placed = _sound(nodes, jobs)
    needle = None
    if fault == "missing":
        # one allocation of one group gone: the job is one short
        placed[jobs[3]["ID"]] = placed[jobs[3]["ID"]][:-1]
        needle = "committed != asked"
    elif fault == "over":
        # job 2's 108 placements of 500 MHz on one node
        placed[jobs[2]["ID"]] = [nodes[0].id] * 108
        needle = "nodes over resources - reserved"
    elif fault == "dc":
        # job 5 held to dc1, its allocations where they were: on nodes
        # of all three datacenters
        jobs[5] = dict(jobs[5], Datacenters=["dc1"])
        assert {table[n][1] for n in placed[jobs[5]["ID"]]} == {
            "dc1", "dc2", "dc3"}
        needle = "outside their job's datacenters"
    elif fault == "stray":
        placed["nobody-registered-this"] = [nodes[0].id]
        needle = "nobody registered"
    got = mod.check(cfg, table, jobs, placed)
    assert "held to the plain reference" in capsys.readouterr().out
    if needle is None:
        assert got == []
    else:
        assert len(got) == 1 and needle in got[0], got


# --------------------------------------------------------------- readers

def reader(name):
    return load_module("layer_metrics", name)


def fake_run(programs=None, waves=()):
    cfg = load_json("configs", "groups50k")
    mod = load_module("configs", "groups50k")
    return SimpleNamespace(
        cell=load_json("workloads", CELL), cfg=cfg,
        jobs=[mod.make_job(cfg, i) for i in range(4)],
        device={"kind": "TPU v5 lite"},
        trace={"programs": programs} if programs else {},
        tap_window={"waves": list(waves), "intervals": {}})


def test_groups_roofline_counts_a_round_a_group():
    from benchmark import (groups_cost, kernel_cost, multi_cost, peaks,
                           system_cost)
    run = fake_run({"jit_place_multi_packed": (1, 0.010),
                    "jit_place_multi_chained": (3, 0.030),
                    "jit_other": (5, 1.0)},
                   [{"items": 64, "groups": 192}] * 4)
    _, terms = system_cost.job_shape(run.jobs[0])
    assert terms == 2
    cost = groups_cost.groups_launch(50000, 192, terms)
    assert cost == multi_cost.flat_launch(50000, 192, 1, terms)
    share = reader("place_multi_groups_roofline").read(run)
    want = kernel_cost.roofline(
        cost, peaks.peaks_for("TPU v5 lite"), 0.010)["share_pct"]
    assert share == pytest.approx(want) and 0 < share < 105


def test_groups_roofline_reads_nothing_without_its_kernel_or_groups():
    roofline = reader("place_multi_groups_roofline")
    assert roofline.read(fake_run(None, [{"items": 64}])) is None
    assert roofline.read(fake_run(
        {"jit_place_multi_compact_packed": (2, 0.01)},
        [{"items": 64}])) is None
    run = fake_run({"jit_place_multi_packed": (1, 0.01)}, [{"items": 64}])
    run.cfg = dict(run.cfg, job_mix=[{"count": 4}])
    assert roofline.read(run) is None


def test_items_and_solo_readers(monkeypatch):
    items = reader("groups.items_per_wave")
    solo = reader("groups.solo_evals")
    assert items.UNIT == "items" and solo.UNIT == "evals"
    assert items.read(fake_run(None, [{"items": 64, "groups": 192},
                                      {"items": 64, "groups": 190}])) == 191
    # a program whose wave records carry no `groups`: nothing, no raise
    assert items.read(fake_run(None, [{"items": 64}])) is None
    assert solo.read(None) == REGISTRY.counter_sum("nomad.groups.evals_solo")
    monkeypatch.delattr(generic, "MULTI_GROUP_WAVE")
    assert solo.read(None) is None


# ------------------------------------------------------------- the cell

def test_benchmark_lists_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    # the cell and configuration after it came with a later PR
    # (mig50k-drain)
    assert list(cells)[-2] == CELL and cells[CELL]["chips"] == 1
    assert cells[CELL] == {k: load_json("workloads", CELL)[k] for k in (
        "name", "config", "traffic", "chips", "why")}
    assert bench["configs"][-2]["name"] == "groups50k"
    solo = next(m for m in bench["end_to_end"]
                if m["name"] == "solo_placed_per_s")
    assert solo["workloads"][-2] == CELL and solo["bound"] == 0.2
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 13 and [m["name"] for m in mine] == load_json(
        "workloads", CELL)["per_layer"]
    assert all(m["moves"] == "solo_placed_per_s" for m in mine)
    assert [m for m in mine if m["name"].endswith("_roofline")] == [
        {"name": "place_multi_groups_roofline", "unit": "%",
         "better": "higher", "source": "device_trace", "layer": "kernels",
         "moves": "solo_placed_per_s", "workloads": [CELL]}]


def test_cell_rehearses_on_the_cpu():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.selftest", "rehearse-one", CELL],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"solo_placed_per_s", "setup_s"}
    assert "16 jobs of 48 groups, 600 placements" in p.stdout
