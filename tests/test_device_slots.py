"""Device group slots: GPU nodes that advertise several device groups (an
A100 node in MIG mixed mode: whole GPUs, 3g.20gb and 1g.5gb profiles, a
group each) riding the wave.  A request takes all of its instances from
ONE group it accepts; the flat kernel charges each placement to the
first accepted group with room, in node order, and the carve takes ids
from that group.

Held to the configuration's plain reference (benchmark/configs/mig50k.py,
plain Python over the fleet), to a sequential first-accepted-group replay
of the committed placements, and to the applier's audit, on seeded
fleets of 64-600 nodes with pinned ids.
"""

import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest
from test_device_batched import cluster, counter, placements, wave

from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops.engine import (DEVICE_GROUP_SLOTS, BatchItem,
                                  PlacementEngine)
from nomad_tpu.ops.select import slot_charge, slot_room
from nomad_tpu.scheduler import generic
from nomad_tpu.scheduler.device import CarveLedger, carve_block
from nomad_tpu.structs import (Constraint, NodeDeviceResource,
                               RequestedDevice, allocs_fit, codec)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mig50k-drain"
NOW = 1_700_000_000.0
A100 = "nvidia/gpu/A100-SXM4-40GB"
MIG3 = A100 + " MIG 3g.20gb"
MIG1 = A100 + " MIG 1g.5gb"
ANY = "nvidia/gpu"
MIG2 = A100 + " MIG 2g.10gb"
T4 = "nvidia/gpu/Tesla T4"
MEMORY = {A100: "40960", MIG3: "20480", MIG2: "10240", MIG1: "5120",
          T4: "15360"}
BIG = Constraint(ltarget="${device.attr.memory}", operand=">=",
                 rtarget="20480")
WHOLE = ((A100, 8),)
MIXED = ((A100, 2), (MIG3, 4), (MIG1, 28))
# mig50k.json's job mix: (request, instances, constraints)
MIX = ((MIG1, 1, ()), (MIG1, 1, ()), (MIG3, 1, ()), (ANY, 1, (BIG,)),
       (ANY, 1, ()), (A100, 2, ()), (A100, 4, ()), (A100, 8, ()))


# ----------------------------------------------------------------- fleet

def group(name: str, n: int, tag: str) -> NodeDeviceResource:
    vendor, dtype, model = name.split("/", 2)
    return NodeDeviceResource(
        vendor=vendor, type=dtype, name=model,
        instance_ids=[f"GPU-{tag}-{k}" for k in range(n)],
        attributes={"memory": MEMORY[name]})


def fleet(n: int, seed: int, shapes=None):
    """`n` nodes with pinned ids; node i carries the groups of
    shapes[i % 10] (mig50k's layout: 3 whole A100s, 8 MIG mixed mode)."""
    shapes = {3: WHOLE, 8: MIXED} if shapes is None else shapes
    rng = random.Random(f"fleet:{seed}")
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = f"m{seed:03d}-{i:04d}"
        node.name = f"node-{i}"
        node.datacenter = f"dc{1 + i % 3}"
        node.resources.cpu = rng.choice([4000, 8000, 16000])
        node.resources.memory_mb = rng.choice([8192, 16384, 32768])
        node.resources.devices = [
            group(name, k, f"{i}-{s}")
            for s, (name, k) in enumerate(shapes.get(i % 10, ()))]
        nodes.append(node)
    return nodes


def mig_job(tag: str, i: int, count: int, kind=None):
    request, gpus, constraints = MIX[i % 8] if kind is None else kind
    job = mock.batch_job()
    job.id = f"mig-{tag}-{i:03d}"
    job.name = job.id
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = 250 * gpus, 256 * gpus
    res.devices = [RequestedDevice(name=request, count=gpus,
                                   constraints=list(constraints))]
    return job


# ------------------------------------------------------ plain references

def mig50k():
    from benchmark.loader import load_json, load_module
    return load_json("configs", "mig50k"), load_module("configs", "mig50k")


def table(nodes):
    """mig50k.py's fleet table of `nodes`: (index, dc, cpu, mem, groups)
    net of reserved."""
    return {n.id: (i, n.datacenter, n.capacity()[0], n.capacity()[1],
                   tuple((d.id(), len(d.instance_ids),
                          d.attributes["memory"])
                         for d in n.resources.devices))
            for i, n in enumerate(nodes)}


def reference_failures(nodes, jobs, s, full=True):
    """The configuration's plain reference over the node ids the state
    holds, then what only the allocations show: each holds its count of
    distinct ids of ONE group of its node that its request accepts, by
    name and constraint; no id twice on a node."""
    from benchmark import fleet as fleetlib
    cfg, mod = mig50k()
    wire = [codec.encode(j) for j in jobs]
    placed = placements(s, jobs)
    by_job = {j.id: [r[1] for r in placed[j.id]] for j in jobs}
    bad = mod.reference_ok(cfg, table(nodes), wire, by_job)
    if full:
        bad += fleetlib.check_placements(table(nodes), wire, by_job)
    by_id = {n.id: n for n in nodes}
    held = {}
    for job, w in zip(jobs, wire):
        request = mod._request(w)
        for _name, node_id, devs in placed[job.id]:
            if len(devs) != 1:
                bad.append(f"{job.id}: {len(devs)} device entries")
                continue
            gid, ids = devs[0]
            dev = next((d for d in by_id[node_id].resources.devices
                        if d.id() == gid), None)
            if dev is None or not mod.group_accepts(
                    request, (gid, 0, dev.attributes["memory"])):
                bad.append(f"{job.id}: group {gid} not accepted")
                continue
            if len(ids) != request[1] or len(set(ids)) != len(ids) \
                    or not set(ids) <= set(dev.instance_ids):
                bad.append(f"{job.id}: ids {ids} of {gid}")
            seen = held.setdefault(node_id, set())
            if seen & set(ids):
                bad.append(f"{node_id}: an instance held twice")
            seen.update(ids)
    return bad


def replay(nodes, jobs, s):
    """{(node id, group id): instances} that a sequential replay of the
    committed placements charges, job after job in wave order, each
    allocation to the FIRST group of its node in node order that its
    request accepts and that has room."""
    _, mod = mig50k()
    by_id = {n.id: n for n in nodes}
    placed = placements(s, jobs)
    free = {(n.id, d.id()): len(d.instance_ids)
            for n in nodes for d in n.resources.devices}
    out = {}
    for job in jobs:
        request = mod._request(codec.encode(job))
        for _name, node_id, _devs in placed[job.id]:
            for d in by_id[node_id].resources.devices:
                key = (node_id, d.id())
                if (mod.group_accepts(
                        request, (d.id(), 0, d.attributes["memory"]))
                        and free[key] >= request[1]):
                    free[key] -= request[1]
                    out[key] = out.get(key, 0) + request[1]
                    break
            else:
                out[(node_id, "none")] = 1
    return out


def held_by_group(nodes, jobs, s):
    out = {}
    for rows in placements(s, jobs).values():
        for _name, node_id, devs in rows:
            for gid, ids in devs:
                out[(node_id, gid)] = out.get((node_id, gid), 0) + len(ids)
    return out


# ------------------------------------------------- waves against them

SCENARIOS = {
    # name: (nodes, seed, jobs, count a job, eval_batch)
    "64_nodes_one_wave": (64, 11, 8, 1, 8),
    "120_nodes_two_waves": (120, 2147483659, 16, 1, 8),
    "300_nodes_two_tasks": (300, 7, 24, 2, 8),
    "600_nodes_rehearsal": (600, 31, 16, 3, 16),
}


def run_scenario(name, monkeypatch, chain=True):
    n, seed, n_jobs, count, eval_batch = SCENARIOS[name]
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(n, seed)
    jobs = [mig_job(name[:6], i, count) for i in range(n_jobs)]
    s = cluster(nodes, chain=chain, eval_batch=eval_batch)
    wave(s, jobs, name[:6])
    return s, nodes, jobs


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_wave_holds_the_configurations_reference(name, monkeypatch):
    solo0 = counter("nomad.device.evals_solo")
    batched0 = counter("nomad.device.evals_batched")
    s, nodes, jobs = run_scenario(name, monkeypatch)
    assert reference_failures(nodes, jobs, s) == []
    assert counter("nomad.device.evals_batched") - batched0 == len(jobs)
    assert counter("nomad.device.evals_solo") == solo0
    assert s.plan_applier.stats["plans_refuted"] == 0
    for n in nodes:
        ok, dim, _ = allocs_fit(s.state.node_by_id(n.id),
                                s.state.snapshot().allocs_by_node(n.id),
                                check_devices=True)
        assert ok, (n.id, dim)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_group_use_is_the_sequential_replay(name, monkeypatch):
    """Per (node, group), the instances the committed allocations hold
    are what charging each placement, one after another, to its node's
    first accepted group with room gives; and the packer's slot usage
    says the same."""
    s, nodes, jobs = run_scenario(name, monkeypatch)
    held = held_by_group(nodes, jobs, s)
    assert held == replay(nodes, jobs, s)
    t = s.engine.packer.update(s.state.snapshot())
    for n in nodes:
        row = t.id_to_row[n.id]
        want = [held.get((n.id, d.id()), 0) for d in n.resources.devices]
        assert t.slot_used[row, :len(want)].tolist() == want
        assert t.slot_cap[row, :len(want)].tolist() == [
            len(d.instance_ids) for d in n.resources.devices]
        assert int(t.used[row, 3]) == sum(want)


def test_the_resident_chain_carries_what_the_state_says(monkeypatch):
    """Two waves chained on the device (the second starts from the slot
    usage the first left) place what the same two waves do launched one
    after the other from the committed state, the packer's slot usage."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(120, 2147483659)
    jobs = [mig_job("chain", i, 1) for i in range(16)]

    def run(chain, parts):
        s = cluster(nodes, chain=chain, eval_batch=8)
        for part in parts:
            for i in part:
                s.state.upsert_job(jobs[i])
                ev = mock.eval(job_id=jobs[i].id, type="batch")
                ev.id = f"eval-chain-{i:03d}"
                s.apply_eval_update([ev], now=NOW)
            s.process_all(now=NOW)
        return s

    resident = run(True, [range(16)])
    serial = run(False, [range(8), range(8, 16)])
    assert resident.executor.stats["resident_waves"] >= 1
    assert serial.executor.stats["resident_waves"] == 0

    def groups(s):
        # (alloc name, node, group, instances): which ids of a group the
        # mates of one wave take is their materialize order's
        return {j: [(name, nid, tuple((g, len(i)) for g, i in devs))
                    for name, nid, devs in rows]
                for j, rows in placements(s, jobs).items()}
    assert groups(resident) == groups(serial)
    assert reference_failures(nodes, jobs, resident) == []


def test_one_eval_spans_the_groups_of_one_node(monkeypatch):
    """One MIG node, one job of five `nvidia/gpu` tasks: the node's
    groups fill in node order, two whole GPUs then three 3g.20gb, the
    rows of one plan on one node from two groups, and the applier takes
    it whole."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(10, 5, shapes={8: MIXED})
    job = mig_job("span", 0, 5, kind=(ANY, 1, ()))
    s = cluster(nodes)
    wave(s, [job], "span")
    (rows,) = placements(s, [job]).values()
    assert {r[1] for r in rows} == {nodes[8].id}
    got = sorted(d[0] for _, _, (d,) in rows)
    assert got == [A100] * 2 + [MIG3] * 3
    assert s.plan_applier.stats["plans_refuted"] == 0
    assert reference_failures(nodes, [job], s) == []


def test_a_stop_gives_each_group_its_instances_back(monkeypatch):
    s, nodes, jobs = run_scenario("64_nodes_one_wave", monkeypatch)
    for job in jobs:
        s.deregister_job(job.namespace, job.id, now=NOW)
    s.process_all(now=NOW)
    t = s.engine.packer.update(s.state.snapshot())
    assert int(t.slot_used.sum()) == 0 and int(t.used[:, 3].sum()) == 0
    again = [mig_job("again", i, 1) for i in range(8)]
    wave(s, again, "again")
    assert reference_failures(nodes, again, s) == []


# ----------------------------------------------------- the kernel's rule

@pytest.mark.parametrize("seed", [1, 2, 2147483659])
def test_slot_charge_is_one_placement_after_another(seed):
    """`slot_room` and `slot_charge` against a plain loop: each of c_i
    placements of `need` instances goes to the first accepted slot whose
    free instances still hold it."""
    rng = np.random.default_rng(seed)
    n, s = 200, DEVICE_GROUP_SLOTS
    cap = rng.integers(0, 30, (n, s)).astype(np.int32)
    used = np.minimum(rng.integers(0, 30, (n, s)), cap).astype(np.int32)
    accept = rng.random((n, s)) < 0.6
    for need in (1, 2, 4):
        # the kernel's slot-major layout, [S, N]
        room = np.asarray(slot_room(cap.T, used.T, accept.T, np.int32(need),
                                    64))
        c_i = np.array([rng.integers(0, r + 1) for r in room.sum(axis=0)],
                       np.int32)
        took, charged = (np.asarray(x).T for x in
                         slot_charge(room, c_i, np.int32(need)))
        for i in range(n):
            free = (cap[i] - used[i]).tolist()
            want = np.zeros(s, np.int32)
            for _ in range(c_i[i]):
                k = next(k for k in range(s)
                         if accept[i, k] and free[k] >= need)
                free[k] -= need
                want[k] += 1
            assert took[i].tolist() == want.tolist(), (i, need)
            assert charged[i].tolist() == (want * need).tolist()


def test_a_device_less_wave_launches_the_program_it_did():
    """A wave with no device ask carries no slot inputs: the launch's
    input tree, shapes and buffer are those of a wave without the
    mechanism."""
    nodes = fleet(64, 3)
    s = cluster(nodes)
    engine = PlacementEngine(mesh=False)
    job = mock.batch_job()
    job.task_groups[0].count = 5
    s.state.upsert_job(job)
    snap = s.state.snapshot()
    built = engine.build_multi_inputs(
        snap, [BatchItem(job=job, tg=job.task_groups[0], count=5)])
    inp = built["inp"]
    assert built["slots"] is None
    assert (inp.dev_cap, inp.dev_used0, inp.dev_accept, inp.g_dev) == (
        None,) * 4
    pending = engine.dispatch_batch(
        snap, [BatchItem(job=job, tg=job.task_groups[0], count=5)])
    assert pending["buf"].shape == (1, built["rs"] + 16)
    assert pending["slot_used"] is None and not pending["slot_takes"]
    assert pending["dev_items"] == [False]
    (bd,) = engine.collect_batch(pending)
    assert bd.slots is None and (bd.picks >= 0).sum() == 5


def test_a_device_wave_carries_the_slots_the_fleet_needs():
    """S = 1 on a fleet of single-group nodes (gpu50k's), all the slots
    where a node advertises several; a request's accept table is built
    once a node-table version and reused after."""
    for shapes, want in (({3: WHOLE}, 1), ({3: WHOLE, 8: MIXED},
                                          DEVICE_GROUP_SLOTS)):
        nodes = fleet(40, 9, shapes=shapes)
        s = cluster(nodes)
        engine = PlacementEngine(mesh=False)
        job = mig_job("s", 7, 2)
        s.state.upsert_job(job)
        snap = s.state.snapshot()
        item = [BatchItem(job=job, tg=job.task_groups[0], count=2)]
        built0 = counter("nomad.engine.device_slot_tables_built")
        reused0 = counter("nomad.engine.device_slot_tables_reused")
        inp = engine.build_multi_inputs(snap, item)["inp"]
        n = len(nodes)
        # slot-major: the nodes on the TPU's lanes
        assert inp.dev_cap.shape == inp.dev_used0.shape == (want, n)
        assert inp.dev_accept.shape == (8, want, n)
        engine.build_multi_inputs(snap, item)
        assert counter("nomad.engine.device_slot_tables_built") - built0 \
            == 1
        assert counter("nomad.engine.device_slot_tables_reused") \
            - reused0 >= 1


def test_the_lowering_span_and_the_pick_counter(monkeypatch):
    """One `device_lower` interval a wave with a device ask, inside that
    wave's dispatch; every placement counted once by its node's kind."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(60, 13)
    jobs = [mig_job("pick", i, 1, kind=(MIG1, 1, ())) for i in range(4)] \
        + [mig_job("pick", 4 + i, 1, kind=(A100, 8, ())) for i in range(2)]
    multi0 = REGISTRY.counter("nomad.device.picks", node="multi_group")
    single0 = REGISTRY.counter("nomad.device.picks", node="single_group")
    s = cluster(nodes)
    wave(s, jobs, "pick")
    assert REGISTRY.counter("nomad.device.picks",
                            node="multi_group") - multi0 == 4
    assert REGISTRY.counter("nomad.device.picks",
                            node="single_group") - single0 == 2
    timers = s.stage_timers
    lowers = [(a, b) for _, a, b in timers.intervals("device_lower")]
    dispatches = [(a, b) for _, a, b in timers.intervals("dispatch")]
    assert len(lowers) == 1
    assert all(any(lo <= a and b <= hi for lo, hi in dispatches)
               for a, b in lowers)


# ------------------------------------------------------- carve and audit

def test_carve_takes_each_rows_ids_from_its_charged_group():
    nodes = fleet(10, 2, shapes={8: MIXED})
    s = cluster(nodes)
    snap = s.state.snapshot()
    node_ids = [n.id for n in nodes]
    ledger = CarveLedger()
    req = RequestedDevice(name=ANY, count=1)
    rec = ledger.open(snap.index)
    ids, groups, short = carve_block(
        snap, ledger, rec, req, np.array([8, 8, 8, 8], np.int32),
        node_ids, slots=np.array([0, 2, 2, 1], np.int32))
    assert short == []
    assert ids.tolist() == [["GPU-8-0-0"], ["GPU-8-2-0"], ["GPU-8-2-1"],
                            ["GPU-8-1-0"]]
    assert groups == {(8, 0): ("nvidia", "gpu", "A100-SXM4-40GB"),
                      (8, 2): ("nvidia", "gpu", "A100-SXM4-40GB MIG 1g.5gb"),
                      (8, 1): ("nvidia", "gpu", "A100-SXM4-40GB MIG 3g.20gb")}
    flat = [i for row in ids.tolist() for i in row]
    assert len(set(flat)) == len(flat) == 4
    # a mate asks three of the whole A100s, of which one is left: the
    # node is short and gives none of its rows ids
    rec2 = ledger.open(snap.index)
    ids2, groups2, short2 = carve_block(
        snap, ledger, rec2, req, np.array([8, 8, 8], np.int32), node_ids,
        slots=np.array([0, 0, 0], np.int32))
    assert short2 == [8] and groups2 == {}
    assert ids2.tolist() == [[""], [""], [""]]
    assert nodes[8].id not in rec2[1]


def test_the_applier_refutes_an_id_of_another_group(monkeypatch):
    """A block row that holds an instance of another group of its node
    under its group's name is refuted on that node, and the repair
    places it again soundly."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(20, 19, shapes={8: MIXED})
    jobs = [mig_job("audit", i, 2, kind=(MIG1, 1, ())) for i in range(2)]
    s = cluster(nodes)
    worker = s.workers[0]
    real_submit = worker.submit_plan_async
    seen = []

    def submit(plan):
        if plan.alloc_blocks and not seen:
            block = plan.alloc_blocks[0]
            node = s.state.node_by_id(block.node_table[int(block.picks[0])])
            seen.append(node.id)
            ids = block.device_ids.copy()
            ids[0, 0] = node.resources.devices[0].instance_ids[0]
            block.device_ids = ids
        return real_submit(plan)

    monkeypatch.setattr(worker, "submit_plan_async", submit)
    refuted0 = s.plan_applier.stats["plans_refuted"]
    wave(s, jobs, "audit")
    assert seen and s.plan_applier.stats["plans_refuted"] > refuted0
    assert reference_failures(nodes, jobs, s) == []


# --------------------------------------------------- admission, solo path

def test_more_groups_than_slots_go_solo_and_hold_the_reference(
        monkeypatch):
    """One node of five groups, one more than the tensors have slots:
    every device eval of the fleet goes solo under rule `slots`, and the
    solo path's exact check holds the reference."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    five = ((A100, 2), (MIG3, 4), (MIG2, 6), (MIG1, 14), (T4, 4))
    assert len(five) == DEVICE_GROUP_SLOTS + 1
    nodes = fleet(60, 29, shapes={3: WHOLE, 8: MIXED, 0: five})
    jobs = [mig_job("solo", i, 1) for i in range(8)]
    before = REGISTRY.counter("nomad.device.evals_solo", rule="slots")
    s = cluster(nodes)
    wave(s, jobs, "solo")
    assert REGISTRY.counter("nomad.device.evals_solo",
                            rule="slots") - before == len(jobs)
    assert reference_failures(nodes, jobs, s) == []


# -------------------------------------------- the benchmark's own files

@pytest.mark.parametrize("size", ["full", "rehearse"])
def test_the_configurations_inequality_holds(size):
    from benchmark.loader import load_json
    cfg, mod = mig50k()
    traffic = load_json("traffic", "drain256-purge")
    if size == "rehearse":
        cfg = dict(cfg, **cfg["rehearse"])
        traffic = dict(traffic, **traffic["rehearse"])
    jobs = [mod.make_job(cfg, i) for i in range(traffic["jobs_per_cycle"])]
    shapes = {s["at"]: s["groups"] for s in cfg["gpu_shapes"]}
    fleet_t = {f"n{i}": (i, "dc1", 4000, 8000, tuple(
        (g["group"], g["instances"], g["attributes"]["memory"])
        for g in shapes.get(i % cfg["gpu_every"], ())))
        for i in range(cfg["nodes"])}
    failures = mod.check(cfg, fleet_t, jobs, {})
    assert not any("inequality" in f for f in failures), failures
    if size == "full":
        tasks = sum(j["TaskGroups"][0]["Count"] for j in jobs)
        asked = sum(j["TaskGroups"][0]["Count"] * mod._request(j)[1]
                    for j in jobs)
        only_mig = sum(j["TaskGroups"][0]["Count"] for j in jobs
                       if "MIG" in mod._request(j)[0])
        assert (tasks, asked, only_mig) == (4096, 9728, 1536)
        assert sum(g[1] for n in fleet_t.values() for g in n[4]) == 210000


def test_check_passes_a_sound_answer_and_names_a_doctored_one():
    from benchmark.loader import load_json
    cfg, mod = mig50k()
    cfg = dict(cfg, **cfg["rehearse"])
    per_cycle = load_json("traffic", "drain256-purge")["rehearse"][
        "jobs_per_cycle"]
    nodes, fleet_t = mod.build_fleet(cfg, 2147483659)
    jobs = [mod.make_job(cfg, i) for i in range(per_cycle)]
    whole = [n.id for n in nodes if fleet_t[n.id][4][:1] == (
        (A100, 8, "40960"),)]
    mixed = [n.id for n in nodes if len(fleet_t[n.id][4]) == 3]
    good = {}
    for j in jobs:
        name, count, _ = mod._request(j)
        pool = mixed if "MIG" in name else whole
        good[j["ID"]] = [pool.pop() for _ in range(
            j["TaskGroups"][0]["Count"])]
    assert mod.check(cfg, fleet_t, jobs, good) == []
    plain = next(n.id for n in nodes if not fleet_t[n.id][4])
    mig_job_ = next(j for j in jobs if "MIG 1g" in mod._request(j)[0])
    eight = next(j for j in jobs if mod._request(j)[1] == 8)
    cases = {
        "no device group their request accepts": {
            **good, mig_job_["ID"]: [whole[0]] + good[mig_job_["ID"]][1:]},
        "no group assignment holds": {
            **good, eight["ID"]: [mixed[0]] + good[eight["ID"]][1:]},
        "committed != asked": {**good, eight["ID"]: good[eight["ID"]][:-1]},
        "accepts": {**good, mig_job_["ID"]:
                    [plain] + good[mig_job_["ID"]][1:]},
    }
    for needle, doctored in cases.items():
        assert any(needle in f for f in mod.check(cfg, fleet_t, jobs,
                                                  doctored)), needle


@pytest.mark.parametrize("asks, ok", [
    # ((request, instances), ...) on one MIG node: 2 A100, 4 3g, 28 1g
    ([((ANY, 1, ()), 1)] * 34, True),
    ([((ANY, 1, ()), 1)] * 35, False),
    ([((A100, 2, ()), 2), ((ANY, 1, ()), 1)], True),
    ([((A100, 2, ()), 2)] * 2, False),
    ([((ANY, 1, (("${device.attr.memory}", ">=", "20480"),)), 1)] * 6, True),
    ([((ANY, 1, (("${device.attr.memory}", ">=", "20480"),)), 1)] * 7,
     False),
    ([((A100 + " MIG 3g.20gb", 1, ()), 1)] * 4
     + [((ANY, 1, (("${device.attr.memory}", ">=", "20480"),)), 1)] * 2,
     True),
])
def test_the_references_group_assignment(asks, ok):
    _, mod = mig50k()
    groups = ((A100, 2, "40960"), (MIG3, 4, "20480"), (MIG1, 28, "5120"))
    assert mod.assignable(groups, asks) is ok


def test_mig_cost_counts_the_slot_state_on_the_flat_launch():
    from benchmark import mig_cost, multi_cost
    cfg, _ = mig50k()
    assert mig_cost.slots_of(cfg) == 3 and mig_cost.signatures_of(cfg) == 5
    base = multi_cost.flat_launch(50_000, 64, 5, 2)
    c = mig_cost.mig_launch(50_000, 64, 5, 2, 3)
    assert c["ops"] == base["ops"] + 64 * 50_000 * 4
    assert c["bytes"] == base["bytes"] + 50_000 * 3 * (12 + 5)


def test_the_job_asks_its_constraint_on_the_wire():
    cfg, mod = mig50k()
    from nomad_tpu.structs import Job
    job = codec.decode(Job, mod.make_job(cfg, 3))
    (req,) = job.task_groups[0].tasks[0].resources.devices
    assert (req.name, req.count) == (ANY, 1)
    assert [(c.ltarget, c.operand, c.rtarget) for c in req.constraints] \
        == [("${device.attr.memory}", ">=", "20480")]
    plain = codec.decode(Job, mod.make_job(cfg, 4))
    assert plain.task_groups[0].tasks[0].resources.devices[0].constraints \
        == []


def test_benchmark_lists_the_cell_and_its_metrics():
    from benchmark.loader import load_json
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert list(cells)[-1] == CELL and cells[CELL]["chips"] == 1
    assert cells[CELL] == {k: load_json("workloads", CELL)[k] for k in (
        "name", "config", "traffic", "chips", "why")}
    assert bench["configs"][-1]["name"] == "mig50k"
    assert bench["configs"][-1]["reduced"] == ["regions", "heartbeats"]
    solo = next(m for m in bench["end_to_end"]
                if m["name"] == "solo_placed_per_s")
    assert solo["workloads"][-1] == CELL and solo["bound"] == 0.2
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 14 and [m["name"] for m in mine] == load_json(
        "workloads", CELL)["per_layer"]
    assert all(m["moves"] == "solo_placed_per_s" for m in mine)
    assert [m for m in mine if m["name"].endswith("_roofline")] == [
        {"name": "place_multi_mig_roofline", "unit": "%",
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
    assert "16 jobs, 48 tasks, 30 of them accepting whole nodes (60)" \
        in p.stdout


def test_the_readers_read_nothing_without_their_sources():
    """The new per-layer readers on a run with no trace and no counter
    (a program without the mechanism) read nothing and raise nothing."""
    from types import SimpleNamespace

    from benchmark.loader import load_module
    run = SimpleNamespace(_trace_state="off", trace={},
                          tap_window={"waves": []}, cfg={}, jobs=[])
    for name in ("mig.device_lower_ms_per_wave",
                 "place_multi_mig_roofline"):
        assert load_module("layer_metrics", name).read(run) is None
    share = load_module("layer_metrics", "mig.multi_group_pick_share")
    assert share.UNIT == "%"
    got = share.read(run)
    assert got is None or 0.0 <= got <= 100.0
