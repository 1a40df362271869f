"""The batched device path (ISSUE 30): device-asking evals riding a wave,
device instances as a capacity dimension of the node tensors, instance
ids carved per node as AllocBlock columns, the applier's device audit.

Held to a plain reference (plain Python over the fleet the test built)
AND to the solo path (`generic.DEVICE_BATCHED = False`: the exact scan,
then `_assign_devices` one allocation at a time) on seeded fleets of
64-300 nodes.  Node, job and eval ids are pinned, so two runs of one
scenario compute the same placements.
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
from nomad_tpu.ops.engine import DEVICE_GROUP_SLOTS
from nomad_tpu.scheduler import generic
from nomad_tpu.scheduler.device import (CarveLedger, carve_block,
                                        held_instances)
from nomad_tpu.structs import (
    Affinity,
    AllocatedDeviceResource,
    Allocation,
    NodeDeviceResource,
    RES_DIMS,
    RES_NAMES,
    RequestedDevice,
    Resources,
    allocs_fit,
    codec,
)

NOW = 1_700_000_000.0
IDS_KEY = codec.wire_name("device_ids")
A100 = "nvidia/gpu/A100-SXM4-40GB"
T4 = "nvidia/gpu/Tesla T4"
ANY = "nvidia/gpu"


# ----------------------------------------------------------------- fleet

def group(name: str, n: int, tag: str) -> NodeDeviceResource:
    vendor, dtype, model = name.split("/", 2)
    return NodeDeviceResource(
        vendor=vendor, type=dtype, name=model,
        instance_ids=[f"GPU-{tag}-{k}" for k in range(n)],
        attributes={"memory": "40960" if "A100" in name else "15360"})


def fleet(n: int, seed: int, a100_every=(10, 3), t4_every=(10, 8)):
    """`n` nodes with pinned ids: node i an A100 node (8 instances)
    where i % a100_every[0] == a100_every[1], a T4 node (4) likewise."""
    rng = random.Random(f"fleet:{seed}")
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = f"n{seed:03d}-{i:04d}"
        node.name = f"node-{i}"
        node.datacenter = f"dc{1 + i % 3}"
        node.resources.cpu = rng.choice([4000, 8000, 16000])
        node.resources.memory_mb = rng.choice([8192, 16384, 32768])
        if i % a100_every[0] == a100_every[1]:
            node.resources.devices = [group(A100, 8, f"a{i}")]
        elif i % t4_every[0] == t4_every[1]:
            node.resources.devices = [group(T4, 4, f"t{i}")]
        nodes.append(node)
    return nodes


def gpu_job(tag: str, i: int, request: str, gpus: int, count: int,
            devices=None):
    job = mock.batch_job()
    job.id = f"gpu-{tag}-{i:03d}"
    job.name = job.id
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = 250 * gpus, 256 * gpus
    res.devices = devices if devices is not None else [
        RequestedDevice(name=request, count=gpus)]
    return job


MIX = ((ANY, 1), (ANY, 1), (ANY, 1), (ANY, 1), (A100, 2), (A100, 4),
       (A100, 8), (A100, 8))


def mixed_jobs(tag: str, n: int, count: int):
    return [gpu_job(tag, i, *MIX[i % 8], count) for i in range(n)]


def cluster(nodes, chain=True, eval_batch=8) -> Server:
    s = Server(dev_mode=True, eval_batch=eval_batch, mesh=False)
    s.executor.chain_enabled = chain
    s.establish_leadership()
    for n in nodes:
        s.register_node(n.copy(), now=NOW)
    return s


def wave(s: Server, jobs, tag: str) -> None:
    """Registers `jobs` with pinned eval ids and runs the worker until
    the broker is empty: waves of `eval_batch`, in this order."""
    for i, job in enumerate(jobs):
        s.state.upsert_job(job)
        ev = mock.eval(job_id=job.id, type="batch")
        ev.id = f"eval-{tag}-{i:03d}"
        s.apply_eval_update([ev], now=NOW)
    s.process_all(now=NOW)


def stop(s: Server, jobs) -> None:
    for job in jobs:
        s.deregister_job(job.namespace, job.id, now=NOW)
    s.process_all(now=NOW)


def placements(s: Server, jobs):
    """{job id: sorted [(alloc name, node id, device group, ids)]} of the
    live allocations."""
    snap = s.state.snapshot()
    out = {}
    for job in jobs:
        rows = []
        for a in snap.allocs_by_job(job.namespace, job.id):
            if a.terminal_status():
                continue
            devs = tuple((ad.group_id(), tuple(ad.device_ids))
                         for ad in a.allocated_devices)
            rows.append((a.name, a.node_id, devs))
        out[job.id] = sorted(rows)
    return out


def failed_dimensions(s: Server, jobs):
    """{job id: set of exhausted dimensions of its failed task group}."""
    snap = s.state.snapshot()
    out = {}
    for job in jobs:
        dims = set()
        for ev in snap.evals_by_job(job.namespace, job.id):
            for metric in (ev.failed_tg_allocs or {}).values():
                dims.update(metric.dimension_exhausted)
        out[job.id] = dims
    return out


# ------------------------------------------------------- plain reference

def accepts(request: str, dev: NodeDeviceResource) -> bool:
    want = request.split("/")
    have = [dev.vendor, dev.type, dev.name]
    return have[1] == want[0] if len(want) == 1 else have[:len(want)] == want


def reference_failures(nodes, jobs, placed, full=True):
    """What the deployment guarantees, by plain Python over the fleet:
    per job exactly `count` allocations (`full`), each holding exactly
    its request's count of instances of ONE group of its node that the
    request's name accepts; per node every instance id at most once and
    the instances held within the group's count; cpu and memory within
    capacity net of reserved."""
    by_id = {n.id: n for n in nodes}
    bad = []
    held = {}
    used = {}
    for job in jobs:
        tg = job.task_groups[0]
        (req,) = tg.tasks[0].resources.devices
        rows = placed[job.id]
        if full and len(rows) != tg.count:
            bad.append(f"{job.id}: {len(rows)} of {tg.count} placed")
        if len(rows) > tg.count:
            bad.append(f"{job.id}: more than asked")
        for _name, node_id, devs in rows:
            node = by_id[node_id]
            if node.datacenter not in job.datacenters:
                bad.append(f"{job.id}: outside its datacenters")
            if len(devs) != 1:
                bad.append(f"{job.id}: {len(devs)} device groups held")
                continue
            gid, ids = devs[0]
            dev = next((d for d in node.resources.devices
                        if d.id() == gid), None)
            if dev is None or not accepts(req.name, dev):
                bad.append(f"{job.id}: group {gid} on {node_id} does not "
                           f"match {req.name}")
                continue
            if len(ids) != req.count or len(set(ids)) != len(ids):
                bad.append(f"{job.id}: holds {ids} for count {req.count}")
            if not set(ids) <= set(dev.instance_ids):
                bad.append(f"{job.id}: unknown instance among {ids}")
            seen = held.setdefault(node_id, set())
            if seen & set(ids):
                bad.append(f"{node_id}: instance held twice {ids}")
            seen.update(ids)
            u = used.setdefault(node_id, [0, 0])
            u[0] += tg.tasks[0].resources.cpu
            u[1] += tg.tasks[0].resources.memory_mb
    for node_id, ids in held.items():
        have = sum(len(d.instance_ids)
                   for d in by_id[node_id].resources.devices)
        if len(ids) > have:
            bad.append(f"{node_id}: {len(ids)} instances held of {have}")
    for node_id, (cpu, mem) in used.items():
        cap = by_id[node_id].capacity()
        if cpu > cap[0] or mem > cap[1]:
            bad.append(f"{node_id}: over cpu or memory")
    return bad


def device_counters():
    c = REGISTRY.snapshot()["counters"]
    return {k: v for k, v in c.items() if "device" in str(k)}


def counter(name: str) -> float:
    return REGISTRY.counter_sum(name)


# ------------------------------------------- waves against the references

SCENARIOS = {
    # name: (nodes, seed, jobs, count a job, eval_batch)
    "64_nodes_one_wave": (64, 11, 8, 1, 8),
    "120_nodes_two_waves": (120, 2147483659, 16, 1, 8),
    "300_nodes_mixed": (300, 7, 24, 1, 8),
    "300_nodes_three_tasks": (300, 31, 8, 3, 8),
}


def run_scenario(name, batched, monkeypatch, chain=True):
    n, seed, n_jobs, count, eval_batch = SCENARIOS[name]
    monkeypatch.setattr(generic, "DEVICE_BATCHED", batched)
    nodes = fleet(n, seed)
    jobs = mixed_jobs(name[:6], n_jobs, count)
    s = cluster(nodes, chain=chain, eval_batch=eval_batch)
    wave(s, jobs, name[:6])
    return s, nodes, jobs


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_wave_holds_the_plain_reference(name, monkeypatch):
    solo0 = counter("nomad.device.evals_solo")
    batched0 = counter("nomad.device.evals_batched")
    s, nodes, jobs = run_scenario(name, True, monkeypatch)
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed) == []
    # every eval rode a wave, its rows columnar: blocks, no loose rows
    assert counter("nomad.device.evals_batched") - batched0 == len(jobs)
    assert counter("nomad.device.evals_solo") == solo0
    snap = s.state.snapshot()
    assert len(snap.alloc_blocks()) == len(jobs) and not snap.allocs()
    assert all(b.device_ids is not None and b.device_ids.shape
               == (b.count, b.resources_tuple()[3])
               for b in snap.alloc_blocks())
    assert s.plan_applier.stats["plans_refuted"] == 0


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_wave_agrees_with_the_solo_path(name, monkeypatch):
    s, nodes, jobs = run_scenario(name, True, monkeypatch)
    batched = placements(s, jobs)
    solo0 = counter("nomad.device.evals_solo")
    s2, _, _ = run_scenario(name, False, monkeypatch)
    solo = placements(s2, jobs)
    assert counter("nomad.device.evals_solo") - solo0 == len(jobs)
    assert reference_failures(nodes, jobs, solo) == []
    # the same per-job counts, every allocation with its count of
    # instances of a matching group (the reference held both to that)
    assert ({j: len(r) for j, r in batched.items()}
            == {j: len(r) for j, r in solo.items()})
    assert failed_dimensions(s, jobs) == failed_dimensions(s2, jobs)


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "solo"])
def test_a_short_fleet_fails_on_the_devices_dimension(batched,
                                                      monkeypatch):
    """12 instances, 16 one-GPU tasks: the first jobs of the order fill
    the fleet, the rest fail with `devices` exhausted on both paths."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", batched)
    nodes = fleet(30, 5, a100_every=(30, 3), t4_every=(30, 8))
    assert sum(n.capacity()[3] for n in nodes) == 12
    jobs = [gpu_job("short", i, ANY, 1, 4) for i in range(4)]
    s = cluster(nodes)
    wave(s, jobs, "short")
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed, full=False) == []
    assert [len(placed[j.id]) for j in jobs] == [4, 4, 4, 0]
    dims = failed_dimensions(s, jobs)
    assert dims[jobs[3].id] == {"devices"}
    assert not any(dims[j.id] for j in jobs[:3])


def test_mates_of_one_wave_share_a_node(monkeypatch):
    """One A100 node, four mates of one wave asking 2 GPUs each: all
    land on it, each with its own two instances, first free first."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(20, 3, a100_every=(20, 3), t4_every=(20, 21))
    jobs = [gpu_job("mates", i, A100, 2, 1) for i in range(4)]
    s = cluster(nodes)
    wave(s, jobs, "mates")
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed) == []
    gpu_node = nodes[3]
    rows = [placed[j.id][0] for j in jobs]
    assert {r[1] for r in rows} == {gpu_node.id}
    ids = [i for r in rows for i in r[2][0][1]]
    assert ids == gpu_node.resources.devices[0].instance_ids
    t = s.engine.packer.update(s.state.snapshot())
    row = t.id_to_row[gpu_node.id]
    assert t.cap[row, 3] == 8 and t.used[row, 3] == 8


def test_a_whole_node_ask_among_one_gpu_asks(monkeypatch):
    """Two A100 nodes; one-GPU asks first, then a whole-node ask in the
    same wave: the kernel's device dimension keeps a node whole for it
    or fails it on `devices`, never over-commits."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(20, 9, a100_every=(10, 3), t4_every=(20, 21))
    jobs = ([gpu_job("whole", i, ANY, 1, 1) for i in range(5)]
            + [gpu_job("whole", 5, A100, 8, 1)])
    s = cluster(nodes)
    wave(s, jobs, "whole")
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed, full=False) == []
    assert all(len(placed[j.id]) == 1 for j in jobs[:5])
    whole = placed[jobs[5].id]
    if whole:
        # bin-packing put the five one-GPU tasks on one node
        assert len({placed[j.id][0][1] for j in jobs[:5]}) == 1
        assert len(whole[0][2][0][1]) == 8
    else:
        assert failed_dimensions(s, jobs)[jobs[5].id] == {"devices"}


# ---------------------------------- stops, and the device's resident chain

def two_cycles(chain: bool, monkeypatch):
    """Cycle 1 takes EVERY instance of the fleet; its jobs stop; cycle 2
    asks for every instance again, in two waves."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(64, 17)
    a100 = [n for n in nodes if n.resources.devices
            and n.resources.devices[0].name.startswith("A100")]
    t4 = [n for n in nodes if n.resources.devices
          and n.resources.devices[0].name.startswith("Tesla")]

    def cycle(tag):
        return ([gpu_job(tag, i, A100, 8, 1) for i in range(len(a100))]
                + [gpu_job(tag, 100 + i, ANY, 4, 1)
                   for i in range(len(t4))])

    s = cluster(nodes, chain=chain, eval_batch=8)
    first = cycle("c1")
    wave(s, first, "c1")
    p1 = placements(s, first)
    assert reference_failures(nodes, first, p1) == []
    t = s.engine.packer.update(s.state.snapshot())
    assert int(t.used[:, 3].sum()) == int(t.cap[:, 3].sum()) == sum(
        n.capacity()[3] for n in nodes)
    stop(s, first)
    t = s.engine.packer.update(s.state.snapshot())
    assert int(t.used[:, 3].sum()) == 0
    second = cycle("c2")
    wave(s, second, "c2")
    p2 = placements(s, second)
    assert reference_failures(nodes, second, p2) == []
    return p2, dict(s.executor.stats), s


def test_a_stop_gives_instances_back_on_the_resident_chain(monkeypatch):
    serial, st_serial, _ = two_cycles(False, monkeypatch)
    resident, st_res, s = two_cycles(True, monkeypatch)
    assert resident == serial                   # bit for bit
    assert st_serial["resident_waves"] == 0
    assert st_res["resident_waves"] >= 1, st_res
    assert s.plan_applier.stats["plans_refuted"] == 0
    assert s.state.quality_summary()["devices_in_use"] == sum(
        len(i) for rows in resident.values() for _, _, d in rows
        for _, i in d)


# ----------------------------------------------------- admission fallbacks

def two_requests(tag):
    return gpu_job(tag, 0, ANY, 1, 1, devices=[
        RequestedDevice(name=ANY, count=1),
        RequestedDevice(name=A100, count=1)])


def with_affinity(tag):
    return gpu_job(tag, 0, ANY, 1, 1, devices=[RequestedDevice(
        name=ANY, count=1, affinities=[Affinity(
            ltarget="${device.model}", rtarget="Tesla T4", operand="=",
            weight=50)])])


@pytest.mark.parametrize("rule, make_job, more_groups", [
    ("requests", two_requests, 0),
    ("affinity", with_affinity, 0),
    # one node of more device groups than the tensors have slots
    ("slots", lambda tag: gpu_job(tag, 0, ANY, 1, 1), DEVICE_GROUP_SLOTS),
    ("off", lambda tag: gpu_job(tag, 0, ANY, 1, 1), 0),
])
def test_what_the_rule_refuses_takes_the_solo_path(rule, make_job,
                                                   more_groups,
                                                   monkeypatch):
    monkeypatch.setattr(generic, "DEVICE_BATCHED", rule != "off")
    nodes = fleet(40, 23)
    for k in range(more_groups):
        nodes[3].resources.devices.append(group(T4, 2, f"extra{k}"))
    odd = make_job(f"fb-{rule}")
    mate = gpu_job(f"fb-{rule}", 1, ANY, 1, 1)
    before = REGISTRY.counter("nomad.device.evals_solo", rule=rule)
    s = cluster(nodes)
    wave(s, [odd, mate], f"fb-{rule}")
    assert REGISTRY.counter("nomad.device.evals_solo", rule=rule) \
        - before >= 1
    snap = s.state.snapshot()
    for job in (odd, mate):
        (a,) = [a for a in snap.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        node = snap.node_by_id(a.node_id)
        want = job.task_groups[0].tasks[0].resources.devices
        assert len(a.allocated_devices) == len(want)
        ok, dim, _ = allocs_fit(node, snap.allocs_by_node(node.id),
                                check_devices=True)
        assert ok, dim


def test_a_two_group_fleet_rides_the_wave(monkeypatch):
    """A node that advertises two device groups no longer sends the
    fleet's device asks solo: every eval rides a wave, each allocation
    holds instances of ONE group its request accepts."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(40, 23)
    nodes[3].resources.devices.append(group(T4, 2, "extra"))
    jobs = mixed_jobs("two", 8, 1)
    solo0 = counter("nomad.device.evals_solo")
    batched0 = counter("nomad.device.evals_batched")
    s = cluster(nodes)
    wave(s, jobs, "two")
    assert counter("nomad.device.evals_solo") == solo0
    assert counter("nomad.device.evals_batched") - batched0 == len(jobs)
    assert reference_failures(nodes, jobs, placements(s, jobs)) == []
    assert s.plan_applier.stats["plans_refuted"] == 0


# ------------------------------------------------- the engine's static mask

def test_static_mask_is_built_once_and_rebuilt_on_a_node_write(
        monkeypatch):
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(80, 41)
    s = cluster(nodes)
    built0 = counter("nomad.engine.device_masks_built")
    reused0 = counter("nomad.engine.device_masks_reused")
    wave(s, mixed_jobs("m1", 8, 1), "m1")
    built = counter("nomad.engine.device_masks_built") - built0
    assert built == 2                   # nvidia/gpu and the A100 model
    wave(s, mixed_jobs("m2", 8, 1), "m2")
    assert counter("nomad.engine.device_masks_built") - built0 == 2
    assert counter("nomad.engine.device_masks_reused") > reused0
    # a node write moves the node table's version: both masks again,
    # and the written node's new group is in them
    late = s.state.node_by_id(nodes[0].id).copy()
    late.resources.devices = [group(A100, 8, "late")]
    s.register_node(late, now=NOW)
    wave(s, mixed_jobs("m3", 8, 1), "m3")
    assert counter("nomad.engine.device_masks_built") - built0 == 4
    t = s.engine.packer.update(s.state.snapshot())
    snap = s.state.snapshot()
    mask = s.engine.device_static_mask(
        t, snap, RequestedDevice(name=A100, count=1))
    assert mask[t.id_to_row[late.id]]
    assert int(mask.sum()) == 1 + sum(
        1 for n in nodes if n.resources.devices
        and n.resources.devices[0].name.startswith("A100"))
    assert not mask.flags.writeable
    assert s.engine.single_group_fleet(t)


def test_solo_device_mask_walks_no_node_for_a_simple_ask(monkeypatch):
    """`_device_mask` for one request on a single-group fleet reads the
    cached static mask: no node is looked at per eval."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", False)
    nodes = fleet(60, 43)
    s = cluster(nodes)
    wave(s, [gpu_job("walk", 0, ANY, 1, 2)], "walk0")      # builds it
    snap = s.state.snapshot()
    looked = []
    real = type(snap).node_by_id
    monkeypatch.setattr(type(snap), "node_by_id",
                        lambda self, nid: looked.append(nid)
                        or real(self, nid))
    t = s.engine.packer.update(snap)
    job = gpu_job("walk", 1, ANY, 1, 2)
    mask = s.engine._device_mask(job.task_groups, t, snap, set())
    assert looked == []
    assert mask.shape == (1, t.n) and int(mask.sum()) == 12


def test_the_resource_width_is_one_constant():
    assert RES_DIMS == len(RES_NAMES) == 4 and RES_NAMES[3] == "devices"
    s = cluster(fleet(20, 1))
    t = s.engine.packer.update(s.state.snapshot())
    assert t.cap.shape == t.used.shape == (20, RES_DIMS)
    job = gpu_job("width", 0, A100, 4, 1)
    req = s.engine.packer.lower_task_groups(job, job.task_groups).req
    assert req.shape == (1, RES_DIMS) and req[0, 3] == 4
    alloc = Allocation(resources=Resources(cpu=5, memory_mb=6, disk_mb=7),
                       allocated_devices=[AllocatedDeviceResource(
                           device_ids=["a", "b"])])
    assert alloc.usage() == (5, 6, 7, 2)


# ------------------------------------------------------- the carve itself

def test_carve_takes_first_free_in_the_groups_order():
    nodes = fleet(10, 2, a100_every=(10, 3), t4_every=(10, 8))
    s = cluster(nodes)
    snap = s.state.snapshot()
    ledger = CarveLedger()
    node_ids = [n.id for n in nodes]
    req = RequestedDevice(name=ANY, count=2)
    # rows 0 and 2 on the A100 node, row 1 on the T4 node
    picks = np.array([3, 8, 3], np.int32)
    rec = ledger.open(snap.index)
    ids, groups, short = carve_block(snap, ledger, rec, req, picks,
                                     node_ids)
    assert short == []
    assert ids.tolist() == [["GPU-a3-0", "GPU-a3-1"],
                            ["GPU-t8-0", "GPU-t8-1"],
                            ["GPU-a3-2", "GPU-a3-3"]]
    assert groups == {3: ("nvidia", "gpu", "A100-SXM4-40GB"),
                      8: ("nvidia", "gpu", "Tesla T4")}
    # a mate of the same wave carves against the open record
    rec2 = ledger.open(snap.index)
    ids2, _, short2 = carve_block(snap, ledger, rec2, req,
                                  np.array([8, 3], np.int32), node_ids)
    assert short2 == []
    assert ids2.tolist() == [["GPU-t8-2", "GPU-t8-3"],
                             ["GPU-a3-4", "GPU-a3-5"]]
    # the T4 node is full now: a third mate is short there, takes
    # nothing of it, and still gets the A100 node's last two
    rec3 = ledger.open(snap.index)
    ids3, groups3, short3 = carve_block(snap, ledger, rec3, req,
                                        np.array([8, 3], np.int32),
                                        node_ids)
    assert short3 == [8] and 8 not in groups3
    assert ids3.tolist() == [["", ""], ["GPU-a3-6", "GPU-a3-7"]]
    assert nodes[8].id not in rec3[1]
    # settled at an index a later snapshot has reached: forgotten; a
    # plan that never committed: dropped whole
    ledger.settle(rec, snap.index + 1)
    ledger.settle(rec2, None)
    ledger.open(snap.index + 1)
    assert ledger.held(nodes[3].id) == {"GPU-a3-6", "GPU-a3-7"}


def test_carve_ledger_under_threads():
    """More workers than cores opening, noting, reading and settling
    records of shared nodes: no update is lost (what every thread's
    committed records hold is what the ledger reports), and a ledger
    whose records all settled at or under a later snapshot is empty."""
    import sys
    import threading
    ledger = CarveLedger()
    nodes = [f"node-{k}" for k in range(4)]
    workers, rounds = 16, 200
    errors = []

    def work(w):
        try:
            for r in range(rounds):
                rec = ledger.open(0)
                nid = nodes[(w + r) % len(nodes)]
                ids = {f"{w}-{r}-a", f"{w}-{r}-b"}
                ledger.note(rec, nid, ids)
                if not ids <= ledger.held(nid):
                    errors.append((w, r, "own carve not held"))
                # every third plan never commits, the rest at index 5
                ledger.settle(rec, None if r % 3 == 0 else 5)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append((w, repr(e)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    want = {nid: set() for nid in nodes}
    for w in range(workers):
        for r in range(rounds):
            if r % 3:
                want[nodes[(w + r) % len(nodes)]].update(
                    {f"{w}-{r}-a", f"{w}-{r}-b"})
    assert {nid: ledger.held(nid) for nid in nodes} == want
    ledger.open(5)                       # a snapshot that shows them all
    assert all(ledger.held(nid) == set() for nid in nodes)
    assert len(ledger) == 1 and not any(
        v for k, v in ledger._by_node.items())


# -------------------------------------------- the applier and the repair

def test_a_foreign_write_is_refuted_and_repaired(monkeypatch):
    """Between a wave's dispatch and its first commit a foreign write
    takes every instance of the node the first plan picked: the applier
    refutes that node on the device audit, the rows re-enter through the
    repair eval, nothing is double-booked."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(30, 13, a100_every=(10, 3), t4_every=(30, 31))
    jobs = [gpu_job("fw", i, A100, 8, 1) for i in range(2)]
    s = cluster(nodes)
    worker = s.workers[0]
    real_submit = worker.submit_plan_async
    state = {"done": False, "node": None}

    def submit(plan):
        if not state["done"] and plan.alloc_blocks:
            state["done"] = True
            block = plan.alloc_blocks[0]
            nid = block.node_table[0]
            state["node"] = nid
            dev = s.state.node_by_id(nid).resources.devices[0]
            foreign = mock.alloc()
            foreign.node_id = nid
            foreign.resources = Resources(cpu=10, memory_mb=10)
            foreign.allocated_devices = [AllocatedDeviceResource(
                task="t", vendor=dev.vendor, type=dev.type, name=dev.name,
                device_ids=list(dev.instance_ids))]
            s.state.upsert_allocs([foreign])
        return real_submit(plan)

    monkeypatch.setattr(worker, "submit_plan_async", submit)
    refuted0 = s.plan_applier.stats["plans_refuted"]
    wave(s, jobs, "fw")
    assert state["node"] is not None
    assert s.plan_applier.stats["plans_refuted"] - refuted0 >= 1
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed) == []
    assert all(r[1] != state["node"] for rows in placed.values()
               for r in rows)
    snap = s.state.snapshot()
    for n in nodes:
        if n.resources.devices:
            ok, dim, _ = allocs_fit(snap.node_by_id(n.id),
                                    snap.allocs_by_node(n.id),
                                    check_devices=True)
            assert ok, (n.id, dim)
    t = s.engine.packer.update(snap)
    assert int(t.used[:, 3].sum()) == 24


def test_the_applier_audits_device_columns(monkeypatch):
    """A block that claims one instance twice on a node, or an instance
    the node's group does not have, is refuted on that node."""
    monkeypatch.setattr(generic, "DEVICE_BATCHED", True)
    nodes = fleet(20, 19, a100_every=(10, 3), t4_every=(30, 31))
    jobs = [gpu_job("audit", i, A100, 2, 2) for i in range(2)]
    s = cluster(nodes)
    worker = s.workers[0]
    real_submit = worker.submit_plan_async
    seen = []

    def submit(plan):
        if plan.alloc_blocks and not seen:
            block = plan.alloc_blocks[0]
            seen.append(block.node_table[int(block.picks[0])])
            ids = block.device_ids.copy()
            ids[0, 1] = ids[0, 0]               # one instance twice
            block.device_ids = ids
        return real_submit(plan)

    monkeypatch.setattr(worker, "submit_plan_async", submit)
    refuted0 = s.plan_applier.stats["plans_refuted"]
    wave(s, jobs, "audit")
    assert seen and s.plan_applier.stats["plans_refuted"] > refuted0
    placed = placements(s, jobs)
    assert reference_failures(nodes, jobs, placed, full=False) == []


# ------------------------------------------------------------- the reads

@pytest.fixture(scope="module")
def served():
    """An agent with its HTTP API, 64 nodes, one wave of 8 GPU jobs of 2
    tasks placed through the threaded worker."""
    from nomad_tpu.agent import Agent
    nodes = fleet(64, 53)
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        srv.state.upsert_nodes([n.copy() for n in nodes])
        jobs = mixed_jobs("http", 8, 2)
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
        yield agent, nodes, jobs
    finally:
        agent.shutdown()


def http_get(agent, path):
    with urllib.request.urlopen(agent.address + path, timeout=60) as r:
        return json.load(r)


def test_reads_agree_on_allocated_devices(served):
    agent, nodes, jobs = served
    snap = agent.server.state.snapshot()
    assert snap.alloc_blocks() and not snap.allocs()   # columnar rows
    cols = http_get(agent, "/v1/allocations?columnar=true")["Columns"]
    node_of = dict(zip(cols["ID"], cols["NodeID"]))
    total = 0
    for job in jobs:
        (req,) = job.task_groups[0].tasks[0].resources.devices
        rows = http_get(agent, f"/v1/job/{job.id}/allocations")
        assert len(rows) == job.task_groups[0].count
        for row in rows:
            total += 1
            (dev,) = row["AllocatedDevices"]
            assert len(dev[IDS_KEY]) == req.count
            one = http_get(agent, f"/v1/allocation/{row['ID']}")
            assert one["AllocatedDevices"] == row["AllocatedDevices"]
            assert one["NodeID"] == row["NodeID"] == node_of[row["ID"]]
            by_node = [a for a in snap.allocs_by_node(row["NodeID"])
                       if a.id == row["ID"]]
            assert len(by_node) == 1
            (ad,) = by_node[0].allocated_devices
            assert ad.device_ids == dev[IDS_KEY]
            assert (ad.vendor, ad.type, ad.name) == (
                dev["Vendor"], dev["Type"], dev["Name"])
    assert total == len(node_of) == 16


def test_allocs_fit_accepts_what_the_carve_produced(served):
    agent, nodes, jobs = served
    snap = agent.server.state.snapshot()
    for n in nodes:
        allocs = snap.allocs_by_node(n.id)
        ok, dim, _ = allocs_fit(snap.node_by_id(n.id), allocs,
                                check_devices=True)
        assert ok, (n.id, dim)
        have = sum(len(d.instance_ids) for d in n.resources.devices)
        assert len(held_instances(allocs)) == sum(
            a.usage()[3] for a in allocs) <= have


def test_served_wave_counts_and_the_carve_span(served):
    """The threaded pass: every eval on a wave, none solo, the carve's
    interval recorded once an eval INSIDE that eval's materialize."""
    agent, _, jobs = served
    timers = agent.server.stage_timers
    carves = [(a, b) for _, a, b in timers.intervals("device_carve")]
    mats = [(a, b) for _, a, b in timers.intervals("materialize")]
    assert len(carves) == len(jobs)
    assert all(any(lo <= a and b <= hi for lo, hi in mats)
               for a, b in carves)
    metrics = http_get(agent, "/v1/metrics")
    names = json.dumps(metrics)
    for series in ("nomad.device.evals_batched",
                   "nomad.device.instances_carved",
                   "nomad.engine.device_masks_built"):
        assert series in names


# ------------------------------------------------ the benchmark's own files

def gpu50k():
    from benchmark.loader import load_json, load_module
    return load_json("configs", "gpu50k"), load_module("configs", "gpu50k")


def rehearsal_cycle():
    from benchmark.loader import load_json
    cfg, mod = gpu50k()
    cfg = dict(cfg, **cfg["rehearse"])
    traffic = load_json("traffic", "drain256-purge")
    per_cycle = traffic["rehearse"]["jobs_per_cycle"]
    nodes, table = mod.build_fleet(cfg, 2147483659)
    jobs = [mod.make_job(cfg, i) for i in range(per_cycle)]
    return cfg, mod, nodes, table, jobs


def sound_placements(nodes, table, jobs):
    """A hand-made sound answer: each task on a node of its own."""
    by_group = {}
    for n in nodes:
        by_group.setdefault(table[n.id][4], []).append(n.id)
    free = {g: list(ids) for g, ids in by_group.items()}
    out = {}
    for job in jobs:
        (dev,) = job["TaskGroups"][0]["Tasks"][0]["Resources"]["Devices"]
        g = A100 if dev["Name"] == A100 or dev["Count"] > 4 else T4
        out[job["ID"]] = [free[g].pop()
                          for _ in range(job["TaskGroups"][0]["Count"])]
    return out


@pytest.mark.parametrize("size", ["full", "rehearse"])
def test_the_configurations_inequality_holds(size):
    from benchmark.loader import load_json
    cfg, _ = gpu50k()
    traffic = load_json("traffic", "drain256-purge")
    if size == "rehearse":
        cfg = dict(cfg, **cfg["rehearse"])
        traffic = dict(traffic, **traffic["rehearse"])
    shapes = {s["at"]: s for s in cfg["gpu_shapes"]}
    per_node = [shapes.get(i % cfg["gpu_every"])
                for i in range(cfg["nodes"])]
    whole = sum(1 for s in per_node
                if s and s["group"] == cfg["whole_node_group"])
    have = sum(s["instances"] for s in per_node if s)
    mix = cfg["job_mix"]
    jobs = traffic["jobs_per_cycle"]
    tasks = jobs * cfg["count_per_job"]
    asked = sum(mix[i % len(mix)]["count"] for i in range(jobs)) \
        * cfg["count_per_job"]
    assert tasks < whole and asked <= have, (tasks, whole, asked, have)
    if size == "full":
        assert (tasks, asked, whole, have) == (4096, 13312, 5000, 60000)


def test_check_passes_a_sound_answer_and_names_a_doctored_one():
    cfg, mod, nodes, table, jobs = rehearsal_cycle()
    good = sound_placements(nodes, table, jobs)
    assert mod.check(cfg, table, jobs, good) == []
    plain = next(n.id for n in nodes if not table[n.id][4])
    t4 = next(n.id for n in nodes if table[n.id][4] == T4)
    whole_job = next(j for j in jobs if mod._request(j)[1] == 8)
    one_gpu = [j for j in jobs if mod._request(j)[1] == 1]
    cases = {
        "does not accept": {**good, one_gpu[0]["ID"]:
                            [plain] + good[one_gpu[0]["ID"]][1:]},
        "does not accept ": {**good, whole_job["ID"]:
                             [t4] + good[whole_job["ID"]][1:]},
        "than they have instances": {
            **good, **{j["ID"]: [t4] * len(good[j["ID"]])
                       for j in one_gpu[:2]}},
        "committed != asked": {**good, one_gpu[1]["ID"]:
                               good[one_gpu[1]["ID"]][:-1]},
    }
    for needle, doctored in cases.items():
        assert any(needle.strip() in f
                   for f in mod.check(cfg, table, jobs, doctored)), needle


def test_check_fails_loudly_on_a_cfg_over_its_inequality():
    cfg, mod, nodes, table, jobs = rehearsal_cycle()
    good = sound_placements(nodes, table, jobs)
    # as many tasks live together as there are A100 nodes: no longer
    # "an untouched node exists at every step"
    few = {nid: row for nid, row in table.items() if row[4] != A100}
    keep = [nid for nid, row in table.items() if row[4] == A100]
    tasks = sum(j["TaskGroups"][0]["Count"] for j in jobs)
    few.update((nid, table[nid]) for nid in keep[:tasks])
    failures = mod.check(cfg, few, jobs, good)
    assert any("does not hold its own inequality" in f for f in failures)


def test_multi_cost_counts_the_flat_launch_at_the_new_width():
    from benchmark import kernel_cost, multi_cost
    assert multi_cost.RES_WIDTH == RES_DIMS
    assert [multi_cost.rounds_per_eval(c) for c in (16, 64, 260, 3000)] \
        == [1, 1, 1, 3]
    c = multi_cost.flat_launch(50_000, 64, signatures=2, terms=2)
    assert c["ops"] == 64 * 50_000 * kernel_cost.ROUND_OPS_PER_CANDIDATE
    # capacity and usage read once and usage written once at RES_DIMS
    # int32 a node: 3 x 4 x 4 bytes a node of the total
    assert c["bytes"] > 50_000 * 3 * RES_DIMS * 4
    assert multi_cost.flat_launch(50_000, 128)["ops"] == 2 * c["ops"]
