"""Allocation ids come off one process-wide pool, refilled thousands at a
time by the caller that finds it short (structs/structs.py new_ids /
new_id): what an id is, that none is handed out twice across threads or a
fork, and how many runs of the minting routine the placement path pays."""

import itertools
import json
import os
import re
import sys
import threading
import time
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.scheduler import generic
from nomad_tpu.structs import structs
from nomad_tpu.structs.structs import (
    ID_DIRECT_MIN,
    ID_POOL_REFILL,
    new_id,
    new_ids,
)

UUID4 = re.compile(
    r"[0-9a-f]{8}-[0-9a-f]{4}-4[0-9a-f]{3}-[0-9a-f]{4}-[0-9a-f]{12}")


def stats():
    return dict(structs.ID_STATS)


def moved(before):
    return {k: v - before[k] for k, v in structs.ID_STATS.items()}


@pytest.mark.parametrize(
    "count", [0, 1, 31, 32, 260, 4096, 49000, ID_POOL_REFILL + 1])
def test_shape(count):
    ids = new_ids(count)
    assert len(ids) == count == len(set(ids))
    assert all(type(i) is str and UUID4.fullmatch(i) for i in ids)
    assert all(i[14] == "4" for i in ids)


def test_single_id_shape():
    assert UUID4.fullmatch(new_id()) and new_id() != new_id()


def test_a_million_ids_never_repeat():
    """Through the pool, past it and one at a time: as many distinct
    strings as were asked for."""
    got = []
    counts = itertools.cycle((1, 16, 260, 3000, 49000))
    while len(got) < 1_000_000:
        got.extend(new_ids(next(counts)))
    got.extend(new_id() for _ in range(10_000))
    assert len(set(got)) == len(got)


def test_eight_threads_share_no_id():
    """Mixed counts from the pool at once; a take that were slice-then-
    delete under the interpreter lock alone hands two callers one id."""
    assert 3000 <= ID_DIRECT_MIN          # all four counts are the pool's
    got = [[] for _ in range(8)]
    start = threading.Barrier(8)

    def take(out, k):
        start.wait()
        for r in range(400):
            count = (1, 16, 260, 3000)[(r + k) % 4]
            out.extend(new_ids(count) if count > 1 else [new_id()])

    before = stats()
    threads = [threading.Thread(target=take, args=(got[k], k))
               for k in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)       # a torn take shows sooner
    try:
        [t.start() for t in threads]
        [t.join(120) for t in threads]
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    total = sum(len(g) for g in got)
    assert total == 8 * 100 * (1 + 16 + 260 + 3000)
    assert len(set().union(*got)) == total
    m = moved(before)
    assert m["pool_served"] == total and m["minted_direct"] == 0
    assert m["pool_refills"] <= total // ID_POOL_REFILL + 2


def test_new_id_and_new_ids_drain_one_pool():
    new_id()                                  # the pool exists
    if len(structs._id_pool) < 300:
        structs._id_pool.clear()
        new_id()
    held = list(structs._id_pool)
    before = stats()
    one = new_id()
    some = new_ids(260)
    assert one == held[-1] and some == held[-261:-1]
    assert structs._id_pool == held[:-261]
    assert moved(before) == {"pool_refills": 0, "pool_served": 261,
                             "minted_direct": 0}


@pytest.mark.parametrize("count", [ID_DIRECT_MIN + 1, 49000])
def test_a_large_count_mints_past_the_pool(count):
    new_id()
    held = list(structs._id_pool)
    before = stats()
    ids = new_ids(count)
    assert structs._id_pool == held and not set(ids) & set(held)
    assert moved(before) == {"pool_refills": 0, "pool_served": 0,
                             "minted_direct": count}


def test_a_short_pool_refills_in_the_callers_call():
    """No thread mints ahead: the refill is the take that ran short."""
    structs._id_pool[:] = new_ids(10)
    before, threads = stats(), threading.active_count()
    ids = new_ids(260)
    assert len(set(ids)) == 260
    assert len(structs._id_pool) == 10 + ID_POOL_REFILL - 260
    assert moved(before)["pool_refills"] == 1
    assert threading.active_count() == threads


@pytest.mark.skipif(not hasattr(os, "fork"), reason="no fork here")
def test_a_forked_child_starts_with_an_empty_pool():
    new_id()
    assert structs._id_pool
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:                              # the child: report and go
        try:
            empty = not structs._id_pool
            ids = new_ids(4) + [new_id()]
            os.write(w, (f"{int(empty)} " + " ".join(ids)).encode())
        finally:
            os._exit(0)
    os.close(w)
    deadline = time.monotonic() + 60          # a hung child fails, not hangs
    while os.waitpid(pid, os.WNOHANG) == (0, 0):
        if time.monotonic() > deadline:
            os.kill(pid, 9)
        time.sleep(0.01)
    empty, *child_ids = os.read(r, 4096).decode().split()
    os.close(r)
    assert empty == "1" and len(child_ids) == 5
    assert all(UUID4.fullmatch(i) for i in child_ids)
    assert not set(child_ids) & set(new_ids(4) + [new_id()])
    assert not set(child_ids) & set(structs._id_pool)


@pytest.mark.parametrize("calls, count", [
    (64, 260),             # a csi50k wave's evals: 64 runs before the pool
    (3000, 1),             # a solo eval's rows via new_id(): 12 before
    (64, 16),              # a gpu50k wave's evals
])
def test_one_run_of_the_minting_routine(calls, count, monkeypatch):
    """The count that pins the mechanism where no chip is: os.urandom
    calls for a wave's worth of takes from an empty pool."""
    structs._id_pool.clear()
    real, seen = os.urandom, []

    def counted(n):
        seen.append(n)
        return real(n)

    monkeypatch.setattr(structs.os, "urandom", counted)
    for _ in range(calls):
        new_ids(count) if count > 1 else new_id()
    assert seen == [16 * ID_POOL_REFILL]


def test_refill_size_is_within_the_range_measured():
    assert 8192 <= ID_POOL_REFILL <= 65536
    assert 4096 <= ID_DIRECT_MIN < 49000


# ------------------------------------------------- the counters, served

def test_a_served_wave_moves_the_counters(monkeypatch):
    """An agent with its HTTP API, 64 nodes, one wave of four batch jobs
    x 70 through the threaded worker: the rows' ids are the pool's,
    `pool_served` moves by the placements (and the plans' few singles),
    and /v1/metrics carries the three series in both formats."""
    from nomad_tpu.agent import Agent

    asked = []
    real = generic.new_ids
    monkeypatch.setattr(generic, "new_ids",
                        lambda n: asked.append(n) or real(n))
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        nodes = []
        for i in range(64):
            node = mock.node()
            node.datacenter = f"dc{1 + i % 3}"
            nodes.append(node)
        srv.state.upsert_nodes(nodes)
        jobs = []
        for i in range(4):
            job = mock.batch_job()
            job.id = job.name = f"ids-wave-{i}"
            job.datacenters = ["dc1", "dc2", "dc3"]
            job.task_groups[0].count = 70
            job.task_groups[0].tasks[0].resources.cpu = 10
            job.task_groups[0].tasks[0].resources.memory_mb = 10
            jobs.append(job)
        srv.stop_scheduling()
        for job in jobs:
            srv.register_job(job)
        before = stats()
        srv.start_scheduling()
        deadline = time.monotonic() + 120
        placed = 0
        while placed < 280 and time.monotonic() < deadline:
            time.sleep(0.05)
            snap = srv.state.snapshot()
            placed = sum(1 for j in jobs
                         for a in snap.allocs_by_job(j.namespace, j.id)
                         if not a.terminal_status())
        assert placed == 280 == sum(asked)
        m = moved(before)
        assert m["minted_direct"] == 0 and m["pool_refills"] <= 1
        assert placed <= m["pool_served"] <= placed + 16 * len(jobs)
        with urllib.request.urlopen(agent.address + "/v1/metrics",
                                    timeout=60) as r:
            flat = json.load(r)
        for k in structs.ID_STATS:
            assert flat[f"nomad.ids.{k}"] >= before[k] + m[k]
        with urllib.request.urlopen(
                agent.address + "/v1/metrics?format=prometheus",
                timeout=60) as r:
            text = r.read().decode()
        assert "# TYPE nomad_ids_pool_refills counter" in text
        assert re.search(r"^nomad_ids_pool_served \d+$", text, re.M)
        assert re.search(r"^nomad_ids_minted_direct \d+$", text, re.M)
        assert (REGISTRY.snapshot()["counters"]["nomad.ids.pool_served"]
                == structs.ID_STATS["pool_served"])
    finally:
        agent.shutdown()
