"""Static ports as a feasibility rule of the kernels, and small networked
evals on the columnar carve (ISSUE 38).

The scan and the flat multi-eval kernel carry, per static port value a
launch's groups ask, the nodes that hold it: a static ask passes by a
node whose port is taken, by a live allocation, by a wave-mate or by its
own job earlier in the launch.  Held here to upstream's outcome (every
static ask with a free node is placed, on a node of its own), to the
sequential NetworkIndex oracle (`generic.PORT_BATCHED = False`) bit for
bit, and to the configuration `ports50k` with its checker, its readers
and its cell rehearsed on the CPU.  Node, job and eval ids are pinned, so
two runs of one scenario compute the same placements.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.loader import load_json, load_module  # noqa: E402
from nomad_tpu import mock  # noqa: E402
from nomad_tpu.core.server import Server  # noqa: E402
from nomad_tpu.core.telemetry import REGISTRY  # noqa: E402
from nomad_tpu.ops import engine as engine_mod  # noqa: E402
from nomad_tpu.ops import select  # noqa: E402
from nomad_tpu.ops.engine import BatchItem, PlacementEngine  # noqa: E402
from nomad_tpu.scheduler import Harness, generic  # noqa: E402
from nomad_tpu.structs import (  # noqa: E402
    MAX_DYNAMIC_PORT,
    MIN_DYNAMIC_PORT,
    OP_DISTINCT_HOSTS,
    Constraint,
    NetworkResource,
    Port,
)

NOW = 1_700_000_000.0
CELL = "ports50k-drain"


# ----------------------------------------------------------------- fleet

def fleet(n: int, cpu: int = 16000, mem: int = 32768, tag: str = "pw"):
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = f"{tag}-node-{i:04d}"
        node.name = f"node-{i}"
        node.resources.cpu = cpu
        node.resources.memory_mb = mem
        nodes.append(node)
    return nodes


def networked(job_id: str, count: int, static=(), dynamic=("http",),
              cpu: int = 100, mem: int = 64, distinct: bool = False,
              batch: bool = True):
    """A job of `count` whose group carries ONE network block: static
    ports `static` ({label: value}) and dynamic ports `dynamic`."""
    job = mock.batch_job() if batch else mock.job()
    job.id = job.name = job_id
    job.update = None
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    if static or dynamic:
        tg.networks = [NetworkResource(
            reserved_ports=[Port(label=lb, value=v)
                            for lb, v in dict(static).items()],
            dynamic_ports=[Port(label=lb) for lb in dynamic])]
    if distinct:
        tg.constraints.append(Constraint(operand=OP_DISTINCT_HOSTS,
                                         rtarget="true"))
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


def committed(snap, jobs):
    """{(job, name): (node, ports)} of the jobs' live allocations."""
    return {(j.id, a.name): (a.node_id,
                             tuple(sorted(a.allocated_ports.items())))
            for j in jobs for a in live(snap, j)}


def no_port_twice(snap, jobs):
    seen = set()
    for j in jobs:
        for a in live(snap, j):
            for port in a.allocated_ports.values():
                assert (a.node_id, port) not in seen, (a.node_id, port)
                seen.add((a.node_id, port))
    return seen


def counters(*names):
    return {n: REGISTRY.counter_sum(n) for n in names}


# ------------------------------------- the issue's 40-node case, both paths

@pytest.mark.parametrize("eval_batch", [1, 8])
def test_every_static_ask_with_a_free_node_is_placed(eval_batch):
    """Six jobs of 4 asking static 8080 and two dynamic-only jobs on 40
    nodes: every static ask has a free node, so all 24 are placed, on 24
    different nodes, with no blocked eval (6 and 12 of 24 before the
    kernels saw the port)."""
    s = cluster(fleet(40), eval_batch=eval_batch)
    jobs = [networked(f"lb-{i}", 4, static={"lb": 8080}) for i in range(6)]
    jobs += [networked(f"api-{i}", 4) for i in range(2)]
    before = counters("nomad.ports.runner_up_redirects")
    wave(s, jobs)
    snap = s.state.snapshot()
    holders = [a for j in jobs[:6] for a in live(snap, j)]
    assert len(holders) == 24
    assert len({a.node_id for a in holders}) == 24
    assert all(a.allocated_ports["lb"] == 8080 for a in holders)
    assert all(len(live(snap, j)) == 4 for j in jobs[6:])
    assert not [e for e in snap.evals() if e.status == "blocked"]
    assert all(not snap.eval_by_id(f"eval-{j.id}").failed_tg_allocs
               for j in jobs)
    no_port_twice(snap, jobs)
    # nothing was moved off its pick by the host
    assert counters("nomad.ports.runner_up_redirects") == before
    s.shutdown()


def test_static_ask_without_a_free_node_fails_by_name():
    """Three nodes, four asks of one value: three placed, the fourth
    fails with upstream's dimension and leaves a blocked eval."""
    s = cluster(fleet(3), eval_batch=8)
    jobs = [networked(f"lb-{i}", 2, static={"lb": 443}, dynamic=())
            for i in range(2)]
    wave(s, jobs)
    snap = s.state.snapshot()
    assert sum(len(live(snap, j)) for j in jobs) == 3
    failed = [snap.eval_by_id(f"eval-{j.id}").failed_tg_allocs for j in jobs]
    (metric,) = [m for f in failed for m in f.values()]
    assert metric.dimension_exhausted == {
        "network: reserved port collision 443": 3}
    assert metric.nodes_exhausted == 3 and metric.nodes_filtered == 0
    assert [e for e in snap.evals() if e.status == "blocked"]
    s.shutdown()


# ------------------------------------------------ the sequential oracle

def mix_jobs(n: int, tag: str):
    """`n` jobs of ports50k's mix (job_mix by i % 8), tiny asks."""
    cfg = load_json("configs", "ports50k")
    jobs = []
    for i in range(n):
        kind = cfg["job_mix"][i % 8]
        static = {lb: cfg["static_ports"][(i // 8) % 4]
                  for lb in kind.get("static", ())}
        jobs.append(networked(
            f"{tag}-{i:03d}", kind["count"], static=static,
            dynamic=kind.get("dynamic", ()), cpu=kind["cpu_mhz"],
            mem=kind["memory_mb"],
            distinct=kind.get("distinct_hosts", False), batch=False))
    return jobs


def run_mix(batched: bool, monkeypatch, eval_batch: int):
    monkeypatch.setattr(generic, "PORT_BATCHED", batched)
    s = cluster(fleet(60, cpu=8000, mem=16384), eval_batch=eval_batch)
    before = counters("nomad.ports.batched_rows",
                      "nomad.ports.sequential_rows")
    first, second = mix_jobs(16, "mix-a"), mix_jobs(16, "mix-b")
    wave(s, first)
    wave(s, second)        # meets the ports the first wave left
    snap = s.state.snapshot()
    out = committed(snap, first + second)
    no_port_twice(snap, first + second)
    after = counters("nomad.ports.batched_rows",
                     "nomad.ports.sequential_rows")
    s.shutdown()
    return out, {k: after[k] - before[k] for k in after}


def test_wave_of_the_mix_equals_the_sequential_oracle(monkeypatch):
    """Two waves of the rehearsal's mix through the columnar carve and
    through the per-allocation oracle: the same (job, name) -> (node,
    ports), bit for bit; the carve takes every networked row (small
    evals too), the oracle none."""
    got, rows = run_mix(True, monkeypatch, 16)
    want, oracle_rows = run_mix(False, monkeypatch, 16)
    assert len(got) == 2 * 110
    assert got == want
    networked_rows = 2 * (110 - 2 * 6)
    assert rows == {"nomad.ports.batched_rows": networked_rows,
                    "nomad.ports.sequential_rows": 0}
    assert oracle_rows == {"nomad.ports.batched_rows": 0,
                           "nomad.ports.sequential_rows": networked_rows}


def test_solo_block_with_a_static_port_equals_the_oracle(monkeypatch):
    """The Harness runs an eval alone: a block of 70 asking a static and
    two dynamic ports leaves the scan as arrays and is carved as columns;
    the oracle assigns the same ports one allocation at a time."""
    def run(batched):
        monkeypatch.setattr(generic, "PORT_BATCHED", batched)
        h = Harness()
        for n in fleet(80, tag="hb"):
            h.state.upsert_node(n)
        jobs = [networked("edge", 70, static={"lb": 8080},
                          dynamic=("admin", "metrics")),
                networked("edge-2", 6, static={"lb": 8080})]
        for job in jobs:
            h.state.upsert_job(job)
            ev = mock.eval(job_id=job.id, type=job.type)
            ev.id = f"eval-{job.id}"
            assert h.process(job.type, ev) is None
        snap = h.state.snapshot()
        no_port_twice(snap, jobs)
        return committed(snap, jobs), bool(h.plans[0].alloc_blocks)

    got, as_block = run(True)
    want, oracle_block = run(False)
    assert as_block and not oracle_block
    assert len(got) == 76 and got == want
    assert len({node for node, _ in got.values()}) == 76


# ----------------------------------------------------------- wave-mates

def test_wave_mates_asking_one_value_never_share_a_node():
    """Eight mates of 3 ask one value on 30 nodes whose bin-packing
    would pile them all onto the first: 24 nodes, one each; a mate that
    asks another value may share them."""
    s = cluster(fleet(30), eval_batch=16)
    same = [networked(f"lb-{i}", 3, static={"lb": 9090}) for i in range(8)]
    other = networked("other", 3, static={"lb": 80})
    wave(s, same + [other])
    snap = s.state.snapshot()
    nodes = [a.node_id for j in same for a in live(snap, j)]
    assert len(nodes) == 24 == len(set(nodes))
    assert len({a.node_id for a in live(snap, other)}) == 3
    assert {a.node_id for a in live(snap, other)} & set(nodes)
    no_port_twice(snap, same + [other])
    s.shutdown()


def test_chained_wave_sees_the_ports_of_the_wave_before():
    """Two launches with nothing committed between them: the second
    starts from the first's port state (executor chain), not from the
    store, and passes the first's nodes by."""
    h = Harness()
    eng = PlacementEngine(mesh=False)
    eng.packer.attach(h.state)
    for n in fleet(6, tag="ch"):
        h.state.upsert_node(n)
    jobs = [networked(f"lb-{i}", 2, static={"lb": 8080}) for i in range(3)]
    for job in jobs:
        h.state.upsert_job(job)
    snap = h.state.snapshot()
    items = [BatchItem(job=j, tg=j.task_groups[0], count=2) for j in jobs]
    first = eng.dispatch_batch(snap, items[:2], seed=[1, 2])
    picks_a = [d.picks.tolist() for d in eng.collect_batch(first)]
    values, taken, rest = first["ports"]
    assert values == (8080,) and rest == {}
    assert taken.shape[0] == engine_mod.PORT_SLOTS_MIN
    chain = (first["used"], first["node_version"], first["npad"],
             first["ports"])
    second = eng.dispatch_batch(snap, items[2:] * 2, seed=[3, 3],
                                used0_dev=chain)
    picks_b = [d.picks.tolist() for d in eng.collect_batch(second)]
    assert second["chained"]
    used = {p for ps in picks_a for p in ps}
    assert len(used) == 4
    # two nodes are left: the first item takes them, the second finds none
    assert set(picks_b[0]) == set(range(6)) - used
    assert picks_b[1] == [-1, -1]
    # a wave that asks no static port hands the state on as it got it
    plain = [BatchItem(job=j, tg=j.task_groups[0], count=1) for j in (
        networked("p1", 1), networked("p2", 1))]
    for it in plain:
        h.state.upsert_job(it.job)
    third = eng.dispatch_batch(
        snap, plain, seed=[4, 5],
        used0_dev=(second["used"], second["node_version"], second["npad"],
                   second["ports"]))
    eng.collect_batch(third)
    assert third["ports"] is second["ports"]
    # a wave that asks ANOTHER value takes that one's holders from the
    # state and hands 8080's on beside them, out of the kernel's way
    other = networked("other", 1, static={"lb": 443})
    h.state.upsert_job(other)
    fourth = eng.dispatch_batch(
        snap, [BatchItem(job=other, tg=other.task_groups[0], count=1)] * 2,
        seed=[6, 7],
        used0_dev=(third["used"], third["node_version"], third["npad"],
                   third["ports"]))
    eng.collect_batch(fourth)
    values, taken, rest = fourth["ports"]
    assert values == (443,) and list(rest) == [8080]
    assert int(np.asarray(rest[8080]).sum()) == 6
    assert int(np.asarray(taken).sum()) == 2


# ------------------------------------------------- the state's own holders

def test_live_holder_masks_its_node_until_it_stops():
    """A static value held by a live allocation masks its node; once the
    allocation stops the node is free again (the packer's port ledger,
    kept by the alloc events)."""
    s = cluster(fleet(2), eval_batch=1)
    first = networked("first", 2, static={"lb": 8080})
    wave(s, [first])
    snap = s.state.snapshot()
    assert len({a.node_id for a in live(snap, first)}) == 2
    packer = s.engine.packer
    assert sorted(packer.static_port_holders(8080)[1]) == sorted(
        a.node_id for a in live(snap, first))
    second = networked("second", 1, static={"lb": 8080})
    wave(s, [second])
    assert not live(s.state.snapshot(), second)
    # one holder stops: its node, and only it, takes the next ask
    gone = live(snap, first)[0]
    upd = gone.copy_skip_job()
    upd.client_status = "complete"
    s.state.update_allocs_from_client([upd])
    assert packer.static_port_holders(8080)[1] == [
        a.node_id for a in live(snap, first)[1:]]
    # (the blocked eval `second` left may wake and take it first)
    third = networked("third", 1, static={"lb": 8080})
    wave(s, [third])
    snap = s.state.snapshot()
    (placed,) = live(snap, second) + live(snap, third)
    assert placed.node_id == gone.node_id
    s.shutdown()


def test_ledger_counts_blocks_rows_and_node_reservations():
    from nomad_tpu.pack.packer import ClusterPacker
    h = Harness()
    nodes = fleet(70, tag="ld")
    nodes[0].reserved.reserved_ports = [22]
    for n in nodes:
        h.state.upsert_node(n)
    packer = h.engine.packer
    h.engine.packer.update(h.state.snapshot())
    assert packer.static_port_holders(22)[1] == [nodes[0].id]
    job = networked("wide", 66, static={"lb": 8080})
    h.state.upsert_job(job)
    ev = mock.eval(job_id=job.id, type=job.type)
    assert h.process(job.type, ev) is None
    assert h.plans[-1].alloc_blocks               # committed as ONE block
    v0, holders = packer.static_port_holders(8080)
    assert len(holders) == 66 == len(set(holders))
    # a row of the block stops: the block is materialized, the unit's
    # holders become the rows' own, one leaves
    row = live(h.state.snapshot(), job)[5]
    upd = row.copy_skip_job()
    upd.client_status = "failed"
    h.state.update_allocs_from_client([upd])
    v1, holders = packer.static_port_holders(8080)
    assert v1 > v0 and len(holders) == 65 and row.node_id not in holders
    # a rebuild from the snapshot reads the same holders
    fresh = ClusterPacker()
    fresh.update(h.state.snapshot())
    assert sorted(fresh.static_port_holders(8080)[1]) == sorted(holders)
    assert fresh.static_port_holders(22)[1] == [nodes[0].id]


def test_port_masks_are_built_once_a_version_of_their_holders():
    h = Harness()
    eng = PlacementEngine(mesh=False)
    eng.packer.attach(h.state)
    for n in fleet(8, tag="mk"):
        h.state.upsert_node(n)
    t = eng.packer.update(h.state.snapshot())
    before = counters("nomad.engine.port_masks_built",
                      "nomad.engine.port_masks_reused")
    a = eng.static_port_mask(t, 8, 8080)
    b = eng.static_port_mask(t, 8, 8080)
    assert a is b and not np.asarray(a).any()
    holder = mock.alloc(node_id=t.node_ids[3])
    holder.resources = networked("x", 1, static={"lb": 8080}
                                 ).task_groups[0].combined_resources()
    h.state.upsert_allocs([holder])
    c = eng.static_port_mask(eng.packer.update(h.state.snapshot()), 8, 8080)
    assert np.flatnonzero(np.asarray(c)).tolist() == [3]
    after = counters("nomad.engine.port_masks_built",
                     "nomad.engine.port_masks_reused")
    assert after["nomad.engine.port_masks_built"] - before[
        "nomad.engine.port_masks_built"] == 2
    assert after["nomad.engine.port_masks_reused"] - before[
        "nomad.engine.port_masks_reused"] == 1


# --------------------------------------------------- distinct_hosts + ports

@pytest.mark.parametrize("eval_batch", [1, 8])
def test_distinct_hosts_beside_ports(eval_batch):
    s = cluster(fleet(12), eval_batch=eval_batch)
    ha = networked("ha", 10, dynamic=("http",), distinct=True, batch=False)
    lb = networked("lb", 3, static={"lb": 80}, dynamic=("admin",),
                   distinct=True, batch=False)
    api = networked("api", 8, dynamic=("http", "metrics"), batch=False)
    wave(s, [ha, lb, api])
    snap = s.state.snapshot()
    assert len({a.node_id for a in live(snap, ha)}) == 10
    assert len({a.node_id for a in live(snap, lb)}) == 3
    assert len(live(snap, api)) == 8
    seen = no_port_twice(snap, [ha, lb, api])
    dynamic = [p for _, p in seen if p != 80]
    assert len(dynamic) == 10 + 3 + 16
    assert all(MIN_DYNAMIC_PORT <= p <= MAX_DYNAMIC_PORT for p in dynamic)
    s.shutdown()


# ------------------------------------------------------------ the kernels

def _multi_inputs(n=12, items=3, count=2, asks=((0,), (0,), (1,))):
    """A hand-built wave over `n` equal nodes: item g of `count` asks
    the static slots `asks[g]`."""
    import jax.numpy as jnp
    from nomad_tpu.structs import RES_DIMS
    g = items
    kp = 4
    cap = np.zeros((n, RES_DIMS), np.int32)
    cap[:, :3] = (16000, 32768, 100000)
    req = np.zeros((g, RES_DIMS), np.int32)
    req[:, :3] = (100, 64, 10)
    pt_ask = np.zeros((g, kp), bool)
    for gi, slots in enumerate(asks):
        pt_ask[gi, list(slots)] = True
    taken0 = np.zeros((kp, n), bool)
    taken0[0, :2] = True                  # slot 0 held on nodes 0 and 1
    return select.MultiEvalInputs(
        attrs=jnp.zeros((n, 1), jnp.int32), cap=jnp.asarray(cap),
        used0=jnp.zeros((n, RES_DIMS), jnp.int32),
        elig=jnp.ones(n, bool), luts=jnp.zeros((1, 1), bool),
        base_mask=jnp.ones((1, n), bool),
        con=jnp.zeros((1, 1, 3), jnp.int32), u_mask=jnp.zeros(1, jnp.int32),
        aff=jnp.zeros((1, 1, 4), jnp.int32), req=jnp.asarray(req),
        desired=jnp.full(g, count, jnp.int32),
        dh_limit=jnp.zeros(g, jnp.int32),
        g_static=jnp.zeros(g, jnp.int32), g_aff=jnp.zeros(g, jnp.int32),
        g_job=jnp.arange(g, dtype=jnp.int32),
        job_count0=jnp.zeros((g, n), jnp.int32),
        spread_algo=jnp.asarray(False),
        round_g=jnp.arange(4, dtype=jnp.int32) % g,
        round_want=jnp.asarray([count] * g + [0] * (4 - g), jnp.int32),
        seed=jnp.zeros(g, jnp.uint32),
        pt_taken0=jnp.asarray(taken0), pt_ask=jnp.asarray(pt_ask))


def test_flat_kernel_carries_port_state_across_rounds():
    inp = _multi_inputs()
    buf, used, _, taken = select.place_multi_packed(inp, 64)
    buf = np.asarray(buf)
    fills, meta = buf[:, :64], buf[:, 64:]
    rows = [sorted((f >> 11)[(f & 2047) > 0].tolist()) for f in fills[:3]]
    # item 0: the two best free nodes past the holders, one each; item 1
    # (same value): the next two; item 2 (another value, held nowhere):
    # bin-packing's choice, the nodes item 0 warmed
    assert rows == [[2, 3], [4, 5], [2, 3]]
    assert (fills[:3] & 2047).max() == 1
    # column 14: the nodes that hold a value the round asks, as it leaves
    assert meta[:3, 14].tolist() == [4, 6, 2]
    assert meta[:3, 8].tolist() == [4, 6, 2]        # exhausted, not filtered
    assert meta[:3, 7].tolist() == [0, 0, 0]
    taken = np.asarray(taken)
    assert np.flatnonzero(taken[0]).tolist() == [0, 1, 2, 3, 4, 5]
    assert np.flatnonzero(taken[1]).tolist() == [2, 3]
    assert not taken[2:].any()
    assert np.asarray(used)[:6, 0].tolist() == [0, 0, 200, 200, 100, 100]


def test_flat_kernel_without_port_state_is_the_program_it_was():
    inp = _multi_inputs()._replace(pt_taken0=None, pt_ask=None)
    out = select.place_multi_packed(inp, 64)
    assert len(out) == 3
    fills = np.asarray(out[0])[:3, :64]
    # bin-packing piles each item onto one node
    assert [(f & 2047).max() for f in fills] == [2, 2, 2]


def test_scan_places_a_static_ask_one_a_node():
    """The exact scan through the engine: four placements of one eval on
    a fleet whose bin-packing prefers one node take four nodes, and a
    fifth ask on four free nodes names the collision."""
    h = Harness()
    eng = PlacementEngine(mesh=False)
    for n in fleet(5, tag="sc"):
        h.state.upsert_node(n)
    job = networked("scan", 6, static={"lb": 8080})
    from nomad_tpu.ops.engine import PlacementRequest
    ds = eng.place(h.state.snapshot(), job, job.task_groups,
                   [PlacementRequest(tg_name=job.task_groups[0].name)] * 6)
    assert len({d.node_id for d in ds[:5]}) == 5
    assert ds[5].node_id is None
    assert ds[5].metric.dimension_exhausted == {
        "network: reserved port collision 8080": 5}


# --------------------------------------------------------------- applier

def _static_block(job, nodes, block_id="blk-static"):
    from nomad_tpu.structs import AllocBlock, Allocation, new_ids
    tmpl = Allocation(
        namespace=job.namespace, job_id=job.id, job=job,
        task_group=job.task_groups[0].name, desired_status="run",
        client_status="pending",
        resources=job.task_groups[0].combined_resources())
    uniq = sorted(set(nodes))
    row = {nid: i for i, nid in enumerate(uniq)}
    n = len(nodes)
    return AllocBlock(
        id=block_id, template=tmpl, ids=new_ids(n),
        name_prefix=f"{job.id}.{job.task_groups[0].name}[",
        indexes=list(range(n)),
        picks=np.array([row[nid] for nid in nodes], np.int32),
        node_table=uniq, metrics=[], round_size=max(n, 1),
        port_labels=["lb", "admin"],
        ports=np.array([[8080, MIN_DYNAMIC_PORT + i] for i in range(n)],
                       np.int32))


@pytest.mark.parametrize("planted", ["live-holder", "in-plan"])
def test_applier_refutes_a_planted_static_duplicate(planted):
    from nomad_tpu.core import PlanApplier, PlanQueue
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Plan
    state = StateStore()
    q = PlanQueue()
    q.set_enabled(True)
    applier = PlanApplier(state, q)
    n1, n2 = fleet(2, tag="ap")
    for n in (n1, n2):
        state.upsert_node(n)
    job = networked("lb", 2, static={"lb": 8080}, dynamic=("admin",))
    state.upsert_job(job)
    plan = Plan(eval_id="e1", job=job)
    if planted == "live-holder":
        holder = mock.alloc(job=job, node_id=n1.id)
        holder.resources = job.task_groups[0].combined_resources()
        holder.allocated_ports = {"lb": 8080, "admin": 31000}
        state.upsert_allocs([holder])
        plan.alloc_blocks.append(_static_block(job, [n1.id, n2.id]))
    else:
        plan.alloc_blocks.append(_static_block(job, [n1.id], "blk-a"))
        plan.alloc_blocks.append(_static_block(job, [n1.id, n2.id], "blk-b"))
    pending = q.enqueue(plan)
    applier.apply_one(pending)
    result, err = pending.wait(1)
    assert err is None
    assert result.refuted_nodes == [n1.id]
    rows = [a for a in state.snapshot().allocs_by_node(n2.id)
            if not a.terminal_status()]
    assert [a.allocated_ports["lb"] for a in rows] == [8080]


# ------------------------------------------------- admission and counters

def test_refusals_are_named_and_counted(monkeypatch):
    h = Harness()
    for n in fleet(4, tag="rf"):
        h.state.upsert_node(n)
    wide = networked("wide", 2, static={f"p{i}": 9000 + i for i in range(
        generic.PORT_WAVE_MAX_STATIC + 1)})
    lb = networked("lb", 2, static={"lb": 8080})
    api = networked("api", 2)
    before = REGISTRY.counter_labels("nomad.ports.evals_solo")
    for job, engine, want in (
            (wide, PlacementEngine(mesh=False), "static_count"),
            (lb, h.engine, "mesh"),             # the tests' 8 devices
            (lb, PlacementEngine(mesh=False), ""),
            (api, h.engine, "")):
        h.state.upsert_job(job)
        sched = generic.GenericScheduler(h.state.snapshot(), h, is_batch=True,
                                         engine=engine, now=NOW)
        ev = mock.eval(job_id=job.id, type=job.type)
        prep = sched.prepare_batch(ev)
        assert (prep is None) == bool(want), (job.id, want)
    after = REGISTRY.counter_labels("nomad.ports.evals_solo")
    grew = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    assert grew == {"rule=static_count": 1, "rule=mesh": 1}


@pytest.fixture(scope="module")
def served():
    """An agent with its HTTP API, 40 nodes, one wave of ports50k's mix
    through the threaded worker."""
    from nomad_tpu.agent import Agent
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        srv = agent.server
        srv.state.upsert_nodes(fleet(40, cpu=8000, mem=16384, tag="sv"))
        jobs = mix_jobs(16, "served")
        names = ("nomad.ports.evals_batched", "nomad.ports.evals_solo",
                 "nomad.ports.batched_rows", "nomad.ports.sequential_rows",
                 "nomad.ports.runner_up_redirects",
                 "nomad.engine.port_masks_built")
        before = counters(*names)
        srv.stop_scheduling()
        for job in jobs:
            srv.register_job(job)
        srv.start_scheduling()
        want = sum(j.task_groups[0].count for j in jobs)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            snap = srv.state.snapshot()
            if sum(len(live(snap, j)) for j in jobs) == want:
                break
            time.sleep(0.05)
        after = counters(*names)
        with urllib.request.urlopen(agent.address + "/v1/metrics") as r:
            metrics = json.loads(r.read())
        yield SimpleNamespace(
            server=srv, jobs=jobs, want=want, metrics=metrics,
            grew={k: after[k] - before[k] for k in names})
    finally:
        agent.shutdown()


def test_served_wave_counts_itself(served):
    snap = served.server.state.snapshot()
    assert sum(len(live(snap, j)) for j in served.jobs) == served.want
    no_port_twice(snap, served.jobs)
    assert served.grew["nomad.ports.evals_batched"] == 14
    assert served.grew["nomad.ports.evals_solo"] == 0
    assert served.grew["nomad.ports.batched_rows"] == 110 - 12
    assert served.grew["nomad.ports.sequential_rows"] == 0
    assert served.grew["nomad.ports.runner_up_redirects"] == 0
    # 80 and 443: the two load balancers of sixteen jobs
    assert served.grew["nomad.engine.port_masks_built"] == 2


def test_counters_are_on_the_metrics_endpoint(served):
    text = json.dumps(served.metrics)
    for series in ("nomad.ports.evals_batched", "nomad.ports.batched_rows",
                   "nomad.engine.port_masks_built",
                   "nomad.wavepipe.port_assign_s"):
        assert series in text, series


def test_port_assign_lies_inside_materialize(served):
    timers = served.server.stage_timers
    assigns = [(a, b) for _, a, b in timers.intervals("port_assign")]
    mats = [(a, b) for _, a, b in timers.intervals("materialize")]
    assert len(assigns) == 14          # one an eval that asks ports
    assert all(any(lo <= a and b <= hi for lo, hi in mats)
               for a, b in assigns)


# --------------------------------------------------- the configuration

def ports50k():
    cfg = load_json("configs", "ports50k")
    return dict(cfg, **cfg["rehearse"]), load_module("configs", "ports50k")


def test_configuration_states_its_mix():
    cfg, mod = ports50k()
    full = load_json("configs", "ports50k")
    counts = [m["count"] for m in full["job_mix"]]
    assert sum(counts) / len(counts) == full["count_per_job"] == 6.875
    traffic = load_json("traffic", "drain256-keep")
    assert traffic["jobs_per_cycle"] * full["count_per_job"] == 1760
    assert "between_cycles" not in traffic
    jobs = [mod.make_job(cfg, i) for i in range(32)]
    assert [mod.static_asks(j) for j in jobs[6::8]] == [
        [80], [443], [8080], [9090]]
    assert [mod.distinct_hosts(j) for j in jobs[:8]] == [
        False] * 4 + [True] + [False] * 3
    assert all("Update" not in j or not j["Update"] for j in jobs)
    base = load_json("configs", "csi50k")
    twin = load_module("configs", "csi50k")
    a = mod.build_fleet(cfg, 2147483683)
    b = twin.build_fleet(dict(base, **cfg["rehearse"]), 2147483683)
    assert [n.id for n in a[0]] == [n.id for n in b[0]] and a[1] == b[1]


def test_a_program_whose_kernels_see_no_port_is_refused_at_load(
        monkeypatch, capsys):
    monkeypatch.delattr(engine_mod, "STATIC_PORT_FEASIBILITY")
    with pytest.raises(SystemExit) as e:
        load_module("configs", "ports50k")
    assert e.value.code == 5
    assert "STATIC_PORT_FEASIBILITY" in capsys.readouterr().err


def _sound(cfg, nodes, jobs):
    """Every job on nodes of its own, the static values' holders apart."""
    ids = [n.id for n in nodes]
    out, at = {}, 0
    for job in jobs:
        count = job["TaskGroups"][0]["Count"]
        out[job["ID"]] = ids[at:at + count]
        at += count
    return out


@pytest.mark.parametrize("fault", ["none", "static-twice", "same-job-twice",
                                   "distinct-hosts", "short"])
def test_check_names_a_planted_fault(fault):
    cfg, mod = ports50k()
    nodes, fleet_table = mod.build_fleet(cfg, 2147483683)
    jobs = [mod.make_job(cfg, i) for i in range(40)]
    placed = _sound(cfg, nodes, jobs)
    needle = None
    if fault == "static-twice":
        # jobs 6 and 38 both ask port 80: one node for one of each
        assert mod.static_asks(jobs[6]) == mod.static_asks(jobs[38]) == [80]
        placed[jobs[38]["ID"]][0] = placed[jobs[6]["ID"]][0]
        needle = "static port 80: 1 nodes hold two live allocations"
    elif fault == "same-job-twice":
        placed[jobs[14]["ID"]][1] = placed[jobs[14]["ID"]][0]
        needle = "static port 443: 1 nodes hold two live allocations"
    elif fault == "distinct-hosts":
        placed[jobs[4]["ID"]][3] = placed[jobs[4]["ID"]][2]
        needle = "distinct_hosts jobs with two allocations on one node"
    elif fault == "short":
        placed[jobs[5]["ID"]] = placed[jobs[5]["ID"]][:-1]
        needle = "committed != asked"
    got = mod.check(cfg, fleet_table, jobs, placed)
    if needle is None:
        assert got == []
    else:
        assert len(got) == 1 and needle in got[0], got


# --------------------------------------------------------------- readers

def reader(name):
    return load_module("layer_metrics", name)


def fake_run(programs=None, waves=()):
    cfg = load_json("configs", "ports50k")
    mod = load_module("configs", "ports50k")
    return SimpleNamespace(
        cell=load_json("workloads", CELL), cfg=cfg,
        jobs=[mod.make_job(cfg, i) for i in range(8)],
        device={"kind": "TPU v5 lite"},
        trace={"programs": programs} if programs else {},
        tap_window={"waves": list(waves), "intervals": {}})


def test_ports_roofline_counts_the_mix_not_the_padding():
    from benchmark import (kernel_cost, multi_cost, peaks, ports_cost,
                           system_cost)
    mix = load_json("configs", "ports50k")["job_mix"]
    assert ports_cost.rounds_per_wave(mix, 64) == 64
    assert ports_cost.static_rounds_share(mix) == 1 / 8
    _, terms = system_cost.job_shape(fake_run().jobs[0])
    cost = ports_cost.ports_launch(50000, 64, 1 / 8, 4, terms)
    flat = multi_cost.flat_launch(50000, 64, 1, terms)
    assert cost["bytes"] == flat["bytes"] + 2 * 4 * 50000
    assert cost["ops"] == 64 * 50000 * (64 + 0.125 * 4)
    run = fake_run({"jit_place_multi_packed": (1, 0.010),
                    "jit_place_multi_chained": (3, 0.030),
                    "jit_other": (5, 1.0)}, [{"items": 64}] * 4)
    share = reader("place_multi_ports_roofline").read(run)
    want = kernel_cost.roofline(
        cost, peaks.peaks_for("TPU v5 lite"), 0.010)["share_pct"]
    assert share == pytest.approx(want) and 0 < share < 105


def test_ports_roofline_reads_nothing_without_its_kernel_or_ports():
    roofline = reader("place_multi_ports_roofline")
    assert roofline.read(fake_run(None, [{"items": 64}])) is None
    assert roofline.read(fake_run(
        {"jit_place_multi_compact_packed": (2, 0.01)},
        [{"items": 64}])) is None
    run = fake_run({"jit_place_multi_packed": (1, 0.01)}, [{"items": 64}])
    run.cfg = dict(run.cfg, static_ports=[])
    assert roofline.read(run) is None


def test_counter_readers_read_the_registry(served, monkeypatch):
    share = reader("ports.sequential_share")
    solo = reader("ports.solo_evals")
    assert share.UNIT == "%" and solo.UNIT == "evals"
    got = share.read(None)
    seq = REGISTRY.counter_sum("nomad.ports.sequential_rows")
    rows = seq + REGISTRY.counter_sum("nomad.ports.batched_rows")
    assert rows > 0 and got == pytest.approx(100.0 * seq / rows)
    assert solo.read(None) == REGISTRY.counter_sum("nomad.ports.evals_solo")
    # a program whose kernels see no port: nothing to read, no raise
    monkeypatch.delattr(engine_mod, "STATIC_PORT_FEASIBILITY")
    assert share.read(None) is None and solo.read(None) is None


def test_assign_reader_reads_the_port_assign_spans():
    src = open(os.path.join(REPO, "benchmark", "layer_metrics",
                            "ports.assign_ms_per_eval.py")).read()
    assert '"port_assign"' in src and reader(
        "ports.assign_ms_per_eval").UNIT == "ms"
    from nomad_tpu.core.wavepipe import STAGES
    assert "port_assign" in STAGES


# ------------------------------------------------------------- the cell

def test_benchmark_lists_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == 8 and sum(
        w["chips"] == 4 for w in cells.values()) == 1
    assert cells[CELL] == {k: load_json("workloads", CELL)[k] for k in (
        "name", "config", "traffic", "chips", "why")}
    solo = next(m for m in bench["end_to_end"]
                if m["name"] == "solo_placed_per_s")
    # the cells after it came with later PRs (groups50k-drain)
    assert solo["workloads"][-2:] == [CELL, "groups50k-drain"]
    assert solo["bound"] == 0.2
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == load_json(
        "workloads", CELL)["per_layer"]
    assert all(m["moves"] == "solo_placed_per_s" for m in mine)
    assert [m for m in mine if m["name"].endswith("_roofline")] == [
        {"name": "place_multi_ports_roofline", "unit": "%",
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
    assert "static asks by value" in p.stdout


# ------------------------------------------- waves one behind another

def test_prefetched_wave_reads_ports_from_the_state_as_it_is(monkeypatch):
    """Three waves of eight whose bin-packing piles them onto the same
    warm nodes: the second wave is dispatched before the first commits,
    so its snapshot lacks the first's ports; its port indexes are built
    from the store as it stands when its materialize begins (the first
    wave committed whole by then), and nothing is refuted.  With the
    batch's own snapshot for a view, the applier refutes the re-issued
    ports and repair evals follow."""
    from nomad_tpu.structs import TRIGGER_PLAN_REFUTE

    def run(fresh_view: bool):
        if not fresh_view:
            monkeypatch.setattr(generic, "asks_ports", lambda tg: False)
        s = cluster(fleet(6, tag="pf"), eval_batch=8)
        jobs = [networked(f"api-{i:02d}", 4, dynamic=("http", "metrics"),
                          batch=False) for i in range(24)]
        wave(s, jobs)
        snap = s.state.snapshot()
        assert all(len(live(snap, j)) == 4 for j in jobs)
        no_port_twice(snap, jobs)
        repairs = [e for e in snap.evals()
                   if e.triggered_by == TRIGGER_PLAN_REFUTE]
        s.shutdown()
        return len(repairs)

    assert run(True) == 0
    assert run(False) > 0
