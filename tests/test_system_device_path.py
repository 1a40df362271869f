"""The system scheduler's device path (one `place_system` launch, one
AllocBlock per task group) against a plain reference AND against the
per-node walk (`SYSTEM_BATCHED = False`), on seeded fleets of 64-300
nodes: same node sets, same eval status, same metric roll-up.  Then the
one-allocation-per-node block through the store, the applier and the
HTTP reads.

The plain reference below is independent of the scheduler: plain Python
over the node objects the test built.
"""

import random
import time

import pytest

from benchmark.fleet import seeded_ids
from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler import system as system_sched
from nomad_tpu.structs import (
    Constraint,
    NodeDeviceResource,
    OP_EQ,
    RequestedDevice,
    Resources,
    Task,
    TaskGroup,
)

NOW = 1_700_000_000.0
DIMS = ("cpu", "memory", "disk")


# ------------------------------------------------------------ the fleets

def build_fleet(seed, n):
    """`n` mock nodes with seeded ids, datacenters dc1-dc3 and racks."""
    rng = random.Random(f"sysfleet:{seed}")
    nodes = []
    for i, nid in enumerate(seeded_ids(rng, n)):
        node = mock.node(id=nid, datacenter=f"dc{1 + i % 3}")
        node.attributes["platform.rack"] = f"r{i % 5}"
        nodes.append(node)
    return nodes


def system_job(job_id, groups=(("agent", 100, 128),), constraints=()):
    """A system job over dc1-dc3 with one task group per (name, cpu,
    memory) and the mock job's kernel.name constraint."""
    job = mock.system_job(id=job_id, datacenters=["dc1", "dc2", "dc3"])
    job.constraints = list(job.constraints) + list(constraints)
    job.task_groups = [
        TaskGroup(name=name, count=1, tasks=[Task(
            name=name, driver="exec", config={"command": "/bin/date"},
            resources=Resources(cpu=cpu, memory_mb=mem))])
        for name, cpu, mem in groups]
    return job


def filler(node, cpu=0, mem=0, disk=0):
    """A running allocation of another job that holds part of a node."""
    a = mock.alloc(node_id=node.id, client_status="running")
    a.resources = Resources(cpu=cpu, memory_mb=mem, disk_mb=disk)
    return a


class Scenario:
    """One seeded fleet, what already runs on it, and the job."""

    def __init__(self, seed, n, job, fillers=(), mutate=None):
        self.seed, self.n, self.job = seed, n, job
        self.fillers = fillers        # (node index, cpu, mem, disk)
        self.mutate = mutate          # fn(nodes) before they are loaded

    def harness(self):
        nodes = build_fleet(self.seed, self.n)
        if self.mutate is not None:
            self.mutate(nodes)
        h = Harness()
        h.state.upsert_nodes(nodes)
        if self.fillers:
            h.state.upsert_allocs([filler(nodes[i], c, m, d)
                                   for i, c, m, d in self.fillers])
        h.state.upsert_job(self.job)
        return h, nodes


# ------------------------------------------------------- plain reference

def _passes(node, job, tg):
    for c in list(job.constraints) + list(tg.constraints):
        assert c.operand == OP_EQ and c.ltarget.startswith("${attr.")
        if node.attributes.get(c.ltarget[len("${attr."):-1]) != c.rtarget:
            return False
    return all(node.attributes.get("driver." + t.driver) for t in tg.tasks)


def reference(nodes, fillers, job):
    """Per task group, for a job nothing of which runs yet: (nodes that
    get an allocation, nodes evaluated, nodes filtered, {dimension:
    nodes exhausted})."""
    domain = [n for n in nodes
              if n.status == "ready" and n.drain is None
              and n.scheduling_eligibility == "eligible"
              and n.datacenter in job.datacenters]
    used = {n.id: [0, 0, 0] for n in nodes}
    for i, cpu, mem, disk in fillers:
        for k, v in enumerate((cpu, mem, disk)):
            used[nodes[i].id][k] += v
    out = {}
    for tg in job.task_groups:
        ask = (sum(t.resources.cpu for t in tg.tasks),
               sum(t.resources.memory_mb for t in tg.tasks),
               tg.ephemeral_disk.size_mb)
        placed, filtered, exhausted = set(), 0, {}
        for n in domain:
            if not _passes(n, job, tg):
                filtered += 1
                continue
            cap = (n.resources.cpu - n.reserved.cpu,
                   n.resources.memory_mb - n.reserved.memory_mb,
                   n.resources.disk_mb - n.reserved.disk_mb)
            over = [d for d, u, a, c in zip(DIMS, used[n.id], ask, cap)
                    if u + a > c]
            if over:
                exhausted[over[0]] = exhausted.get(over[0], 0) + 1
                continue
            placed.add(n.id)
            for k, a in enumerate(ask):
                used[n.id][k] += a
        out[tg.name] = (placed, len(domain), filtered, exhausted)
    return out


# --------------------------------------------------------------- running

def run_eval(scn, batched, monkeypatch, evals=1, node_update=None):
    """The scenario through the Harness on one path.  `evals` > 1
    re-evaluates the job; `node_update(h, nodes)` adds a node and
    returns the eval for it."""
    monkeypatch.setattr(system_sched, "SYSTEM_BATCHED", batched)
    h, nodes = scn.harness()
    host_fit0 = REGISTRY.counter("nomad.system.host_fit_nodes")
    for _ in range(evals):
        ev = mock.eval(job_id=scn.job.id, type="system")
        assert h.process("system", ev, now=NOW) is None
    if node_update is not None:
        ev = node_update(h, nodes)
        assert h.process("system", ev, now=NOW) is None
    return h, nodes, REGISTRY.counter(
        "nomad.system.host_fit_nodes") - host_fit0


def live_by_group(h, job):
    out = {}
    for a in h.state.allocs_by_job(job.namespace, job.id):
        if not a.terminal_status():
            assert a.name == f"{job.id}.{a.task_group}[0]"
            out.setdefault(a.task_group, []).append(a.node_id)
    return out


def rollup(metric):
    return (metric.nodes_evaluated, metric.nodes_filtered,
            dict(metric.constraint_filtered), metric.nodes_exhausted,
            dict(metric.dimension_exhausted))


def outcome(h):
    """What an eval left behind, in a form two paths can be held equal
    by: status, the failed groups' roll-ups, the decision record."""
    ev = h.evals[-1]
    dec = h.decisions[-1]
    return (ev.status,
            {name: rollup(m) for name, m in ev.failed_tg_allocs.items()},
            sorted((d.task_group, d.desired, d.placed, d.failed,
                    rollup(d.metric) if d.metric is not None else None)
                   for d in dec.task_groups.values()))


# ------------------------------------------------------------- scenarios

def _rack_constraint():
    return Constraint("${attr.platform.rack}", OP_EQ, "r2")


def _hold_out(nodes):
    for i, n in enumerate(nodes):
        if i % 7 == 3:
            n.scheduling_eligibility = "ineligible"
        elif i % 11 == 5:
            n.status = "down"
        elif i % 13 == 0:
            n.attributes["kernel.name"] = "windows"


def _full(dim, n):
    """Every fourth node too full on `dim` for a 100 MHz / 128 MB / 300 MB
    ask, by one unit."""
    room = {"cpu": (3900 - 99, 0, 0), "memory": (0, 7936 - 127, 0),
            "disk": (0, 0, 102400 - 299)}[dim]
    return [(i,) + room for i in range(0, n, 4)]


def scenarios():
    out = {}
    for seed, n in ((11, 64), (12, 300)):
        out[f"constraint-{n}"] = Scenario(
            seed, n, system_job(f"sys-con-{n}",
                                constraints=[_rack_constraint()]))
        out[f"ineligible-down-{n}"] = Scenario(
            seed, n, system_job(f"sys-elig-{n}"), mutate=_hold_out)
        for dim in DIMS:
            out[f"full-{dim}-{n}"] = Scenario(
                seed, n, system_job(f"sys-{dim}-{n}"),
                fillers=_full(dim, n))
        # the second group fits only where the first left room: every
        # third node has 150 MHz free, the two groups ask 100 each
        out[f"two-groups-{n}"] = Scenario(
            seed, n, system_job(f"sys-two-{n}", groups=(
                ("ship", 100, 128), ("scrape", 100, 64))),
            fillers=[(i, 3900 - 150, 0, 0) for i in range(0, n, 3)])
    # every dimension at once, and nothing placeable at all
    out["mixed-full-128"] = Scenario(
        13, 128, system_job("sys-mixed"), mutate=_hold_out,
        fillers=[f for k, dim in enumerate(DIMS)
                 for f in _full(dim, 128)[k::3]])
    out["nothing-fits-64"] = Scenario(
        14, 64, system_job("sys-none", groups=(("big", 5000, 128),)))
    out["all-filtered-64"] = Scenario(
        15, 64, system_job("sys-filtered", constraints=[
            Constraint("${attr.platform.rack}", OP_EQ, "r9")]))
    return out


SCENARIOS = scenarios()


@pytest.mark.parametrize("name", sorted(SCENARIOS))
class TestRegistration:
    def test_device_path_equals_reference(self, name, monkeypatch):
        scn = SCENARIOS[name]
        h, nodes, host_fit = run_eval(scn, True, monkeypatch)
        want = reference(nodes, scn.fillers, scn.job)
        got = live_by_group(h, scn.job)
        ev = h.evals[-1]
        assert ev.status == "complete"
        assert host_fit == 0
        for tg, (placed, evaluated, filtered, exhausted) in want.items():
            assert sorted(got.get(tg, [])) == sorted(placed), tg
            if exhausted or (not placed and filtered == evaluated):
                assert rollup(ev.failed_tg_allocs[tg]) == (
                    evaluated, filtered,
                    {"feasibility": filtered} if filtered else {},
                    sum(exhausted.values()), exhausted), tg
            else:
                assert tg not in ev.failed_tg_allocs
        # all of it left as columnar blocks, one a group that placed
        plan = h.plans[-1] if h.plans else None
        n_blocks = sum(1 for placed, *_ in want.values() if placed)
        assert (len(plan.alloc_blocks) if plan else 0) == n_blocks
        assert not plan or not plan.node_allocation

    def test_device_path_equals_host_walk(self, name, monkeypatch):
        scn = SCENARIOS[name]
        dev, _, _ = run_eval(scn, True, monkeypatch)
        host, _, host_fit = run_eval(scn, False, monkeypatch)
        assert host_fit > 0 or name == "all-filtered-64"
        got, want = live_by_group(dev, scn.job), live_by_group(host, scn.job)
        assert {tg: sorted(v) for tg, v in got.items()} == {
            tg: sorted(v) for tg, v in want.items()}
        assert outcome(dev) == outcome(host)
        # every alloc carries its group's roll-up on both paths
        for h in (dev, host):
            for a in h.state.allocs_by_job(scn.job.namespace, scn.job.id):
                assert a.metrics.nodes_evaluated == len(
                    h.snapshot().ready_nodes_in_pool(scn.job.datacenters))


@pytest.mark.parametrize("batched", [True, False], ids=["device", "host"])
class TestOtherEvals:
    def test_reeval_with_every_node_holding_the_job_is_a_noop(
            self, batched, monkeypatch):
        scn = Scenario(21, 96, system_job("sys-noop"), mutate=_hold_out)
        h, nodes, _ = run_eval(scn, batched, monkeypatch, evals=2)
        assert len(h.plans) == 1          # the second eval submitted none
        assert h.evals[-1].status == "complete"
        assert not h.evals[-1].failed_tg_allocs
        want = reference(nodes, (), scn.job)["agent"][0]
        assert sorted(live_by_group(h, scn.job)["agent"]) == sorted(want)

    def test_node_update_eval_places_on_the_new_node_alone(
            self, batched, monkeypatch):
        scn = Scenario(22, 64, system_job("sys-newnode"))
        newbie = mock.node(id="0badc0de-0000-4000-8000-000000000001",
                           datacenter="dc2")

        def add(h, nodes):
            h.state.upsert_node(newbie)
            return mock.eval(job_id=scn.job.id, type="system",
                             triggered_by="node-update", node_id=newbie.id)

        h, nodes, _ = run_eval(scn, batched, monkeypatch, node_update=add)
        plan = h.plans[-1]
        placed = ([a for v in plan.node_allocation.values() for a in v]
                  + [a for b in plan.alloc_blocks
                     for a in b.materialize_all()])
        assert [a.node_id for a in placed] == [newbie.id]
        assert placed[0].metrics.nodes_evaluated == 1
        assert len(live_by_group(h, scn.job)["agent"]) == 65

    def test_job_update_in_place_and_destructive(self, batched, monkeypatch):
        """A new version: nodes that hold the job are updated on the host
        (in place where the tasks did not change, stopped and placed
        again where they did); nodes that hold nothing take the block."""
        scn = Scenario(23, 64, system_job("sys-update"))
        h, nodes, _ = run_eval(scn, batched, monkeypatch)
        extra = [mock.node(id=f"0badc0de-0000-4000-8000-00000000001{k}",
                           datacenter="dc1") for k in range(3)]
        h.state.upsert_nodes(extra)
        v2 = system_job("sys-update", groups=(("agent", 200, 128),))
        v2.version = scn.job.version + 1
        h.state.upsert_job(v2)
        assert h.process("system", mock.eval(job_id=v2.id, type="system"),
                         now=NOW) is None
        live = [a for a in h.state.allocs_by_job(v2.namespace, v2.id)
                if not a.terminal_status()]
        assert sorted(a.node_id for a in live) == sorted(
            n.id for n in nodes + extra)
        assert {a.job_version for a in live} == {v2.version}
        assert {a.resources.cpu for a in live} == {200}
        plan = h.plans[-1]
        stops = [a for v in plan.node_update.values() for a in v]
        assert len(stops) == 64
        in_blocks = [nid for b in plan.alloc_blocks for nid in b.node_table]
        assert sorted(in_blocks) == (sorted(n.id for n in extra)
                                     if batched else [])


class TestFallback:
    def test_device_asking_group_takes_the_host_walk(self, monkeypatch):
        job = system_job("sys-gpu")
        job.task_groups[0].tasks[0].resources.devices = [
            RequestedDevice(name="nvidia/gpu", count=1)]

        def gpus(nodes):
            for n in nodes[::2]:
                n.resources.devices = [NodeDeviceResource(
                    vendor="nvidia", type="gpu", name="t4",
                    instance_ids=["gpu-0"])]

        scn = Scenario(31, 64, job, mutate=gpus)
        dev, nodes, host_fit = run_eval(scn, True, monkeypatch)
        host, _, _ = run_eval(scn, False, monkeypatch)
        assert host_fit > 0
        assert not dev.plans[-1].alloc_blocks
        got = sorted(live_by_group(dev, job)["agent"])
        assert got == sorted(live_by_group(host, job)["agent"])
        assert got == sorted(n.id for n in nodes[::2])
        assert outcome(dev) == outcome(host)

    def test_port_asking_group_takes_the_host_walk(self, monkeypatch):
        from nomad_tpu.structs import NetworkResource, Port
        job = system_job("sys-port")
        job.task_groups[0].networks = [NetworkResource(
            dynamic_ports=[Port(label="http")])]
        scn = Scenario(32, 64, job)
        dev, nodes, host_fit = run_eval(scn, True, monkeypatch)
        assert host_fit == 64 and not dev.plans[-1].alloc_blocks
        assert len(live_by_group(dev, job)["agent"]) == 64


def test_counters_count_both_paths(monkeypatch):
    scn = SCENARIOS["two-groups-64"]
    want = reference(build_fleet(scn.seed, scn.n), scn.fillers, scn.job)
    for batched in (True, False):
        before = {k: REGISTRY.counter(f"nomad.system.{k}")
                  for k in ("nodes_evaluated", "placed", "host_fit_nodes")}
        run_eval(scn, batched, monkeypatch)
        delta = {k: REGISTRY.counter(f"nomad.system.{k}") - v
                 for k, v in before.items()}
        assert delta["nodes_evaluated"] == 2 * 64
        assert delta["placed"] == sum(len(p) for p, *_ in want.values())
        assert (delta["host_fit_nodes"] == 0) == batched


# ------------------------------- the one-allocation-per-node block, served

def _wait(fn, timeout=60.0, period=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(period)
    return fn()


@pytest.fixture(scope="module")
def served():
    """A threaded agent with 48 loaded nodes and one system job placed
    over HTTP: (agent, api, nodes, job)."""
    from nomad_tpu.agent import Agent
    from nomad_tpu.api.client import APIClient
    from nomad_tpu.structs import codec

    agent = Agent(num_clients=0, num_workers=1, heartbeat_ttl=86400.0,
                  log_level="warn", mesh=False)
    agent.start()
    try:
        nodes = build_fleet(41, 48)
        nodes[5].scheduling_eligibility = "ineligible"
        agent.server.state.upsert_nodes(nodes)
        api = APIClient(address=agent.address)
        job = system_job("sys-served")
        before = agent.server.stage_timers.counts().get("system_place", 0)
        resp = api.jobs.register(codec.encode(job))
        ev = _wait(lambda: (api.evaluations.info(resp["EvalID"])["Status"]
                            == "complete"))
        assert ev
        assert agent.server.stage_timers.counts()["system_place"] \
            == before + 1
        yield agent, api, nodes, job
    finally:
        agent.shutdown()


class TestServedBlock:
    def test_block_shape(self, served):
        agent, _, nodes, job = served
        blocks = agent.server.state._blocks_by_job[(job.namespace, job.id)]
        assert len(blocks) == 1
        b = blocks[0]
        want = sorted(n.id for i, n in enumerate(nodes) if i != 5)
        assert sorted(b.node_table) == want and b.count == 47
        assert b.picks.tolist() == list(range(47))
        assert set(b.indexes) == {0} and len(set(b.ids)) == 47
        assert b.demand_by_node()[want[0]] == (1, 100, 128, 300)

    def test_reads_agree(self, served):
        agent, api, nodes, job = served
        state = agent.server.state.snapshot()
        want = sorted(n.id for i, n in enumerate(nodes) if i != 5)
        by_job = state.allocs_by_job(job.namespace, job.id)
        assert sorted(a.node_id for a in by_job) == want
        by_node = [a for n in nodes for a in state.allocs_by_node(n.id)
                   if a.job_id == job.id]
        assert sorted(a.id for a in by_node) == sorted(a.id for a in by_job)
        assert all(a.name == f"{job.id}.agent[0]" for a in by_job)
        rows = api.jobs.allocations(job.id)
        assert sorted(r["NodeID"] for r in rows) == want
        assert sorted(r["ID"] for r in rows) == sorted(a.id for a in by_job)
        cols = api.get("/v1/allocations", columnar="true")["Columns"]
        mine = [(i, n) for i, n, j in zip(cols["ID"], cols["NodeID"],
                                          cols["JobID"]) if j == job.id]
        assert sorted(n for _, n in mine) == want
        assert sorted(i for i, _ in mine) == sorted(a.id for a in by_job)

    def test_metrics_series(self, served):
        _, api, _, _ = served
        series = api.get("/v1/metrics")
        assert series["nomad.system.placed"] >= 47
        assert series["nomad.system.nodes_evaluated"] >= 47
        assert "nomad.system.host_fit_nodes" in series
        assert series["nomad.wavepipe.system_place_s"] > 0

    def test_stop_after_purge_leaves_no_live_row(self, served):
        agent, api, nodes, job = served
        resp = api.jobs.deregister(job.id, purge=True)
        assert _wait(lambda: (api.evaluations.info(resp["EvalID"])["Status"]
                              == "complete"))
        state = agent.server.state.snapshot()
        assert [a for n in nodes for a in state.allocs_by_node(n.id)
                if not a.terminal_status()] == []
        cols = api.get("/v1/allocations", columnar="true")["Columns"]
        assert job.id not in cols["JobID"]


def test_evaluate_plan_withholds_exactly_the_refuted_nodes_row(monkeypatch):
    """A node that filled up between the snapshot and the commit: the
    applier's per-node walk masks that one row out of the 1-per-node
    block and commits the rest."""
    from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue

    monkeypatch.setattr(system_sched, "SYSTEM_BATCHED", True)
    scn = Scenario(51, 64, system_job("sys-refute"))
    h, nodes = scn.harness()
    h.no_submit = True
    assert h.process("system", mock.eval(job_id=scn.job.id, type="system"),
                     now=NOW) is None
    plan = h.plans[-1]
    assert plan.alloc_blocks[0].count == 64
    victim = nodes[17]
    h.state.upsert_allocs([filler(victim, cpu=3900 - 50)])
    applier = PlanApplier(h.state, PlanQueue())
    result = applier.evaluate_plan(plan)
    assert result.refuted_nodes == [victim.id]
    kept = result.alloc_blocks[0]
    assert kept.count == 63 and victim.id not in kept.node_table
    assert sorted(kept.node_table) == sorted(
        n.id for n in nodes if n is not victim)
    h.state.upsert_plan_results(plan, result)
    live = live_by_group(h, scn.job)["agent"]
    assert sorted(live) == sorted(kept.node_table)


# ------------------------- the benchmark's plain reference and its checker

def _bench_fleet(nodes=100):
    from benchmark.loader import load_json, load_module
    mod = load_module("configs", "system50k")
    held_out = len(range(7, nodes, 50))
    cfg = dict(load_json("configs", "system50k"), nodes=nodes,
               count_per_job=nodes - held_out)
    loaded, fleet = mod.build_fleet(cfg, 3)
    return mod, cfg, loaded, fleet


def _doctored(kind, want, loaded):
    """A cycle's allocation list that is wrong in one way."""
    ids = sorted(want)
    if kind == "missing":
        return ids[1:]
    if kind == "twice":
        return ids + ids[:1]
    if kind == "ineligible":
        return ids + [loaded[7].id]
    if kind == "elsewhere":
        return ids[1:] + [loaded[57].id]
    raise AssertionError(kind)


class TestBenchmarkReference:
    def test_fleet_is_csi50ks_with_nodes_held_out(self):
        from benchmark.loader import load_module
        mod, cfg, loaded, fleet = _bench_fleet()
        base, table = load_module("configs", "csi50k").build_fleet(cfg, 3)
        assert [n.id for n in loaded] == [n.id for n in base]
        assert [i for i, n in enumerate(loaded)
                if n.scheduling_eligibility == "ineligible"] == [7, 57]
        assert all(fleet[n.id][:4] == table[n.id] for n in loaded)

    def test_a_sound_list_passes(self):
        mod, cfg, loaded, fleet = _bench_fleet()
        job = mod.make_job(cfg, 0)
        want = mod.reference_nodes(cfg, fleet, job)
        assert want == {n.id for i, n in enumerate(loaded)
                        if i not in (7, 57)}
        assert mod.check(cfg, fleet, [job], {job["ID"]: sorted(want)}) == []

    @pytest.mark.parametrize("kind", ["missing", "twice", "ineligible",
                                      "elsewhere"])
    def test_a_doctored_list_is_caught(self, kind):
        mod, cfg, loaded, fleet = _bench_fleet()
        job = mod.make_job(cfg, 0)
        want = mod.reference_nodes(cfg, fleet, job)
        got = mod.check(cfg, fleet, [job],
                        {job["ID"]: _doctored(kind, want, loaded)})
        assert any("not on exactly the reference's nodes" in f for f in got)

    def test_a_program_without_the_device_path_is_refused_at_load(
            self, monkeypatch, capsys):
        from benchmark.loader import load_module
        monkeypatch.delattr(system_sched, "SYSTEM_BATCHED")
        with pytest.raises(SystemExit) as e:
            load_module("configs", "system50k")
        assert e.value.code == 5
        assert "device path" in capsys.readouterr().err

    def test_the_per_node_walk_still_loads_the_configuration(
            self, monkeypatch):
        from benchmark.loader import load_module
        monkeypatch.setattr(system_sched, "SYSTEM_BATCHED", False)
        assert load_module("configs", "system50k").reference_nodes

    def test_an_unregistered_jobs_allocations_are_caught(self):
        mod, cfg, loaded, fleet = _bench_fleet()
        job = mod.make_job(cfg, 0)
        want = sorted(mod.reference_nodes(cfg, fleet, job))
        got = mod.check(cfg, fleet, [job], {job["ID"]: want,
                                            "nobody": want[:1]})
        assert any("nobody registered" in f for f in got)

    def test_a_wrong_stated_size_is_caught(self):
        mod, cfg, loaded, fleet = _bench_fleet()
        job = mod.make_job(cfg, 0)
        want = sorted(mod.reference_nodes(cfg, fleet, job))
        got = mod.check(dict(cfg, count_per_job=len(want) + 1), fleet,
                        [job], {job["ID"]: want})
        assert any("the configuration states" in f for f in got)

    def test_earlier_jobs_of_the_cycle_count_against_a_node(self):
        # two jobs live together whose asks fill a 4000 MHz node: the
        # second belongs only where the first left room, and a list
        # that ignores that is over capacity
        mod, cfg, loaded, fleet = _bench_fleet()
        big = dict(cfg, ask_cpu_mhz=2000)
        mod._TEMPLATE.clear()
        try:
            jobs = [mod.make_job(big, 0), mod.make_job(big, 1)]
        finally:
            mod._TEMPLATE.clear()
        first = mod.reference_nodes(cfg, fleet, jobs[0])
        held = {n: [2000, 128] for n in first}
        second = mod.reference_nodes(cfg, fleet, jobs[1], held)
        assert second and second < first
        assert all(fleet[n][2] >= 4000 for n in second)
        cfg2 = dict(cfg, count_per_job=len(first))
        sound = {jobs[0]["ID"]: sorted(first), jobs[1]["ID"]: sorted(second)}
        assert [f for f in mod.check(cfg2, fleet, jobs, sound)
                if "states" not in f] == []
        crowded = dict(sound, **{jobs[1]["ID"]: sorted(first)})
        got = mod.check(cfg2, fleet, jobs, crowded)
        assert any("over resources - reserved" in f for f in got)

    def test_roofline_cost_counts_the_jobs_shape(self):
        from benchmark import system_cost
        mod, cfg, _, _ = _bench_fleet()
        job = mod.make_job(cfg, 0)
        # the kernel.name constraint and the exec driver's check
        assert system_cost.job_shape(job) == (1, 2)
        cost = system_cost.system_launch(50_000, 1, 2)
        assert cost["bytes"] == 50_000 * (2 * 4 + 24 + 4 + 1)
        assert cost["ops"] == 50_000 * (2 * 8 + 17)
