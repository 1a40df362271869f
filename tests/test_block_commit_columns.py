"""A block commits by columns (ISSUE 27): the state store's per-node
ledgers (the placement fence, the live-allocation ledger, the block
registry by node) and the packer's block units take an AllocBlock in a
constant number of Python-level calls.  Held here to a plain PER-NODE
reference written in this file: after every commit the store and the
packer must read what a walk over the block's nodes would have left.

Then the refusals the short cuts must not lose, and a scaling test that
needs no clock: Python-level calls of one `apply_one`, counted.
"""

import random
import sys

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.plan_apply import PendingPlan, PlanApplier, PlanQueue
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.pack.packer import ClusterPacker
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocBlock,
    AllocMetric,
    Allocation,
    Plan,
    PlanResult,
    RES_DIMS,
    Resources,
)

ZONES = ("dc1", "dc2", "dc3")
CHAINS = (None, "chainA", "chainB", "chainC")


# ------------------------------------------------------------ the fleet

def build_nodes(rng, n):
    nodes = []
    for i in range(n):
        node = mock.node(datacenter=ZONES[i % 3])
        node.resources.cpu = rng.choice((2000, 4000, 8000))
        node.resources.memory_mb = rng.choice((4096, 8192))
        node.reserved.cpu = rng.choice((0, 100))
        nodes.append(node)
    return nodes


def avail(node):
    return (node.resources.cpu - node.reserved.cpu,
            node.resources.memory_mb - node.reserved.memory_mb,
            node.resources.disk_mb - node.reserved.disk_mb)


def make_block(job, node_ids, res):
    """`job`'s block with one allocation per entry of `node_ids`
    (repeats allowed: several allocations on that node)."""
    table = list(dict.fromkeys(node_ids))
    row = {nid: i for i, nid in enumerate(table)}
    k = len(node_ids)
    tg = job.task_groups[0]
    return AllocBlock(
        id=f"block-{job.id}",
        template=Allocation(
            namespace=job.namespace, eval_id=f"eval-{job.id}",
            job_id=job.id, job=job, task_group=tg.name,
            resources=Resources(cpu=res[0], memory_mb=res[1],
                                disk_mb=res[2]),
            desired_status="run", client_status="pending",
            job_version=job.version),
        ids=[f"{job.id}-a{i}" for i in range(k)],
        name_prefix=f"{job.id}.{tg.name}[",
        indexes=list(range(k)),
        picks=np.array([row[nid] for nid in node_ids], np.int32),
        node_table=table,
        metrics=[AllocMetric()],
        round_size=max(k, 1),
    )


def commit(state, block, chain):
    plan = Plan(eval_id=block.template.eval_id, job=block.template.job)
    plan.alloc_blocks.append(block)
    if chain is not None:
        plan.coupled_batch = (chain, state.placement_seq())
    idx = state.upsert_plan_results(plan, PlanResult(alloc_blocks=[block]))
    assert idx > 0
    return idx


# -------------------------------------------------- the plain reference

class Reference:
    """What the store and the packer hold after each write, kept node by
    node in plain Python."""

    def __init__(self, nodes, packed):
        self.nodes = {n.id: n for n in nodes}   # the store's node table
        self.fence = {}                         # nid -> (seq, origin)
        self.live = {}              # nid -> [count, usage by dimension]
        self.blocks = {}                        # nid -> [block id, ...]
        self.used = {n.id: [0] * RES_DIMS for n in packed}  # packer's rows
        self.counted = {}                       # alloc id -> (nid, res)
        self.units = {}                         # block id -> block
        self.used_bumps = 0

    # the store's side
    def node_written(self, node, seq):
        self.nodes[node.id] = node
        self.fence[node.id] = (seq, None)

    def _live(self, nid, d, res):
        row = self.live.setdefault(nid, [0] * (1 + RES_DIMS))
        row[0] += d
        for k in range(RES_DIMS):
            row[1 + k] += d * res[k]

    def block_committed(self, block, seq, origin):
        res = block.resources_tuple()
        touched = False
        self.units[block.id] = block
        for nid, c in zip(block.node_table, block.node_counts().tolist()):
            self.fence[nid] = (seq, origin)
            self.blocks.setdefault(nid, []).append(block.id)
            self._live(nid, c, res)
            if c and nid in self.used:
                for k in range(RES_DIMS):
                    self.used[nid][k] += c * res[k]
                touched = True
        self.used_bumps += touched

    def allocs_written(self, allocs, seq, origin=None):
        """Per-alloc rows: fresh ones, or successors of counted ones."""
        touched = False
        for a in allocs:
            res = a.usage()
            self.fence[a.node_id] = (seq, origin)
            old = self.counted.pop(a.id, None)
            if old is not None:
                self._live(old[0], -1, old[1])
                if old[0] in self.used:
                    for k in range(RES_DIMS):
                        self.used[old[0]][k] -= old[1][k]
                    touched = True
            if not a.terminal_status():
                self.counted[a.id] = (a.node_id, res)
                self._live(a.node_id, 1, res)
                if a.node_id in self.used:
                    for k in range(RES_DIMS):
                        self.used[a.node_id][k] += res[k]
                    touched = True
        self.used_bumps += touched

    def block_materialized(self, block):
        """Representation change: rows counted per alloc, no delta."""
        del self.units[block.id]
        res = block.resources_tuple()
        for a in block.materialize_all():
            self.counted[a.id] = (a.node_id, res)
        for nid in block.node_table:
            self.blocks[nid].remove(block.id)

    # the readings
    def unchanged(self, nid, seq0, chain, own_chain_ok):
        e = self.fence.get(nid)
        if e is None or e[0] <= seq0:
            return True
        return bool(own_chain_ok and chain is not None and e[1] == chain)

    def quality(self):
        in_use = {nid: r for nid, r in self.live.items() if r[0] > 0}
        zones, fills = {}, [0.0, 0.0, 0.0]
        for nid, row in in_use.items():
            node = self.nodes.get(nid)
            if node is None:
                continue            # counted in nodes_in_use only
            zones[node.datacenter] = zones.get(node.datacenter, 0) + row[0]
            for k, cap in enumerate(avail(node)):
                if cap > 0:
                    fills[k] += min(row[1 + k] / cap, 1.0)
        n = len(in_use)
        zmax = max(zones.values(), default=0)
        zmin = min(zones.values(), default=0)
        return {
            "nodes_in_use": n,
            "zone_allocs_max": zmax,
            "zone_allocs_min": zmin,
            "zone_balance_max_over_min": zmax / zmin if zmin else 0.0,
            "fill_cpu": fills[0] / n if n else 0.0,
            "fill_memory": fills[1] / n if n else 0.0,
            "fill_disk": fills[2] / n if n else 0.0,
            "devices_in_use": sum(r[RES_DIMS] for r in in_use.values()),
        }


def held_equal(state, packer, ref, used_v0, seqs):
    """Every observable of the commit against the reference."""
    got = state.quality_summary()
    want = ref.quality()
    for key in ("nodes_in_use", "zone_allocs_max", "zone_allocs_min"):
        assert got[key] == want[key], key
    for key in ("zone_balance_max_over_min", "fill_cpu", "fill_memory",
                "fill_disk"):
        # the columns sum the same terms in another order: a float64
        # sum of at most a few hundred terms in [0, 1]
        assert got[key] == pytest.approx(want[key], rel=1e-12, abs=1e-15)
    # the fence, node by node: the O(1) answer is only ever taken where
    # the walk would agree
    every = sorted(set(ref.fence) | set(ref.nodes))
    for seq0 in seqs:
        for chain in CHAINS:
            for own in (True, False):
                for nid in every:
                    assert state.nodes_unchanged_since(
                        [nid], seq0, chain, own_chain_ok=own) \
                        == ref.unchanged(nid, seq0, chain, own), \
                        (nid, seq0, chain, own)
                want_all = all(ref.unchanged(nid, seq0, chain, own)
                               for nid in every)
                assert state.nodes_unchanged_since(
                    every, seq0, chain, own_chain_ok=own) == want_all
    snap = state.snapshot()
    by_node = {nid: [b.id for b in bs]
               for nid, bs in snap._blocks_by_node.items()}
    assert by_node == {nid: ids for nid, ids in ref.blocks.items() if ids}
    t = packer._tensors
    for nid, used in ref.used.items():
        assert t.used[t.id_to_row[nid]].tolist() == used, nid
    assert set(t.id_to_row) == set(ref.used)
    assert t.used_version - used_v0 == ref.used_bumps
    assert set(packer._block_counted) == set(ref.units)


# ------------------------------------------------------------ the shapes

def _one_a_node(rng, ids, late, ghost):
    return [("jobA", "chainA", list(ids))]


def _several_a_node(rng, ids, late, ghost):
    some = rng.sample(ids, len(ids) // 2)
    return [("jobA", "chainA", [rng.choice(some) for _ in range(90)])]


def _two_blocks_share_nodes(rng, ids, late, ghost):
    a = rng.sample(ids, 2 * len(ids) // 3)
    b = rng.sample(ids, 2 * len(ids) // 3)
    return [("jobA", "chainA", a + a[:7]), ("jobB", "chainB", b),
            ("jobC", "chainA", rng.sample(ids, 5))]


def _node_holds_another_jobs_block(rng, ids, late, ghost):
    return [("jobB", None, rng.sample(ids, 9)),
            ("jobA", "chainA", list(ids))]


def _node_unknown_to_the_packer(rng, ids, late, ghost):
    return [("jobA", "chainA", list(ids) + [late, late, ghost])]


SHAPES = {f.__name__[1:]: f for f in (
    _one_a_node, _several_a_node, _two_blocks_share_nodes,
    _node_holds_another_jobs_block, _node_unknown_to_the_packer)}


@pytest.mark.parametrize("seed", [11, 2147483659])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_commit_reads_as_the_per_node_walk(shape, seed):
    rng = random.Random(f"{shape}:{seed}")
    nodes = build_nodes(rng, 40)
    state = StateStore()
    packer = ClusterPacker()
    packer.attach(state)
    seqs = [state.placement_seq()]
    state.upsert_nodes(nodes)
    ref = Reference(nodes, nodes)
    for n in nodes:
        ref.node_written(n, state.placement_seq())
    # per-alloc rows of another job on a few nodes, before the packer
    # builds and after: pending per-alloc deltas and block scatters meet
    # in one flush
    def fillers(k):
        out = []
        for node in rng.sample(nodes, k):
            a = mock.alloc(node_id=node.id, client_status="running")
            a.resources = Resources(cpu=rng.choice((50, 250)),
                                    memory_mb=64, disk_mb=10)
            out.append(a)
        return out

    first = fillers(6)
    state.upsert_allocs(first)
    ref.allocs_written(first, state.placement_seq())
    seqs.append(state.placement_seq())
    packer.update(state.snapshot())
    ref.used_bumps = 0          # the build counted `first` from the snapshot
    used_v0 = packer._tensors.used_version
    # a node the store learns of after the packer built its rows, and
    # one the store never sees
    late = mock.node(datacenter="dc2")
    if shape == "node_unknown_to_the_packer":
        state.upsert_node(late)
        ref.node_written(late, state.placement_seq())
    ghost = "node-the-store-never-saw"
    second = fillers(4)
    state.upsert_allocs(second)
    ref.allocs_written(second, state.placement_seq())
    seqs.append(state.placement_seq())

    ids = [n.id for n in nodes]
    blocks = []
    for job_id, chain, node_ids in SHAPES[shape](rng, ids, late.id, ghost):
        job = mock.batch_job(id=f"{job_id}-{seed}")
        block = make_block(job, node_ids,
                           (rng.choice((10, 100)), rng.choice((16, 128)),
                            rng.choice((0, 300))))
        commit(state, block, chain)
        ref.block_committed(block, state.placement_seq(), chain)
        seqs.append(state.placement_seq())
        blocks.append(block)
        held_equal(state, packer, ref, used_v0, seqs)

    # a member goes terminal: the store materializes its block (a
    # BlockMaterialized event, no usage delta), then the Allocations
    # event retires that one row
    block = blocks[-1]
    member = state.alloc_by_id(block.ids[0]).copy_skip_job()
    member.client_status = "complete"
    state.update_allocs_from_client([member])
    ref.block_materialized(block)
    ref.allocs_written([member], state.placement_seq())
    seqs.append(state.placement_seq())
    held_equal(state, packer, ref, used_v0, seqs)
    # and the rest of it
    rest = []
    for aid in block.ids[1:]:
        a = state.alloc_by_id(aid).copy_skip_job()
        a.client_status = "failed"
        rest.append(a)
    state.update_allocs_from_client(rest)
    ref.allocs_written(rest, state.placement_seq())
    seqs.append(state.placement_seq())
    held_equal(state, packer, ref, used_v0, seqs)

    # a node write makes the packer read that node's usage again: from
    # its ledger, units included (the dirty-row refresh), or, where the
    # late node changed the membership, from the snapshot (a rebuild)
    holder = next((nid for nid in ids if ref.blocks.get(nid)), ids[0])
    again = state.node_by_id(holder).copy()
    again.attributes["platform.rack"] = "r9"
    state.upsert_node(again)
    ref.node_written(again, state.placement_seq())
    t = packer.update(state.snapshot())
    live = {nid: [0] * RES_DIMS for nid in ref.nodes}
    for nid, res in ref.counted.values():
        for k in range(RES_DIMS):
            live[nid][k] += res[k]
    for block in ref.units.values():
        res = block.resources_tuple()
        for nid, c in zip(block.node_table, block.node_counts().tolist()):
            if nid in live:
                for k in range(RES_DIMS):
                    live[nid][k] += c * res[k]
    assert set(t.id_to_row) == set(live)
    for nid, used in live.items():
        assert t.used[t.id_to_row[nid]].tolist() == used, nid
    assert state.quality_summary() == pytest.approx(ref.quality(),
                                                    rel=1e-12, abs=1e-15)


def test_a_restored_store_reads_the_same_quality():
    rng = random.Random("restore")
    nodes = build_nodes(rng, 12)
    state = StateStore()
    state.upsert_nodes(nodes)
    job = mock.batch_job(id="restore-job")
    commit(state, make_block(job, [n.id for n in nodes] * 2, (100, 64, 0)),
           "chainA")
    want = state.quality_summary()
    assert want["nodes_in_use"] == 12
    fresh = StateStore()
    fresh.snapshot_restore(state.snapshot_save())
    assert fresh.quality_summary() == pytest.approx(want, rel=1e-12)
    empty = StateStore()
    empty.snapshot_restore(StateStore().snapshot_save())
    assert empty.upsert_nodes([]) > 0
    assert empty.quality_summary()["nodes_in_use"] == 0


def test_units_nobody_reads_are_folded_before_they_outgrow_the_rows():
    """The ledger keeps a committed block as a unit until the gauges
    are read; on a store whose gauges nobody reads (a follower) the
    units are folded once they name more nodes than the ledger has
    rows, so the list stays bounded and the sums stay right."""
    rng = random.Random("unread")
    nodes = build_nodes(rng, 8)
    state = StateStore()
    state.upsert_nodes(nodes)
    ids = [n.id for n in nodes]
    longest = 0
    for i in range(400):
        job = mock.batch_job(id=f"unread-{i}")
        commit(state, make_block(job, ids, (1, 1, 0)), None)
        longest = max(longest, len(state._live._pending_blocks))
    assert longest <= (8 + 1024) // 8 + 1
    q = state.quality_summary()
    assert q["nodes_in_use"] == 8 and q["zone_allocs_max"] == 3 * 400
    assert not state._live._pending_blocks


# ----------------------------------------------------------- the refusals

def _fenced(n=24, volumes=False):
    """A store, its applier, and one fenced block plan over `n` nodes."""
    rng = random.Random(f"fenced:{n}")
    nodes = build_nodes(rng, n)
    state = StateStore()
    state.upsert_nodes(nodes)
    job = mock.system_job(id=f"sys-{n}")
    block = make_block(job, [x.id for x in nodes], (100, 128, 0))
    block.indexes = [0] * n
    plan = Plan(eval_id="eval-fenced", job=job)
    plan.alloc_blocks.append(block)
    plan.coupled_batch = ("eval-fenced", state.placement_seq())
    return state, PlanApplier(state, PlanQueue()), plan, nodes


def _apply(applier, plan):
    pending = PendingPlan(plan)
    applier.apply_one(pending)
    assert pending.error is None, pending.error
    return pending.result


def test_a_clean_fence_is_answered_without_a_walk():
    state, applier, plan, nodes = _fenced()
    fast0 = REGISTRY.counter("nomad.plan.fence_fast")
    result = _apply(applier, plan)
    assert result.alloc_blocks and not result.refuted_nodes
    assert applier.stats["fast_path"] == 1
    assert applier.stats["full_check"] == 0
    # both of the applier's reads, in nodes
    assert applier.stats["fence_fast"] == 2 * len(nodes)
    assert applier.stats["fence_walked"] == 0
    assert REGISTRY.counter("nomad.plan.fence_fast") - fast0 \
        == 2 * len(nodes)


def test_a_foreign_write_elsewhere_walks_and_still_skips_the_fit():
    state, applier, plan, nodes = _fenced()
    other = mock.node()
    state.upsert_node(other)            # a placement write, not on a plan node
    result = _apply(applier, plan)
    assert result.alloc_blocks and not result.refuted_nodes
    assert applier.stats["fast_path"] == 1
    assert applier.stats["fence_fast"] == 0
    assert applier.stats["fence_walked"] == 2 * len(nodes)


def test_a_foreign_write_to_a_plan_node_runs_the_full_check():
    state, applier, plan, nodes = _fenced()
    a = mock.alloc(node_id=nodes[3].id, client_status="running")
    a.resources = Resources(cpu=10, memory_mb=10)
    state.upsert_allocs([a])
    result = _apply(applier, plan)
    assert result.alloc_blocks and not result.refuted_nodes
    assert applier.stats["fast_path"] == 0
    assert applier.stats["full_check"] == 1


def test_a_foreign_write_between_fence_read_and_commit_is_refused():
    """The fence is re-verified under the store lock: the write lands
    after both of the applier's reads, the commit returns -1, and the
    full check runs."""
    state, applier, plan, nodes = _fenced()
    victim = nodes[5]
    evaluate = applier.evaluate_plan
    upsert = state.upsert_plan_results
    returned = []

    def evaluate_then_write(plan, skip_fit=False, fenced_first=False):
        result = evaluate(plan, skip_fit=skip_fit, fenced_first=fenced_first)
        if skip_fit:
            a = mock.alloc(node_id=victim.id, client_status="running")
            a.resources = Resources(cpu=victim.resources.cpu, memory_mb=10)
            state.upsert_allocs([a])
        return result

    def recording(*args, **kwargs):
        returned.append(upsert(*args, **kwargs))
        return returned[-1]

    applier.evaluate_plan = evaluate_then_write
    state.upsert_plan_results = recording
    result = _apply(applier, plan)
    assert returned[0] == -1 and returned[1] > 0
    assert applier.stats["fast_path"] == 1
    assert applier.stats["full_check"] == 1
    # the full check saw the node filled up under the plan
    assert result.refuted_nodes == [victim.id]
    assert victim.id not in result.alloc_blocks[0].node_table


@pytest.mark.parametrize("how", ["down", "gone"])
def test_a_down_or_missing_node_demotes_the_whole_block_admission(how):
    state, applier, plan, nodes = _fenced()
    victim = nodes[7]
    # on the live head, behind an intact fence: the status flip happened
    # before the plan's snapshot
    if how == "down":
        state.update_node_status(victim.id, "down")
    else:
        state.delete_node(victim.id)
    plan.coupled_batch = ("eval-fenced", state.placement_seq())
    assert not PlanApplier._blocks_ok(state, plan)
    assert not state.nodes_up([x.id for x in nodes])
    assert state.nodes_up([x.id for x in nodes if x is not victim])
    result = _apply(applier, plan)
    assert applier.stats["fast_path"] == 1      # fit skipped, nodes walked
    assert result.refuted_nodes == [victim.id]
    assert victim.id not in result.alloc_blocks[0].node_table


def test_a_volume_write_after_the_claim_checks_forces_the_redo():
    state, applier, plan, nodes = _fenced()
    evaluate = applier.evaluate_plan
    upsert = state.upsert_plan_results
    returned = []

    def evaluate_then_write(plan, skip_fit=False, fenced_first=False):
        result = evaluate(plan, skip_fit=skip_fit, fenced_first=fenced_first)
        if skip_fit:
            state._volume_seq += 1     # what every volume mutation does
        return result

    def recording(*args, **kwargs):
        returned.append(upsert(*args, **kwargs))
        return returned[-1]

    applier.evaluate_plan = evaluate_then_write
    state.upsert_plan_results = recording
    result = _apply(applier, plan)
    assert returned[0] == -1 and returned[1] > 0
    assert applier.stats["full_check"] == 1
    assert result.alloc_blocks[0].count == len(nodes)


def test_a_chain_reads_past_its_own_run_in_constant_time():
    """Plans of one chain, committed back to back: the second's fence
    read sees only its own chain's writes since the snapshot and does
    not walk; a chain interleaved with another does."""
    state, applier, plan, nodes = _fenced(12)
    seq0 = state.placement_seq()
    ids = [n.id for n in nodes]
    for i, chain in enumerate(("chainA", "chainA")):
        job = mock.batch_job(id=f"run-{i}")
        p = Plan(eval_id=f"eval-run-{i}", job=job)
        p.alloc_blocks.append(make_block(job, ids[:6], (10, 10, 0)))
        p.coupled_batch = (chain, seq0)
        _apply(applier, p)
    # plan 0: nothing written, both reads fast.  plan 1: its own run is
    # tolerated in O(1); the fenced-first read (own chain not tolerated)
    # walks and finds the chain's own write
    assert applier.stats["fence_fast"] == 3 * 6
    assert applier.stats["fence_walked"] == 6
    assert applier.stats["fast_path"] == 2
    job = mock.batch_job(id="run-b")
    p = Plan(eval_id="eval-run-b", job=job)
    p.alloc_blocks.append(make_block(job, ids[6:], (10, 10, 0)))
    p.coupled_batch = ("chainB", seq0)
    _apply(applier, p)
    # chainB's nodes are untouched: the walk says so, the fit is skipped
    assert applier.stats["fence_walked"] == 6 + 2 * 6
    assert applier.stats["fast_path"] == 3


# -------------------------------------------------------------- scaling

def _calls_of_apply_one(n):
    """Python-level and C-level calls of one `apply_one` on a fenced
    system block of `n` nodes, the packer attached and the quality
    gauges flushed inside it, as in the served path."""
    state, applier, plan, nodes = _fenced(n)
    packer = ClusterPacker()
    packer.attach(state)
    packer.update(state.snapshot())
    plan.coupled_batch = ("eval-fenced", state.placement_seq())
    applier._quality_next = 0.0
    pending = PendingPlan(plan)
    calls = [0]

    def count(frame, event, arg):
        if event in ("call", "c_call"):
            calls[0] += 1

    sys.setprofile(count)
    try:
        applier.apply_one(pending)
    finally:
        sys.setprofile(None)
    assert pending.error is None and pending.result.alloc_blocks
    assert not pending.result.refuted_nodes
    assert applier.stats["fence_walked"] == 0
    t = packer._tensors
    assert int(t.used[:, 0].sum()) == 100 * n
    assert state.quality_summary()["nodes_in_use"] == n
    return calls[0]


def test_apply_one_makes_no_call_a_node():
    """A per-node loop on the commit path of a fleet-wide block fails
    here, not in a benchmark: 2,000 more nodes may cost fewer than
    2 calls each (at `3145b23` it was ~20)."""
    small, large = _calls_of_apply_one(2000), _calls_of_apply_one(4000)
    assert large - small < 2 * 2000, (small, large)
    assert large < 2 * 4000, large
