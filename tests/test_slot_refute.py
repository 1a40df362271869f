"""The name-slot refute at the FSM boundary compares slots by integer
name index (state/state_store.py `_refute_replayed_placements_locked`).

Held here to the formatted-name form it replaces, kept below as a plain
oracle: on seeded random plans against seeded store contents (live rows
and blocks, holders stopped or preempted by the plan itself, re-commits
under the same ids, other groups and versions, partial replays inside a
block, system-family jobs, names spelled off the canonical form) both
must keep the same rows, the same blocks and split the same blocks.
Then a replay through `upsert_plan_results`, and the fresh job's path,
which must count `none_held` and build no name string.
"""

import random
from typing import Dict, Tuple

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.state import StateStore
from nomad_tpu.state import state_store as state_store_mod
from nomad_tpu.structs import (
    AllocBlock,
    AllocMetric,
    Allocation,
    Plan,
    PlanResult,
    Resources,
)

GROUPS = ("web", "api")
NODES = ("n0", "n1", "n2")
SLOTS = 10                      # name indexes 0..9: collisions are common


# ------------------------------------------------------------ the oracle

def oracle_refute(store, result) -> None:
    """The refute as it read before slots went by integer index: every
    held slot and every checked row keyed by its formatted name."""
    touched = set()
    for node_allocs in result.node_update.values():
        touched.update(a.id for a in node_allocs)
    for node_allocs in result.node_preemptions.values():
        touched.update(a.id for a in node_allocs)

    live_cache: Dict[Tuple[str, str], Dict[Tuple, str]] = {}

    def live_slots(ns, job_id):
        key = (ns, job_id)
        slots = live_cache.get(key)
        if slots is not None:
            return slots
        slots = {}
        for a in store._allocs_by_job.get(key, {}).values():
            if (a.id in touched or a.desired_status != "run"
                    or a.client_terminal_status()):
                continue
            slots[(a.task_group, a.name, a.job_version)] = a.id
        for b in store._blocks_by_job.get(key, ()):
            tmpl = b.template
            for i, bid in zip(b.indexes, b.ids):
                if bid in touched:
                    continue
                slots[(tmpl.task_group, f"{b.name_prefix}{i}]",
                       tmpl.job_version)] = bid
        live_cache[key] = slots
        return slots

    def system_family(job):
        return job is not None and job.type in ("system", "sysbatch")

    for nid, node_allocs in list(result.node_allocation.items()):
        keep = []
        for a in node_allocs:
            if not system_family(a.job):
                holder = live_slots(a.namespace, a.job_id).get(
                    (a.task_group, a.name, a.job_version))
                if holder is not None and holder != a.id:
                    continue
            keep.append(a)
        if len(keep) != len(node_allocs):
            result.node_allocation[nid] = keep

    if result.alloc_blocks:
        kept_blocks = []
        for block in result.alloc_blocks:
            tmpl = block.template
            if system_family(tmpl.job):
                kept_blocks.append(block)
                continue
            slots = live_slots(tmpl.namespace, tmpl.job_id)
            colliding = {
                j for j, (i, bid) in enumerate(zip(block.indexes, block.ids))
                if slots.get((tmpl.task_group, f"{block.name_prefix}{i}]",
                              tmpl.job_version)) not in (None, bid)}
            if not colliding:
                kept_blocks.append(block)
                continue
            if len(colliding) == len(block.ids):
                continue
            rows = block.materialize_all()
            for j, row in enumerate(rows):
                if j not in colliding:
                    result.node_allocation.setdefault(
                        row.node_id, []).append(row)
        result.alloc_blocks = kept_blocks


# ------------------------------------------------------- seeded contents

def make_jobs():
    jobs = {}
    for jid, jtype in (("svc-a", "service"), ("svc-b", "batch"),
                       ("sys-c", "system"), ("sb-d", "sysbatch")):
        jobs[jid] = mock.job(id=jid, type=jtype)
    return jobs


def prefix_of(job_id, group, odd):
    """A block's name prefix: the schedulers' `job.group[`, or (odd) one
    no scheduler builds, whose row names carry no `[` at all."""
    return f"{job_id}.{group}" if odd else f"{job_id}.{group}["


def name_of(job_id, group, i, style):
    if style == "padded":                  # `[07]`: not str(7)'s spelling
        return f"{job_id}.{group}[0{i}]"
    return f"{prefix_of(job_id, group, style == 'odd')}{i}]"


def pick_style(rng):
    return rng.choices(("canonical", "padded", "odd"), (8, 1, 1))[0]


def block(bid, job, group, version, prefix, indexes, ids, rng):
    return AllocBlock(
        id=bid,
        template=Allocation(
            namespace=job.namespace, eval_id=f"eval-{ids[0]}",
            job_id=job.id, job=job, task_group=group,
            resources=Resources(cpu=10, memory_mb=10, disk_mb=10),
            desired_status="run", client_status="pending",
            job_version=version),
        ids=list(ids), name_prefix=prefix, indexes=list(indexes),
        picks=np.array([rng.randrange(len(NODES)) for _ in ids], np.int32),
        node_table=list(NODES), metrics=[AllocMetric()],
        round_size=max(len(ids), 1))


def row(job, group, version, name, aid, node, desired="run",
        client="running"):
    return Allocation(
        id=aid, namespace=job.namespace, eval_id=f"eval-{aid}", name=name,
        node_id=node, job_id=job.id, job=job, task_group=group,
        resources=Resources(cpu=10, memory_mb=10, disk_mb=10),
        desired_status=desired, client_status=client, job_version=version,
        metrics=AllocMetric())


def seeded_store(seed):
    """A store holding a seeded mix of live and dead rows and blocks of
    four jobs; returns (store, jobs, held) with `held` the
    (job, group, version, prefix-or-name-style, index, id) of every
    allocation written, for the plan to collide with."""
    rng = random.Random(seed)
    jobs = make_jobs()
    store = StateStore()
    for nid in NODES:
        store.upsert_node(mock.node(id=nid))
    held = []
    blocks = []
    for k in range(rng.randint(1, 4)):
        job = jobs[rng.choice(("svc-a", "svc-b", "sys-c"))]
        group, version = rng.choice(GROUPS), rng.choice((0, 1))
        odd = rng.random() < 0.15
        indexes = rng.sample(range(SLOTS), rng.randint(1, 6))
        ids = [f"s{seed}-b{k}-{i}" for i in indexes]
        blocks.append(block(f"s{seed}-b{k}", job, group, version,
                            prefix_of(job.id, group, odd), indexes, ids, rng))
        held.extend((job, group, version, "odd" if odd else "canonical",
                     i, aid) for i, aid in zip(indexes, ids))
    # one plan of blocks into an empty store: nothing to refute yet
    store.upsert_plan_results(Plan(eval_id=f"setup-{seed}"),
                              PlanResult(alloc_blocks=blocks))
    rows = []
    for k in range(rng.randint(0, 10)):
        job = jobs[rng.choice(("svc-a", "svc-b", "sys-c"))]
        group, version = rng.choice(GROUPS), rng.choice((0, 1))
        i, style = rng.randrange(SLOTS), pick_style(rng)
        aid = f"s{seed}-r{k}"
        desired = rng.choices(("run", "stop", "evict"), (6, 1, 1))[0]
        client = rng.choices(("running", "pending", "complete", "failed",
                              "lost"), (5, 2, 1, 1, 1))[0]
        rows.append(row(job, group, version, name_of(job.id, group, i, style),
                        aid, rng.choice(NODES), desired, client))
        held.append((job, group, version, style, i, aid))
    store.upsert_allocs(rows)
    return store, jobs, held


def seeded_result(seed, jobs, held):
    """A plan result against `seeded_store(seed)`, built afresh on each
    call (the same seed builds the same objects)."""
    rng = random.Random(seed * 7919 + 1)
    res = PlanResult()
    live_ids = [h[5] for h in held]
    for table in (res.node_update, res.node_preemptions):
        for aid in rng.sample(live_ids, min(len(live_ids),
                                            rng.randint(0, 2))):
            table.setdefault(rng.choice(NODES), []).append(
                Allocation(id=aid))
    for k in range(rng.randint(0, 8)):
        if held and rng.random() < 0.6:
            # a slot the store holds: replay (new id), re-commit (its
            # own id), or the same name in another group or version
            job, group, version, style, i, aid = rng.choice(held)
            what = rng.choice(("replay", "recommit", "group", "version"))
            if what == "group":
                group = GROUPS[1 - GROUPS.index(group)]
            elif what == "version":
                version = 1 - version
            if what != "recommit":
                aid = f"p{seed}-r{k}"
        else:
            job = jobs[rng.choice(tuple(jobs))]
            group, version = rng.choice(GROUPS), rng.choice((0, 1))
            i, style = rng.randrange(SLOTS), pick_style(rng)
            aid = f"p{seed}-r{k}"
        res.node_allocation.setdefault(rng.choice(NODES), []).append(
            row(job, group, version, name_of(job.id, group, i, style),
                aid, rng.choice(NODES)))
    for k in range(rng.randint(0, 4)):
        job = jobs[rng.choice(tuple(jobs))]
        group, version = rng.choice(GROUPS), rng.choice((0, 1))
        odd = rng.random() < 0.15
        same = [h for h in held if h[0] is job and h[1:3] == (group, version)
                and h[3] == ("odd" if odd else "canonical")]
        if same and rng.random() < 0.4:    # the held slots, all of them
            indexes = sorted({h[4] for h in same})
        else:
            indexes = rng.sample(range(SLOTS), rng.randint(1, SLOTS))
        by_index = {h[4]: h[5] for h in same}
        # rows of a held slot re-commit under the holder's id now and
        # then: a partial replay, a whole replay or a plain re-commit
        ids = [by_index[i] if i in by_index and rng.random() < 0.4
               else f"p{seed}-b{k}-{i}" for i in indexes]
        res.alloc_blocks.append(block(f"p{seed}-b{k}", job, group, version,
                                      prefix_of(job.id, group, odd),
                                      indexes, ids, rng))
    return res


def outcome(res):
    return ({nid: [a.id for a in allocs]
             for nid, allocs in res.node_allocation.items()},
            [(b.id, list(b.ids)) for b in res.alloc_blocks])


SEEDS = list(range(40))


def refute_both(seed):
    store, jobs, held = seeded_store(seed)
    mine, theirs = (seeded_result(seed, jobs, held) for _ in range(2))
    assert outcome(mine) == outcome(theirs)   # built alike
    before = outcome(mine)
    with store._lock:
        store._refute_replayed_placements_locked(mine)
    oracle_refute(store, theirs)
    return before, outcome(mine), outcome(theirs)


@pytest.mark.parametrize("seed", SEEDS)
def test_masks_match_formatted_name_oracle(seed):
    _, mine, theirs = refute_both(seed)
    assert mine == theirs


def test_seeds_reach_every_branch():
    """The seeds above mask rows, drop whole blocks and split blocks;
    otherwise the parity says little."""
    masked_rows = dropped = split = 0
    for seed in SEEDS:
        before, after, _ = refute_both(seed)
        rows_before = {a for ids in before[0].values() for a in ids}
        rows_after = {a for ids in after[0].values() for a in ids}
        masked_rows += bool(rows_before - rows_after)
        kept = {bid for bid, _ in after[1]}
        for bid, ids in before[1]:
            if bid not in kept:
                if rows_after & set(ids):
                    split += 1
                else:
                    dropped += 1
    assert masked_rows and dropped and split


@pytest.mark.parametrize("name, slot", [
    ("web.api[7]", ("web.api[", 7)),
    ("web.api[07]", ("web.api[", "07]")),
    ("web.api[-3]", ("web.api[", -3)),
    ("web.api[x]", ("web.api[", "x]")),
    ("web.api7]", ("", "web.api7]")),
    ("a[b[12]", ("a[b[", 12)),
])
def test_name_slot_spelling(name, slot):
    assert state_store_mod._name_slot(name) == slot


# ------------------------------------------------- through the store

def slot_checks():
    counters = REGISTRY.snapshot()["counters"]
    return {how: counters.get(f"nomad.store.slot_checks{{how={how}}}", 0)
            for how in ("none_held", "intersected")}


def fleet_block(job, nodes, indexes, tag):
    tg = job.task_groups[0]
    return AllocBlock(
        id=f"blk-{tag}",
        template=Allocation(
            namespace=job.namespace, eval_id=f"eval-{tag}", job_id=job.id,
            job=job, task_group=tg.name,
            resources=Resources(cpu=10, memory_mb=10, disk_mb=10),
            desired_status="run", client_status="pending",
            job_version=job.version),
        ids=[f"{tag}-{i}" for i in indexes],
        name_prefix=f"{job.id}.{tg.name}[",
        indexes=list(indexes),
        picks=np.arange(len(indexes), dtype=np.int32) % len(nodes),
        node_table=[n.id for n in nodes],
        metrics=[AllocMetric()],
        round_size=max(len(indexes), 1))


def commit(state, block):
    plan = Plan(eval_id=block.template.eval_id, job=block.template.job)
    plan.alloc_blocks.append(block)
    return state.upsert_plan_results(plan, PlanResult(alloc_blocks=[block]))


def live_names(state, job):
    return sorted(a.name for a in state.allocs_by_job(job.namespace, job.id)
                  if a.desired_status == "run")


def test_fresh_job_counts_none_held_and_builds_no_name(monkeypatch):
    state = StateStore()
    nodes = [mock.node() for _ in range(4)]
    state.upsert_nodes(nodes)
    job = mock.job()

    def no_names(*_a, **_kw):
        raise AssertionError("a fresh job's commit built a name")

    monkeypatch.setattr(AllocBlock, "materialize_all", no_names)
    monkeypatch.setattr(state_store_mod, "_name_slot", no_names)
    before = slot_checks()
    assert commit(state, fleet_block(job, nodes, range(260), "first")) > 0
    after = slot_checks()
    assert after["none_held"] == before["none_held"] + 1
    assert after["intersected"] == before["intersected"]
    assert len(state.snapshot().alloc_blocks()) == 1


def test_replayed_block_is_masked_through_the_store():
    state = StateStore()
    nodes = [mock.node() for _ in range(4)]
    state.upsert_nodes(nodes)
    job = mock.job()
    commit(state, fleet_block(job, nodes, range(70), "first"))
    names = live_names(state, job)
    assert len(names) == 70

    # the deposed leader's plan lands again under new ids: all masked
    before = slot_checks()
    commit(state, fleet_block(job, nodes, range(70), "retry"))
    assert slot_checks()["intersected"] == before["intersected"] + 1
    assert live_names(state, job) == names

    # a retry reaching past the held slots: only the new slots commit
    commit(state, fleet_block(job, nodes, range(60, 80), "wider"))
    wider = live_names(state, job)
    assert len(wider) == 80 and len(set(wider)) == 80
    kept = [a.id for a in state.allocs_by_job(job.namespace, job.id)
            if a.id.startswith("wider-")]
    assert sorted(kept) == sorted(f"wider-{i}" for i in range(70, 80))

    # a row of a held slot under a new id is masked too; under its
    # holder's id it is a re-commit and stays
    holder = next(a for a in state.allocs_by_job(job.namespace, job.id)
                  if a.name.endswith("[3]"))
    replay = row(job, holder.task_group, holder.job_version, holder.name,
                 "row-replay", nodes[0].id)
    plan = Plan(eval_id="eval-row", job=job)
    res = PlanResult(node_allocation={nodes[0].id: [replay]})
    state.upsert_plan_results(plan, res)
    assert state.alloc_by_id("row-replay") is None
