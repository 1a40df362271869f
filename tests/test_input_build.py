"""The wave's input build derives from the node table when the node table
changes, not when a wave launches: the packer's membership check goes by
the store's node events (pack/packer.py update), the compact path's
candidate frames by what they were derived from (ops/engine.py
_candidate_frames)."""

import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import PlacementEngine
from nomad_tpu.pack import ClusterPacker
from nomad_tpu.pack.interner import UNSET
from nomad_tpu.state import StateStore

from test_batch_evals import build_zoned_cluster, zoned_items

CHECKED = "nomad.packer.membership_checked"
BUILT = "nomad.engine.frames_built"
REUSED = "nomad.engine.frames_reused"


class Counted:
    """Reads of registry counters relative to now."""

    def __init__(self, *names):
        self.names = names
        self.take()

    def take(self):
        now = [REGISTRY.counter(n) for n in self.names]
        delta = [a - b for a, b in zip(now, getattr(self, "_at", now))]
        self._at = now
        return delta[0] if len(delta) == 1 else tuple(delta)


# ------------------------------------------------------------------ packer

def table_of(packer, t):
    """A packer's tensors as {node id: (attributes by column name, elig,
    cap, used, dc, pool)}: what two packers with different vocabularies
    and column orders must agree on."""
    string = packer.interner.string
    cols = [(k, c) for k, c in packer.columns.items()
            if c < t.attrs.shape[1]]
    out = {}
    for nid, row in t.id_to_row.items():
        attrs = {k: string(int(t.attrs[row, c])) for k, c in cols
                 if t.attrs[row, c] != UNSET}
        out[nid] = (attrs, bool(t.elig[row]), tuple(t.cap[row].tolist()),
                    tuple(t.used[row].tolist()), string(int(t.dc[row])),
                    string(int(t.pool[row])))
    assert list(t.id_to_row) == t.node_ids
    return out


def attached_packer(n_nodes=12):
    store = StateStore()
    nodes = [mock.node() for _ in range(n_nodes)]
    store.upsert_nodes(nodes)
    packer = ClusterPacker()
    packer.attach(store)
    packer.update(store.snapshot())
    return store, packer, nodes


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_update_equals_fresh_build(seed):
    """Over a random run of node upserts, eligibility and status flips,
    deletes, re-adds and alloc commits, the attached packer's update()
    holds what a fresh packer's build() reads from the same snapshot."""
    rng = random.Random(seed)
    store, packer, nodes = attached_packer()
    live = {n.id: n for n in nodes}
    gone = {}
    allocs = []
    job = mock.job()
    store.upsert_job(job)

    def add():
        n = mock.node()
        n.datacenter = rng.choice(["dc1", "dc2"])
        n.attributes["rack"] = f"r{rng.randrange(4)}"
        store.upsert_node(n)
        live[n.id] = n

    def rewrite():
        n = live[rng.choice(sorted(live))].copy()
        n.attributes = {**n.attributes, "rack": f"r{rng.randrange(9)}",
                        f"tag{rng.randrange(3)}": "x"}
        n.resources.cpu = rng.choice([2000, 4000, 8000])
        store.upsert_node(n)
        live[n.id] = n

    def flip_eligibility():
        store.update_node_eligibility(
            rng.choice(sorted(live)),
            rng.choice(["eligible", "ineligible"]))

    def flip_status():
        store.update_node_status(rng.choice(sorted(live)),
                                 rng.choice(["ready", "down"]))

    def delete():
        if len(live) > 3:
            nid = rng.choice(sorted(live))
            gone[nid] = live.pop(nid)
            store.delete_node(nid)

    def readd():
        if gone:
            nid = rng.choice(sorted(gone))
            live[nid] = gone.pop(nid)
            store.upsert_node(live[nid])

    def commit_allocs():
        new = [mock.alloc(job=job, node_id=rng.choice(sorted(live)))
               for _ in range(rng.randrange(1, 4))]
        store.upsert_allocs(new)
        allocs.extend(new)

    def finish_alloc():
        if allocs:
            a = allocs.pop(rng.randrange(len(allocs))).copy_skip_job()
            a.client_status = "complete"
            store.update_allocs_from_client([a])

    ops = [add, rewrite, flip_eligibility, flip_status, delete, readd,
           commit_allocs, finish_alloc]
    for _ in range(40):
        for _ in range(rng.randrange(1, 4)):
            rng.choice(ops)()
        snap = store.snapshot()
        t = packer.update(snap)
        fresh = ClusterPacker()
        assert table_of(packer, t) == table_of(fresh, fresh.build(snap))
        assert set(t.id_to_row) == set(live)


def _add(store, nodes):
    store.upsert_node(mock.node())
    return len(nodes) + 1


def _delete(store, nodes):
    store.delete_node(nodes[3].id)
    return len(nodes) - 1


def _restore(store, nodes):
    store.snapshot_restore(store.snapshot_save())
    return len(nodes)


@pytest.mark.parametrize("change", [_add, _delete, _restore, "replica_feed"])
def test_membership_change_forces_rebuild(change):
    store, packer, nodes = attached_packer()
    if change == "replica_feed":
        # a store fed by apply_export fires no "Node" event: there the
        # packer finds the change by the walk, as it always did
        parent = store
        store = StateStore()
        store.apply_export(parent.export_since(0))
        packer = ClusterPacker()
        packer.attach(store)

        def change(store, nodes):
            parent.upsert_nodes([mock.node(), mock.node()])
            export = parent.export_since(store.latest_index())
            assert export["kind"] == "delta"
            store.apply_export(export)
            assert not packer._dirty     # no event reached the packer
            return len(nodes) + 2
    t0 = packer.update(store.snapshot())
    seen = Counted(CHECKED)
    assert packer.update(store.snapshot()) is t0
    assert seen.take() == 0
    want = change(store, nodes)
    snap = store.snapshot()
    t1 = packer.update(snap)
    assert t1 is not t0                      # a rebuild, not a row refresh
    assert t1.n == want and set(t1.id_to_row) == {
        n.id for n in snap.nodes()}
    fresh = ClusterPacker()
    assert table_of(packer, t1) == table_of(fresh, fresh.build(snap))
    # what the change cost to find: the dirty ids; nothing on a restore
    # (the event says all); every node where no event says anything
    assert seen.take() == {"_add": 1, "_delete": 1, "_restore": 0}.get(
        change.__name__, want)
    assert packer.update(store.snapshot()) is t1
    assert seen.take() == 0


@pytest.mark.parametrize("events, checked", [
    ("none", 0), ("allocs", 0), ("jobs", 0), ("one_node", 1),
    ("three_nodes", 3), ("same_node_twice", 1)])
def test_membership_checked_counts_dirty_ids(events, checked):
    store, packer, nodes = attached_packer()
    t0 = packer.update(store.snapshot())
    seen = Counted(CHECKED)
    if events == "allocs":
        store.upsert_allocs([mock.alloc(node_id=nodes[0].id)])
    elif events == "jobs":
        store.upsert_job(mock.job())
    elif events == "one_node":
        store.update_node_eligibility(nodes[1].id, "ineligible")
    elif events == "three_nodes":
        for n in nodes[:3]:
            store.update_node_status(n.id, "down")
    elif events == "same_node_twice":
        store.update_node_eligibility(nodes[1].id, "ineligible")
        store.update_node_eligibility(nodes[1].id, "eligible")
    for _ in range(3):
        assert packer.update(store.snapshot()) is t0    # never a rebuild
    assert seen.take() == checked
    assert CHECKED in REGISTRY.snapshot()["counters"]


# ------------------------------------------------------------------ frames

def frames_case(case):
    """(engine, harness, nodes, items) for one case of the frame tests."""
    if case == "mesh8":
        h, nodes = build_zoned_cluster(512)     # mesh-multiple node count
        eng = PlacementEngine()                 # auto-mesh (8 devices)
        assert eng.mesh is not None
    else:
        h, nodes = build_zoned_cluster()
        eng = PlacementEngine(mesh=False)
    eng.packer.attach(h.state)
    return eng, h, nodes, zoned_items(h, 10, 30)


def frames_of(built):
    return (np.array(built["cand_rows"]), np.array(built["cand_valid"]))


def candidates(built):
    return set(built["cand_rows"][built["cand_valid"]].tolist())


@pytest.mark.parametrize("case", [
    "reuse", "other_weights", "eligibility_flip", "other_signatures",
    "other_datacenters", "masked", "mesh8"])
def test_candidate_frames_by_node_table_version(case):
    eng, h, nodes, items = frames_case(case)
    seen = Counted(BUILT, REUSED)
    first = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
    assert first["cand_rows"] is not None
    assert seen.take() == (1, 0)
    again = eng.build_multi_inputs(h.state.snapshot(), items, seed=4)
    assert seen.take() == (0, 1)
    for a, b in zip(frames_of(first), frames_of(again)):
        assert np.array_equal(a, b)
    assert again["cand_dev"] is first["cand_dev"]       # no second upload
    assert not first["cand_rows"].flags.writeable
    # the frames are what a new engine derives from the same snapshot
    cold = PlacementEngine(mesh=None if case == "mesh8" else False)
    for a, b in zip(frames_of(again), frames_of(cold.build_multi_inputs(
            h.state.snapshot(), items, seed=3))):
        assert np.array_equal(a, b)
    row = eng.packer.update(h.state.snapshot()).id_to_row
    seen.take()                 # the counters are the process's: drop cold's

    if case in ("reuse", "mesh8"):
        if case == "mesh8":
            assert first["cand_rows"].ndim == 3          # [S, L, Nc_loc]
            assert first["cand_rows"].shape[0] == eng.n_devices
        # the launch takes the same frames, decisions unchanged
        d0 = eng.place_batch(h.state.snapshot(), items, seed=3)
        assert seen.take() == (0, 1)
        d1 = cold.place_batch(h.state.snapshot(), items, seed=3)
        for a, b in zip(d0, d1):
            assert np.array_equal(a.picks, b.picks)
        # the kept device copies count as resident
        held = sum(a.nbytes for a in first["cand_dev"])
        with_frames = eng.device_resident_bytes()
        eng._frame_cache.clear()
        assert eng.device_resident_bytes() == with_frames - held
    elif case == "other_weights":
        # a drain's next wave: new jobs, the same signatures first seen
        # in another order and weighing differently (the heaviest-first
        # order of the clique's prover differs): still the same frames,
        # and the lanes' order is no part of what is decided
        import nomad_tpu.ops.engine as em
        wave = zoned_items(h, 13, 30)[2:]
        built = eng.build_multi_inputs(h.state.snapshot(), wave, seed=5)
        assert seen.take() == (0, 1)
        assert built["cand_dev"] is first["cand_dev"]
        d_lanes = eng.place_batch(h.state.snapshot(), wave, seed=5)
        lanes, em.MAX_LANES = em.MAX_LANES, 1       # the flat schedule
        try:
            d_flat = PlacementEngine(mesh=False).place_batch(
                h.state.snapshot(), wave, seed=5)
        finally:
            em.MAX_LANES = lanes
        for a, b in zip(d_lanes, d_flat):
            assert np.array_equal(a.picks, b.picks)
    elif case == "eligibility_flip":
        victim = nodes[7].id
        assert row[victim] in candidates(again)
        h.state.update_node_eligibility(victim, "ineligible")
        after = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert seen.take() == (1, 0)             # version moved: rebuilt
        assert row[victim] not in candidates(after)
        assert candidates(after) == candidates(again) - {row[victim]}
        eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert seen.take() == (0, 1)
    elif case == "other_signatures":
        fewer = [it for it in items if "zone4" not in
                 it.tg.volumes["data"].source]
        sub = eng.build_multi_inputs(h.state.snapshot(), fewer, seed=3)
        assert seen.take() == (1, 0)
        assert sub["n_lanes"] == 4 and first["n_lanes"] == 5
        # both sets stay: a wave of either kind reuses its own
        eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        eng.build_multi_inputs(h.state.snapshot(), fewer, seed=3)
        assert seen.take() == (0, 2)
    elif case == "other_datacenters":
        for it in items:
            it.job.datacenters = ["dc1", "dc2"]
        two = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert seen.take() == (1, 0)
        dc3 = {row[n.id] for n in nodes if n.datacenter == "dc3"}
        assert candidates(two) == candidates(first) - dc3
    elif case == "masked":
        victim = nodes[7].id
        masked = eng.build_multi_inputs(h.state.snapshot(), items, seed=3,
                                        masked_node_ids=[victim])
        assert seen.take() == (1, 0)             # built, and not kept
        assert candidates(masked) == candidates(first) - {row[victim]}
        unmasked = eng.build_multi_inputs(h.state.snapshot(), items, seed=3)
        assert seen.take() == (0, 1)
        assert row[victim] in candidates(unmasked)
