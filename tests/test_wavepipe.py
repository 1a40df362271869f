"""Wave-pipelined commit engine (core/wavepipe.py).

The pipelining contract, proven rather than asserted:
  - wave k+1's device dispatch STARTS before wave k's host commit
    COMPLETES (stage-timer intervals), with capacity still coupled
    through the device-side usage chain;
  - rows the applier refutes are masked out of the next chained
    dispatch's constraint input and are never double-committed — the
    repair re-places only the missing rows;
  - the pipelined columnar commit paths (fenced wholesale, full-check
    columnar, forced per-alloc expansion, plain Harness) all land
    IDENTICAL final state-store contents for the same eval batch.
"""

import json
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.core.wavepipe import StageTimers, WavePipeline
from nomad_tpu.ops.engine import BatchItem
from nomad_tpu.scheduler import Harness
from nomad_tpu.structs import Allocation, Resources, new_id

NOW = 1.7e9


def build_cluster(n_nodes=12, cpu=4000, mem=8192):
    h = Harness()
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = cpu
        n.resources.memory_mb = mem
        nodes.append(n)
    h.state.upsert_nodes(nodes)
    return h, nodes


def make_items(h, n_items, count, cpu=500, mem=64):
    items = []
    for _ in range(n_items):
        job = mock.batch_job()
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = cpu
        tg.tasks[0].resources.memory_mb = mem
        h.state.upsert_job(job)
        items.append(BatchItem(job=job, tg=tg, count=count))
    return items


def commit_decisions(h, items, decisions):
    """Host commit of a wave's picks as ordinary allocs (the test's
    stand-in for materialize+commit; the worker path is covered by the
    end-to-end tests below)."""
    allocs = []
    for it, bd in zip(items, decisions):
        ask = it.tg.combined_resources()
        for pick in bd.picks.tolist():
            if pick < 0:
                continue
            allocs.append(Allocation(
                id=new_id(), namespace=it.job.namespace, job_id=it.job.id,
                job=it.job, task_group=it.tg.name,
                node_id=bd.node_ids[pick], resources=ask,
                desired_status="run", client_status="pending"))
    h.state.upsert_allocs(allocs)
    return allocs


def picked_nodes(decisions):
    return {bd.node_ids[p] for bd in decisions
            for p in bd.picks.tolist() if p >= 0}


class TestStageTimers:
    def test_overlap_math(self):
        t = StageTimers()
        t.record("device", 0.0, 3.0, wave=2)
        t.record("commit", 1.0, 2.0, wave=1)
        t.record("commit", 2.5, 4.0, wave=2)
        assert abs(t.overlap("device", "commit") - 1.5) < 1e-9
        assert abs(t.totals()["commit"] - 2.5) < 1e-9
        rep = t.report()
        assert rep["overlap_s"]["device*commit"] == 1.5
        t.reset()
        assert t.totals() == {}


class TestPipelineOverlap:
    def test_next_wave_dispatches_before_prior_commit(self):
        """The pipelining contract itself: wave 2 is dispatched (chained
        on wave 1's device-side proposed usage) BEFORE wave 1's commit
        runs, the stage timers prove the ordering, and the committed
        result still never oversubscribes a node — i.e. the chain, not
        the store, carried wave 1's usage into wave 2's scoring."""
        h, nodes = build_cluster(n_nodes=6)
        timers = StageTimers()
        pipe = WavePipeline(h.engine, timers)
        snap = h.state.snapshot()
        # 2 waves x 12 asks of 1000 cpu vs 6 nodes x 3 usable slots:
        # wave 2 must see wave 1's proposed usage or nodes oversubscribe
        items1 = make_items(h, 3, 4, cpu=1000)
        items2 = make_items(h, 3, 4, cpu=1000)
        w1 = pipe.dispatch(snap, items1, seed=3)
        d1 = pipe.collect(w1)
        w2 = pipe.dispatch(snap, items2, seed=4,
                           used0_dev=pipe.chain_state(w1))
        with pipe.commit(w1.wave):
            commit_decisions(h, items1, d1)
        d2 = pipe.collect(w2)
        with pipe.commit(w2.wave):
            commit_decisions(h, items2, d2)

        disp = {w: (t0, t1) for w, t0, t1 in timers.intervals("dispatch")}
        com = {w: (t0, t1) for w, t0, t1 in timers.intervals("commit")}
        # wave 2's dispatch started before wave 1's commit completed
        assert disp[w2.wave][0] < com[w1.wave][1]
        # every stage of the pipeline reported wall time
        totals = timers.totals()
        for stage in ("dispatch", "device", "d2h", "commit"):
            assert stage in totals, totals
        # capacity stayed coupled across the chain: per-node cpu within
        # the usable envelope (4000 cap - 100 reserved)
        by_node = {}
        snap2 = h.state.snapshot()
        for n in nodes:
            cpu = sum(a.resources.cpu for a in snap2.allocs_by_node(n.id)
                      if not a.terminal_status())
            by_node[n.id] = cpu
            assert cpu <= 3900, (n.id, cpu)
        # and the cluster actually filled: 18 usable slots for 24 asks
        placed = sum(len(bd.picks[bd.picks >= 0]) for bd in d1 + d2)
        assert placed == 18, placed


class TestRefuteRepair:
    def test_masked_nodes_excluded_from_chained_dispatch(self):
        """A refuted node is the binpack kernel's FAVORITE node (most
        filled); the mask must beat that preference in the next chained
        wave, and a fresh dispatch must clear the mask."""
        h, nodes = build_cluster(n_nodes=8, cpu=8000, mem=16384)
        pipe = WavePipeline(h.engine)
        snap = h.state.snapshot()
        items1 = make_items(h, 2, 3, cpu=200)
        w1 = pipe.dispatch(snap, items1, seed=1)
        d1 = pipe.collect(w1)
        target = sorted(picked_nodes(d1))[0]
        pipe.note_refuted([target])
        assert target in pipe.masked_nodes()
        items2 = make_items(h, 2, 3, cpu=200)
        w2 = pipe.dispatch(snap, items2, seed=2,
                           used0_dev=pipe.chain_state(w1))
        d2 = pipe.collect(w2)
        assert (d2[0].picks >= 0).all() and (d2[1].picks >= 0).all()
        assert target not in picked_nodes(d2), "masked node re-picked"
        # a FRESH (unchained) dispatch sees committed state and clears
        # the mask
        items3 = make_items(h, 2, 3, cpu=200)
        w3 = pipe.dispatch(snap, items3, seed=3)
        pipe.collect(w3)
        assert not pipe.masked_nodes()

    def test_refuted_rows_repaired_not_double_committed(self):
        """End-to-end through the Server: a foreign write lands on a
        block's node between dispatch and commit, the applier refutes
        that node's rows COLUMNAR, the repair path masks the node +
        re-queues only the missing rows, and the final state carries
        exactly `count` live allocs per job — never a double commit."""
        s = Server(dev_mode=True, eval_batch=8)
        s.establish_leadership()
        nodes = []
        for _ in range(4):
            n = mock.node()
            n.resources.cpu = 8000
            n.resources.memory_mb = 16384
            s.register_node(n, now=NOW)
            nodes.append(n)
        jobs = []
        for _ in range(2):           # >=2 batchable evals -> one wave;
            job = mock.batch_job()   # count >= 64 -> columnar blocks
            job.task_groups[0].count = 80
            job.task_groups[0].tasks[0].resources.cpu = 100
            job.task_groups[0].tasks[0].resources.memory_mb = 64
            s.register_job(job, now=NOW)
            jobs.append(job)

        applier = s.plan_applier
        orig = applier._apply_one
        sabotage = {"armed": True, "node": None}

        def foreign_write_then_apply(pending):
            plan = pending.plan
            if sabotage["armed"] and plan.alloc_blocks:
                # hit the block's MOST-LOADED node (node_table order is
                # row-index order, not load order): >= 2 rows there stop
                # fitting, so the full re-check must refute them
                blk = plan.alloc_blocks[0]
                nid = blk.node_table[int(np.argmax(blk.node_counts()))]
                sabotage["armed"] = False
                sabotage["node"] = nid
                # fill the node: usable 7900, foreign takes 7800 -> the
                # block's 100-cpu rows there no longer fit (at most one)
                s.state.upsert_allocs([Allocation(
                    id=new_id(), namespace="default", job_id="foreign-job",
                    task_group="tg", node_id=nid,
                    resources=Resources(cpu=7800, memory_mb=64),
                    desired_status="run", client_status="pending")])
            return orig(pending)

        applier._apply_one = foreign_write_then_apply
        s.process_all(now=NOW)

        assert sabotage["node"] is not None, "no block plan was applied"
        assert applier.stats["plans_refuted"] >= 1, applier.stats
        # the refuted node went through the pipeline's mask (a later
        # FRESH dispatch legitimately clears it — committed state then
        # accounts the foreign write — so assert via the repair stats)
        pipe = s.workers[0].pipeline
        assert pipe.stats["repairs"] >= 1, pipe.stats
        assert pipe.stats["masked_nodes"] >= 1, pipe.stats
        snap = s.state.snapshot()
        for job in jobs:
            live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            # exactly count allocs — refuted rows re-placed ONCE
            assert len(live) == 80, (job.id, len(live))
            assert len({a.id for a in live}) == 80
        # the sabotaged node never oversubscribed (usable 7900)
        cpu = sum(a.resources.cpu
                  for a in snap.allocs_by_node(sabotage["node"])
                  if not a.terminal_status())
        assert cpu <= 7900, cpu
        # the repair eval is recorded and completed
        evs = [e for job in jobs
               for e in snap.evals_by_job(job.namespace, job.id)]
        assert any(e.triggered_by == "plan-refute-repair" for e in evs)
        assert all(e.status == "complete" for e in evs), \
            [(e.status, e.status_description) for e in evs]


def _fixed_cluster_nodes(n_nodes=16, seed=11):
    rng = random.Random(seed)
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = rng.choice([4000, 8000])
        n.resources.memory_mb = 16384
        nodes.append(n)
    return nodes


def _contents(state):
    """Comparable final-state fingerprint: every live alloc's
    (name, node, cpu) — ids are random, names are deterministic."""
    snap = state.snapshot()
    rows = []
    for job in snap.jobs():
        for a in snap.allocs_by_job(job.namespace, job.id):
            if a.terminal_status():
                continue
            rows.append((a.name, a.node_id, a.resources.cpu))
    return sorted(rows)


class TestPipelinedSerialParity:
    def _run(self, nodes, mode):
        """One fixed eval batch through a given commit path.  Node ids,
        job ids, and eval ids are pinned, so every variant computes the
        SAME placements — what differs is the commit machinery."""
        s = Server(dev_mode=True, eval_batch=8)
        s.establish_leadership()
        for n in nodes:
            s.register_node(n, now=NOW)
        for i in range(3):
            job = mock.batch_job()
            job.id = f"parity-{i}"
            tg = job.task_groups[0]
            tg.count = 80          # >= 64: the solo path runs the same
            tg.tasks[0].resources.cpu = 100    # the water-fill
            tg.tasks[0].resources.memory_mb = 64
            s.state.upsert_job(job)
            ev = mock.eval(job_id=job.id, type=job.type)
            ev.id = f"eval-parity-{i}"
            s.apply_eval_update([ev], now=NOW)
        applier = s.plan_applier
        if mode == "full_check":
            # break every fence: the applier runs the COLUMNAR full
            # re-check (plan_apply._eval_blocks) instead of wholesale
            s.state.nodes_unchanged_since = lambda *a, **k: False
        elif mode == "expanded":
            # force the pre-wavepipe behavior: per-alloc expansion + the
            # per-node AllocsFit loop
            orig = applier._apply_one

            def expand_first(pending):
                pending.plan.expand_blocks()
                pending.plan.coupled_batch = None
                return orig(pending)
            applier._apply_one = expand_first
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        for i in range(3):
            live = [a for a in snap.allocs_by_job("default", f"parity-{i}")
                    if not a.terminal_status()]
            assert len(live) == 80, (mode, i, len(live))
        return _contents(s.state)

    def test_commit_paths_identical_state(self):
        nodes = _fixed_cluster_nodes()
        fenced = self._run(nodes, "fenced")
        full = self._run(nodes, "full_check")
        expanded = self._run(nodes, "expanded")
        assert fenced == full
        assert fenced == expanded

    def test_harness_serial_matches_server_pipeline(self):
        """The scheduler-Harness serial path (no applier, direct
        upsert) lands the same final contents as the Server's batched
        wave — same nodes, same jobs, same eval ids -> same picks."""
        nodes = _fixed_cluster_nodes()
        server_contents = self._run(nodes, "fenced")
        h = Harness()
        h.state.upsert_nodes(nodes)
        for i in range(3):
            job = mock.batch_job()
            job.id = f"parity-{i}"
            tg = job.task_groups[0]
            tg.count = 80
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.memory_mb = 64
            h.state.upsert_job(job)
        for i in range(3):
            ev = mock.eval(job_id=f"parity-{i}", type="batch")
            ev.id = f"eval-parity-{i}"
            h.state.upsert_evals([ev])
            err = h.process("batch", ev, now=NOW)
            assert err is None, err
        assert _contents(h.state) == server_contents

    def test_multiwave_pipeline_places_everything_exactly(self):
        """Small eval_batch forces several chained waves through the
        wave pipeline; aggregate state must match the serial path:
        every job fully placed, no refutes, no node oversubscribed."""
        nodes = _fixed_cluster_nodes(n_nodes=10, seed=4)
        s = Server(dev_mode=True, eval_batch=3)
        s.establish_leadership()
        for n in nodes:
            s.register_node(n, now=NOW)
        jobs = []
        for _ in range(9):
            job = mock.batch_job()
            job.task_groups[0].count = 12
            job.task_groups[0].tasks[0].resources.cpu = 50
            job.task_groups[0].tasks[0].resources.memory_mb = 16
            s.register_job(job, now=NOW)
            jobs.append(job)
        s.process_all(now=NOW)
        snap = s.state.snapshot()
        for job in jobs:
            live = [a for a in snap.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()]
            assert len(live) == 12, (job.id, len(live))
        assert s.plan_applier.stats["plans_refuted"] == 0
        assert s.workers[0].stats["nacked"] == 0
        # stage timers saw the pipeline run (dispatch + commit at least)
        totals = s.stage_timers.totals()
        assert totals.get("dispatch", 0) > 0
        assert totals.get("commit", 0) > 0


class TestBlockColumnarRefute:
    def test_without_nodes_masks_rows(self):
        from nomad_tpu.structs import AllocBlock
        tmpl = Allocation(id="t", namespace="default", job_id="j",
                          task_group="tg",
                          resources=Resources(cpu=10, memory_mb=10))
        block = AllocBlock(
            id="b1", template=tmpl,
            ids=[f"a{i}" for i in range(6)],
            name_prefix="j.tg[", indexes=list(range(6)),
            picks=np.array([0, 1, 2, 0, 1, 2], np.int32),
            node_table=["n0", "n1", "n2"], round_size=1024)
        kept = block.without_nodes({"n1"})
        assert kept.count == 4
        assert kept.node_table == ["n0", "n2"]
        assert set(kept.ids) == {"a0", "a2", "a3", "a5"}
        rows = kept.materialize_all()
        assert {a.node_id for a in rows} == {"n0", "n2"}
        # demand reflects only surviving rows
        assert kept.demand_by_node() == {
            "n0": (2, 20, 20, 0), "n2": (2, 20, 20, 0)}
        # masking every node -> nothing survives
        assert block.without_nodes({"n0", "n1", "n2"}) is None
        # masking nothing returns the block itself
        assert block.without_nodes(set()) is block


class TestExecutorResidentParity:
    """The device-resident executor contract (ops/executor.py), on the
    single-device and on the sharded engine: multi-pass scheduling that
    rides the retained usage chain lands BIT-FOR-BIT the same state as
    the serial host-round-trip path — including across a forced
    invalidation (a node knocked out of the table mid-run)."""

    # the engine's mesh: None = its auto choice (the conftest's
    # 8-virtual-device mesh -> sharded), False = the single device
    MESHES = pytest.mark.parametrize(
        "mesh", [None, False], ids=["mesh8", "single-device"])

    def _run_waves(self, nodes, resident, drain_mid=False, mesh=None):
        """`resident=False` is the serial reference
        (DeviceExecutor.chain_enabled)."""
        s = Server(dev_mode=True, eval_batch=4, mesh=mesh)
        s.executor.chain_enabled = resident
        s.establish_leadership()
        for n in nodes:
            s.register_node(n, now=NOW)

        def wave(tag):
            for i in range(4):
                job = mock.batch_job()
                job.id = f"res-{tag}-{i}"
                tg = job.task_groups[0]
                tg.count = 12
                tg.tasks[0].resources.cpu = 100
                tg.tasks[0].resources.memory_mb = 64
                s.state.upsert_job(job)
                ev = mock.eval(job_id=job.id, type="batch")
                ev.id = f"eval-res-{tag}-{i}"
                s.apply_eval_update([ev], now=NOW)
            # each wave is one worker pass: the chain crosses passes
            # through the executor's retained slot, not the prefetch
            s.process_all(now=NOW)

        wave("a")
        upload_bytes_a = s.executor.stats["upload_bytes"]
        if drain_mid:
            # a node-table write the chain cannot see (drain-style
            # ineligibility; no reschedule evals, so both runs stay on
            # pinned eval ids): the executor must invalidate and the
            # next wave re-sync from the packer
            s.set_node_eligibility(nodes[0].id, False)
        wave("b")
        stats = dict(s.executor.stats)
        stats["upload_bytes_wave_a"] = upload_bytes_a
        stats["shard_h2d_bytes"] = s.engine.shard_h2d_bytes
        refuted = s.plan_applier.stats["plans_refuted"]
        return _contents(s.state), stats, refuted

    @MESHES
    def test_resident_chain_bitwise_equals_serial(self, mesh):
        nodes = _fixed_cluster_nodes(n_nodes=12, seed=7)
        serial, st_serial, _ = self._run_waves(nodes, False, mesh=mesh)
        resident, st_res, refuted = self._run_waves(nodes, True,
                                                    mesh=mesh)
        assert resident == serial
        # the serial reference never chained; the resident run did
        assert st_serial["resident_waves"] == 0
        assert st_res["resident_waves"] >= 1, st_res
        assert refuted == 0

    @MESHES
    def test_forced_invalidation_mid_run(self, mesh):
        nodes = _fixed_cluster_nodes(n_nodes=12, seed=7)
        serial, _, _ = self._run_waves(nodes, False, drain_mid=True,
                                       mesh=mesh)
        resident, st_res, refuted = self._run_waves(
            nodes, True, drain_mid=True, mesh=mesh)
        assert resident == serial
        assert st_res["invalidations"] >= 1, st_res
        assert refuted == 0
        # wave a still chained within itself or across its own passes;
        # the invalidation only severed the chain at the drain
        assert st_res["resident_waves"] >= 0

    def test_executor_upload_accounting(self):
        nodes = _fixed_cluster_nodes(n_nodes=12, seed=7)
        _, stats, _ = self._run_waves(nodes, True)
        # node tensors + used uploaded at least once, metered in bytes
        assert stats["uploads"] >= 1
        assert stats["upload_bytes"] > 0

    @pytest.mark.skipif(__import__("jax").device_count() < 2,
                        reason="needs the virtual multi-device mesh")
    def test_sharded_resident_matches_single_device_serial(self):
        """THE promotion contract (ISSUE 7): the 8-way sharded engine
        riding the retained resident chain lands BIT-FOR-BIT the same
        state as the serial single-device host-round-trip path."""
        nodes = _fixed_cluster_nodes(n_nodes=28, seed=7)  # 28 % 8 != 0
        serial_1dev, st_1, _ = self._run_waves(nodes, False,
                                               mesh=False)
        sharded_res, st_s, refuted = self._run_waves(nodes, True)
        assert sharded_res == serial_1dev
        assert st_1["resident_waves"] == 0
        assert st_s["resident_waves"] >= 1, st_s
        assert refuted == 0

    @pytest.mark.skipif(__import__("jax").device_count() < 2,
                        reason="needs the virtual multi-device mesh")
    def test_sharded_invalidation_reuploads_one_shard(self):
        """A mid-run single-node eligibility write dirties ONE shard:
        the sharded run must invalidate the chain, re-sync only that
        shard (engine dirty-shard patch, asserted via the executor's
        upload_bytes meter), and still match the single-device serial
        run bit-for-bit."""
        nodes = _fixed_cluster_nodes(n_nodes=64, seed=7)
        serial_1dev, _, _ = self._run_waves(nodes, False,
                                            mesh=False, drain_mid=True)
        sharded_res, st, refuted = self._run_waves(nodes, True,
                                                   drain_mid=True)
        assert sharded_res == serial_1dev
        assert refuted == 0
        assert st["invalidations"] >= 1, st
        assert st["shard_h2d_bytes"] > 0, \
            "invalidation fell back to a full-tensor re-sync"
        # wave b's re-sync (everything after wave a) moved at most the
        # dirty shard's slice of each tensor — strictly less than wave
        # a's full upload (8 shards; 2x slack covers the used heal +
        # per-wave delta scatters)
        wave_b_bytes = st["upload_bytes"] - st["upload_bytes_wave_a"]
        assert wave_b_bytes <= 2 * (st["upload_bytes_wave_a"] // 8) + 512, \
            (wave_b_bytes, st["upload_bytes_wave_a"])


# ------------------------------------------------- the stages of a pass

# recorded on the worker's thread, inside its `pass`; never nested in
# one another, which is what makes "unnamed = pass - the rest" a
# subtraction (benchmark/host_spans.py reads the same from a trace)
WORKER_STAGES = ("prepare", "dispatch", "device_wait", "d2h", "solo_place",
                 "system_place", "materialize", "plan_wait", "finalize",
                 "batch_admin", "eval_update", "ack")
NEW_STAGES = ("pass", "prepare", "device_wait", "plan_wait", "eval_update",
              "ack", "solo_place", "system_place", "store_upsert",
              "dequeue", "finalize", "batch_admin", "stream_send")


@pytest.fixture(scope="module")
def small_pass():
    """A batched wave, a solo eval and a system eval on a threaded
    server (tests/stage_pass.py)."""
    from stage_pass import run_small_pass
    return run_small_pass()


N_WAVE = 6          # stage_pass.N_BATCHED: the evals of the one wave


def _spans(server, stage):
    return [(a, b) for _, a, b in server.stage_timers.intervals(stage)]


def _inside(span, outers):
    return any(lo <= span[0] and span[1] <= hi for lo, hi in outers)


class TestPassStages:
    def test_stage_list_is_the_recorders(self, small_pass):
        from nomad_tpu.core.wavepipe import STAGES
        # device_carve, port_assign, spread_lower and mesh_launch are the
        # stages this pass does not take (no eval in it asks for a device
        # or a port or carries a spread stanza, and its engine has no
        # mesh) and the ones that nest, in their eval's materialize and
        # their wave's dispatch: tests/test_device_batched.py,
        # tests/test_ports_wave.py, tests/test_spread_batched.py and
        # tests/test_mesh_served.py hold them to that
        assert set(small_pass.stage_timers.counts()) | {
            "device_carve", "port_assign", "spread_lower",
            "mesh_launch"} == set(STAGES)
        # dequeue is the worker's, before a pass; stream_send the
        # stream follower's HTTP handler's (stage_pass.py has one)
        assert set(WORKER_STAGES) | {"pass", "device", "commit",
                                     "store_upsert", "device_carve",
                                     "port_assign",
                                     "spread_lower", "mesh_launch",
                                     "dequeue",
                                     "stream_send"} == set(STAGES)

    @pytest.mark.parametrize("stage", NEW_STAGES)
    def test_new_stage_recorded(self, small_pass, stage):
        assert small_pass.stage_timers.counts().get(stage, 0) >= 1

    def test_solo_path_records_materialize(self, small_pass):
        # the batched path's intervals carry their wave; the solo
        # path's, taken at the call that follows engine.place, and the
        # system path's, round its block build, do not
        waves = [w for w, _, _ in
                 small_pass.stage_timers.intervals("materialize")]
        assert waves.count(-1) == 2 and len(waves) == 8

    def test_system_eval_is_one_system_place(self, small_pass):
        # one launch an eval, and its block build follows it
        (place,) = _spans(small_pass, "system_place")
        last = _spans(small_pass, "materialize")[-1]
        assert place[1] <= last[0]
        assert small_pass.stage_timers.counts()["solo_place"] == 1

    @pytest.mark.parametrize("stage", WORKER_STAGES)
    def test_worker_stage_inside_a_pass_and_disjoint(self, small_pass,
                                                     stage):
        passes = _spans(small_pass, "pass")
        others = [iv for s in WORKER_STAGES if s != stage
                  for iv in _spans(small_pass, s)]
        mine = _spans(small_pass, stage)
        assert mine
        for a, b in mine:
            assert _inside((a, b), passes), (stage, a, b)
            assert all(b <= lo or hi <= a for lo, hi in others), stage
        # nor does a stage overlap itself
        ordered = sorted(mine)
        assert all(x[1] <= y[0] for x, y in zip(ordered, ordered[1:]))

    def test_dequeue_before_a_pass_and_only_with_work(self, small_pass):
        # one a pass that was not prefetched, closed before the pass
        # opens; the worker's empty polls between the drains (one every
        # 0.1 s) leave none
        passes = sorted(_spans(small_pass, "pass"))
        dequeues = sorted(_spans(small_pass, "dequeue"))
        assert len(dequeues) == len(passes) == 3
        for (a, b), (lo, _) in zip(dequeues, passes):
            assert a <= b <= lo
        for (_, hi), (a, _) in zip(passes, dequeues[1:]):
            assert hi <= a

    def test_one_finalize_per_eval_of_a_wave(self, small_pass):
        # after its plan_wait, before the next eval's; wave-less, as
        # every per-eval stage
        finals = sorted(_spans(small_pass, "finalize"))
        waits = sorted(_spans(small_pass, "plan_wait"))[:N_WAVE]
        assert len(finals) == N_WAVE
        for (_, b), (a, _) in zip(waits, finals):
            assert b <= a
        assert {w for w, _, _ in small_pass.stage_timers.intervals(
            "finalize")} == {-1}

    def test_batch_admin_carries_its_wave(self, small_pass):
        # three on the pass of a wave (its launch's wave id on each),
        # two on a pass that launched nothing
        t = small_pass.stage_timers
        (wave,) = {w for w, _, _ in t.intervals("dispatch")}
        assert sorted(w for w, _, _ in t.intervals("batch_admin")) == [
            -1, -1, -1, -1, wave, wave, wave]

    def test_stream_send_once_an_event_delivered(self, small_pass):
        # the follower read every eval's updates; each line was one span
        assert (small_pass.stage_timers.counts()["stream_send"]
                == len(small_pass.stream_lines) >= 8)

    def test_events_sent_counts_every_event_delivered(self, small_pass):
        """A line is a frame, the events of one index: the broker counts
        the frames and the events in them, and `/v1/metrics`'s
        `nomad.stream.events_sent` the same events."""
        from stage_pass import stream_events
        lines = small_pass.stream_lines
        events = stream_events(lines)
        stats = small_pass.events.stats()
        assert stats["FramesSent"] == len(lines)
        assert (stats["EventsSent"] == small_pass.stream_events_sent
                == len(events) > len(lines))
        for line in lines:
            frame = json.loads(line)
            assert {ev["Index"] for ev in frame["Events"]} == {
                frame["Index"]}

    def test_stages_account_for_the_pass(self, small_pass):
        totals = small_pass.stage_timers.totals()
        named = sum(totals[s] for s in WORKER_STAGES)
        assert 0.8 * totals["pass"] <= named <= totals["pass"]

    def test_one_ack_per_eval(self, small_pass):
        worker = small_pass.workers[0]
        assert (small_pass.stage_timers.counts()["ack"]
                == worker.stats["acked"] + worker.stats["nacked"] == 8)

    def test_store_upsert_inside_commit(self, small_pass):
        commits = _spans(small_pass, "commit")
        upserts = _spans(small_pass, "store_upsert")
        assert len(upserts) == len(commits) == 8
        assert all(_inside(u, commits) for u in upserts)


class TestCpuMarkers:
    def test_two_a_pass_never_falling_across_a_restart(self, monkeypatch):
        """The worker marks the threads' CPU by role immediately before
        and after every pass; `stop_scheduling` / `start_scheduling`
        brings new worker and applier threads under the same roles, and
        what the old ones had stays in the totals."""
        import threading

        from nomad_tpu.core import telemetry
        from nomad_tpu.core import worker as worker_mod
        from stage_pass import run_small_pass
        marks, workers = [], set()
        real = worker_mod.mark_cpu

        def spy(wave=-1):
            real(wave)
            marks.append((wave, telemetry.thread_cpu_by_role()))
            workers.add(threading.current_thread())

        monkeypatch.setattr(worker_mod, "mark_cpu", spy)
        server = run_small_pass(rounds=2)
        passes = server.stage_timers.counts()["pass"]
        assert passes == 4 and len(marks) == 2 * passes
        assert len(workers) == 2                 # a thread each round
        for (_, before), (_, after) in zip(marks, marks[1:]):
            for role in ("worker", "applier", "http"):
                assert before.get(role, 0.0) <= after[role], role
        # the batched passes' closing markers carry their launch's wave
        waves = {w for w, _, _ in server.stage_timers.intervals("dispatch")}
        assert {w for w, _ in marks if w >= 0} == waves and len(waves) == 2
        first, last = marks[0][1], marks[-1][1]
        assert last["worker"] > first.get("worker", 0.0)
        assert last["applier"] > first.get("applier", 0.0)

    def test_empty_poll_records_no_dequeue(self):
        """`dequeue` is entered by the broker once an eval is in hand: a
        poll that times out empty leaves no interval (and no span)."""
        from nomad_tpu.core.server import Server
        s = Server(dev_mode=False, num_workers=1, eval_batch=8, mesh=False)
        s.establish_leadership()
        worker = s.workers[0]
        try:
            assert worker.run_once(timeout=0.0) == 0
            assert worker.run_once(timeout=0.06) == 0
            counts = s.stage_timers.counts()
            assert "dequeue" not in counts and "pass" not in counts
        finally:
            s.shutdown()
