"""Kernel parity tests: device ops vs independently-written host oracles
(reference semantics: scheduler/feasible.go, rank.go, spread.go)."""

import re

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops import PlacementEngine, PlacementRequest
from nomad_tpu.ops.feasibility import feasible_mask
from nomad_tpu.ops.scoring import binpack_score
from nomad_tpu.pack import ClusterPacker
from nomad_tpu.scheduler import Harness
from nomad_tpu.structs import (
    Constraint,
    Resources,
    Spread,
    SpreadTarget,
    score_fit_binpack,
    score_fit_spread,
)

import jax.numpy as jnp


def host_check(props: dict, c: Constraint) -> bool:
    """Independent re-derivation of checkConstraint for single node."""
    key = c.ltarget.strip("${}")
    if not key.startswith(("attr.", "meta.", "node.", "driver.")):
        key = "attr." + key
    val = props.get(key)
    op, rt = c.operand, c.rtarget
    if op in ("=", "==", "is"):
        return val is not None and val == rt
    if op in ("!=", "not"):
        return val != rt
    if op == "is_set":
        return val is not None
    if op == "is_not_set":
        return val is None
    if val is None:
        return False
    if op == "regexp":
        return re.search(rt, val) is not None
    if op == "set_contains":
        return set(x.strip() for x in rt.split(",")) <= set(
            x.strip() for x in val.split(","))
    if op == "set_contains_any":
        return bool(set(x.strip() for x in rt.split(",")) & set(
            x.strip() for x in val.split(",")))
    if op == "version":
        from nomad_tpu.utils.version import check_constraint
        return check_constraint(val, rt)
    if op in ("<", "<=", ">", ">="):
        try:
            l, r = float(val), float(rt)
        except ValueError:
            l, r = val, rt
        return {"<": l < r, "<=": l <= r, ">": l > r, ">=": l >= r}[op]
    raise AssertionError(f"op {op}")


def build_cluster(specs):
    """specs: list of dicts of extra attributes."""
    h = Harness()
    nodes = []
    for extra in specs:
        n = mock.node()
        n.attributes = {**n.attributes, **extra}
        from nomad_tpu.structs import compute_class
        n.computed_class = compute_class(n)
        h.state.upsert_node(n)
        nodes.append(n)
    return h, nodes


CONSTRAINT_CASES = [
    Constraint("${attr.kernel.name}", "=", "linux"),
    Constraint("${attr.kernel.name}", "=", "windows"),
    Constraint("${attr.kernel.name}", "!=", "windows"),
    Constraint("${attr.missing.key}", "!=", "anything"),
    Constraint("${attr.missing.key}", "=", "anything"),
    Constraint("${attr.os.version}", ">", "21"),
    Constraint("${attr.os.version}", "<=", "22.04"),
    Constraint("${attr.os.name}", "regexp", "^ubu"),
    Constraint("${attr.os.name}", "regexp", "centos|rhel"),
    Constraint("${attr.nomad.version}", "version", ">= 1.5"),
    Constraint("${attr.nomad.version}", "version", "< 1.0"),
    Constraint("${attr.tags}", "set_contains", "web,fast"),
    Constraint("${attr.tags}", "set_contains_any", "gpu,fast"),
    Constraint("${attr.rack}", "is_set", ""),
    Constraint("${attr.rack}", "is_not_set", ""),
    Constraint("${node.datacenter}", "=", "dc1"),
]


class TestFeasibilityParity:
    def test_all_operators_match_oracle(self):
        specs = [
            {},
            {"os.version": "20.10", "tags": "web,fast,ssd", "rack": "r1"},
            {"os.name": "centos", "nomad.version": "0.9.1"},
            {"tags": "gpu", "os.version": "23.10"},
        ]
        h, nodes = build_cluster(specs)
        snap = h.snapshot()
        packer = ClusterPacker()
        t = packer.build(snap)

        job = mock.job()
        for c in CONSTRAINT_CASES:
            job.constraints = [c]
            job.task_groups[0].tasks[0].constraints = []
            tgt = packer.lower_task_groups(job, job.task_groups)
            ctx = packer.job_context(job, snap, t)
            mask = np.asarray(feasible_mask(
                jnp.asarray(t.attrs), jnp.asarray(t.elig),
                jnp.asarray(ctx.dc_mask), jnp.asarray(ctx.pool_mask),
                jnp.asarray(tgt.con), jnp.asarray(tgt.luts)))[0]
            from nomad_tpu.pack.packer import node_property_map
            for i, nd in enumerate(nodes):
                props = node_property_map(nd)
                want = (host_check(props, c)
                        and props.get("driver.exec") == "1"
                        and nd.datacenter == "dc1")
                assert mask[t.id_to_row[nd.id]] == want, (
                    f"constraint {c} node {i}: dev={mask[t.id_to_row[nd.id]]} "
                    f"oracle={want}")


class TestBinpackParity:
    def test_matches_struct_oracle(self):
        rng = np.random.default_rng(0)
        cap = rng.integers(100, 10000, size=(64, 3)).astype(np.float32)
        used = (cap * rng.uniform(0, 1.2, size=(64, 3))).astype(np.float32)
        req = np.zeros(3, np.float32)
        dev = np.asarray(binpack_score(jnp.asarray(cap), jnp.asarray(used),
                                       jnp.asarray(req)))
        for i in range(64):
            want = score_fit_binpack(cap[i, 0], cap[i, 1], used[i, 0], used[i, 1])
            assert dev[i] == pytest.approx(want, abs=1e-4)

    def test_spread_algo_matches(self):
        cap = np.array([[4000, 8192, 1000]], np.float32)
        used = np.array([[1000, 2048, 0]], np.float32)
        dev = np.asarray(binpack_score(jnp.asarray(cap), jnp.asarray(used),
                                       jnp.zeros(3), spread_algo=True))
        want = score_fit_spread(4000, 8192, 1000, 2048)
        assert dev[0] == pytest.approx(want, abs=1e-4)


class TestPlacementEngine:
    def test_capacity_consumed_sequentially(self):
        # 2 nodes, each fits exactly 2 allocs of 1000MHz: 4 placements must
        # split 2/2; a 5th must fail.
        h = Harness()
        nodes = []
        for _ in range(2):
            n = mock.node()
            n.resources.cpu = 2100
            n.reserved.cpu = 0
            n.resources.memory_mb = 99999
            n.reserved.memory_mb = 0
            h.state.upsert_node(n)
            nodes.append(n)
        job = mock.batch_job()
        job.task_groups[0].tasks[0].resources = Resources(cpu=1000, memory_mb=10)
        job.task_groups[0].count = 5
        h.state.upsert_job(job)

        eng = PlacementEngine()
        reqs = [PlacementRequest(tg_name="worker") for _ in range(5)]
        snap = h.snapshot()
        decisions = eng.place(snap, job, job.task_groups, reqs)
        placed = [d for d in decisions if d.node_id]
        failed = [d for d in decisions if not d.node_id]
        assert len(placed) == 4 and len(failed) == 1
        from collections import Counter
        counts = Counter(d.node_id for d in placed)
        assert sorted(counts.values()) == [2, 2]
        # exhaustion metric must name the dimension
        assert failed[0].metric.dimension_exhausted.get("cpu", 0) > 0

    def test_anti_affinity_spreads_same_job(self):
        # plenty of capacity on both nodes: anti-affinity should still
        # split a 2-count service group across nodes
        h = Harness()
        for _ in range(2):
            h.state.upsert_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        h.state.upsert_job(job)
        eng = PlacementEngine()
        decisions = eng.place(h.snapshot(), job, job.task_groups,
                              [PlacementRequest(tg_name="web")] * 2)
        assert decisions[0].node_id != decisions[1].node_id

    def test_reschedule_penalty_avoids_prev_node(self):
        h = Harness()
        nodes = [mock.node() for _ in range(2)]
        for n in nodes:
            h.state.upsert_node(n)
        job = mock.job()
        h.state.upsert_job(job)
        eng = PlacementEngine()
        d = eng.place(h.snapshot(), job, job.task_groups,
                      [PlacementRequest(tg_name="web",
                                        prev_node_id=nodes[0].id)])
        assert d[0].node_id == nodes[1].id

    def test_spread_targets_respected(self):
        h = Harness()
        for dc, cnt in (("dc1", 4), ("dc2", 4), ("dc3", 4)):
            for _ in range(cnt):
                h.state.upsert_node(mock.node(datacenter=dc))
        job = mock.job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        job.spreads = [Spread(attribute="${node.datacenter}", weight=100,
                              targets=(SpreadTarget("dc1", 50),
                                       SpreadTarget("dc2", 30),
                                       SpreadTarget("dc3", 20)))]
        job.task_groups[0].count = 10
        h.state.upsert_job(job)
        eng = PlacementEngine()
        decisions = eng.place(h.snapshot(), job, job.task_groups,
                              [PlacementRequest(tg_name="web")] * 10)
        snap = h.snapshot()
        from collections import Counter
        dcs = Counter(snap.node_by_id(d.node_id).datacenter
                      for d in decisions if d.node_id)
        assert dcs["dc1"] == 5 and dcs["dc2"] == 3 and dcs["dc3"] == 2

    def test_distinct_hosts(self):
        h = Harness()
        for _ in range(3):
            h.state.upsert_node(mock.node())
        job = mock.job()
        job.constraints.append(Constraint("", "distinct_hosts", ""))
        job.task_groups[0].count = 4
        h.state.upsert_job(job)
        eng = PlacementEngine()
        decisions = eng.place(h.snapshot(), job, job.task_groups,
                              [PlacementRequest(tg_name="web")] * 4)
        placed = [d.node_id for d in decisions if d.node_id]
        assert len(placed) == 3 and len(set(placed)) == 3
        assert decisions[3].node_id is None

    def test_metrics_shape(self):
        h = Harness()
        h.state.upsert_node(mock.node())
        h.state.upsert_node(mock.node(datacenter="dc2"))
        job = mock.job()
        h.state.upsert_job(job)
        eng = PlacementEngine()
        d = eng.place(h.snapshot(), job, job.task_groups,
                      [PlacementRequest(tg_name="web")])[0]
        m = d.metric
        assert m.nodes_evaluated == 2
        assert m.nodes_filtered == 1          # dc2 node
        assert m.nodes_available == {"dc1": 1, "dc2": 1}
        assert len(m.score_meta_data) >= 1
        assert m.score_meta_data[0].node_id == d.node_id


class TestReviewRegressions:
    """Regression tests for review findings on the pack/ops layer."""

    def test_engine_sees_committed_allocs(self):
        # A reused engine must not serve stale device tensors: after allocs
        # are committed to state, the next place() must see reduced capacity.
        h = Harness()
        n = mock.node()
        n.resources.cpu = 2100
        n.reserved.cpu = 0
        n.resources.memory_mb = 99999
        n.reserved.memory_mb = 0
        h.state.upsert_node(n)
        job = mock.batch_job()
        job.task_groups[0].tasks[0].resources = Resources(cpu=1000, memory_mb=10)
        h.state.upsert_job(job)
        eng = PlacementEngine()

        for _ in range(2):
            d = eng.place(h.snapshot(), job, job.task_groups,
                          [PlacementRequest(tg_name="worker")])[0]
            assert d.node_id == n.id
            a = mock.alloc(job=job, node_id=n.id)
            a.resources = Resources(cpu=1000, memory_mb=10)
            h.state.upsert_allocs([a])

        # third must fail: 2x1000 committed on a 2100 node
        d = eng.place(h.snapshot(), job, job.task_groups,
                      [PlacementRequest(tg_name="worker")])[0]
        assert d.node_id is None
        assert d.metric.dimension_exhausted.get("cpu", 0) > 0

    def test_tiebreak_seed_diversifies_equal_nodes(self):
        # Equal-score nodes must be picked differently by different eval
        # seeds (the reference's shuffled-node-order analog) or concurrent
        # workers collide on identical nodes and refute each other's plans.
        h = Harness()
        for _ in range(32):
            h.state.upsert_node(mock.node())
        job = mock.batch_job()
        h.state.upsert_job(job)
        snap = h.snapshot()
        eng = PlacementEngine()
        tg = job.task_groups[0]
        reqs = [PlacementRequest(tg_name=tg.name)]
        picks = {eng.place(snap, job, [tg], reqs, seed=s)[0].node_id
                 for s in (1, 2, 3, 4, 5, 6)}
        assert len(picks) > 1, "seeds did not diversify tie-break"
        # seed 0 stays deterministic
        a = eng.place(snap, job, [tg], reqs, seed=0)[0].node_id
        b = eng.place(snap, job, [tg], reqs, seed=0)[0].node_id
        assert a == b

    def test_used_delta_replay_concurrent_with_alloc_events(self):
        # Applier-thread alloc events racing a worker's device `used` sync
        # must neither skip nor double-apply deltas: the engine holds the
        # packer lock across read-version -> fetch-deltas -> commit.
        import threading

        h = Harness()
        nodes = [mock.node() for _ in range(16)]
        for n in nodes:
            h.state.upsert_node(n)
        eng = PlacementEngine()
        eng.packer.attach(h.state)
        eng.packer.update(h.snapshot())
        job = mock.job()
        errors = []

        def writer():
            try:
                # > the 256-entry replay window, so the trimmed-window
                # full-re-upload path races too
                for i in range(300):
                    a = mock.alloc(job=job, node_id=nodes[i % 16].id)
                    h.state.upsert_allocs([a])
            except Exception as e:  # pragma: no cover - fail loudly below
                errors.append(e)

        th = threading.Thread(target=writer)
        th.start()
        while th.is_alive():
            eng._used_device(eng.packer._tensors)
        th.join()
        assert not errors
        t = eng.packer._tensors
        dev = np.asarray(eng._used_device(t))
        assert (dev == t.used).all()

    def test_distinct_property_enforced(self):
        # 4 nodes in 2 racks; distinct_property on meta.rack with limit 1
        # must place at most one alloc per rack.
        h = Harness()
        for rack in ("r1", "r1", "r2", "r2"):
            n = mock.node()
            n.meta = {"rack": rack}
            from nomad_tpu.structs import compute_class
            n.computed_class = compute_class(n)
            h.state.upsert_node(n)
        job = mock.job()
        job.constraints.append(
            Constraint("${meta.rack}", "distinct_property", "1"))
        job.task_groups[0].count = 3
        h.state.upsert_job(job)
        eng = PlacementEngine()
        ds = eng.place(h.snapshot(), job, job.task_groups,
                       [PlacementRequest(tg_name="web")] * 3)
        placed = [d.node_id for d in ds if d.node_id]
        assert len(placed) == 2
        snap = h.snapshot()
        racks = {snap.node_by_id(nid).meta["rack"] for nid in placed}
        assert racks == {"r1", "r2"}
        assert ds[2].node_id is None

    def test_distinct_property_counts_existing_allocs(self):
        h = Harness()
        nodes = []
        for rack in ("r1", "r2"):
            n = mock.node()
            n.meta = {"rack": rack}
            from nomad_tpu.structs import compute_class
            n.computed_class = compute_class(n)
            h.state.upsert_node(n)
            nodes.append(n)
        job = mock.job()
        job.constraints.append(
            Constraint("${meta.rack}", "distinct_property", "1"))
        h.state.upsert_job(job)
        # existing alloc in r1
        h.state.upsert_allocs([mock.alloc(job=job, node_id=nodes[0].id)])
        eng = PlacementEngine()
        d = eng.place(h.snapshot(), job, job.task_groups,
                      [PlacementRequest(tg_name="web")])[0]
        assert d.node_id == nodes[1].id

    def test_lut_rows_do_not_grow_per_eval(self):
        packer = ClusterPacker()
        h = Harness()
        h.state.upsert_node(mock.node())
        job = mock.job()
        job.constraints = [Constraint("${attr.os.name}", "regexp", "^ubu")]
        packer.build(h.snapshot())
        packer.lower_task_groups(job, job.task_groups)
        luts_before = len(packer._luts)
        for i in range(5):
            # grow the vocab each round, then re-lower the same predicate
            packer.interner.intern(f"brand-new-value-{i}")
            packer.lower_task_groups(job, job.task_groups)
        assert len(packer._luts) == luts_before
        # extended rows must cover the full vocab
        assert packer.lut_matrix().shape[1] == len(packer.interner)

    def test_bulk_kernel_rejects_over_capacity_unrequested_dim(self):
        # A node over capacity in a dimension the task group does NOT
        # request (e.g. disk after a shrunk re-registration) must be
        # infeasible in the water-fill rounds (the flat multi-eval kernel,
        # one item), matching capacity_fit's all-dims check in the exact
        # scan kernel.
        import jax.numpy as jnp
        from nomad_tpu.ops.select import (MultiEvalInputs, PlacementInputs,
                                          place_jit, place_multi_packed_jit)

        n, p = 8, 64
        attrs = np.zeros((n, 4), np.int32)
        cap = np.tile(np.array([[4000, 8192, 1000]], np.int32), (n, 1))
        used = np.zeros((n, 3), np.int32)
        used[0, 2] = 1100            # node 0 over disk capacity
        inp = PlacementInputs(
            attrs=jnp.asarray(attrs), cap=jnp.asarray(cap),
            used0=jnp.asarray(used), elig=jnp.ones(n, bool),
            dc_mask=jnp.ones(n, bool), pool_mask=jnp.ones(n, bool),
            luts=jnp.ones((1, 4), bool),
            con=jnp.zeros((1, 0, 3), jnp.int32),
            aff=jnp.zeros((1, 0, 4), jnp.int32),
            req=jnp.asarray(np.array([[100, 10, 0]], np.int32)),  # no disk ask
            desired=jnp.asarray(np.array([p], np.int32)),
            dh_limit=jnp.zeros(1, jnp.int32),
            sp_nodeval=jnp.full((1, n), -1, jnp.int32),
            sp_weight=jnp.zeros(1, jnp.float32),
            sp_expected=jnp.zeros((1, 1), jnp.float32),
            sp_counts0=jnp.zeros((1, 1), jnp.float32),
            pd_nodeval=jnp.full((1, n), -1, jnp.int32),
            pd_limit=jnp.zeros(1, jnp.int32),
            pd_apply=jnp.zeros((1, 1), bool),
            pd_counts0=jnp.zeros((1, 1), jnp.int32),
            tg_idx=jnp.zeros(p, jnp.int32),
            prev_row=jnp.full(p, -1, jnp.int32),
            active=jnp.ones(p, bool),
            job_count0=jnp.zeros(n, jnp.int32),
            spread_algo=jnp.asarray(False),
        )
        assert (np.asarray(place_jit(inp).picks) != 0).all()
        # the same inputs as a wave of one item: two rounds of 32
        multi = MultiEvalInputs(
            attrs=inp.attrs, cap=inp.cap, used0=inp.used0, elig=inp.elig,
            luts=inp.luts, base_mask=jnp.ones((1, n), bool), con=inp.con,
            u_mask=jnp.zeros(1, jnp.int32), aff=inp.aff, req=inp.req,
            desired=inp.desired, dh_limit=inp.dh_limit,
            g_static=jnp.zeros(1, jnp.int32), g_aff=jnp.zeros(1, jnp.int32),
            g_job=jnp.zeros(1, jnp.int32),
            job_count0=inp.job_count0[None, :], spread_algo=inp.spread_algo,
            round_g=jnp.zeros(2, jnp.int32),
            round_want=jnp.full(2, 32, jnp.int32))
        fills = np.asarray(place_multi_packed_jit(multi, 32)[0])[:, :32]
        rows, counts = fills >> 11, fills & 2047
        assert counts.sum() == p          # node 0 refused, 7 nodes hold 64
        assert (rows[counts > 0] != 0).all(), fills
