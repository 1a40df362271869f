"""Device-executor seam (nomad_tpu/ops/executor.py): the retained
resident-chain slot (claim/retain/invalidate semantics, store-write
coupling), and the telemetry meters the seam exports.  The bit-for-bit
proof that the resident chain equals its serial reference lives in
tests/test_wavepipe.py (TestExecutorResidentParity)."""

import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.ops import PlacementEngine
from nomad_tpu.ops.executor import DeviceExecutor
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Allocation, DrainStrategy, Resources

NOW = 1.7e9


def _engine():
    return PlacementEngine(mesh=False)


def _alloc(alloc_id, client_status):
    return Allocation(id=alloc_id, namespace="default", job_id="j",
                      task_group="tg", node_id="n1",
                      resources=Resources(cpu=10, memory_mb=10),
                      desired_status="run", client_status=client_status)


# cause -> the write under test, on a store that holds node "n1" with
# the running alloc "a-1" on it
STORE_WRITES = {
    "upsert_node": lambda st: st.upsert_node(mock.node()),
    "delete_node": lambda st: st.delete_node("n1"),
    "update_node_status": lambda st: st.update_node_status("n1", "down"),
    "update_node_eligibility":
        lambda st: st.update_node_eligibility("n1", "ineligible"),
    "update_node_drain":
        lambda st: st.update_node_drain("n1", DrainStrategy()),
    "terminal_alloc":
        lambda st: st.upsert_allocs([_alloc("a-1", "complete")]),
    "snapshot_restore":
        lambda st: st.snapshot_restore(st.snapshot_save()),
    "live_placement":
        lambda st: st.upsert_allocs([_alloc("a-2", "running")]),
}


class TestChainSlot:
    def test_claim_pops_single_consumer(self):
        ex = DeviceExecutor(_engine())
        triple = (object(), 1, 8)
        ex.retain_chain("bid", 3, triple, masked={"n1"})
        got = ex.claim_chain()
        assert got == ("bid", 3, triple, frozenset({"n1"}))
        assert ex.claim_chain() is None

    def test_chain_disabled_is_inert(self):
        ex = DeviceExecutor(_engine())
        ex.chain_enabled = False
        ex.retain_chain("bid", 3, (object(), 1, 8))
        assert ex.claim_chain() is None

    def test_invalidate_counts_only_real_drops(self):
        ex = DeviceExecutor(_engine())
        ex.invalidate("noop")
        assert ex.stats["invalidations"] == 0
        ex.retain_chain("bid", 3, (object(), 1, 8))
        ex.invalidate("test")
        assert ex.stats["invalidations"] == 1
        assert ex.claim_chain() is None

    @pytest.mark.parametrize("origin,dropped", [
        ("bid", 0),              # the chain's own commit
        ("someone-else", 1),     # a plan from outside the chain
    ])
    def test_foreign_plan_invalidates_own_does_not(self, origin, dropped):
        ex = DeviceExecutor(_engine())
        triple = (object(), 1, 8)
        ex.retain_chain("bid", 3, triple)
        ex.note_plan_commit(origin)
        assert ex.stats["invalidations"] == dropped
        kept = ex.claim_chain()
        assert kept is None if dropped else kept[2] is triple

    @pytest.mark.parametrize("cause", sorted(STORE_WRITES))
    def test_store_writes_invalidate(self, cause):
        """Every store write that changes node state the chain cannot
        observe drops it; a live placement (what the chain itself
        proposes) does not."""
        store = StateStore()
        node = mock.node()
        node.id = "n1"
        store.upsert_node(node)
        store.upsert_allocs([_alloc("a-1", "running")])
        ex = DeviceExecutor(_engine())
        ex.attach_store(store)
        ex.retain_chain("bid", 1, (object(), 1, 8))
        STORE_WRITES[cause](store)
        kept = cause == "live_placement"
        assert ex.stats["invalidations"] == (0 if kept else 1)
        assert (ex.claim_chain() is not None) == kept


class TestServerWiring:
    def test_server_builds_and_wires_executor(self):
        s = Server(dev_mode=True)
        assert s.executor.name == "jax"
        assert s.executor.engine is s.engine
        assert s.plan_applier.executor is s.executor
        for w in s.workers:
            assert w.pipeline.executor is s.executor

    def test_residency_metrics_ride_the_registry(self):
        c0 = REGISTRY.counter("nomad.executor.resident_waves")
        u0 = REGISTRY.counter("nomad.executor.uploads")
        s = Server(dev_mode=True, eval_batch=4)
        s.establish_leadership()
        for _ in range(8):
            n = mock.node()
            n.resources.cpu = 8000
            n.resources.memory_mb = 16384
            s.register_node(n, now=NOW)
        for wave in range(2):
            for _ in range(4):
                job = mock.batch_job()
                job.task_groups[0].count = 8
                job.task_groups[0].tasks[0].resources.cpu = 50
                job.task_groups[0].tasks[0].resources.memory_mb = 16
                s.register_job(job, now=NOW)
            s.process_all(now=NOW)
        assert s.executor.stats["resident_waves"] >= 1
        assert REGISTRY.counter("nomad.executor.resident_waves") > c0
        assert REGISTRY.counter("nomad.executor.uploads") > u0
        assert REGISTRY.counter("nomad.executor.upload_bytes") > 0
        assert REGISTRY.histogram("nomad.executor.h2d_s") is not None

    def test_serial_vs_resident_same_aggregate_state(self):
        """Chain off (the serial reference: host round-trip every
        wave) and chain on land identical live-alloc counts with zero
        refutes."""
        def run(resident):
            s = Server(dev_mode=True, eval_batch=4)
            s.executor.chain_enabled = resident
            s.establish_leadership()
            for _ in range(8):
                n = mock.node()
                n.resources.cpu = 8000
                n.resources.memory_mb = 16384
                s.register_node(n, now=NOW)
            jobs = []
            for wave in range(3):
                for _ in range(4):
                    job = mock.batch_job()
                    job.task_groups[0].count = 8
                    job.task_groups[0].tasks[0].resources.cpu = 50
                    job.task_groups[0].tasks[0].resources.memory_mb = 16
                    s.register_job(job, now=NOW)
                    jobs.append(job)
                s.process_all(now=NOW)
            snap = s.state.snapshot()
            placed = sum(
                1 for j in jobs
                for a in snap.allocs_by_job(j.namespace, j.id)
                if not a.terminal_status())
            return placed, s.plan_applier.stats["plans_refuted"], \
                dict(s.executor.stats)

        placed_off, refuted_off, st_off = run(False)
        placed_on, refuted_on, st_on = run(True)
        assert placed_off == placed_on == 12 * 8
        assert refuted_off == refuted_on == 0
        assert st_off["resident_waves"] == 0
        assert st_on["resident_waves"] >= 1
