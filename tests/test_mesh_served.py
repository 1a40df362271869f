"""The served path with the node axis sharded (`Agent(mesh=None)` on the
eight virtual devices) against the single-device path
(`Agent(mesh=False)`), on csi50k-mesh4's rehearsal fleet: HTTP
registration -> broker -> worker -> WavePipeline -> DeviceExecutor ->
the engine's mesh legs -> plans -> applier, two cycles so that chained
sharded waves run.  Scheduling is held while a cycle registers and the id
pool is seeded, as chip_smoke.run_leg does, so both legs launch the same
waves with the same tie-break seeds and can be compared node for node.
At 601 nodes one shard is padded."""

import os
import random
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import fleet as fleetlib  # noqa: E402
from benchmark.loader import load_json, load_module  # noqa: E402

SEED = 36
JOBS_PER_CYCLE, CYCLES = 160, 2      # 64 + 64 + 32 evals: three waves
CHAINED = "multi_compact_chained"


def _launches() -> dict:
    from nomad_tpu.core.telemetry import REGISTRY
    return REGISTRY.counter_labels("nomad.engine.mesh_launches")


def run_leg(n_nodes: int, mesh) -> dict:
    from nomad_tpu.agent import Agent
    from nomad_tpu.api.client import APIClient
    from nomad_tpu.core.flightrec import FLIGHT
    from nomad_tpu.structs import structs as structs_mod

    cfg = load_json("configs", "csi50k-mesh4")
    cfg.update(cfg["rehearse"], nodes=n_nodes)
    mod = load_module("configs", "csi50k-mesh4")
    nodes, fleet = mod.build_fleet(cfg, SEED)
    launches0 = _launches()
    wave0 = max((w["Wave"] for w in FLIGHT.waves()), default=0)
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
                  log_level="warn", mesh=mesh)
    try:
        agent.start()
        server = agent.server
        api = APIClient(address=agent.address, timeout=120.0)
        server.state.upsert_nodes(nodes)
        mod.install(cfg, nodes, lambda path, body: api.put(path, body=body))
        jobs = [mod.make_job(cfg, i)
                for i in range(JOBS_PER_CYCLE * CYCLES)]
        for c in range(CYCLES):
            batch = jobs[c * JOBS_PER_CYCLE:(c + 1) * JOBS_PER_CYCLE]
            server.stop_scheduling()
            structs_mod._id_pool[:] = fleetlib.seeded_ids(
                random.Random(SEED + 1 + c), 16 * len(batch))
            evals = {api.jobs.register(j)["EvalID"] for j in batch}
            structs_mod._id_pool.clear()      # random ids from here on
            server.start_scheduling()
            deadline = time.monotonic() + 300.0
            while True:
                status = {e["ID"]: e["Status"]
                          for e in api.evaluations.list()
                          if e["ID"] in evals}
                if len(status) == len(evals) and all(
                        s in ("complete", "failed", "canceled")
                        for s in status.values()):
                    break
                assert time.monotonic() < deadline, status
                time.sleep(0.05)
            assert set(status.values()) == {"complete"}
        cols = api.get("/v1/allocations", columnar="true")["Columns"]
        by_job: dict = {}
        for job_id, node_id in zip(cols["JobID"], cols["NodeID"]):
            by_job.setdefault(job_id, []).append(node_id)
        timers = server.stage_timers
        return {
            "by_job": {j: sorted(v) for j, v in by_job.items()},
            "failures": mod.check(cfg, fleet, jobs, by_job),
            "mesh": server.engine.mesh,
            "launches": {k: n - launches0.get(k, 0)
                         for k, n in _launches().items()
                         if n - launches0.get(k, 0)},
            "waves": [w for w in FLIGHT.waves()
                      if w["Wave"] > wave0 and "items" in w],
            "dispatch": [(a, b) for _, a, b in timers.intervals("dispatch")],
            "mesh_launch": [(a, b) for _, a, b
                            in timers.intervals("mesh_launch")],
        }
    finally:
        agent.shutdown()


@pytest.fixture(scope="module", params=[600, 601])
def legs(request):
    import jax
    assert jax.device_count() == 8      # tests/conftest.py
    return {"n": request.param,
            "mesh": run_leg(request.param, None),
            "off": run_leg(request.param, False)}


def test_sharding_changes_no_answer(legs):
    a, b = legs["mesh"]["by_job"], legs["off"]["by_job"]
    assert len(a) == JOBS_PER_CYCLE * CYCLES
    differ = sorted(j for j in set(a) | set(b) if a.get(j) != b.get(j))
    assert not differ, (len(differ), differ[:3])


@pytest.mark.parametrize("leg", ["mesh", "off"])
def test_plain_check_is_clean(legs, leg):
    assert legs[leg]["failures"] == []


def test_mesh_leg_is_sharded_over_every_device(legs):
    assert legs["mesh"]["mesh"].devices.size == 8
    assert legs["off"]["mesh"] is None
    assert (legs["n"] % 8 != 0) == any(
        w["padded_row_fraction"] > 0 for w in legs["mesh"]["waves"])


def test_mesh_launches_counted_by_kind(legs):
    from nomad_tpu.ops.engine import SHARDED_KINDS
    got = legs["mesh"]["launches"]
    assert got[f"kind={CHAINED}"] > 0 and got["kind=multi_compact"] > 0
    assert {k.partition("=")[2] for k in got} <= set(SHARDED_KINDS)
    # one count a wave: the flight recorder saw the same launches
    waves = legs["mesh"]["waves"]
    assert (got[f"kind={CHAINED}"] + got["kind=multi_compact"]
            == len(waves) == 3 * CYCLES)
    assert legs["off"]["launches"] == {}


def test_wave_records_carry_mesh_devices(legs):
    assert {w["mesh_devices"] for w in legs["mesh"]["waves"]} == {8}
    assert {w["mesh_devices"] for w in legs["off"]["waves"]} == {1}
    assert all(w["collective_bytes"] > 0 for w in legs["mesh"]["waves"])
    assert not any("collective_bytes" in w for w in legs["off"]["waves"])


def test_mesh_launch_span_inside_its_dispatch(legs):
    spans, outers = legs["mesh"]["mesh_launch"], legs["mesh"]["dispatch"]
    assert len(spans) == len(outers) == 3 * CYCLES
    for a, b in spans:
        assert any(lo <= a and b <= hi for lo, hi in outers), (a, b)
    assert legs["off"]["mesh_launch"] == []
