"""One small worker pass on a threaded server, shared by the stage-span
tests (tests/test_wavepipe.py, tests/test_host_spans.py): a full batched
wave of plain batch jobs, then one spread job, which takes the solo
path, then one system job, which `place_system` places.  Threaded (applier and worker threads of their own), because in
dev_mode the worker applies plans inline and `commit` would nest in the
worker's stages."""

import time

from nomad_tpu import mock
from nomad_tpu.core.server import Server
from nomad_tpu.structs import Spread, SpreadTarget

N_BATCHED, N_SOLO, N_SYSTEM = 6, 1, 1


def run_small_pass(between=None):
    """Runs the pass and returns the server, shut down, its
    `stage_timers` holding every interval.  `between()` runs after the
    fleet is loaded and before the first eval is released (a test starts
    the profiler there)."""
    s = Server(dev_mode=False, num_workers=1, eval_batch=8, mesh=False,
               heartbeat_ttl=3600)
    s.establish_leadership()
    now = time.time()
    for i in range(24):
        node = mock.node()
        node.datacenter = f"dc{1 + i % 3}"
        s.register_node(node, now=now)
    if between is not None:
        between()
    s.stage_timers.reset()
    jobs = []
    for _ in range(N_BATCHED):
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 12
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 16
        jobs.append(job)
    for job in jobs:
        s.register_job(job, now=now)
    s.start_scheduling()
    try:
        _drain(s, N_BATCHED)
        solo = mock.job()
        solo.datacenters = ["dc1", "dc2", "dc3"]
        tg = solo.task_groups[0]
        tg.count = 9
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 16
        tg.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                             targets=(SpreadTarget("dc1", 50),
                                      SpreadTarget("dc2", 30),
                                      SpreadTarget("dc3", 20)))]
        s.register_job(solo, now=now)
        _drain(s, N_BATCHED + N_SOLO)
        daemon = mock.system_job()
        daemon.datacenters = ["dc1", "dc2", "dc3"]
        daemon.task_groups[0].tasks[0].resources.cpu = 50
        daemon.task_groups[0].tasks[0].resources.memory_mb = 16
        s.register_job(daemon, now=now)
        _drain(s, N_BATCHED + N_SOLO + N_SYSTEM)
    finally:
        s.stop_scheduling()
        s.shutdown()
    return s


def _drain(server, acked: int, timeout_s: float = 120.0) -> None:
    worker = server.workers[0]
    deadline = time.monotonic() + timeout_s
    while worker.stats["acked"] + worker.stats["nacked"] < acked:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"{worker.stats['acked']} evals acked of {acked}")
        time.sleep(0.01)
