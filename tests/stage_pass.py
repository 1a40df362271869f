"""One small worker pass on a threaded server, shared by the stage-span
tests (tests/test_wavepipe.py, tests/test_host_spans.py): a full batched
wave of plain batch jobs, then one spread job, which takes the solo
path, then one system job, which `place_system` places.  Threaded
(applier and worker threads of their own), because in dev_mode the
worker applies plans inline and `commit` would nest in the worker's
stages.  One follower reads `/v1/event/stream?topic=Evaluation` over
HTTP throughout, as the benchmark's client does."""

import http.client
import threading
import time
from types import SimpleNamespace

from nomad_tpu import mock
from nomad_tpu.api.http_server import HTTPAPIServer
from nomad_tpu.core.server import Server
from nomad_tpu.structs import Spread, SpreadTarget

N_BATCHED, N_SOLO, N_SYSTEM = 6, 1, 1


def _follow(address: str, lines: list) -> threading.Thread:
    """Start reading the evaluation stream; returns once the server has
    answered, so the subscription precedes every eval of the pass."""
    host, port = address[len("http://"):].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=60)
    conn.request("GET", "/v1/event/stream?topic=Evaluation")
    response = conn.getresponse()

    def read():
        try:
            for line in response:
                if line.strip() not in (b"", b"{}"):
                    lines.append(line)
        finally:
            conn.close()

    thread = threading.Thread(target=read, name="stream-follower",
                              daemon=True)
    thread.start()
    return thread


def run_small_pass(between=None, rounds=1):
    """Runs the pass and returns the server, shut down, its
    `stage_timers` holding every interval and `stream_lines` what the
    follower read.  `between()` runs after the fleet is loaded and
    before the first eval is released (a test starts the profiler
    there).  `rounds` over 1 repeats the batched wave after a
    `stop_scheduling` / `start_scheduling`: new worker and applier
    threads, the same roles."""
    s = Server(dev_mode=False, num_workers=1, eval_batch=8, mesh=False,
               heartbeat_ttl=3600)
    s.establish_leadership()
    api = HTTPAPIServer(SimpleNamespace(server=s))
    api.start()
    s.stream_lines = []
    follower = _follow(api.addr, s.stream_lines)
    now = time.time()
    for i in range(24):
        node = mock.node()
        node.datacenter = f"dc{1 + i % 3}"
        s.register_node(node, now=now)
    if between is not None:
        between()
    s.stage_timers.reset()
    acked = 0
    for _ in range(rounds - 1):
        _register_batched(s, now)
        s.start_scheduling()
        try:
            acked += N_BATCHED
            _drain(s, acked)
        finally:
            s.stop_scheduling()
    _register_batched(s, now)
    s.start_scheduling()
    try:
        acked += N_BATCHED
        _drain(s, acked)
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
        _drain(s, acked + N_SOLO)
        daemon = mock.system_job()
        daemon.datacenters = ["dc1", "dc2", "dc3"]
        daemon.task_groups[0].tasks[0].resources.cpu = 50
        daemon.task_groups[0].tasks[0].resources.memory_mb = 16
        s.register_job(daemon, now=now)
        _drain(s, acked + N_SOLO + N_SYSTEM)
        _drain_stream(s, acked + N_SOLO + N_SYSTEM)
    finally:
        s.stop_scheduling()
        s.shutdown()                # closes the stream: the follower ends
        follower.join(timeout=30)
        api.shutdown()
    assert not follower.is_alive()
    return s


def _register_batched(server, now: float) -> None:
    for _ in range(N_BATCHED):
        job = mock.batch_job()
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 12
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 16
        server.register_job(job, now=now)


def _drain_stream(server, evals: int, timeout_s: float = 60.0) -> None:
    """Until the follower has read every eval's `complete`."""
    deadline = time.monotonic() + timeout_s
    while sum(b'"complete"' in ln for ln in server.stream_lines) < evals:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"{len(server.stream_lines)} stream lines, fewer than "
                f"{evals} evals complete in them")
        time.sleep(0.01)


def _drain(server, acked: int, timeout_s: float = 120.0) -> None:
    worker = server.workers[0]
    deadline = time.monotonic() + timeout_s
    while worker.stats["acked"] + worker.stats["nacked"] < acked:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"{worker.stats['acked']} evals acked of {acked}")
        time.sleep(0.01)
