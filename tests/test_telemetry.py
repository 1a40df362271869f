"""End-to-end eval-lifecycle tracing + metrics registry (ISSUE 4):
registry/histogram units, prometheus exposition grammar, the
broker→applier trace join, virtual-clock timing determinism, the
streaming endpoints (`/v1/agent/monitor`, `/v1/event/stream`
disconnect cleanup), and LogRing drop accounting."""

import json
import re
import threading
import time
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.chaos.clock import VirtualClock
from nomad_tpu.core.logging import LogRing, RING, log
from nomad_tpu.core.server import Server
from nomad_tpu.core.telemetry import (
    Histogram,
    MetricsRegistry,
    REGISTRY,
    StatCounters,
    TRACER,
    span_id,
)
from nomad_tpu.structs import codec, new_id


def _wait(fn, timeout=60, period=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(period)
    return fn()


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_counters_gauges_labels(self):
        reg = MetricsRegistry()
        reg.inc("t.hits")
        reg.inc("t.hits", 4)
        reg.inc("t.hits", 2, code="500")
        reg.set_gauge("t.depth", 7)
        assert reg.counter("t.hits") == 5
        assert reg.counter("t.hits", code="500") == 2
        assert reg.gauge("t.depth") == 7
        snap = reg.snapshot()
        assert snap["counters"]["t.hits"] == 5
        assert snap["counters"]['t.hits{code=500}'] == 2
        # snapshot must be JSON-safe
        json.dumps(snap)

    def test_histogram_percentiles(self):
        h = Histogram(buckets=(0.001, 0.01, 0.1, 1.0))
        for _ in range(90):
            h.observe(0.005)           # lands in the (0.001, 0.01] bucket
        for _ in range(10):
            h.observe(0.5)             # lands in the (0.1, 1.0] bucket
        assert h.count == 100
        assert h.sum == pytest.approx(90 * 0.005 + 10 * 0.5)
        s = h.summary()
        # p50 interpolates inside the 0.001..0.01 bucket; p99 inside
        # 0.1..1.0; estimates must be ordered and bucket-bounded
        assert 0.001 < s["p50"] <= 0.01
        assert 0.1 < s["p99"] <= 1.0
        assert s["p50"] <= s["p95"] <= s["p99"]

    def test_histogram_timed_block_reads_injected_clock(self):
        clock = VirtualClock()
        reg = MetricsRegistry(clock=clock)
        with reg.time("t.block_s"):
            clock.advance(2.5)
        s = reg.histogram("t.block_s")
        assert s["count"] == 1
        assert s["sum"] == pytest.approx(2.5)

    def test_stat_counters_concurrent_increments_lose_nothing(self):
        # the satellite's point: bare-dict `stats["x"] += 1` from many
        # threads loses updates; StatCounters must not
        name = f"t.atomic.{new_id()[:8]}"
        sc = StatCounters(name, ("n",))
        threads = [threading.Thread(
            target=lambda: [sc.inc("n") for _ in range(1000)])
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sc["n"] == 8000
        assert REGISTRY.counter(f"{name}.n") == 8000
        # dict-protocol compatibility with the old stats blocks
        assert dict(sc) == {"n": 8000}
        sc["n"] = 0
        assert sc["n"] == 0


# ----------------------------------------------------------- exposition

_TYPE_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' -?[0-9]+(\.[0-9]+)?([eE][-+][0-9]+)?$')


def assert_valid_exposition(text):
    """Every line is a `# TYPE` comment or a sample; histogram bucket
    series are cumulative with le=+Inf equal to _count."""
    assert text.endswith("\n")
    families = {}
    samples = []
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert _TYPE_RE.match(line), f"bad TYPE line: {line!r}"
            _, _, fam, kind = line.split()
            families[fam] = kind
            continue
        assert _SAMPLE_RE.match(line.replace('le="+Inf"', 'le="Inf"')), \
            f"bad sample line: {line!r}"
        samples.append(line)
    assert families and samples
    # cumulative bucket check per histogram family
    for fam, kind in families.items():
        if kind != "histogram":
            continue
        buckets = [ln for ln in samples
                   if ln.startswith(f"{fam}_bucket")]
        assert buckets, f"histogram {fam} has no buckets"
        by_labels = {}
        for ln in buckets:
            labels = re.sub(r',?le="[^"]*"', "", ln.split(" ")[0])
            by_labels.setdefault(labels, []).append(
                float(ln.rsplit(" ", 1)[1]))
        for series in by_labels.values():
            assert series == sorted(series), "buckets not cumulative"
        count_lines = [ln for ln in samples
                       if ln.startswith(f"{fam}_count")]
        assert count_lines, f"histogram {fam} lacks _count"
        assert any(ln.startswith(f"{fam}_sum") for ln in samples)
    return families


class TestPrometheusExposition:
    def test_grammar_and_cumulative_buckets(self):
        reg = MetricsRegistry()
        reg.inc("t.requests", 3)
        reg.inc("t.requests", 1, code="500")
        reg.set_gauge("t.depth", 2)
        for v in (0.002, 0.02, 0.2, 2.0):
            reg.observe("t.latency_s", v)
        families = assert_valid_exposition(reg.prometheus())
        assert families["t_requests"] == "counter"
        assert families["t_depth"] == "gauge"
        assert families["t_latency_seconds"] == "histogram"
        # the _s suffix renders as _seconds, with quantile gauges
        for q in ("p50", "p95", "p99"):
            assert families[f"t_latency_seconds_{q}"] == "gauge"


# ------------------------------------------------------------ trace join


class TestTraceJoin:
    def test_broker_to_applier_trace_join(self):
        TRACER.reset()
        s = Server(num_workers=1)
        s.establish_leadership()
        s.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        ev = s.register_job(job)
        assert ev.trace_id == ev.id     # stamped at the FSM boundary
        s.process_all()
        spans = TRACER.trace(ev.trace_id)
        names = {sp["Name"] for sp in spans}
        assert {"eval", "broker.wait", "worker.schedule",
                "plan.queue_wait", "plan.apply"} <= names, names
        # consistent parent/child links: every parent id resolves
        ids = {sp["SpanID"] for sp in spans}
        for sp in spans:
            assert sp["ParentID"] == "" or sp["ParentID"] in ids, sp
        root = next(sp for sp in spans if sp["Name"] == "eval")
        assert root["ParentID"] == ""
        assert root["SpanID"] == span_id(ev.trace_id, "eval")
        # the wait histogram observed the dequeue
        assert REGISTRY.histogram("nomad.broker.wait_s")["count"] >= 1
        assert REGISTRY.histogram("nomad.worker.schedule_s",
                                  type=job.type)["count"] >= 1

    def test_follow_up_evals_inherit_trace(self):
        ev = mock.eval()
        ev.trace_id = "tid-123"
        fu = ev.create_failed_follow_up_eval(wait_until=99.0)
        assert fu.trace_id == "tid-123"
        blocked = ev.create_blocked_eval({}, escaped=False)
        assert blocked.trace_id == "tid-123"


class TestVirtualClockDeterminism:
    def _run_once(self):
        """One synchronous dev-server pass on a VirtualClock with a
        scripted advance schedule — the deterministic shape chaos
        scenarios drive (same clock seam, no thread races)."""
        TRACER.reset()
        REGISTRY.reset()
        clock = VirtualClock(epoch=1.7e9)
        s = Server(num_workers=1, clock=clock)
        s.establish_leadership()
        s.register_node(mock.node())
        clock.advance(1.0)
        job = mock.job()
        job.task_groups[0].count = 2
        s.register_job(job)
        clock.advance(0.5)
        s.process_all()
        clock.advance(0.25)
        spans = sorted(TRACER.spans(), key=lambda sp: sp["Seq"])
        return json.dumps(
            [(sp["Name"], sp["Start"], sp["End"], sp["Duration"])
             for sp in spans]).encode()

    def test_same_run_twice_is_byte_identical(self):
        a = self._run_once()
        b = self._run_once()
        assert a == b
        assert b"worker.schedule" in a


# ---------------------------------------------------------- end to end


@pytest.fixture(scope="module")
def agent():
    TRACER.reset()
    ag = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600)
    ag.start()
    yield ag
    ag.shutdown()


@pytest.fixture(scope="module")
def api(agent):
    return APIClient(address=agent.address)


class TestEndToEnd:
    def _register(self, api, count=1, run_for=300):
        job = mock.batch_job()
        job.task_groups[0].count = count
        job.task_groups[0].tasks[0].config = {"run_for_s": run_for}
        resp = api.jobs.register(codec.encode(job))
        assert resp["EvalID"]
        return job, resp["EvalID"]

    def test_one_run_yields_one_joined_trace(self, api):
        _, eval_id = self._register(api)

        def full_trace():
            try:
                t = api.agent.trace(eval_id)
            except Exception:  # noqa: BLE001 - not recorded yet
                return None
            names = {sp["Name"] for sp in t["Spans"]}
            want = {"eval", "broker.wait", "worker.schedule",
                    "plan.queue_wait", "plan.apply", "client.alloc_start"}
            return t if want <= names else None

        t = _wait(full_trace, timeout=30)
        assert t, "trace never covered the full lifecycle: " + str(
            api.agent.traces())
        spans = t["Spans"]
        ids = {sp["SpanID"] for sp in spans}
        for sp in spans:
            assert sp["ParentID"] == "" or sp["ParentID"] in ids, sp
        # tree shape: broker/schedule under the root eval span, plan
        # spans under schedule, alloc start under plan.apply
        by_name = {sp["Name"]: sp for sp in spans}
        root_id = by_name["eval"]["SpanID"]
        assert by_name["broker.wait"]["ParentID"] == root_id
        assert by_name["worker.schedule"]["ParentID"] == root_id
        sched_id = by_name["worker.schedule"]["SpanID"]
        assert by_name["plan.queue_wait"]["ParentID"] == sched_id
        assert by_name["plan.apply"]["ParentID"] == sched_id
        assert by_name["client.alloc_start"]["ParentID"] == \
            by_name["plan.apply"]["SpanID"]
        # summaries list the trace too
        assert any(row["TraceID"] == eval_id
                   for row in api.agent.traces())

    def test_prometheus_endpoint(self, api):
        self._register(api)
        _wait(lambda: REGISTRY.histogram("nomad.plan.apply_s"))
        text = api.agent.metrics(format="prometheus")
        families = assert_valid_exposition(text)
        # acceptance: histogram families with percentile summaries for
        # broker wait, schedule, and plan-apply latency
        for fam in ("nomad_broker_wait_seconds",
                    "nomad_worker_schedule_seconds",
                    "nomad_plan_apply_seconds"):
            assert families.get(fam) == "histogram", families
            for q in ("p50", "p95", "p99"):
                assert families.get(f"{fam}_{q}") == "gauge"
        assert families.get("nomad_broker_acked") == "counter"
        assert families.get("nomad_state_nodes") == "gauge"

    def test_metrics_json_includes_percentile_summaries(self, api):
        m = api.agent.metrics()
        assert "nomad.broker.total_ready" in m     # legacy keys survive
        assert "nomad.state.nodes" in m
        assert "nomad.broker.wait_s.p99" in m
        assert "nomad.broker.wait_s.count" in m

    def test_operator_debug_bundle(self, api):
        bundle = api.operator.debug()
        for key in ("Stats", "Metrics", "Prometheus", "Traces", "Spans",
                    "Logs", "Threads"):
            assert key in bundle, sorted(bundle)
        assert isinstance(bundle["Prometheus"], str)
        assert bundle["Traces"], "debug bundle has no traces"

    # --------------------------------------------- streaming endpoints

    def test_monitor_stream_backlog_then_live(self, agent):
        marker_backlog = f"backlog-{new_id()[:8]}"
        log("telemetry-test", "warn", marker_backlog)
        url = f"{agent.address}/v1/agent/monitor?log_level=trace"
        subs_before = len(RING._subs)
        resp = urllib.request.urlopen(url, timeout=10)
        try:
            assert _wait(lambda: len(RING._subs) == subs_before + 1,
                         timeout=5)
            # backlog: the pre-subscribe record arrives first
            seen = []
            while True:
                line = resp.readline()
                seen.append(line)
                if marker_backlog.encode() in line:
                    break
                assert line, f"stream ended early: {seen}"
            # live: a record logged after subscribe streams through
            marker_live = f"live-{new_id()[:8]}"
            log("telemetry-test", "warn", marker_live)
            while True:
                line = resp.readline()
                assert line, "stream ended before live record"
                if marker_live.encode() in line:
                    break
            rec = json.loads(line)
            assert rec["component"] == "telemetry-test"
        finally:
            resp.close()
        # disconnect cleanup: once the client is gone, the next write
        # attempts fail and the subscription is unsubscribed
        def drained():
            log("telemetry-test", "warn", "poke")
            return len(RING._subs) == subs_before
        assert _wait(drained, timeout=10), "monitor sub never cleaned up"

    def test_event_stream_cleanup_on_disconnect(self, agent, api):
        events = agent.server.events
        subs_before = len(events._subs)
        url = f"{agent.address}/v1/event/stream?topic=Job"
        resp = urllib.request.urlopen(url, timeout=10)
        try:
            assert _wait(lambda: len(events._subs) == subs_before + 1,
                         timeout=5)
            # a matching event streams through while connected
            self._register(api)
            line = resp.readline()
            assert line
            batch = json.loads(line)
            assert batch["Events"][0]["Topic"] == "Job"
        finally:
            resp.close()

        def drained():
            self._register(api)      # generate events -> write fails
            return len(events._subs) == subs_before
        assert _wait(drained, timeout=10), "event sub never cleaned up"


# --------------------------------------------------------------- logring


class TestLogRing:
    def test_wrap_trim_and_subscriber_drops_are_counted(self):
        ring = LogRing(size=8)
        trim0 = REGISTRY.counter("nomad.logring.dropped", reason="trim")
        for i in range(9):
            ring.log("t", "info", f"m{i}")
        assert REGISTRY.counter("nomad.logring.dropped",
                                reason="trim") == trim0 + 2  # size // 4
        q = ring.subscribe(maxsize=1)
        sub0 = REGISTRY.counter("nomad.logring.dropped",
                                reason="subscriber")
        for i in range(3):
            ring.log("t", "info", f"s{i}")
        assert REGISTRY.counter(
            "nomad.logring.dropped", reason="subscriber") == sub0 + 2
        ring.unsubscribe(q)

    def test_min_level_gates_producer_side(self):
        ring = LogRing(size=16)
        ring.min_level = "warn"
        ring.log("t", "debug", "invisible")
        ring.log("t", "error", "visible")
        msgs = [r["msg"] for r in ring.tail(10)]
        assert "visible" in msgs and "invisible" not in msgs


# ---------------------------------------------------------- cheap scrape


class TestCheapScrape:
    def test_state_counts_match_tables(self):
        s = Server(num_workers=1)
        s.establish_leadership()
        assert s.state.counts()["nodes"] == 0
        s.register_node(mock.node())
        s.register_job(mock.job())
        counts = s.state.counts()
        assert counts["nodes"] == 1
        assert counts["jobs"] == 1
        assert counts["evals"] >= 1


# ------------------------------------------------- the process runtime


class TestGcHook:
    """The collector from inside the program (core/telemetry.py): a
    span per collection of generation 1 or 2, plain accumulators every
    generation, no lock anywhere in the hook."""

    def test_two_servers_install_it_once(self):
        import gc
        from nomad_tpu.core import telemetry
        Server(num_workers=1)
        Server(num_workers=1)
        assert gc.callbacks.count(telemetry._on_gc) == 1

    def test_forced_collections_counted_by_generation(self):
        import gc
        from nomad_tpu.core import telemetry
        telemetry.install_gc_hook()
        before = (list(telemetry._gc_collections),
                  list(telemetry._gc_pause_s))
        for generation in (0, 1, 2, 2):
            gc.collect(generation)
        n = [b - a for a, b in zip(before[0], telemetry._gc_collections)]
        s = [b - a for a, b in zip(before[1], telemetry._gc_pause_s)]
        # the forced ones, and whatever an allocation in between (this
        # thread's or another's) may have set off
        assert n[2] >= 2 and n[1] >= 1 and n[0] >= 1
        assert all(x > 0.0 for x in s)
        assert telemetry._gc_open[1] is None       # no span left open
        flat = REGISTRY.snapshot()["counters"]
        for generation in "012":
            assert flat[f"nomad.runtime.gc_collections{{generation="
                        f"{generation}}}"] >= 1
            assert flat[f"nomad.runtime.gc_pause_s{{generation="
                        f"{generation}}}"] > 0.0

    def test_span_for_generations_1_and_2_none_for_0(self, tmp_path):
        import gc
        import jax
        from benchmark import span_args
        from benchmark import trace_reduce as tr
        from nomad_tpu.core import telemetry
        telemetry.install_gc_hook()
        gc.collect()                 # so the forced ones below are short
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            was = gc.isenabled()
            gc.disable()             # the forced collections alone
            try:
                for generation in (0, 1, 0, 2, 0):
                    gc.collect(generation)
            finally:
                if was:
                    gc.enable()
        finally:
            jax.profiler.stop_trace()
        spans = span_args.parse(tr.find_xplane(str(tmp_path)))["gc"]
        assert [g for _, _, g in spans] == [1, 2]
        assert all(b > a for a, b, _ in spans)

    def test_no_deadlock_under_the_recorders_locks(self):
        """A collection can begin at any allocation, also inside
        `MetricsRegistry.observe` or `StageTimers.record` while this
        very thread holds that object's non-reentrant lock: a hook that
        called either there would never return."""
        import gc
        from nomad_tpu.core import telemetry
        from nomad_tpu.core.wavepipe import StageTimers
        telemetry.install_gc_hook()
        timers = StageTimers()
        done = []

        def collect_holding_both():
            with REGISTRY._lock, timers._lock:
                gc.collect(1)
                gc.collect(2)
            done.append(True)

        t = threading.Thread(target=collect_holding_both, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive() and done == [True]


def _burn(seconds: float) -> None:
    """Spend about `seconds` of this thread's CPU."""
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        sum(range(2000))


class TestThreadCpu:
    """CPU seconds by thread role: each thread's own stamps, summed."""

    def _run(self, name, seconds=0.05):
        from nomad_tpu.core import telemetry

        def body():
            _burn(seconds)
            telemetry.stamp_thread_cpu()

        t = threading.Thread(target=body, name=name, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()

    def test_exited_threads_keep_their_seconds_in_the_roles_total(self):
        from nomad_tpu.core import telemetry
        before = telemetry.thread_cpu_by_role().get("worker", 0.0)
        readings = []
        for _ in range(3):           # a new thread every round, one role
            self._run("worker-7")
            readings.append(telemetry.thread_cpu_by_role()["worker"])
        assert readings == sorted(readings)
        # all three threads' seconds, none twice
        assert 0.15 <= readings[-1] - before < 0.15 + 0.1
        # the table holds no slot of an exited thread
        assert all(slot[0].is_alive() for slot in telemetry._cpu_slots)

    def test_a_reused_ident_does_not_inherit_the_old_reading(self):
        from nomad_tpu.core import telemetry
        before = telemetry.thread_cpu_by_role().get("applier", 0.0)
        # the exited thread's slot is still in the table when the next
        # thread, very likely under its ident, stamps for the first
        # time: a slot is the thread's own, not its ident's
        self._run("plan-applier")
        self._run("plan-applier")
        after = telemetry.thread_cpu_by_role()["applier"]
        assert 0.1 <= after - before < 0.1 + 0.1

    def test_a_thread_reads_its_clock_once_a_tick(self, monkeypatch):
        # a read costs a system call (6 us on the chip's host) and the
        # clock moves once in 10 ms there: stamps in between keep the
        # reading they have
        from nomad_tpu.core import telemetry
        reads = []
        real = time.thread_time_ns

        def counted():
            if threading.current_thread().name == "probe-x":
                reads.append(1)          # other threads stamp too
            return real()

        def body():
            monkeypatch.setattr(telemetry.time, "thread_time_ns", counted)
            for _ in range(1000):
                telemetry.stamp_thread_cpu()
            first = len(reads)
            time.sleep(2.5 * telemetry._CPU_READ_EVERY_S)
            telemetry.stamp_thread_cpu()
            done.append((first, len(reads)))

        done = []
        t = threading.Thread(target=body, name="probe-x", daemon=True)
        t.start()
        t.join(timeout=30)
        monkeypatch.undo()
        assert not t.is_alive()
        ((first, after),) = done
        assert 1 <= first <= 3 and after == first + 1

    def test_stamps_race_sums_without_losing_or_doubling(self, monkeypatch):
        import sys
        from nomad_tpu.core import telemetry
        # every stamp a read: the fold is what is under test here
        monkeypatch.setattr(telemetry, "_CPU_READ_EVERY_S", 0.0)
        before = telemetry.thread_cpu_by_role().get("client", 0.0)
        stop = threading.Event()
        seen, finals = [], []

        def reader():
            while not stop.is_set():
                seen.append(telemetry.thread_cpu_by_role().get(
                    "client", 0.0))

        def stamper():
            for _ in range(100):
                sum(range(2000))
                telemetry.stamp_thread_cpu()
            finals.append(time.thread_time())    # just past its last stamp

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            watch = threading.Thread(target=reader, daemon=True)
            watch.start()
            for _ in range(4):       # rounds of short-lived threads
                threads = [threading.Thread(target=stamper, daemon=True,
                                            name=f"client-{i}")
                           for i in range(8)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
                assert not any(t.is_alive() for t in threads)
            stop.set()
            watch.join(timeout=30)
            assert not watch.is_alive()
        finally:
            sys.setswitchinterval(interval)
        assert seen == sorted(seen)              # a role's total never falls
        # every thread's seconds once: none lost at an exit or at an
        # ident handed on, none counted twice
        total = telemetry.thread_cpu_by_role()["client"] - before
        assert len(finals) == 32
        assert total <= sum(finals)
        assert total == pytest.approx(sum(finals), rel=0.05, abs=0.01)

    def test_marker_folds_the_other_roles(self):
        from nomad_tpu.core import telemetry, wavepipe
        self._run("raft-x", 0.02)
        self._run("Thread-9 (anything)", 0.02)
        cpu = telemetry.thread_cpu_by_role()
        assert cpu["raft"] >= 0.02 and cpu["other"] >= 0.02
        wavepipe.mark_cpu()          # sums and emits; nothing to assert
        assert telemetry.thread_cpu_by_role()["other"] >= cpu["other"]


class TestRuntimeOnTheApi:
    def test_handler_threads_answer_http(self, agent, api):
        from nomad_tpu.core import profiling, telemetry
        before = telemetry.thread_cpu_by_role().get("http", 0.0)
        api.agent.metrics()
        # the handler stamps itself after the response has left, under
        # the role its name answers to: socketserver's own name for it
        # would have read `other`
        assert _wait(lambda: telemetry.thread_cpu_by_role().get(
            "http", 0.0) > before, timeout=30)
        assert profiling.role_of(
            "http-api-Thread-3 (process_request_thread)") == "http"
        assert profiling.role_of(
            "Thread-3 (process_request_thread)") == "other"
        # and the sampler's tables see the same name
        import http.client
        host, port = agent.address[len("http://"):].split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        try:
            conn.request("GET", "/v1/status/leader")
            conn.getresponse().read()       # kept alive: its thread lives
            names = [t.name for t in threading.enumerate()
                     if "process_request_thread" in t.name]
            assert names and all(n.startswith("http-api-") for n in names)
        finally:
            conn.close()

    def test_metrics_carry_thread_cpu_and_gc_pause(self, api):
        import gc
        gc.collect()
        m = api.agent.metrics()
        assert m["nomad.runtime.thread_cpu_s{role=http}"] > 0.0
        for generation in "012":
            assert f"nomad.runtime.gc_pause_s{{generation={generation}}}" \
                in m
        assert m["nomad.runtime.gc_collections{generation=2}"] >= 1
        text = api.agent.metrics(format="prometheus")
        families = assert_valid_exposition(text)
        assert families["nomad_runtime_thread_cpu_seconds"] == "counter"
        assert families["nomad_runtime_gc_pause_seconds"] == "counter"
        assert 'nomad_runtime_thread_cpu_seconds{role="http"}' in text
        assert 'nomad_runtime_gc_collections{generation="2"}' in text
