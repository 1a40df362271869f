"""Continuous-profiling plane (core/profiling.py): stack
classification, role mapping, the GIL-wait split, folded-stack export,
the compile ledger, on-demand capture bundles, and — the satellite-3
contract — sampler NEUTRALITY: a seeded virtual-time soak must replay
byte-identical with the sampler on or off, because the sampler reads
the real clock and writes to none of the deterministic surfaces."""

import sys
import threading
import time

from nomad_tpu.core import profiling
from nomad_tpu.core.profiling import (
    BUCKETS, SCHEMA, CompileLedger, SamplingProfiler, activity,
    classify_stack, current_activity, role_of, role_window,
)

# ------------------------------------------------------- classification


def test_role_of_prefix_table():
    assert role_of("worker-3") == "worker"
    assert role_of("plan-applier") == "applier"
    assert role_of("raft-follower-2") == "raft"
    assert role_of("heartbeat-watcher") == "raft"
    assert role_of("server-tick") == "broker"
    assert role_of("http-api-9") == "http"
    assert role_of("client-node-1") == "client"
    assert role_of("chaos-partition") == "chaos"
    assert role_of("MainThread") == "other"


def _frame_named(name):
    # a real frame whose innermost co_name is `name` — classify_stack
    # only looks at code objects, so a renamed local works
    src = f"def {name}():\n    import sys\n    return sys._getframe()\n"
    ns = {}
    exec(compile(src, __file__, "exec"), ns)
    return ns[name]()


def test_classify_device_wait_by_func_name():
    assert classify_stack(_frame_named("block_until_ready")) \
        == "device-wait"
    assert classify_stack(_frame_named("fetch")) == "device-wait"


def test_classify_real_engine_fetch_is_device_wait():
    """Sample a thread blocked in a REAL device->host fetch: jax.Array
    converts in C++, so the frame the table must know is the engine's
    own `_fetch` (a jax-internal name here goes stale silently and
    books every fetch as host time)."""
    import jax
    import jax.numpy as jnp

    from nomad_tpu.ops import PlacementEngine

    @jax.jit
    def slow(x):
        return jax.lax.fori_loop(
            0, 40, lambda i, a: jnp.sin(a) @ a.T * 1e-3 + a, x)

    x = jnp.ones((600, 600))
    slow(x).block_until_ready()            # compile outside the samples
    eng = PlacementEngine(mesh=False)
    done = threading.Event()

    def fetcher():
        try:
            for _ in range(5):
                eng._fetch(slow(x))
        finally:
            done.set()

    t = threading.Thread(target=fetcher, name="worker-fetch", daemon=True)
    t.start()
    seen = set()
    while not done.is_set():
        frame = sys._current_frames().get(t.ident)
        if frame is not None:
            seen.add(classify_stack(frame))
        time.sleep(0.001)
    t.join(5.0)
    assert not t.is_alive()
    assert "device-wait" in seen, seen


def test_classify_wire_and_idle_by_filename():
    ns = {}
    exec(compile("import sys\nf = sys._getframe()",
                 "/x/core/wire.py", "exec"), ns)
    assert classify_stack(ns["f"]) == "wire"
    ns = {}
    exec(compile("import sys\nf = sys._getframe()",
                 "/x/chaos/clock.py", "exec"), ns)
    assert classify_stack(ns["f"]) == "idle"


def test_classify_host_residual():
    assert classify_stack(sys._getframe()) == "host"


def test_classify_parked_event_wait_is_idle():
    """A thread parked in Event.wait shows threading.py:wait innermost;
    that is idle (no work queued), not lock contention."""
    ev = threading.Event()
    ready = threading.Event()

    def park():
        try:
            ready.set()
            ev.wait(5.0)
        except Exception:
            pass

    t = threading.Thread(target=park, name="park-test", daemon=True)
    t.start()
    ready.wait(2.0)
    time.sleep(0.02)
    frame = sys._current_frames().get(t.ident)
    try:
        assert frame is not None
        assert classify_stack(frame) == "idle"
    finally:
        ev.set()
        t.join(2.0)


def test_classify_semaphore_acquire_is_lock_wait():
    """Semaphore.acquire is a Python frame in threading.py named
    `acquire` — the lock-wait signature."""
    sem = threading.Semaphore(0)
    ready = threading.Event()

    def contend():
        try:
            ready.set()
            sem.acquire(timeout=5.0)
        except Exception:
            pass

    t = threading.Thread(target=contend, name="sem-test", daemon=True)
    t.start()
    ready.wait(2.0)
    time.sleep(0.02)
    frame = sys._current_frames().get(t.ident)
    try:
        assert frame is not None
        assert classify_stack(frame) == "lock-wait"
    finally:
        sem.release()
        t.join(2.0)


# ------------------------------------------------------ activity markers


def test_activity_marker_nesting_and_cross_thread_publish():
    ident = threading.get_ident()
    assert current_activity() is None
    assert ident not in profiling._MARKS
    with activity("device-wait"):
        assert current_activity() == "device-wait"
        assert profiling._MARKS[ident] == "device-wait"
        with activity("wire"):
            assert current_activity() == "wire"
            assert profiling._MARKS[ident] == "wire"
        assert current_activity() == "device-wait"
        assert profiling._MARKS[ident] == "device-wait"
    assert current_activity() is None
    assert ident not in profiling._MARKS


# -------------------------------------------------------------- sampler


def _burn(stop):
    # pure-Python spin: classified `host`, keeps the GIL busy
    x = 0
    while not stop.is_set():
        x += 1
    return x


def test_sampler_buckets_roles_and_gil_split():
    """Two runnable worker threads spinning Python: with one GIL, each
    runnable sample splits 1/N own-bucket + (N-1)/N gil-wait — the
    measurement ROADMAP item 5 is scoped from."""
    p = SamplingProfiler(hz=97.0)
    stop = threading.Event()
    threads = [threading.Thread(target=_burn, args=(stop,),
                                name=f"worker-{i}", daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    try:
        assert p.start()
        assert p.running
        time.sleep(0.6)
    finally:
        stop.set()
        p.stop()
        for t in threads:
            t.join(2.0)
    snap = p.snapshot()
    assert snap["samples"] > 10
    assert snap["thread_samples"] >= snap["samples"]
    assert not snap["running"]
    assert set(snap["buckets"]) == set(BUCKETS)
    # every sample lands in a named bucket by construction
    assert snap["attributed_fraction"] >= 0.90
    worker = snap["roles"]["worker"]
    assert worker.get("gil-wait", 0.0) > 0.0
    assert snap["gil_wait_fraction"] > 0.0
    assert snap["gil_wait_fraction_by_role"]["worker"] == \
        snap["gil_wait_fraction"]
    # two always-runnable spinners: each carries ~1/2 gil-wait
    assert 0.2 <= snap["gil_wait_fraction"] <= 0.8
    folded = p.folded()
    assert folded
    assert any(line.startswith("worker;") and line.rsplit(" ", 1)[1]
               .isdigit() for line in folded.splitlines())
    assert p.folded(role="worker")
    assert "worker;" not in p.folded(role="broker")


def test_sampler_marker_beats_stack_heuristics():
    """A `with activity("device-wait")` around a pure-Python spin must
    classify as device-wait even though the frames say host."""
    p = SamplingProfiler(hz=97.0)
    stop = threading.Event()

    def marked():
        try:
            with activity("device-wait"):
                _burn(stop)
        except Exception:
            pass

    t = threading.Thread(target=marked, name="worker-marked",
                         daemon=True)
    t.start()
    try:
        p.start()
        time.sleep(0.4)
    finally:
        stop.set()
        p.stop()
        t.join(2.0)
    snap = p.snapshot()
    assert snap["roles"]["worker"].get("device-wait", 0.0) > 0.0


def test_sampler_idle_thread_classified_idle():
    p = SamplingProfiler(hz=97.0)
    ev = threading.Event()
    t = threading.Thread(target=lambda: ev.wait(10.0) and None,
                         name="worker-parked", daemon=True)
    t.start()
    try:
        p.start()
        time.sleep(0.4)
    finally:
        p.stop()
        ev.set()
        t.join(2.0)
    snap = p.snapshot()
    assert snap["roles"]["worker"].get("idle", 0.0) > 0.0


def test_sampler_reset_and_hz_retune():
    p = SamplingProfiler(hz=97.0)
    p.start()
    time.sleep(0.15)
    assert p.start(hz=53.0)   # re-tune while running: idempotent
    assert p.hz == 53.0
    p.stop()
    assert p.snapshot()["samples"] > 0
    p.reset()
    snap = p.snapshot()
    assert snap["samples"] == 0
    assert snap["buckets"] == {b: 0.0 for b in BUCKETS}
    assert p.folded() == ""
    assert not p.start(hz=0)  # hz<=0 is the off switch
    assert not p.running


# ------------------------------------------------------- compile ledger


def test_compile_ledger_accounting_and_hit_rate():
    led = CompileLedger()
    led.note_miss("engine.multi/8x4", compile_s=0.5)
    led.note_hit("engine.multi/8x4")
    led.note_hit("engine.multi/8x4")
    led.note_steady("engine.multi/8x4", 0.01)
    snap = led.snapshot()
    assert snap["hits"] == 2 and snap["misses"] == 1
    assert abs(snap["hit_rate"] - 2 / 3) < 1e-9
    assert snap["first_launch_s"] == 0.5
    site = snap["sites"]["engine.multi/8x4"]
    assert site["steady_calls"] == 1 and site["steady_s"] == 0.01
    led.reset()
    assert led.snapshot()["sites"] == {}
    assert led.snapshot()["hit_rate"] == 0.0


def test_compile_ledger_wrap_times_first_call_only():
    led = CompileLedger()
    calls = []
    wrapped = led.wrap("site/a", lambda x: calls.append(x) or x * 2)
    assert wrapped(3) == 6 and wrapped(4) == 8 and wrapped(5) == 10
    assert calls == [3, 4, 5]
    snap = led.snapshot()
    # only the FIRST call is a miss (jit compiles at first invocation)
    assert snap["sites"]["site/a"]["misses"] == 1
    assert snap["sites"]["site/a"]["first_launch_s"] >= 0.0


# -------------------------------------------------------------- capture


def test_capture_bundle_schema_providers_and_ring():
    p = SamplingProfiler(hz=97.0)
    p.device_ledger_provider = lambda: {"backend": "test",
                                        "hbm_resident_bytes": 7}
    p.flight_provider = lambda: {"rings": []}
    b = p.capture(duration_s=0.05)
    assert b["schema"] == SCHEMA
    assert b["id"] == "prof-0001"
    assert b["duration_s"] == 0.05
    assert not b["sampler_was_running"]   # one-shot start/stop
    assert not p.running                  # restored after capture
    assert set(b["buckets"]) == set(BUCKETS)
    assert 0.0 <= b["attributed_fraction"] <= 1.0
    assert b["device_ledger"] == {"backend": "test",
                                  "hbm_resident_bytes": 7}
    assert b["flight_recorder"] == {"rings": []}
    assert "hits" in b["compile_ledger"]
    assert b["jax_trace"] is None
    assert isinstance(b["folded"], list)
    assert p.get_capture("prof-0001") is b
    assert p.get_capture("prof-9999") is None
    for _ in range(9):
        p.capture(duration_s=0.05)
    caps = p.captures()
    assert len(caps) == profiling._CAPTURE_CAP
    assert caps[-1]["id"] == "prof-0010"   # seq keeps counting
    assert p.get_capture("prof-0001") is None  # evicted from the ring


def test_capture_provider_failure_is_contained():
    def boom():
        raise RuntimeError("server closing")

    p = SamplingProfiler(hz=97.0)
    p.device_ledger_provider = boom
    b = p.capture(duration_s=0.05)
    assert b["device_ledger"] == {"error": "server closing"}


def test_capture_clamps_duration():
    p = SamplingProfiler(hz=97.0)
    assert p.capture(duration_s=-5)["duration_s"] == 0.05


# ---------------------------------------------------------- role_window


def test_role_window_deltas_drop_zero_and_new_roles_appear():
    base = {"roles": {"worker": {"host": 4.0, "idle": 2.0}}}
    cur = {"roles": {"worker": {"host": 7.0, "idle": 2.0,
                                "gil-wait": 1.5},
                     "http": {"wire": 3.0}}}
    w = role_window(base, cur)
    assert w == {"worker": {"host": 3.0, "gil-wait": 1.5},
                 "http": {"wire": 3.0}}
    assert SamplingProfiler._gil_fraction(w, "worker") == 1.5 / 4.5
    assert SamplingProfiler._gil_fraction(w, "absent") == 0.0
    assert role_window(cur, cur) == {}


# ----------------------------------------------------- brief + configure


def test_brief_points_at_capture_surface():
    p = SamplingProfiler(hz=97.0)
    doc = p.brief()
    assert doc["capture_endpoint"] == "/v1/operator/profile"
    assert doc["captures"] == []
    assert set(doc["buckets"]) == set(BUCKETS)


def test_configure_global_start_stop_round_trip():
    was_hz = profiling.PROFILER.hz
    was_running = profiling.PROFILER.running
    try:
        prof = profiling.configure(hz=61.0)
        assert prof is profiling.PROFILER
        assert prof.running and prof.hz == 61.0
        profiling.configure(enabled=False)
        assert not prof.running
        profiling.configure(hz=0)
        assert not prof.running and prof.hz == 0
    finally:
        profiling.PROFILER.hz = was_hz
        if was_running:
            profiling.PROFILER.start()
        else:
            profiling.PROFILER.stop()


# -------------------------------------------- satellite 3: neutrality


def test_soak_replay_identical_with_sampler_on_and_off():
    """The neutrality contract: the always-on sampler observes a
    virtual-time soak but must never participate in its timeline — the
    canonical trace and converged fingerprint stay byte-identical
    whether it runs (at an aggressive hz) or not."""
    from nomad_tpu.chaos.soak import run_soak
    from nomad_tpu.chaos.traffic import TrafficProfile

    profile = TrafficProfile(
        hours=0.05, n_nodes=4, n_zones=2, service_per_hour=40,
        batch_per_hour=40, drains_per_hour=10, flap_storms_per_hour=0,
        preempt_storms_per_hour=0, chaos_scenarios=())
    was_hz = profiling.PROFILER.hz
    was_running = profiling.PROFILER.running
    try:
        profiling.configure(enabled=False)
        off = run_soak(seed=11, profile=profile)
        profiling.configure(hz=211.0)   # aggressive: ~5ms period
        assert profiling.PROFILER.running
        on = run_soak(seed=11, profile=profile)
    finally:
        profiling.PROFILER.stop()
        profiling.PROFILER.hz = was_hz
        if was_running and was_hz > 0:
            profiling.PROFILER.start()
    assert off.ok and on.ok, (off.violations, on.violations)
    assert on.digest == off.digest
    assert on.fingerprint == off.fingerprint
    assert on.trace.canonical_bytes() == off.trace.canonical_bytes()
