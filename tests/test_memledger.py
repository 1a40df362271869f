"""Memory & footprint observability plane (core/memledger.py): the
per-plane byte ledger, journal compaction equivalence, floor-fallback
accounting, idle-shape GC, and the rss_mb SLO rule (ISSUE 19)."""

import threading

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos.clock import SystemClock, VirtualClock
from nomad_tpu.chaos.trace import state_fingerprint
from nomad_tpu.core import flightrec, memledger
from nomad_tpu.core.fanout import WatchHub, _Shape
from nomad_tpu.core.memledger import (
    MEMLEDGER,
    MemLedger,
    approx_sizeof,
    read_rss,
)
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.state.state_store import StateStore


# ---------------------------------------------------------------------------
# estimator + RSS reader
# ---------------------------------------------------------------------------


def test_approx_sizeof_counts_shared_objects_once():
    shared = "x" * 10_000
    doubled = approx_sizeof([shared, "y" * 10_000])
    deduped = approx_sizeof([shared, shared])
    # the second reference to the SAME object must be ~free
    assert deduped < doubled * 0.75
    assert approx_sizeof({}) > 0
    assert approx_sizeof(None) > 0


def test_approx_sizeof_extrapolates_from_samples():
    small = approx_sizeof(list(range(100)), sample=8)
    big = approx_sizeof(list(range(10_000)), sample=8)
    # sampling must still scale the estimate with container length
    assert big > small * 20


def test_read_rss_reports_process_residency():
    doc = read_rss()
    assert doc["rss_bytes"] > 0
    assert doc["rss_peak_bytes"] >= doc["rss_bytes"]


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


def test_scrape_publishes_gauges_and_isolates_sizer_errors():
    ml = MemLedger(min_wall_s=0.0)
    ml.register("alpha", lambda: {"bytes": 1000, "entries": 3,
                                  "cap": 10, "evictions": 2,
                                  "gauges": {"nomad.test.extra": 7.0}})
    ml.register("broken", lambda: 1 / 0)
    doc = ml.scrape()
    assert doc["Schema"] == "nomad-tpu.memory.v1"
    assert doc["Planes"]["alpha"]["bytes"] == 1000
    # the gauges sub-dict is published verbatim, not kept in the doc
    assert "gauges" not in doc["Planes"]["alpha"]
    assert REGISTRY.gauge("nomad.test.extra") == 7.0
    assert REGISTRY.gauge("nomad.mem.plane_bytes", plane="alpha") == 1000
    assert REGISTRY.gauge("nomad.mem.rss_bytes") > 0
    # a raising sizer is an errored plane, never a failed scrape
    assert "error" in doc["Planes"]["broken"]
    assert doc["TrackedBytes"] == 1000
    assert ml.evictions() == {"alpha": 2, "broken": 0}
    assert ml.rss_mb() > 0


def test_sample_throttles_on_injected_clock():
    ml = MemLedger(interval_s=5.0, min_wall_s=0.0)
    ml.register("p", lambda: {"bytes": 1})
    assert ml.sample(100.0) is True
    assert ml.sample(101.0) is False      # inside interval_s
    assert ml.sample(104.9) is False
    assert ml.sample(105.0) is True
    assert ml.stats()["scrapes"] == 2


def test_sample_wall_guard_caps_scrape_rate(monkeypatch):
    # a VirtualClock soak advances hundreds of virtual seconds per wall
    # second; the wall guard must keep that from becoming dozens of
    # scrapes (values are volatile wall facts — skipping loses nothing).
    # Second pass: perf_counter() as a host up for 12.5 s reads it, under
    # min_wall_s; the first sample is taken all the same
    for wall in (None, 12.5):
        if wall is not None:
            monkeypatch.setattr(memledger.time, "perf_counter",
                                lambda: wall)
        ml = MemLedger(interval_s=5.0, min_wall_s=3600.0)
        ml.register("p", lambda: {"bytes": 1})
        assert ml.sample(0.0) is True
        assert ml.sample(1000.0) is False     # wall guard, not interval
        assert ml.stats()["scrapes"] == 1


def test_register_is_last_write_wins_and_unregister_drops():
    ml = MemLedger(min_wall_s=0.0)
    ml.register("p", lambda: {"bytes": 1})
    ml.register("p", lambda: {"bytes": 2})
    assert ml.scrape()["Planes"]["p"]["bytes"] == 2
    ml.unregister("p")
    assert ml.planes() == []


# ---------------------------------------------------------------------------
# journal compaction
# ---------------------------------------------------------------------------


def _churn(store, n_rounds, n_jobs, delete_every=0):
    """Duplicate-heavy write load: the same keys dirtied repeatedly,
    with optional interleaved deletes (tombstone coverage)."""
    jobs = []
    for i in range(n_jobs):
        j = mock.job()
        j.id = f"job-{i}"
        jobs.append(j)
    node = mock.node()
    store.upsert_node(node)
    for r in range(n_rounds):
        for i, j in enumerate(jobs):
            jj = j.copy() if hasattr(j, "copy") else j
            store.upsert_job(jj, preserve_version=True)
            ev = mock.eval(job_id=jj.id)
            ev.id = f"eval-{i}"          # same key every round
            store.upsert_evals([ev])
            if delete_every and r % delete_every == delete_every - 1:
                store.delete_job(jj.namespace, jj.id)
                store.upsert_job(jj, preserve_version=True)


def test_compaction_keeps_floor_at_zero_under_duplicate_churn():
    store = StateStore()
    store._journal_cap = 64
    _churn(store, n_rounds=60, n_jobs=8)
    st = store.journal_stats()
    # merge-by-key coalescing absorbs the duplicate-heavy overflow:
    # nothing evicted, the floor never moves, fallbacks impossible
    assert st["floor"] == 0
    assert st["evictions"] == 0
    assert st["compactions"] > 0
    assert st["bytes_reclaimed"] > 0
    assert st["entries"] <= 64
    assert st["bytes"] > 0
    assert st["gauges"]["nomad.journal.floor_fallbacks"] == 0


def test_compaction_equivalence_full_replay():
    """Newest-wins dedupe must preserve export semantics: a replica
    built purely from the compacted journal's delta (since=0, floor
    still 0) converges to the parent's exact state — including
    tombstoned jobs and re-upserts."""
    store = StateStore()
    store._journal_cap = 64
    _churn(store, n_rounds=40, n_jobs=6, delete_every=4)
    # also leave one job tombstoned for the delete path
    store.delete_job("default", "job-0")
    assert store.journal_stats()["floor"] == 0
    export = store.export_since(0)
    assert export["kind"] == "delta"
    replica = StateStore()
    replica.apply_export(export)
    assert replica.latest_index() == store.latest_index()
    assert (state_fingerprint(replica.snapshot())
            == state_fingerprint(store.snapshot()))
    snap = replica.snapshot()
    assert snap.job_by_id("default", "job-0") is None
    assert snap.job_by_id("default", "job-1") is not None


def test_compaction_equivalence_incremental_cursors():
    """A replica tailing the journal by cursor while compaction runs
    underneath stays bit-identical to the parent at every pull."""
    store = StateStore()
    store._journal_cap = 64
    replica = StateStore()
    for r in range(30):
        _churn(store, n_rounds=2, n_jobs=5,
               delete_every=3 if r % 2 else 0)
        export = store.export_since(replica.latest_index())
        assert export["kind"] in ("delta", "empty")   # never "full"
        replica.apply_export(export)
        assert (state_fingerprint(replica.snapshot())
                == state_fingerprint(store.snapshot()))
    assert store.journal_stats()["floor_fallbacks"] == 0
    assert store.journal_stats()["compactions"] > 0


def test_floor_fallback_counted_under_unique_key_churn():
    """Unique-key churn cannot be coalesced: the journal trims, the
    floor rises, and a cursor below the floor gets a counted full
    resync — the regression the perfcheck gate (== 0 in soaks) pins."""
    store = StateStore()
    store._journal_cap = 64
    for i in range(300):
        ev = mock.eval()
        ev.id = f"uniq-{i}"                  # every write a new key
        store.upsert_evals([ev])
    st = store.journal_stats()
    assert st["floor"] > 0
    assert st["evictions"] > 0
    export = store.export_since(1)           # cursor below the floor
    assert export["kind"] == "full"
    assert store.journal_stats()["floor_fallbacks"] == 1
    replica = StateStore()
    replica.apply_export(export)
    assert (state_fingerprint(replica.snapshot())
            == state_fingerprint(store.snapshot()))


def test_compact_journal_is_idempotent():
    store = StateStore()
    store._journal_cap = 64
    _churn(store, n_rounds=10, n_jobs=4)
    first = store.compact_journal()
    assert store.compact_journal() == 0      # nothing left to reclaim
    assert first >= 0


# ---------------------------------------------------------------------------
# WatchHub idle-shape GC
# ---------------------------------------------------------------------------


def test_watchhub_reap_idle_drops_only_stale_shapes():
    clock = SystemClock()
    hub = WatchHub(StateStore(), clock)
    base = REGISTRY.counter("nomad.fanout.shapes_reaped")
    with hub._lock:
        stale = hub._shapes["stale"] = _Shape(hub._lock)
        stale.touched = 100.0
        active = hub._shapes["active"] = _Shape(hub._lock)
        active.touched = 100.0
        active.waiters = 1                   # a parked client: immune
        fresh = hub._shapes["fresh"] = _Shape(hub._lock)
        fresh.touched = 395.0
    assert hub.reap_idle(now=400.0, idle_s=250.0) == 1
    st = hub.stats()
    assert st["shapes"] == 2
    assert st["shapes_reaped"] == 1
    assert REGISTRY.counter("nomad.fanout.shapes_reaped") == base + 1
    assert hub.reap_idle(now=400.0, idle_s=250.0) == 0   # idempotent
    assert hub.mem_stats()["entries"] == 2


# ---------------------------------------------------------------------------
# rss_mb SLO rule + dump bundles
# ---------------------------------------------------------------------------


def test_rss_mb_rule_disabled_by_default():
    assert flightrec.DEFAULT_SLO["rss_mb"] == -1.0
    w = flightrec.HealthWatchdog(clock=SystemClock())
    doc = w.check()
    row = [r for r in doc["Rules"] if r["Rule"] == "rss_mb"][0]
    assert row["Ok"] is True


def test_rss_mb_rule_breaches_and_dump_carries_memory():
    MEMLEDGER.scrape()
    w = flightrec.HealthWatchdog(slo={"rss_mb": 0.001},
                                 clock=SystemClock())
    doc = w.check()
    row = [r for r in doc["Rules"] if r["Rule"] == "rss_mb"][0]
    assert row["Ok"] is False
    assert row["Observed"] > 0.001
    dumps = w.dumps()
    assert dumps, "breach must snapshot a dump bundle"
    assert dumps[-1]["Memory"]["Schema"] == "nomad-tpu.memory.v1"
    assert dumps[-1]["Memory"]["RSSBytes"] > 0


def test_unknown_slo_key_still_rejected():
    with pytest.raises(ValueError):
        flightrec.HealthWatchdog(slo={"rss_megabytes": 1.0})


# ---------------------------------------------------------------------------
# Server integration: tick sampling + plane registration
# ---------------------------------------------------------------------------


def test_server_registers_planes_and_tick_scrapes():
    from nomad_tpu.core.server import Server
    clock = VirtualClock(epoch=1_700_000_000.0)
    s = Server(num_workers=0, clock=clock)
    try:
        expected = {"state", "journal", "watch_hub", "events",
                    "flight", "timeline", "tracer", "metrics",
                    "logring", "profiler"}
        assert expected <= set(MEMLEDGER.planes())
        s.state.upsert_node(mock.node())
        MEMLEDGER.min_wall_s = 0.0
        before = MEMLEDGER.stats()["scrapes"]
        s.tick()
        clock.advance(MEMLEDGER.interval_s + 1.0)
        s.tick()
        assert MEMLEDGER.stats()["scrapes"] > before
        doc = MEMLEDGER.doc()
        assert doc["Planes"]["state"]["bytes"] > 0
        assert doc["Planes"]["journal"]["entries"] > 0
    finally:
        MEMLEDGER.min_wall_s = 0.5
        s.shutdown()
        clock.close()


def test_operator_memory_surface():
    from nomad_tpu.agent import Agent
    from nomad_tpu.api.client import APIClient
    a = Agent(client_enabled=False, num_workers=0).start()
    try:
        c = APIClient(address=a.address)
        doc = c.operator.memory()
        assert doc["Schema"] == "nomad-tpu.memory.v1"
        assert doc["RSSBytes"] > 0
        assert {"state", "journal", "flight"} <= set(doc["Planes"])
        cached = c.operator.memory(cached=True)
        assert cached["Scrapes"] >= doc["Scrapes"]
        dbg = c.operator.debug()
        assert dbg["Memory"]["RSSBytes"] > 0
        assert "journal" in dbg["Evictions"]
    finally:
        a.shutdown()
