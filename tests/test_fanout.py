"""Read-path fanout plane (core/fanout.py): coalesced blocking-query
watches, the cursor-based event ring, and follower-served reads
(reference: blockingRPC + nomad/stream/event_buffer.go + stale reads)."""

import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.chaos.clock import SystemClock
from nomad_tpu.core import stream
from nomad_tpu.core.fanout import EventRing, WatchHub
from nomad_tpu.core.stream import EventBroker
from nomad_tpu.core.telemetry import REGISTRY
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import (AllocBlock, Deployment, Node, PlanResult,
                               codec)


def _wait(fn, timeout=30, period=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(period)
    return fn()


def _wire_batch_job(count=1, run_for=300):
    job = mock.batch_job()
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].config = {"run_for_s": run_for}
    return codec.encode(job), job


# ---------------------------------------------------------------------------
# WatchHub
# ---------------------------------------------------------------------------


class TestWatchHub:
    def test_coalesced_wake_delivers_to_all_waiters_once(self):
        """K same-shape waiters, one write: every waiter wakes exactly
        once, and the shape's result index is evaluated once per commit
        batch — not once per waiter (the whole point of the hub)."""
        state = StateStore()
        hub = WatchHub(state, SystemClock())
        idx = state.latest_index()
        k = 8
        results = []
        lock = threading.Lock()

        def block():
            got = hub.block(("nodes",), state.latest_index, idx, wait=10)
            with lock:
                results.append(got)

        threads = [threading.Thread(target=block, daemon=True)
                   for _ in range(k)]
        for t in threads:
            t.start()
        _wait(lambda: hub.stats()["waiters"] == k, timeout=5)
        state.upsert_node(Node())
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert results == [True] * k
        st = hub.stats()
        assert st["wakes"] == k
        # one evaluation per commit batch, shared by all K waiters (a
        # couple of batches can race the thread starts; never one-per-K)
        assert st["evals"] <= 4
        assert st["coalesced"] > 0
        # shapes drain with their waiters (no leak of parked conditions)
        assert st["shapes"] == 0 and st["waiters"] == 0

    def test_unrelated_result_index_rides_timeout(self):
        """A store write that does NOT raise the watched result index
        (a deletion, or an unrelated table) must not wake the watcher —
        it rides the wait timeout (reference blockingRPC semantics)."""
        state = StateStore()
        hub = WatchHub(state, SystemClock())
        idx = 7
        done = []

        def block():
            # result index pinned at the caller's index: nothing the
            # store commits can raise it (the deletion-only shape)
            done.append(hub.block(("pinned",), lambda: idx, idx, wait=1.0))

        t = threading.Thread(target=block, daemon=True)
        t.start()
        _wait(lambda: hub.stats()["waiters"] == 1, timeout=5)
        state.upsert_node(Node())       # advances latest_index only
        t.join(timeout=10)
        assert not t.is_alive()
        assert done == [False]
        assert hub.stats()["timeouts"] == 1

    def test_immediate_return_when_already_past(self):
        state = StateStore()
        state.upsert_node(Node())
        hub = WatchHub(state, SystemClock())
        assert hub.block(("nodes",), state.latest_index, 0, wait=5) is True
        assert hub.stats()["evals"] == 1


# ---------------------------------------------------------------------------
# EventRing + cursor subscriptions
# ---------------------------------------------------------------------------


class TestEventRing:
    def test_cursor_replay_from_index(self):
        """A late subscriber seeks by index and replays ring history."""
        broker = EventBroker()
        store = StateStore()
        broker.attach(store)
        n1 = store.upsert_node(Node())
        store.upsert_node(Node())
        sub = broker.subscribe({"Node": ["*"]}, from_index=0)
        got = [sub.next(timeout=1), sub.next(timeout=1)]
        assert all(e is not None for e in got)
        assert [e.index for e in got] == sorted(e.index for e in got)
        assert got[0].index == n1
        # replay from the middle skips the first commit
        sub2 = broker.subscribe({"Node": ["*"]}, from_index=n1)
        ev = sub2.next(timeout=1)
        assert ev is not None and ev.index > n1
        broker.close()

    def test_slow_cursor_drop_accounting(self):
        """A cursor that falls off a small ring counts every lost event
        into its ledger and nomad.stream.dropped — and never blocks the
        publisher (the appends below happen with the sub parked)."""
        before = REGISTRY.counter("nomad.stream.dropped")
        broker = EventBroker(buffer_size=4)
        store = StateStore()
        broker.attach(store)
        sub = broker.subscribe({"Node": ["*"]}, from_index=0)
        n = 12
        for _ in range(n):
            store.upsert_node(Node())
        # ring holds 4 entries; the cursor at seq 0 lost the rest
        evs = []
        while True:
            ev = sub.next(timeout=0.2)
            if ev is None:
                break
            evs.append(ev)
        assert len(evs) == 4
        assert sub.dropped == n - 4
        assert broker.stats()["DroppedTotal"] == n - 4
        assert REGISTRY.counter("nomad.stream.dropped") - before == n - 4
        assert sub.stats()["Dropped"] == n - 4
        broker.close()

    def test_trim_accounts_unexpanded_entries(self):
        """Drop accounting is exact even for entries trimmed before any
        reader expanded them (the O(1) append-time count ledger)."""
        ring = EventRing(capacity=2)
        for i in range(6):
            ring.append("Node", i + 1, object(), count=3)
        st = ring.stats()
        assert st["entries"] == 2
        # 4 trimmed entries x 3 events each sit below the cum base
        probe = ring.fetch(0)
        assert probe[0] == "behind"
        assert probe[2] == 12        # cum_base

    def test_close_wakes_parked_consumer(self):
        broker = EventBroker()
        out = []

        sub = broker.subscribe({"Node": ["*"]})

        def consume():
            out.append(sub.next(timeout=30))

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        time.sleep(0.2)
        broker.close()
        t.join(timeout=5)
        assert not t.is_alive()
        assert out == [None]


# ---------------------------------------------------------------------------
# The topic check before expansion (core/stream.py Subscription._scan)
# ---------------------------------------------------------------------------

_BLOCK_IDS = [f"block-alloc-{i:03d}" for i in range(260)]
_ROW_ALLOCS = [mock.alloc() for _ in range(3)]
_BLOCKED_EVAL = mock.eval(status="blocked", job_id="job-blocked")
_COMPLETE_EVAL = mock.eval(status="complete", job_id="job-done")
_BREACH = {"Rule": "eval_p99_s", "Observed": 2.0}


def _mixed_commits():
    """What a store (and the health watchdog) hands the broker, in
    commit order, as (topic, index, payload)."""
    return [
        ("AllocBlock", 1, AllocBlock(id="blk", ids=list(_BLOCK_IDS))),
        ("Allocations", 2, list(_ROW_ALLOCS)),
        ("Evaluation", 3, _BLOCKED_EVAL),
        ("Evaluation", 4, _COMPLETE_EVAL),
        ("Job", 5, ("default", "job-gone")),
        ("HealthBreach", 6, _BREACH),
        ("PlanResult", 7, PlanResult()),          # the broker ignores it
    ]


def _reference_events(commits, topics, after_index=0):
    """The stream's contract written plainly: expand EVERY commit into
    its events, filter after (what `_scan` did before it looked at an
    entry's topic first).  Events as (topic, type, key, index, payload)."""
    events = []
    for topic, index, payload in commits:
        if index <= after_index:
            continue
        if topic == "AllocBlock":
            events += [("Allocation", "AllocationUpdated", aid, index, None)
                       for aid in payload.ids]
        elif topic == "Allocations":
            events += [("Allocation", "AllocationUpdated", a.id, index, None)
                       for a in payload]
        elif topic == "Evaluation":
            events.append(("Evaluation", "EvaluationUpdated", payload.id,
                           index, payload))
            if payload.status == "blocked":
                events.append(("PlacementFailure", "PlacementFailure",
                               payload.job_id, index, payload))
        elif topic == "Job":
            events.append(("Job", "JobDeregistered", payload[-1], index,
                           None))
        elif topic == "HealthBreach":
            events.append(("HealthBreach", "HealthBreach", payload["Rule"],
                           index, payload))
    return [ev for ev in events
            if any(t in ("*", ev[0])
                   and (not keys or "*" in keys or ev[2] in keys)
                   for t, keys in topics.items())]


def _publish(broker, commits):
    for topic, index, payload in commits:
        broker._on_state_event(topic, index, payload)


def _drain(sub):
    out = []
    while True:
        ev = sub.next(timeout=0.05)
        if ev is None:
            return out
        out.append((ev.topic, ev.type, ev.key, ev.index, ev.payload))


def _skipped_allocs():
    return REGISTRY.counter("nomad.stream.entries_skipped",
                            topic="Allocations")


# subscription, ring entries it steps over unexpanded (of the 6 kept)
_SUBSCRIPTIONS = {
    "evaluation": ({"Evaluation": ["*"]}, 4),
    "allocation": ({"Allocation": ["*"]}, 4),
    "wildcard": ({"*": ["*"]}, 0),
    "placement_failure": ({"PlacementFailure": ["*"]}, 4),
    "allocation_key": ({"Allocation": [_BLOCK_IDS[17]]}, 4),
    "job_and_evaluation": ({"Job": ["*"], "Evaluation": ["*"]}, 3),
}


class TestTopicSkip:
    @pytest.mark.parametrize("name", sorted(_SUBSCRIPTIONS))
    def test_delivers_what_expand_then_filter_delivers(self, name):
        topics, n_skipped = _SUBSCRIPTIONS[name]
        commits = _mixed_commits()
        broker = EventBroker()
        skipped_allocs = _skipped_allocs()
        sub = broker.subscribe(topics, from_index=0)
        _publish(broker, commits)
        want = _reference_events(commits, topics)
        assert want, "every subscription here is owed something"
        assert _drain(sub) == want
        entries = list(broker._ring._entries)
        assert [e.topic for e in entries] == [
            "Allocations", "Allocations", "Evaluation", "Evaluation",
            "Job", "HealthBreach"]
        st = broker.stats()
        assert st["EntriesSkipped"] == n_skipped
        assert st["EntriesExpanded"] == len(entries) - n_skipped
        assert sum(e.expanded is None for e in entries) == n_skipped
        asks_allocs = bool({"*", "Allocation"} & set(topics))
        for e in entries[:2]:
            # no Event object was ever built for a subscriber that did
            # not ask for allocations
            assert (e.expanded is not None) == asks_allocs
        assert (_skipped_allocs() - skipped_allocs
                == (0 if asks_allocs else 2))
        # a second subscriber of the same shape re-uses the cached
        # expansions: one expansion an entry, however many readers
        cached = [e.expanded for e in entries]
        sub2 = broker.subscribe(topics, from_index=0)
        assert _drain(sub2) == want
        assert all(a is b for a, b in
                   zip(cached, (e.expanded for e in entries)))
        assert broker.stats()["EntriesExpanded"] == len(entries) - n_skipped
        assert broker.stats()["EntriesSkipped"] == 2 * n_skipped
        broker.close()

    def test_drop_ledger_counts_stepped_over_entries(self):
        """Two cursors lag off a small ring, one stepping over the alloc
        entries and one expanding them: both lose, and count, the same
        events — the arithmetic on the entries' append-time counts."""
        cap = 4
        broker = EventBroker(buffer_size=cap)
        before = REGISTRY.counter("nomad.stream.dropped")
        subs = {"evaluation": broker.subscribe({"Evaluation": ["*"]}),
                "wildcard": broker.subscribe()}
        head = [("AllocBlock", 1, AllocBlock(id="b0", ids=list(_BLOCK_IDS))),
                ("Evaluation", 2, _COMPLETE_EVAL)]
        _publish(broker, head)
        for name, sub in subs.items():
            assert _drain(sub) == _reference_events(head, sub.topics), name
            assert sub.dropped == 0
        # both cursors now sit past the block; lag them
        tail = []
        for i in range(5):
            tail += [("AllocBlock", 10 + 2 * i,
                      AllocBlock(id=f"b{i + 1}", ids=list(_BLOCK_IDS))),
                     ("Evaluation", 11 + 2 * i,
                      _BLOCKED_EVAL if i % 2 else _COMPLETE_EVAL)]
        _publish(broker, tail)
        counts = [260 if t == "AllocBlock"
                  else 2 if p.status == "blocked" else 1
                  for t, _, p in tail]
        lost = sum(counts[:-cap])
        assert lost == 3 * 260 + 1 + 2 + 1
        for name, sub in subs.items():
            assert (_drain(sub)
                    == _reference_events(tail[-cap:], sub.topics)), name
            assert sub.dropped == lost, name
            assert sub.stats()["Dropped"] == lost
        assert broker.stats()["DroppedTotal"] == 2 * lost
        assert REGISTRY.counter("nomad.stream.dropped") - before == 2 * lost
        broker.close()

    @pytest.mark.parametrize("name", ["evaluation", "placement_failure",
                                      "wildcard"])
    def test_replay_from_index(self, name):
        """A late subscriber's replay is the same whether the entries
        it seeks over were never expanded or already are."""
        topics, _ = _SUBSCRIPTIONS[name]
        commits = _mixed_commits()
        broker = EventBroker()
        _publish(broker, commits)
        for after in (0, 2, 3):
            want = _reference_events(commits, topics, after_index=after)
            assert _drain(broker.subscribe(topics, from_index=after)) == want
        _drain(broker.subscribe())           # expands every entry
        for after in (0, 2, 3):
            want = _reference_events(commits, topics, after_index=after)
            assert _drain(broker.subscribe(topics, from_index=after)) == want
        broker.close()


_LOCKSTEP_CASES = {
    "Node": ("Node", Node()),
    "Node-deregistered": ("Node", "node-1"),
    "Job": ("Job", mock.job()),
    "Job-deregistered": ("Job", ("default", "job-1")),
    "Evaluation": ("Evaluation", _COMPLETE_EVAL),
    "Evaluation-blocked": ("Evaluation", _BLOCKED_EVAL),
    "Evaluation-deleted": ("Evaluation", "eval-1"),
    "Allocations-ids": ("Allocations", stream._AllocIds(list(_BLOCK_IDS))),
    "Allocations-rows": ("Allocations", list(_ROW_ALLOCS)),
    "Deployment": ("Deployment", Deployment()),
    "HealthBreach": ("HealthBreach", _BREACH),
}


@pytest.mark.parametrize("case", sorted(_LOCKSTEP_CASES))
def test_event_topics_table_in_lockstep(case):
    """`_EVENT_TOPICS` is what a subscription trusts when it steps over
    an entry unexpanded, and `_expected_count` what the drop ledger
    trusts: both must say what `_expand` does, for every topic."""
    assert set(stream._EVENT_TOPICS) == set(stream._TYPE_BY_TOPIC)
    assert {t for t, _ in _LOCKSTEP_CASES.values()} == set(
        stream._TYPE_BY_TOPIC), "a topic without a case here"
    topic, payload = _LOCKSTEP_CASES[case]
    events = stream._expand(topic, 9, payload)
    assert len(events) == stream._expected_count(topic, payload) >= 1
    names = stream._EVENT_TOPICS[topic]
    got = [e.topic for e in events]
    if topic == "Allocations":
        assert set(got) == set(names)
    elif case == "Evaluation-blocked":
        assert got == list(names) and len(names) == 2
    else:
        assert got == [names[0]]
    assert all(e.index == 9 for e in events)


# ---------------------------------------------------------------------------
# HTTP plane: hub-backed blocking + columnar lists
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def agent():
    ag = Agent(num_clients=2, num_workers=1, heartbeat_ttl=3600)
    ag.start()
    yield ag
    ag.shutdown()


@pytest.fixture(scope="module")
def api(agent):
    return APIClient(address=agent.address)


class TestHTTPFanout:
    def test_http_waiters_coalesce_on_one_shape(self, api, agent):
        hub = agent.server.watch_hub
        before = hub.stats()
        idx = agent.server.state.latest_index()
        k = 6
        results = []
        lock = threading.Lock()

        def blocked():
            out = api.request("GET", "/v1/jobs",
                              params={"index": idx, "wait": 10})
            with lock:
                results.append(out)

        threads = [threading.Thread(target=blocked, daemon=True)
                   for _ in range(k)]
        for t in threads:
            t.start()
        _wait(lambda: hub.stats()["waiters"] - before["waiters"] >= k,
              timeout=5)
        wire, job = _wire_batch_job()
        api.jobs.register(wire)
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert len(results) == k
        assert all(any(s["ID"] == job.id for s in out) for out in results)
        after = hub.stats()
        assert after["wakes"] - before["wakes"] >= k
        # K HTTP clients shared O(1) evaluations, not one each
        assert after["evals"] - before["evals"] < k
        api.jobs.deregister(job.id, purge=True)

    def test_deletion_only_change_rides_timeout(self, api, agent):
        wire, job = _wire_batch_job()
        api.jobs.register(wire)
        jobs = api.request("GET", "/v1/jobs")
        result_idx = max(s["ModifyIndex"] for s in jobs)
        api.jobs.deregister(job.id, purge=True)
        _wait(lambda: all(s["ID"] != job.id
                          for s in api.request("GET", "/v1/jobs")))
        # the purge advanced the STORE index but lowered the jobs result
        # index — a blocked watcher must ride the timeout, not wake
        t0 = time.perf_counter()
        api.request("GET", "/v1/jobs",
                    params={"index": result_idx, "wait": 1})
        assert time.perf_counter() - t0 >= 0.9

    def test_columnar_allocations_list(self, api, agent):
        wire, job = _wire_batch_job(count=4)
        api.jobs.register(wire)
        rows = _wait(lambda: [a for a in api.request(
            "GET", "/v1/allocations") if a["JobID"] == job.id])
        assert len(rows) >= 4
        out = api.request("GET", "/v1/allocations",
                          params={"columnar": "true"})
        assert out["Columnar"] is True
        cols = out["Columns"]
        assert out["Count"] == len(cols["ID"]) == len(cols["Name"])
        assert set(cols) == {"ID", "Name", "JobID", "NodeID",
                             "ClientStatus", "ModifyIndex"}
        flat = api.request("GET", "/v1/allocations")
        assert sorted(cols["ID"]) == sorted(a["ID"] for a in flat)
        by_id = {a["ID"]: a for a in flat}
        for i, aid in enumerate(cols["ID"]):
            assert cols["Name"][i] == by_id[aid]["Name"]
            assert cols["JobID"][i] == by_id[aid]["JobID"]

    def test_debug_bundle_has_fanout_sections(self, api):
        dbg = api.request("GET", "/v1/operator/debug")
        assert "WatchHub" in dbg and "EventBroker" in dbg
        assert "Follower" in dbg
        assert dbg["EventBroker"]["Ring"]["next_seq"] >= 0


# ---------------------------------------------------------------------------
# ReadFollower: replicated reads, headers, proxying, failover
# ---------------------------------------------------------------------------


class TestReadFollower:
    def test_follower_serves_reads_headers_and_proxies_writes(self):
        leader = Agent(num_clients=1, num_workers=1,
                       heartbeat_ttl=3600).start()
        fol = Agent(num_clients=0, num_workers=1, heartbeat_ttl=3600,
                    follow=leader.address).start()
        try:
            api = APIClient(address=leader.address)
            fapi = APIClient(address=fol.address)
            wire, job = _wire_batch_job()
            api.jobs.register(wire)
            # replicated read served locally by the follower
            assert _wait(lambda: any(
                s["ID"] == job.id for s in fapi.jobs.list()), timeout=15)
            # consistency headers on follower responses
            import urllib.request
            with urllib.request.urlopen(fol.address + "/v1/jobs",
                                        timeout=5) as r:
                assert r.headers["X-Nomad-KnownLeader"] == "true"
                assert int(r.headers["X-Nomad-LastContact"]) >= 0
            # a write through the follower proxies to the upstream
            wire2, job2 = _wire_batch_job()
            resp = fapi.jobs.register(wire2)
            assert resp["EvalID"]
            assert _wait(lambda: any(
                s["ID"] == job2.id for s in api.jobs.list()))
            # ?stale=false forces the leader round-trip too
            out = fapi.request("GET", "/v1/jobs",
                               params={"stale": "false"})
            assert any(s["ID"] == job2.id for s in out)
            st = fol.follower.stats()
            assert st["known_leader"] and st["failures"] == 0
        finally:
            fol.shutdown()
            leader.shutdown()

    def test_follow_excludes_cluster_mode(self):
        with pytest.raises(ValueError):
            Agent(follow="http://127.0.0.1:1", bootstrap_expect=3)

    def test_no_stale_reads_across_failover(self):
        """Chaos scenario: the follower's upstream dies and the next
        candidate is BEHIND the index the follower already served.  The
        follower must skip the lagging upstream (reads never regress)
        and only resume applying once the candidate catches up past its
        head — monotonic stale-bounded reads across failover."""
        a = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
        b = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
        fol = Agent(num_clients=0, num_workers=1, heartbeat_ttl=3600,
                    follow=f"{a.address},{b.address}").start()
        observed = []
        stop = threading.Event()

        def watch_index():
            while not stop.is_set():
                observed.append(fol.server.state.latest_index())
                time.sleep(0.02)

        t = threading.Thread(target=watch_index, daemon=True)
        t.start()
        try:
            api_a = APIClient(address=a.address)
            for _ in range(3):
                wire, _ = _wire_batch_job()
                api_a.jobs.register(wire)
            head = a.server.state.latest_index()
            assert _wait(
                lambda: fol.server.state.latest_index() >= head, timeout=15)
            # kill the leader; candidate B is far behind the follower
            a.shutdown()
            assert b.server.state.latest_index() < head
            assert _wait(lambda: fol.follower.skipped_regressions > 0,
                         timeout=15), "lagging upstream was not skipped"
            assert fol.server.state.latest_index() >= head
            # B catches up past the follower's head -> tail resumes
            api_b = APIClient(address=b.address)
            while b.server.state.latest_index() <= head:
                wire, _ = _wire_batch_job()
                api_b.jobs.register(wire)
            new_head = b.server.state.latest_index()
            assert _wait(
                lambda: fol.server.state.latest_index() >= new_head,
                timeout=15), "follower never resumed from the new leader"
            # flag is set just after the apply inside the same pull —
            # poll rather than racing that window
            assert _wait(lambda: fol.follower.stats()["known_leader"],
                         timeout=10)
        finally:
            stop.set()
            t.join(timeout=5)
            fol.shutdown()
            b.shutdown()
        # the local index NEVER regressed at any sampled instant
        assert observed == sorted(observed), \
            "follower served a regressed index during failover"
