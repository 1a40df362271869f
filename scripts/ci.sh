#!/usr/bin/env bash
# CI pipeline — the single command that reproduces CI locally
# (reference: .github/workflows/test-core.yaml).  Stages:
#   lint     — scripts/lint.py (AST checks: syntax, unused imports,
#              stray prints, whitespace; no external linters required)
#   analyze  — scripts/analyze.py (scripts/analysis/ package): the
#              eight project-invariant passes (lock discipline,
#              COW/snapshot isolation, JAX purity/donation, thread
#              hygiene, injected-timebase, lock-order graph +
#              blocking-under-lock, canonical-plane determinism, wire
#              proto/struct drift); selftest first (each pass must
#              catch its injected violations), then a repo-wide clean
#              run with stale-suppression accounting strict and the
#              findings archived as JSON
#   test     — the full pytest suite on the 8-virtual-device CPU mesh
#              (tests/conftest.py forces JAX_PLATFORMS=cpu +
#              xla_force_host_platform_device_count=8, so the sharded
#              kernels run everywhere)
#   smoke    — bench.py at reduced scale on the CPU backend: the whole
#              broker -> batched-worker -> plan-queue -> applier
#              pipeline must place every alloc (the run asserts
#              completeness internally; a scheduling regression fails
#              the run)
#   soak     — virtual-time production soak (chaos/soak.py): a seeded
#              cluster-day replayed through the real HTTP API on a
#              VirtualClock, byte-identical on same-seed replay, gated
#              on chaos invariants AND live SLOs (zero watchdog
#              breaches, p99 plan-queue, zone balance / fill gauges)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint selftest (injected undefined name must be caught) =="
python scripts/lint.py --selftest

echo "== lint =="
# covers every file under nomad_tpu/ (core/wavepipe.py included),
# tests/, scripts/, bench.py
python scripts/lint.py

echo "== analyze selftest (each pass must catch its injected violations) =="
python scripts/analyze.py --selftest

echo "== analyze (lock/cow/purity/thread/rawtime/lockorder/determinism/wireproto) =="
python scripts/analyze.py --strict-suppressions --json analyze_findings.json

echo "== wavepipe fast smoke (pipelined engine, CPU mesh) =="
# the async dispatch/collect path first and fast: a regression in the
# wave pipeline (chained launches, refute-repair, columnar commit)
# fails tier-1 here in seconds instead of deep in the full suite
python -m pytest tests/test_wavepipe.py -q -m 'not slow'

echo "== tests (8-virtual-device CPU mesh, tier-1: not slow) =="
python -m pytest tests/ -q -m 'not slow'

echo "== telemetry smoke (dev agent: prometheus scrape + trace fetch) =="
# boot a real dev agent over HTTP, run one job, validate the prometheus
# exposition grammar, and fetch the job's eval trace — the end-to-end
# observability contract (core/telemetry.py) in one pass
JAX_PLATFORMS=cpu python - <<'EOF'
import re
import time

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.structs import codec

agent = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
api = APIClient(address=agent.address)
try:
    job = mock.batch_job()
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].config = {"run_for_s": 120}
    eval_id = api.jobs.register(codec.encode(job))["EvalID"]
    assert eval_id, "register returned no eval"

    want = {"eval", "broker.wait", "worker.schedule",
            "plan.queue_wait", "plan.apply", "client.alloc_start"}
    deadline = time.time() + 30
    names = set()
    while time.time() < deadline and not want <= names:
        try:
            names = {s["Name"] for s in api.agent.trace(eval_id)["Spans"]}
        except Exception:
            pass
        time.sleep(0.2)
    assert want <= names, f"trace incomplete: {sorted(names)}"

    text = api.agent.metrics(format="prometheus")
    type_re = re.compile(
        r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})?'
        r' -?[0-9]+(\.[0-9]+)?([eE][-+][0-9]+)?$')
    n = 0
    for line in text.strip().splitlines():
        ok = (type_re.match(line) if line.startswith("#")
              else sample_re.match(line))
        assert ok, f"bad exposition line: {line!r}"
        n += 1
    for fam in ("nomad_broker_wait_seconds_bucket",
                "nomad_worker_schedule_seconds_p99",
                "nomad_plan_apply_seconds_sum"):
        assert fam in text, f"missing family {fam}"

    # placement explainability: an unplaceable job must explain WHICH
    # dimension blocked it via /v1/eval/<id>/explain, and the quality
    # gauges must ride the same exposition (ISSUE 5)
    huge = mock.batch_job()
    huge.task_groups[0].count = 1
    huge.task_groups[0].tasks[0].resources.memory_mb = 1 << 24
    huge_eval = api.jobs.register(codec.encode(huge))["EvalID"]
    deadline = time.time() + 30
    doc = {}
    while time.time() < deadline and not doc.get("BlockedEval"):
        doc = api.evaluations.explain(huge_eval)
        time.sleep(0.2)
    assert doc.get("BlockedEval"), f"never blocked: {doc}"
    tg = doc["TaskGroups"][huge.task_groups[0].name]
    assert tg["Metric"]["DimensionExhausted"].get("memory"), doc
    assert "memory" in tg["Cause"], doc
    pf = api.jobs.placement_failures(huge.id)
    assert pf["Blocked"] and "memory" in pf["Cause"], pf
    text = api.agent.metrics(format="prometheus")
    for fam in ("nomad_quality_nodes_in_use",
                "nomad_quality_zone_balance_max_over_min",
                "nomad_quality_binpack_fill"):
        assert fam in text, f"missing quality family {fam}"
    print(f"explain smoke ok: eval {huge_eval[:8]} blocked on "
          f"{sorted(tg['Metric']['DimensionExhausted'])}")

    # memory ledger rides the same observability contract (ISSUE 19):
    # the operator doc, the debug bundle's Memory + unified Evictions
    # keys, and the nomad.mem.* families in the exposition
    mem = api.operator.memory()
    assert mem["Schema"] == "nomad-tpu.memory.v1", mem
    assert mem["RSSBytes"] > 0 and mem["TrackedBytes"] > 0, mem
    assert {"state", "journal", "flight"} <= set(mem["Planes"]), mem
    dbg = api.operator.debug()
    assert dbg["Memory"]["RSSBytes"] > 0, sorted(dbg)
    assert "journal" in dbg["Evictions"], sorted(dbg["Evictions"])
    text = api.agent.metrics(format="prometheus")
    for fam in ("nomad_mem_rss_bytes", "nomad_mem_plane_bytes"):
        assert fam in text, f"missing memory family {fam}"
    print(f"memory smoke ok: rss={mem['RSSBytes']} "
          f"tracked={mem['TrackedBytes']} planes={len(mem['Planes'])}")
    print(f"telemetry smoke ok: {n} exposition lines, trace {eval_id[:8]}"
          f" spans={sorted(names)}")
finally:
    agent.shutdown()
EOF

echo "== health smoke (unmeetable SLO -> breach + dump bundle) =="
# the dump-on-anomaly plane (core/flightrec.py): boot a dev agent with
# a deliberately-unmeetable plan-queue SLO, drive a workload, and
# assert /v1/operator/health reports the breach, the retained dump
# bundle validates against the schema, and the HealthBreach event
# replays from the stream buffer
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import time
import urllib.request

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.structs import codec

agent = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600,
              slo={"p99_plan_queue_ms": 1e-9, "interval_s": 0.0}).start()
api = APIClient(address=agent.address)
try:
    job = mock.batch_job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].config = {"run_for_s": 120}
    api.jobs.register(codec.encode(job))
    deadline = time.time() + 30
    doc = {}
    while time.time() < deadline:
        doc = api.operator.health(dumps=True)
        if not doc["Healthy"]:
            break
        time.sleep(0.2)
    assert not doc["Healthy"], doc
    bad = {r["Rule"] for r in doc["Rules"] if not r["Ok"]}
    assert "p99_plan_queue_ms" in bad, doc["Rules"]
    bundles = doc["DumpBundles"]
    assert bundles, "breach produced no dump bundle"
    for key in ("Schema", "At", "Breaches", "Verdicts", "SLO",
                "FlightRecorder", "Windows", "Traces", "Spans", "Logs"):
        assert key in bundles[0], sorted(bundles[0])
    assert bundles[0]["Schema"] == "nomad-tpu.health-dump.v1"
    assert any(b["Rule"] == "p99_plan_queue_ms"
               for b in bundles[0]["Breaches"])
    assert "nomad.plan.queue_wait_s" in bundles[0]["Windows"]
    assert bundles[0]["FlightRecorder"]["Evals"], "flight ring empty"
    # the breach rode the event stream: replay from the buffer
    url = agent.address + "/v1/event/stream?topic=HealthBreach:*&index=0"
    got = None
    with urllib.request.urlopen(url, timeout=10) as resp:
        for line in resp:
            line = line.strip()
            if not line or line == b"{}":
                continue
            for e in json.loads(line).get("Events", []):
                if e["Topic"] == "HealthBreach":
                    got = e
                    break
            if got:
                break
    assert got and got["Key"] == "p99_plan_queue_ms", got
    # the CLI verdict exits non-zero on breach (scriptable health check)
    from nomad_tpu.cli import main
    rc = main(["-address", agent.address, "health"])
    assert rc == 1, rc
    print(f"health smoke ok: breach={sorted(bad)} "
          f"dumps={len(bundles)} event={got['Key']}")
finally:
    agent.shutdown()
EOF

echo "== executor smoke (device-resident worker loop, jax backend) =="
# boot a dev agent on the default JAX device executor, push a
# multi-wave workload through the REAL eval-driven path, and assert
# the resident usage chain actually carried across waves
# (nomad.executor.resident_waves > 0) — plus a scoped run of the
# invariant analyzer's JAX purity/donation pass over the new module
JAX_PLATFORMS=cpu python - <<'EOF'
import pathlib
import sys
import time

sys.path.insert(0, "scripts")
from analyze import analyze_source

src = pathlib.Path("nomad_tpu/ops/executor.py").read_text()
findings = analyze_source(src, path="nomad_tpu/ops/executor.py",
                          passes=("purity",))
assert not findings, f"purity/donation findings in executor: {findings}"

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.structs import codec

agent = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
api = APIClient(address=agent.address)
try:
    def wave():
        evals = []
        for _ in range(8):
            job = mock.batch_job()
            tg = job.task_groups[0]
            tg.count = 2
            # long-running tasks: completions would free capacity and
            # (correctly) invalidate the chain mid-smoke
            tg.tasks[0].config = {"run_for_s": 300}
            tg.tasks[0].resources.cpu = 20
            tg.tasks[0].resources.memory_mb = 16
            evals.append(api.jobs.register(codec.encode(job))["EvalID"])
        deadline = time.time() + 30
        while time.time() < deadline:
            done = sum(1 for e in evals
                       if api.evaluations.info(e).get("Status")
                       in ("complete", "failed"))
            if done == len(evals):
                return
            time.sleep(0.1)
        raise AssertionError("executor smoke wave never completed")

    resident = 0
    for _ in range(4):          # multi-wave; stop at the first chain hit
        wave()
        m = api.agent.metrics()
        resident = m.get("nomad.executor.resident_waves", 0)
        if resident > 0:
            break
    assert resident > 0, (
        "no launch rode the resident chain: "
        f"{ {k: v for k, v in m.items() if 'executor' in k} }")
    assert m.get("nomad.executor.uploads", 0) > 0
    print(f"executor smoke ok: resident_waves={resident} "
          f"uploads={m['nomad.executor.uploads']} "
          f"upload_bytes={m['nomad.executor.upload_bytes']}")
finally:
    agent.shutdown()
EOF

echo "== profile smoke (continuous profiling plane, capture bundle) =="
# boot a dev agent under load, take a short on-demand capture through
# POST /v1/operator/profile, and validate the bundle schema: compile
# ledger populated (the agent just compiled its kernels), HBM
# watermark nonzero, h2d split by cause, >= 90% of sampled thread
# time in a named bucket, sampler overhead within the 2% budget
JAX_PLATFORMS=cpu python - <<'EOF'
import time

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.structs import codec

agent = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
api = APIClient(address=agent.address)
try:
    evals = []
    for _ in range(8):
        job = mock.batch_job()
        tg = job.task_groups[0]
        tg.count = 2
        tg.tasks[0].config = {"run_for_s": 300}
        tg.tasks[0].resources.cpu = 20
        tg.tasks[0].resources.memory_mb = 16
        evals.append(api.jobs.register(codec.encode(job))["EvalID"])
    deadline = time.time() + 30
    while time.time() < deadline:
        done = sum(1 for e in evals
                   if api.evaluations.info(e).get("Status")
                   in ("complete", "failed"))
        if done == len(evals):
            break
        time.sleep(0.1)

    st = api.operator.profile_status()
    assert st["running"], "sampler must be always-on by default"
    b = api.operator.profile(duration_s=1.5)
    assert b["schema"] == "nomad-tpu.profile.v1", b["schema"]
    assert b["samples"] > 0, b["samples"]
    assert b["attributed_fraction"] >= 0.90, b["attributed_fraction"]
    assert b["overhead_fraction"] <= 0.02, b["overhead_fraction"]
    comp = b["compile_ledger"]
    assert comp["misses"] > 0 and comp["sites"], comp
    led = b["device_ledger"]
    assert led and led["hbm_high_watermark_bytes"] > 0, led
    assert led["upload_bytes_by_cause"], led
    assert b["folded"], "capture carried no folded stacks"
    assert b["flight_recorder"] is not None
    # retained + addressable by id, and folded into the debug bundle
    assert api.operator.profile_capture(b["id"])["id"] == b["id"]
    dbg = api.operator.debug()
    assert "Profiler" in dbg and "DeviceLedger" in dbg, sorted(dbg)
    print(f"profile smoke ok: {b['id']} samples={b['samples']} "
          f"attributed={b['attributed_fraction']:.3f} "
          f"overhead={b['overhead_fraction']:.5f} "
          f"compile_sites={len(comp['sites'])} "
          f"hbm_watermark={led['hbm_high_watermark_bytes']}")
finally:
    agent.shutdown()
EOF

echo "== timeline smoke (retrospective plane: breach post-mortem + HTTP) =="
# the retrospective timeline plane (core/timeline.py): a seeded
# flap-storm soak with a zero-tolerance heartbeat SLO must produce a
# breach whose post-mortem report pins the storm's own traffic.node.*
# annotation (not merely the nearest-in-time noise); then a live dev
# agent must serve clock-aligned history over GET /v1/operator/timeline
# and render it through `nomad timeline` / `nomad report`
JAX_PLATFORMS=cpu python - <<'EOF'
from nomad_tpu.chaos.soak import run_soak
from nomad_tpu.chaos.traffic import TrafficProfile
from nomad_tpu.core.timeline import build_report, render_report_md

r = run_soak(seed=7, profile=TrafficProfile(
    hours=0.05, n_nodes=4, n_zones=2, service_per_hour=40,
    batch_per_hour=40, drains_per_hour=0.0, flap_storms_per_hour=20.0,
    flap_storm_nodes=2, preempt_storms_per_hour=0.0,
    chaos_scenarios=()), slo={"heartbeat_misses": 0.0})
rep = build_report(r.timeline)
breaches = [i for i in rep["Incidents"]
            if i["Kind"] == "breach" and i["Rule"] == "heartbeat_misses"]
assert breaches, rep["AnnotationKinds"]
attributed = [a for i in breaches for a in i["Attribution"]]
assert any(a["Kind"].startswith("traffic.node.")
           for a in attributed), attributed
md = render_report_md(rep)
assert "heartbeat_misses" in md and "traffic.node." in md
assert len(r.summary["timeline_digest"]) == 64
print(f"timeline report smoke ok: {len(breaches)} heartbeat breach(es)"
      f" attributed to the flap storm, digest"
      f" {r.summary['timeline_digest'][:16]}")
EOF
JAX_PLATFORMS=cpu python - <<'EOF'
import time

from nomad_tpu import mock
from nomad_tpu.agent import Agent
from nomad_tpu.api.client import APIClient
from nomad_tpu.cli import main
from nomad_tpu.structs import codec

agent = Agent(num_clients=1, num_workers=1, heartbeat_ttl=3600).start()
api = APIClient(address=agent.address)
try:
    job = mock.batch_job()
    job.task_groups[0].count = 2
    api.jobs.register(codec.encode(job))
    deadline = time.time() + 30
    doc = {}
    while time.time() < deadline:
        doc = api.operator.timeline()
        if doc["Points"] > 1 and doc["Annotations"]:
            break
        time.sleep(0.2)
    assert doc["Schema"] == "nomad-tpu.timeline.v1", doc["Schema"]
    assert doc["Points"] > 1, doc
    kinds = {a["Kind"] for a in doc["Annotations"]}
    assert "leadership.established" in kinds, sorted(kinds)
    sub = api.operator.timeline(series=["evals_per_s"], step=5.0)
    assert set(sub["Series"]) == {"evals_per_s"}, sorted(sub["Series"])
    assert "Timeline" in api.operator.debug(), "debug bundle lost it"
    assert main(["-address", agent.address, "timeline"]) == 0
    assert main(["-address", agent.address, "report"]) == 0
    print(f"timeline http smoke ok: {doc['Points']} points,"
          f" kinds={sorted(kinds)}")
finally:
    agent.shutdown()
EOF

echo "== perfcheck (trajectory gate comparator, self-check) =="
# the bench/soak tolerance-band comparator must pass the checked-in
# baselines against themselves and catch injected regressions before
# anything trusts its verdicts (the analyze.py --selftest posture)
python scripts/perfcheck.py --self-check

echo "== multichip (8-device virtual mesh: parity, scale soak, bench) =="
# the sharded production path (ISSUE 7): engine-level sharded-vs-single
# parity + padded-row properties, the resident-chain sharded parity
# suite, the >=200k-node quality soak, then a 64k-node sharded bench
# smoke that must report the full 8-way mesh with zero plan refutes.
# (pytest runs already ride the 8-virtual-device mesh via conftest;
# bench.py forces it itself with --mesh 8.)
JAX_PLATFORMS=cpu python -m pytest tests/test_engine_sharded.py -q
JAX_PLATFORMS=cpu python -m pytest tests/test_wavepipe.py -q \
    -k "Resident or Sharded or sharded"
JAX_PLATFORMS=cpu python -m pytest tests/test_multichip_scale.py -q -m slow
JAX_PLATFORMS=cpu python bench.py --nodes 64000 --evals 16 \
    --placements 4000 --iters 1 --mesh 8 --quick | python -c '
import json, sys
out = json.load(sys.stdin)
assert out["n_devices"] == 8, out
assert out["plan_refute_rate"] == 0, out
assert out["sharded_parity_checked"], out
assert out["collective_bytes_per_wave"] > 0, out
print("multichip smoke ok:", out["value"], out["unit"],
      "n_devices", out["n_devices"],
      "collective_bytes_per_wave", out["collective_bytes_per_wave"])'

echo "== chaos (seeded fault-injection scenarios on the virtual clock) =="
# the full chaos suite: every scenario in tests/test_chaos.py with its
# pinned seed (partition / split-brain / flap storm / lossy raft /
# heartbeat expiry), the seed-determinism double-run, and the
# trace-replay check — plus the wall-clock cluster tests the virtual-
# clock scenarios superseded in tier-1
JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q
JAX_PLATFORMS=cpu python -m pytest tests/test_cluster.py -q -m slow

echo "== soak (virtual-time cluster-day replay, gated on live SLOs) =="
# the production soak (chaos/soak.py + chaos/traffic.py): a seeded
# schedule of service/batch/system jobs, rolling deploys, autoscaling
# churn, drains, flap storms, and preemption storms drives a REAL
# agent through the HTTP API on a VirtualClock.  The quick profile
# runs twice and must be byte-identical (same seed, same bytes); the
# summary JSON lands next to the bench JSONs, and the slow marker run
# is the acceptance shape: >=2h virtual, green, zero breaches, <90s
# wall
JAX_PLATFORMS=cpu python -m nomad_tpu soak -quick -check-determinism \
    -json SOAK_ci.json
python - <<'EOF'
import json
out = json.load(open("SOAK_ci.json"))
for k in ("soak_virtual_hours", "soak_evals", "soak_breaches",
          "converged_fingerprint", "trace_digest", "determinism_ok"):
    assert k in out, f"missing summary field {k}"
assert out["ok"] and out["determinism_ok"], out
assert out["soak_breaches"] == 0, out
print("soak summary ok:", out["soak_virtual_hours"], "virtual hours,",
      out["soak_evals"], "evals, fingerprint",
      out["converged_fingerprint"][:16])
EOF
JAX_PLATFORMS=cpu python -m pytest tests/test_soak_sim.py -q -m slow

echo "== networked (port parity gate, churn soak, bench smoke) =="
# batched columnar port assignment (ISSUE 8): the pytest suite runs the
# batched-vs-sequential parity gate + the NetworkIndex edge cases + the
# place->kill->replace churn soak, then a --networked --quick bench
# smoke must report zero (node, port) collisions, a parity-gated run,
# and a networked rate within the acceptance band of the columnar rate
JAX_PLATFORMS=cpu python -m pytest tests/test_ports.py -q
JAX_PLATFORMS=cpu python bench.py --networked --quick | python -c '
import json, sys
out = json.load(sys.stdin)
assert out["port_collisions"] == 0, out
assert out["port_parity_checked"], out
assert out["placed"] == out["want"], out
assert out["port_batched_rows"] > 0, out
# ratio floor: networked must stay within 3x of the columnar rate at
# the same shape (the pre-batch per-alloc path sat ~25x under it);
# CPU-host smoke noise gets a little slack on top of the acceptance
assert out["networked_vs_columnar_ratio"] <= 4.0, out
print("networked smoke ok:", out["value"], out["unit"],
      "ratio", out["networked_vs_columnar_ratio"],
      "collisions", out["port_collisions"])'

echo "== multiproc (process worker plane: pool suite + scaling A/B) =="
# the multi-process worker plane (ISSUE 14): state export/delta
# replica round-trips, device submission front-end serialization,
# sharded dynamic-port cursors, the spawn-based 2-worker integration
# (networked waves complete with zero plan refutes) and worker-crash
# recovery — then a process-mode --workers 2 bench A/B whose scaling
# band perfcheck gates (>= 1.7x over 1 worker on multi-core hosts;
# a single-core host skips the scaling gate HONESTLY, never silently:
# the verdict names the skip and still checks refutes + JSON shape)
JAX_PLATFORMS=cpu python -m pytest tests/test_workerpool.py -q
JAX_PLATFORMS=cpu python bench.py --config 5 --nodes 400 --evals 8 \
    --placements 384 --batch 8 --iters 1 --quick \
    --workers 2 --worker-mode process --mesh off > BENCH_pool.json
python scripts/perfcheck.py --kind workers --fresh BENCH_pool.json

echo "== fanout (read-path plane: hub/ring/follower suite + watcher smoke) =="
# the read-path fanout plane (ISSUE 18): the WatchHub coalescing /
# EventRing cursor / ReadFollower no-stale-reads suite, then a
# --watchers --quick smoke (in-run asserts already fail the run on any
# stale wake or undelivered stream round) judged by the watchers-kind
# perfcheck gates: scale-aware p99 wake band, O(rounds) eval
# coalescing, zero drops, and the parked-vs-idle write-throughput
# ratio floor that stands in for "scheduler throughput must not
# regress under a parked 10k fleet"
JAX_PLATFORMS=cpu python -m pytest tests/test_fanout.py -q
JAX_PLATFORMS=cpu python bench.py --watchers --quick > BENCH_watchers.json
python scripts/perfcheck.py --kind watchers --fresh BENCH_watchers.json

echo "== memory (footprint plane: ledger suite + RSS-gated soak, both directions) =="
# the memory & footprint observability plane (ISSUE 19): the ledger /
# compaction-equivalence / floor-fallback / idle-reap suite, then a
# quick churn soak under a generous RSS ceiling judged by the
# memory-kind perfcheck gates (RSS high-water, floor-fallbacks == 0,
# eviction budget, ledger overhead <= 0.1% of soak wall), and finally
# the fail direction: an absurdly small ceiling must trip the gate
# and exit non-zero (a gate that cannot fail is not a gate)
JAX_PLATFORMS=cpu python -m pytest tests/test_memledger.py -q
JAX_PLATFORMS=cpu python -m nomad_tpu soak -quick -rss-ceiling-mb 8192 \
    -json SOAK_mem.json
python - <<'EOF'
import json
out = json.load(open("SOAK_mem.json"))
for k in ("rss_peak_bytes", "journal_bytes", "journal_compactions",
          "journal_floor_fallbacks", "ring_evictions",
          "mem_scrape_us", "mem_overhead_fraction"):
    assert k in out, f"missing summary field {k}"
assert out["ok"], out
assert out["rss_peak_bytes"] > 0, out
assert out["journal_floor_fallbacks"] == 0, out
print("memory summary ok: rss_peak",
      round(out["rss_peak_bytes"] / 1048576.0, 1), "MiB, journal",
      out["journal_bytes"], "B, overhead",
      out["mem_overhead_fraction"])
EOF
python scripts/perfcheck.py --kind memory --fresh SOAK_mem.json
if JAX_PLATFORMS=cpu python -m nomad_tpu soak -quick \
    -rss-ceiling-mb 1 >/dev/null 2>&1; then
    echo "memory gate FAILED OPEN: 1 MiB RSS ceiling did not trip" >&2
    exit 1
fi
echo "memory gate fail-direction ok: 1 MiB ceiling tripped as expected"

echo "== federation (cluster observability: 3-process cluster, stitching, failover) =="
# the cluster-scope observability plane (ISSUE 20): the obsbus /
# snapshot / stitching / puller suite first, then scripts/fedsmoke.py
# boots three REAL agent processes (separate interpreters = separate
# tracers, so the stitched trace crossing origins is genuine) into one
# raft cluster and asserts: a job registered through a NON-leader
# yields a stitched trace spanning >= 2 origins (the rpc.forward hop +
# the leader's commit spans), nomad.cluster.* families ride the
# leader's exposition, /v1/operator/cluster-health and the
# `nomad cluster status` / `trace status -cluster` verdicts are green
# — then the leader is SIGKILLed and the new leader's verdict must
# re-converge.  The measured scrape CPU duty / peer p99 / stitch
# latency land in FED_ci.json, judged by the federation-kind perfcheck
# gates (overhead <= 0.1%, peer scrape p99 <= 50ms, zero failures on
# the healthy cluster)
JAX_PLATFORMS=cpu python -m pytest tests/test_federation.py -q -m 'not slow'
JAX_PLATFORMS=cpu python scripts/fedsmoke.py --json FED_ci.json
python scripts/perfcheck.py --kind federation --fresh FED_ci.json

echo "== bench smoke (CPU backend, reduced scale) =="
JAX_PLATFORMS=cpu python bench.py --nodes 1000 --evals 16 \
    --placements 2000 --iters 1 | python -c '
import json, sys
out = json.load(sys.stdin)
assert out["value"] > 0, out
assert out["slo_breaches"] == 0, out
assert out["wave_device_s_p99"] > 0, out
print("smoke ok:", out["metric"], out["value"], out["unit"],
      "slo_breaches", out["slo_breaches"])'

echo "== CI green =="
