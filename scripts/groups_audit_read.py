"""Once, outside any timed window, read GET /v1/job/<id>/allocations and
GET /v1/evaluation/<id> for ALL the jobs of one cycle of groups50k-drain
at the full size and hold what the benchmark's check cannot see to the
plain rule: each group's live allocations equal its own `count`, every
allocation names a group of its job, and the eval holds no queued or
failed group.  One JSON line at the end.  On a host with the chip, from
the repo's root:

    env PYTHONPATH=. python3 scripts/groups_audit_read.py <seed> [cycles]

(a third argument runs it at the rehearsal size, on a CPU host).  The
benchmark's own check cannot do this: `run.py verify` hands a
configuration's `check` node ids a job, not which group an allocation
is of (PERF.md section 7)."""
import http.client
import json
import sys
import threading
import time
from urllib.parse import urlsplit

from benchmark.loader import load_json, load_module

seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147942777
cycles = int(sys.argv[2]) if len(sys.argv) > 2 else 1
cfg = load_json("configs", "groups50k")
traffic = load_json("traffic", "drain256-purge")
mod = load_module("configs", "groups50k")
if len(sys.argv) > 3:                  # a CPU dry run at the rehearsal size
    cfg.update(cfg["rehearse"])
    traffic.update(traffic["rehearse"])

import jax  # noqa: E402
from nomad_tpu.agent import Agent  # noqa: E402
from nomad_tpu.core.telemetry import REGISTRY  # noqa: E402

print("platform", jax.devices()[0].platform, flush=True)
nodes, fleet = mod.build_fleet(cfg, seed)
agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
              log_level="warn", mesh=False)
agent.start()
srv = agent.server
srv.state.upsert_nodes(nodes)
u = urlsplit(agent.address)
per_cycle = traffic["jobs_per_cycle"]


def get(conn, path):
    conn.request("GET", path)
    return json.loads(conn.getresponse().read())


def cycle(lo):
    """Registers jobs lo .. lo + per_cycle over 8 connections with
    scheduling held, releases, waits for every eval: the cell's cycle."""
    jobs = [mod.make_job(cfg, i) for i in range(lo, lo + per_cycle)]
    srv.stop_scheduling()
    evals = {}

    def put(part):
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
        for job in part:
            conn.request("PUT", "/v1/jobs", json.dumps({"Job": job}),
                         {"Content-Type": "application/json"})
            evals[job["ID"]] = json.loads(conn.getresponse().read())["EvalID"]
        conn.close()

    threads = [threading.Thread(target=put, args=(jobs[k::8],))
               for k in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    t0 = time.monotonic()
    srv.start_scheduling()
    while True:
        st = [srv.state.eval_by_id(e) for e in evals.values()]
        if all(e is not None and e.status in ("complete", "failed")
               for e in st):
            break
        if time.monotonic() - t0 > 300:
            raise SystemExit("cycle did not settle")
        time.sleep(0.01)
    return jobs, evals, time.monotonic() - t0


jobs, evals, drains = [], {}, []
for c in range(cycles):
    more, ids, drain_s = cycle(c * per_cycle)
    jobs += more
    evals.update(ids)
    drains.append(round(drain_s, 4))
failures = []
allocs = groups = 0
forms = {"block": 0, "rows": 0}
t0 = time.monotonic()
conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
for job in jobs:
    want = {tg["Name"]: tg["Count"] for tg in job["TaskGroups"]}
    rows = [a for a in get(conn, f"/v1/job/{job['ID']}/allocations")
            if a["DesiredStatus"] == "run"]
    got = {}
    for a in rows:
        allocs += 1
        if a["TaskGroup"] not in want:
            failures.append(f"{a['ID']}: group {a['TaskGroup']!r} is none "
                            f"of {job['ID']}'s {sorted(want)}")
        got[a["TaskGroup"]] = got.get(a["TaskGroup"], 0) + 1
    groups += len(want)
    if got != want:
        failures.append(f"{job['ID']}: live by group {got}, asked {want}")
    ev = get(conn, f"/v1/evaluation/{evals[job['ID']]}")
    if ev["Status"] != "complete":
        failures.append(f"{job['ID']}: eval {ev['Status']}")
    queued = {k: v for k, v in (ev.get("QueuedAllocations") or {}).items()
              if v}
    if queued or ev.get("FailedTGAllocs"):
        failures.append(f"{job['ID']}: queued {queued}, failed groups "
                        f"{sorted(ev.get('FailedTGAllocs') or {})}")
for form in forms:
    forms[form] = REGISTRY.counter_labels(
        "nomad.materialize.placements").get(f"form={form}", 0)
counters = {k: REGISTRY.counter_sum(k) for k in (
    "nomad.groups.evals_batched", "nomad.groups.evals_solo")}
print(json.dumps({
    "ok": not failures, "failures": failures[:5], "jobs": len(jobs),
    "groups": groups, "allocations": allocs,
    "placements_by_form": forms, "drain_s": drains,
    "read_s": round(time.monotonic() - t0, 2), "counters": counters,
    "platform": jax.devices()[0].platform}), flush=True)
agent.shutdown()
