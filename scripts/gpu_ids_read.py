"""ISSUE 30, 5 (d): once, outside any timed window, read GET
/v1/job/<id>/allocations for ALL the jobs of a cycle of gpu50k-drain at
the full size and hold the instance ids to the plain reference: every id
belongs to the node's group, each allocation has exactly its count, no id
twice on a node.  One JSON line at the end.  On the chip:

    chiprun -- env PYTHONPATH=. python3 scripts/gpu_ids_read.py <seed>

(a second argument runs it at the rehearsal size, for the CPU here).  The
benchmark's own check cannot do this: `run.py verify` hands a
configuration's `check` node ids only (PERF.md section 7)."""
import http.client
import json
import sys
import threading
import time
from urllib.parse import urlsplit

from benchmark.loader import load_json, load_module

seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147930777
cfg = load_json("configs", "gpu50k")
traffic = load_json("traffic", "drain256-purge")
mod = load_module("configs", "gpu50k")
if len(sys.argv) > 2:                  # a CPU dry run at the rehearsal size
    cfg.update(cfg["rehearse"])
    traffic.update(traffic["rehearse"])

import jax  # noqa: E402
from nomad_tpu.agent import Agent  # noqa: E402

print("platform", jax.devices()[0].platform, flush=True)
nodes, fleet = mod.build_fleet(cfg, seed)
inventory = {n.id: (n.resources.devices[0] if n.resources.devices else None)
             for n in nodes}
agent = Agent(num_clients=0, heartbeat_ttl=86400.0, num_workers=1,
              log_level="warn", mesh=False)
agent.start()
srv = agent.server
srv.state.upsert_nodes(nodes)
u = urlsplit(agent.address)
per_cycle = traffic["jobs_per_cycle"]


def cycle(lo):
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
    return jobs, time.monotonic() - t0, [e for e in st
                                         if e.status != "complete"
                                         or e.failed_tg_allocs]


jobs, warm_s, bad = cycle(0)           # compiles
print(f"warm-up cycle {warm_s:.2f} s, {len(bad)} unsettled", flush=True)
conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
for job in jobs:
    conn.request("DELETE", f"/v1/job/{job['ID']}?purge=true")
    conn.getresponse().read()
time.sleep(5 if len(sys.argv) <= 2 else 1)
jobs, drain_s, bad = cycle(per_cycle)
failures = [f"{len(bad)} evals not complete or with a failed group"] \
    if bad else []
held = {}
allocs = instances = 0
t0 = time.monotonic()
for job in jobs:
    (dev_req,) = job["TaskGroups"][0]["Tasks"][0]["Resources"]["Devices"]
    conn.request("GET", f"/v1/job/{job['ID']}/allocations")
    rows = [a for a in json.loads(conn.getresponse().read())
            if a["DesiredStatus"] == "run"]
    if len(rows) != job["TaskGroups"][0]["Count"]:
        failures.append(f"{job['ID']}: {len(rows)} allocations")
    for a in rows:
        allocs += 1
        devs = a.get("AllocatedDevices") or []
        group = inventory.get(a["NodeID"])
        if len(devs) != 1 or group is None:
            failures.append(f"{a['ID']}: {len(devs)} device entries on "
                            f"{'a GPU' if group else 'a plain'} node")
            continue
        d = devs[0]
        ids = d["DeviceIds"]
        gid = f"{d['Vendor']}/{d['Type']}/{d['Name']}"
        if gid != group.id() or not mod.accepts(dev_req["Name"], gid):
            failures.append(f"{a['ID']}: group {gid} for request "
                            f"{dev_req['Name']} on a {group.id()} node")
        if len(ids) != dev_req["Count"] or len(set(ids)) != len(ids):
            failures.append(f"{a['ID']}: {len(ids)} ids for count "
                            f"{dev_req['Count']}")
        if not set(ids) <= set(group.instance_ids):
            failures.append(f"{a['ID']}: an id the node's group lacks")
        seen = held.setdefault(a["NodeID"], set())
        if seen & set(ids):
            failures.append(f"{a['NodeID']}: an instance held twice")
        seen.update(ids)
        instances += len(ids)
print(json.dumps({
    "ok": not failures, "failures": failures[:5], "jobs": len(jobs),
    "allocations": allocs, "instances": instances,
    "nodes_touched": len(held), "drain_s": round(drain_s, 4),
    "read_s": round(time.monotonic() - t0, 2),
    "platform": jax.devices()[0].platform}), flush=True)
agent.shutdown()
