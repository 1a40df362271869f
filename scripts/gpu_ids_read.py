"""Once, outside any timed window, read GET /v1/job/<id>/allocations for
ALL the jobs of a cycle of a GPU configuration's drain cell (gpu50k or
mig50k, drain256-purge) at the full size and hold the instance ids to the
plain reference: each allocation holds exactly its count of distinct ids
of ONE device group of its node, a group its request accepts by name
(and, in mig50k, by constraint), every id one of that group's own, no id
twice on a node.  One JSON line at the end.  On a TPU host, from the
root of the repository:

    env PYTHONPATH=. python3 scripts/gpu_ids_read.py <config> <seed>

(a third argument runs it at the rehearsal size, for the CPU here).  The
benchmark's own check cannot do this: `run.py verify` hands a
configuration's `check` node ids only (PERF.md section 7)."""
import http.client
import json
import sys
import threading
import time
from urllib.parse import urlsplit

from benchmark.loader import load_json, load_module

config = sys.argv[1] if len(sys.argv) > 1 else "gpu50k"
seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147930777
cfg = load_json("configs", config)
traffic = load_json("traffic", "drain256-purge")
mod = load_module("configs", config)
rehearse = len(sys.argv) > 3
if rehearse:                           # a CPU dry run at the rehearsal size
    cfg.update(cfg["rehearse"])
    traffic.update(traffic["rehearse"])


def accepted(dev_req: dict, group) -> bool:
    """The configuration's own plain match of a wire-form request and a
    node's device group."""
    if hasattr(mod, "group_accepts"):
        return mod.group_accepts(
            (dev_req["Name"], dev_req["Count"], tuple(
                (c["LTarget"], c["Operand"], c["RTarget"])
                for c in dev_req.get("Constraints") or ())),
            (group.id(), len(group.instance_ids),
             group.attributes.get("memory", "0")))
    return mod.accepts(dev_req["Name"], group.id())


import jax  # noqa: E402
from nomad_tpu.agent import Agent  # noqa: E402

print("platform", jax.devices()[0].platform, flush=True)
nodes, fleet = mod.build_fleet(cfg, seed)
inventory = {n.id: {d.id(): d for d in n.resources.devices} for n in nodes}
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
time.sleep(1 if rehearse else 5)
jobs, drain_s, bad = cycle(per_cycle)
failures = [f"{len(bad)} evals not complete or with a failed group"] \
    if bad else []
held = {}
allocs = instances = 0
by_group = {}
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
        groups = inventory.get(a["NodeID"]) or {}
        if len(devs) != 1 or not groups:
            # one entry: all of the request's ids name ONE group
            failures.append(f"{a['ID']}: {len(devs)} device entries on "
                            f"{'a GPU' if groups else 'a plain'} node")
            continue
        d = devs[0]
        ids = d["DeviceIds"]
        gid = f"{d['Vendor']}/{d['Type']}/{d['Name']}"
        group = groups.get(gid)
        if group is None or not accepted(dev_req, group):
            failures.append(f"{a['ID']}: group {gid} for request "
                            f"{dev_req['Name']} on a node of {list(groups)}")
            continue
        if len(ids) != dev_req["Count"] or len(set(ids)) != len(ids):
            failures.append(f"{a['ID']}: {len(ids)} ids for count "
                            f"{dev_req['Count']}")
        if not set(ids) <= set(group.instance_ids):
            failures.append(f"{a['ID']}: an id its group lacks")
        key = "multi_group" if len(groups) > 1 else "single_group"
        by_group[key] = by_group.get(key, 0) + 1
        seen = held.setdefault(a["NodeID"], set())
        if seen & set(ids):
            failures.append(f"{a['NodeID']}: an instance held twice")
        seen.update(ids)
        instances += len(ids)
print(json.dumps({
    "ok": not failures, "failures": failures[:5], "jobs": len(jobs),
    "allocations": allocs, "instances": instances,
    "nodes_touched": len(held), "allocations_by_node": by_group,
    "config": config, "seed": seed, "drain_s": round(drain_s, 4),
    "read_s": round(time.monotonic() - t0, 2),
    "platform": jax.devices()[0].platform}), flush=True)
agent.shutdown()
