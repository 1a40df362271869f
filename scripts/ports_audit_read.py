"""ISSUE 38: once, outside any timed window, read GET
/v1/job/<id>/allocations for ALL the jobs of a few kept cycles of
ports50k-drain at the full size and hold the port VALUES to the plain
rule: no (node, port) twice among live allocations, every dynamic value
inside the client's range 20000-32000, every static value the one its
job asks, every label of the job's network block assigned and no other.
One JSON line at the end.  On the chip:

    chiprun -- env PYTHONPATH=. python3 scripts/ports_audit_read.py <seed> [cycles]

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

seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147938777
cycles = int(sys.argv[2]) if len(sys.argv) > 2 else 4
cfg = load_json("configs", "ports50k")
traffic = load_json("traffic", "drain256-keep")
mod = load_module("configs", "ports50k")
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
lo_dyn, hi_dyn = cfg["dynamic_port_range"]


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


jobs, drains, bad = [], [], []
for c in range(cycles):
    more, drain_s, unsettled = cycle(c * per_cycle)
    jobs += more
    drains.append(round(drain_s, 4))
    bad += unsettled
failures = [f"{len(bad)} evals not complete or with a failed group"] \
    if bad else []
held = {}                               # node id -> {port: alloc id}
allocs = dynamic = static = 0
t0 = time.monotonic()
conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
for job in jobs:
    tg = job["TaskGroups"][0]
    nets = tg.get("Networks") or []
    want_static = {p["Label"]: p["Value"] for net in nets
                   for p in net["ReservedPorts"]}
    want_dynamic = {p["Label"] for net in nets for p in net["DynamicPorts"]}
    conn.request("GET", f"/v1/job/{job['ID']}/allocations")
    rows = [a for a in json.loads(conn.getresponse().read())
            if a["DesiredStatus"] == "run"]
    if len(rows) != tg["Count"]:
        failures.append(f"{job['ID']}: {len(rows)} allocations")
    for a in rows:
        allocs += 1
        ports = a.get("AllocatedPorts") or {}
        if set(ports) != set(want_static) | want_dynamic:
            failures.append(f"{a['ID']}: labels {sorted(ports)} for the "
                            f"ask {sorted(want_static)} + "
                            f"{sorted(want_dynamic)}")
            continue
        for label, value in ports.items():
            if label in want_static:
                static += 1
                if value != want_static[label]:
                    failures.append(f"{a['ID']}: static {label} = {value}, "
                                    f"asked {want_static[label]}")
            else:
                dynamic += 1
                if not lo_dyn <= value <= hi_dyn:
                    failures.append(f"{a['ID']}: dynamic {label} = {value} "
                                    f"outside {lo_dyn}-{hi_dyn}")
            on = held.setdefault(a["NodeID"], {})
            if value in on:
                failures.append(f"{a['NodeID']}: port {value} held by "
                                f"{on[value]} and {a['ID']}")
            on[value] = a["ID"]
counters = {k: REGISTRY.counter_sum(k) for k in (
    "nomad.ports.batched_rows", "nomad.ports.sequential_rows",
    "nomad.ports.evals_batched", "nomad.ports.evals_solo",
    "nomad.ports.runner_up_redirects", "nomad.engine.port_masks_built",
    "nomad.engine.port_masks_reused")}
print(json.dumps({
    "ok": not failures, "failures": failures[:5], "jobs": len(jobs),
    "allocations": allocs, "dynamic_ports": dynamic, "static_ports": static,
    "nodes_touched": len(held),
    "most_ports_on_a_node": max((len(v) for v in held.values()), default=0),
    "drain_s": drains, "read_s": round(time.monotonic() - t0, 2),
    "counters": counters,
    "platform": jax.devices()[0].platform}), flush=True)
agent.shutdown()
