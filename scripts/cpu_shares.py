"""Whose work is a stage's time?  CPU seconds by thread, per function.

A span is a wall, and under one interpreter lock a wall holds other
threads' turns: the worker's `materialize` span grows when the applier
commits beside it, and no span can say which of the two did the work.
`time.thread_time()` is the CPU time of the CALLING thread, waits for the
interpreter lock excluded, so wrapped round a function it says what that
function itself cost the thread that ran it.  (cProfile is no substitute:
on Python 3.12 one profiler sees all threads and bills a lock hand-off to
whichever function was running.)

Builds a configuration's fleet and jobs with the benchmark's loaders (as
scripts/gpu_ids_read.py), wraps each `module:attr.path` given (default: the
wave's pieces), runs N drain cycles through the served path and prints per
cycle, for each name: calls, CPU ms, wall ms, CPU us a call.  A name
imported with `from x import f` is wrapped where it is LOOKED UP
(`nomad_tpu.scheduler.generic:new_ids`).  The agent is imported only
after the wraps are in place, so nothing its import chain binds at
import time holds an unwrapped function.

    chiprun -- env PYTHONPATH=. python3 scripts/cpu_shares.py csi50k drain384

`--metrics nomad.materialize` prints, after each cycle, the `/v1/metrics`
series of that prefix as the agent serves them (counters run since
process start: a cycle's share is the difference of two prints).

What it is for: the PER-FUNCTION rows, inclusive parts of a thread's work
that nothing else reads.  The two threads' totals (`Worker.run_batch`,
`PlanApplier.apply_one`) the program now keeps itself, with nothing
wrapped from outside: each thread stamps its own `time.thread_time()`
(nomad_tpu/core/telemetry.py), `/v1/metrics` serves the sums as
`nomad.runtime.thread_cpu_s{role=...}`, and a traced run of any cell
reads them per pass from the worker's `nomad.cpu` markers
(`lock.worker_cpu_ms_per_pass`, `lock.applier_cpu_ms_per_pass`,
`lock.held_share`: `python3 -m benchmark.span_cells --workload <cell>
...`).  The two rows stay in DEFAULT as the whole the parts are read
against.

On a CPU host: shares of interpreter work, never a speed.  The chip
host's thread clock ticks coarsely (PERF.md section 3): read sums there,
not calls."""
import argparse
import http.client
import importlib
import json
import threading
import time
from urllib.parse import urlsplit

from benchmark.loader import load_json, load_module

DEFAULT = [
    "nomad_tpu.core.worker:Worker.run_batch",
    "nomad_tpu.core.worker:Worker._start_batch",
    "nomad_tpu.core.wavepipe:WavePipeline.collect",
    "nomad_tpu.scheduler.generic:GenericScheduler._materialize_bulk",
    "nomad_tpu.scheduler.generic:new_ids",
    "nomad_tpu.scheduler.generic:GenericScheduler.finalize_batched",
    "nomad_tpu.core.worker:Worker._settle",
    "nomad_tpu.core.plan_apply:PlanApplier.apply_one",
    "nomad_tpu.state.state_store:StateStore.upsert_plan_results",
]

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
[ap.add_argument(a) for a in ("config", "traffic")]
ap.add_argument("names", nargs="*", default=DEFAULT)
ap.add_argument("--seed", type=int, default=2147931031)
ap.add_argument("--cycles", type=int, default=4, help="first one compiles")
ap.add_argument("--rehearse", action="store_true", help="tiny, for the CPU")
ap.add_argument("--metrics", default="", metavar="PREFIX",
                help="after each cycle, the /v1/metrics series that start so")
args = ap.parse_args()

cfg, mod = load_json("configs", args.config), load_module("configs", args.config)
traffic = load_json("traffic", args.traffic)
if args.rehearse:
    cfg.update(cfg["rehearse"])
    traffic.update(traffic["rehearse"])

import jax  # noqa: E402

acc = {}                               # name -> [calls, cpu s, wall s]


def wrap(name):
    module, _, path = name.partition(":")
    *parents, attr = path.split(".")
    owner = importlib.import_module(module)
    for p in parents:
        owner = getattr(owner, p)
    fn = getattr(owner, attr)
    row = acc[name] = [0, 0.0, 0.0]

    def timed(*a, **kw):
        c0, w0 = time.thread_time(), time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            row[0] += 1
            row[1] += time.thread_time() - c0
            row[2] += time.perf_counter() - w0
    setattr(owner, attr, timed)


[wrap(name) for name in args.names]
from nomad_tpu.agent import Agent  # noqa: E402  (after the wraps: see top)
print("platform", jax.devices()[0].platform, flush=True)
nodes, _fleet = mod.build_fleet(cfg, args.seed)
agent = Agent(num_clients=cfg["server"]["num_clients"],
              heartbeat_ttl=float(cfg["server"]["heartbeat_ttl_s"]),
              num_workers=cfg["server"]["workers"], log_level="warn",
              mesh=False)
agent.start()
srv, u = agent.server, urlsplit(agent.address)
srv.state.upsert_nodes(nodes)


def call(conn, method, path, body=None):
    conn.request(method, path, None if body is None else json.dumps(body),
                 {"Content-Type": "application/json"})
    return json.loads(conn.getresponse().read() or b"null")


main_conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
if hasattr(mod, "install"):
    mod.install(cfg, nodes, lambda p, b: call(main_conn, "PUT", p, b))
per_cycle = traffic["jobs_per_cycle"]


def cycle(lo):
    jobs = [mod.make_job(cfg, i) for i in range(lo, lo + per_cycle)]
    srv.stop_scheduling()
    evals = []

    def put(part):
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
        evals.extend(call(conn, "PUT", "/v1/jobs", {"Job": j})["EvalID"]
                     for j in part)
        conn.close()

    threads = [threading.Thread(target=put, args=(jobs[k::8],))
               for k in range(8)]
    [t.start() for t in threads], [t.join() for t in threads]
    for row in acc.values():             # registration is not the drain
        row[:] = [0, 0.0, 0.0]
    t0 = time.monotonic()
    srv.start_scheduling()
    while not all(e is not None and e.status in ("complete", "failed")
                  for e in map(srv.state.eval_by_id, evals)):
        if time.monotonic() - t0 > traffic["cycle_timeout_s"]:
            raise SystemExit("cycle did not settle")
        time.sleep(0.02)
    drain_s = time.monotonic() - t0
    if traffic.get("between_cycles") == "deregister":
        for j in jobs:
            call(main_conn, "DELETE", f"/v1/job/{j['ID']}?purge=true")
        time.sleep(1.0)
    return drain_s


for n in range(args.cycles):
    print(f"cycle {n}: drain {cycle(n * per_cycle) * 1e3:.1f} ms"
          f"{' (compiles)' if n == 0 else ''}")
    print(f"  {'calls':>6} {'cpu ms':>9} {'wall ms':>9} {'cpu us/call':>11}")
    for name, (calls, cpu, wall) in acc.items():
        print(f"  {calls:6d} {cpu * 1e3:9.2f} {wall * 1e3:9.2f} "
              f"{cpu * 1e6 / max(calls, 1):11.1f}  {name.split(':')[1]}",
              flush=True)
    if args.metrics:
        for k, v in sorted(call(main_conn, "GET", "/v1/metrics").items()):
            if k.startswith(args.metrics):
                print(f"  {k} {v}", flush=True)
agent.shutdown()
