#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and runs the Agent (HTTP API, broker, worker,
engine, applier, state store); a child process (benchmark/client.py) is
the only client.  The cell, its configuration, its traffic mix and its
per-layer metrics are files found by name under this directory, so a new
cell is new files, not an edit here.  The last line of stdout is one
JSON object: correct, attempted, failed, metrics, device (and, traced,
breakdown).  Any platform but `tpu` exits non-zero with no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                                            # noqa: E402
import http.client                                         # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import random                                              # noqa: E402
import shutil                                              # noqa: E402
import subprocess                                          # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
import threading                                           # noqa: E402
from urllib.parse import urlsplit                          # noqa: E402

from benchmark.loader import HERE, load_json, load_module  # noqa: E402

CLIENT_TERMINAL = ("complete", "failed", "lost")
SAMPLED_JOBS, SAMPLED_ALLOCS = 5, 1300
EXIT_NO_DEVICE, EXIT_RUN_FAILED, EXIT_COMPILED_IN_WINDOW = 2, 3, 4
EXIT_NO_PROGRAM = 5
SLOW_COMPILE_S = 1.0          # the persistent cache's own threshold


class Child:
    """The client process and the pipe to it."""

    def __init__(self, address: str, plan_path: str, out_path: str,
                 connections: int) -> None:
        self.out_path = out_path
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), address,
             plan_path, out_path, str(connections)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        ready = self._read()
        if ready.get("ev") != "ready":
            raise RuntimeError(f"client did not come up: {ready}")

    def _read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"client exited (code {self.proc.poll()}) mid-run")
        return json.loads(line)

    def call(self, cmd: dict) -> dict:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        return self._read()

    def finish(self) -> list:
        self.call({"op": "quit"})
        self.proc.wait(60)
        with open(self.out_path) as f:
            return json.load(f)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(30)


class Run:
    """What one run knows; handed to the driver and to every per-layer
    reader (`read(run)`)."""

    def __init__(self, cell, cfg, traffic, seed, seconds, trace):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace_on = seed, seconds, trace
        self.server = self.child = self.tap = None
        self.compile_log = self.gc_log = None
        self.due: list = []
        self.jobs: list = []
        self.tmp = ""
        self.c0 = self.c1 = None          # counter snapshots
        self.planq: list = []             # plan-queue latencies in window
        self.spans: dict = {}             # benchmark-side spans
        self.result: dict = {}
        self.address = ""
        self.captured: list = []          # (job indices, {job id: nodes})
        self.records: list = []
        self.device: dict = {}
        self.trace: dict = {}             # trace_reduce.summarize(...)
        self.tap_window: dict = {"waves": [], "intervals": {}}
        self.compile_events: list = []
        self._trace_state = "off"
        self._trace_t0 = 0.0
        self._anchor = 0.0
        self._timers: list = []

    def capture(self, indices) -> None:
        """The live allocations of jobs that are about to be purged, kept
        for the check after the window."""
        self.captured.append((indices, live_allocations(self.address)))

    # ------------------------------------------------- window and tracing

    def window_opens(self, at: float, planned: bool = False) -> None:
        """Counter snapshots at the window's ends.  A drain calls this
        at the instant (and `window_closes` at the other); the open loop
        knows both instants ahead."""
        if not planned:
            self._snap_open()
            return
        for when, fn in ((at, self._snap_open),
                         (at + self.seconds, self._snap_close)):
            t = threading.Timer(max(when - time.monotonic(), 0.0), fn)
            t.daemon = True
            t.start()
            self._timers.append(t)

    def window_closes(self) -> None:
        for t in self._timers:
            t.join(30)
        if self.c1 is None:
            self._snap_close()

    def _snap_open(self) -> None:
        from benchmark import taps
        self.c0 = taps.counters(self.server)

    def _snap_close(self) -> None:
        from benchmark import taps
        self.c1 = taps.counters(self.server)
        ring = self.server.plan_queue.latencies
        # a ring that wrapped no longer says which readings are the window's
        self.planq = (list(ring)[self.c0["planq_latencies"]:]
                      if len(ring) < ring.maxlen else [])

    def trace_tick(self, now: float, closing: bool = False) -> None:
        """Called by a drain at cycle boundaries."""
        if not self.trace_on:
            return
        if self._trace_state == "off" and not closing:
            self._start_trace()
        elif self._trace_state == "on" and (
                closing or now - self._trace_t0
                >= self.cell.get("trace_seconds", 5)):
            self._stop_trace()

    def trace_timer(self, window_t0: float) -> None:
        """The open loop: trace `trace_seconds` from a second into the
        window."""
        if not self.trace_on:
            return

        def body():
            time.sleep(max(window_t0 + 1.0 - time.monotonic(), 0.0))
            self._start_trace()
            time.sleep(self.cell.get("trace_seconds", 5))
            self._stop_trace()

        t = threading.Thread(target=body, name="trace-timer", daemon=True)
        t.start()
        self._timers.append(t)

    def _start_trace(self) -> None:
        import jax
        from benchmark import trace_reduce

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(os.path.join(self.tmp, "trace"),
                                 profiler_options=opts)
        self._anchor = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
            pass
        self._trace_t0 = self._anchor
        self._trace_state = "on"

    def _stop_trace(self) -> None:
        import jax

        self._trace_t1 = time.monotonic()
        jax.profiler.stop_trace()
        self._trace_state = "done"

    def abandon_trace(self) -> None:
        """A run that ends early must not leave the profiler running."""
        if self._trace_state == "on":
            self._stop_trace()

    def reduce_trace(self, n_chips: int) -> None:
        """Busy time inside the timed intervals the trace covers; idle
        gaps named by the StageTimers stage that covers them."""
        from benchmark import trace_reduce as tr

        if self._trace_state != "done":
            return
        path = tr.find_xplane(os.path.join(self.tmp, "trace"))
        if path is None:
            return
        t0 = time.monotonic()
        reduced = tr.reduce_trace(path)
        if reduced["anchor_s"] is None:
            print("trace: no anchor annotation found", flush=True)
            return
        offset = self._anchor - reduced["anchor_s"]
        traced = [(self._trace_t0, self._trace_t1)]
        windows = tr.clip(tr.union(self.result["timed"]), traced)
        self.trace = tr.summarize(reduced, windows, offset, n_chips)
        if not self.trace:
            print("trace: no device plane in it", flush=True)
            return
        # not `device`: that interval is the wait itself, and spans the
        # host phases this is meant to tell apart
        stages = {s: [(a, b) for _, a, b in ivs]
                  for s, ivs in self.tap.intervals.items() if s != "device"}
        for name, ivs in self.spans.items():
            stages[name] = ivs
        self.trace["idle_gaps"] = tr.attribute_gaps(
            self.trace.pop("idle"), stages)
        launches = sum(n for name, (n, _) in self.trace["programs"].items()
                       if name.startswith("jit_place"))
        print(f"trace: {os.path.getsize(path)} bytes, "
              f"{self._trace_t1 - self._trace_t0:.2f} s traced, "
              f"{self.trace['window_s']:.3f} s of it timed, {launches} "
              f"launches of placement kernels in it, device busy "
              f"{self.trace['busy_s']:.4f} s, op events "
              f"{'read' if reduced['ops_read'] else 'too many, programs only'}"
              f"; reduced in {time.monotonic() - t0:.1f} s; idle gaps are "
              f"named by the StageTimers stage covering them, the two "
              f"clocks joined by one anchor taken at start_trace",
              flush=True)
        if launches < self.cell.get("trace_min_launches", 1):
            from benchmark.drivers import RunFailed
            raise RunFailed(
                f"{launches} kernel launches in the trace, fewer than the "
                f"{self.cell['trace_min_launches']} the cell's device "
                f"readings need")


def http_json(address: str, method: str, path: str, body=None,
              timeout: float = 600.0):
    u = urlsplit(address)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"{method} {path}: HTTP {resp.status} "
                               f"{raw[:200]!r}")
        return json.loads(raw)
    finally:
        conn.close()


def live_allocations(address: str):
    """{job id: node ids of its live allocations}, from the columnar
    read, which lists the allocations of jobs that exist."""
    cols = http_json(address, "GET",
                     "/v1/allocations?columnar=true")["Columns"]
    by_job: dict = {}
    for job_id, node_id, cstatus in zip(cols["JobID"], cols["NodeID"],
                                        cols["ClientStatus"]):
        if cstatus not in CLIENT_TERMINAL:
            by_job.setdefault(job_id, []).append(node_id)
    return by_job


def verify(run: Run, config_mod, fleet: dict, address: str) -> list:
    """The comparison that decides `correct`: host code over what the
    client saw and what the HTTP API reads back.  Returns failures."""
    failures = []
    sent = run.result["sent_jobs"]
    recs = [run.records[i] for i in sent]
    unacked = [r["i"] for r in recs if r["http"] != 200 or not r["eval_id"]]
    if unacked:
        failures.append(f"{len(unacked)} registrations not acknowledged, "
                        f"e.g. jobs {unacked[:5]}")
    unsettled = [r["i"] for r in recs if r["eval_id"]
                 and (r["status"] != "complete" or r["failed_tg"])]
    if unsettled:
        failures.append(
            f"{len(unsettled)} acknowledged registrations whose evaluation "
            f"did not reach complete with every placement made, e.g. "
            f"{[(i, run.records[i]['status']) for i in unsettled[:5]]}")
    t0 = time.monotonic()
    by_job = live_allocations(address)
    t1 = time.monotonic()
    # jobs purged between cycles were read then; the rest are live now.
    # Each group is checked as one fleet: capacity sums over the jobs
    # that were live together
    purged = {i for indices, _ in run.captured for i in indices}
    groups = run.captured + [([i for i in sent if i not in purged], by_job)]
    for indices, seen in groups:
        # in the order their evaluations completed: with one worker, the
        # order they were scheduled in, which is not the order they were
        # registered in over several connections
        done = sorted((i for i in indices if run.records[i]["eval_id"]),
                      key=lambda i: (run.records[i]["settled"] is None,
                                     run.records[i]["settled"], i))
        failures += config_mod.check(run.cfg, fleet,
                                     [run.jobs[i] for i in done], seen)
    jobs = [run.jobs[i] for i in groups[-1][0] if run.records[i]["eval_id"]]
    if unsettled:
        # what the API says of them now: tells an evaluation that is not
        # complete from an event the client never got
        now = {}
        for i in unsettled[:20]:
            st = http_json(address, "GET", "/v1/evaluation/"
                           + run.records[i]["eval_id"])["Status"]
            now[st] = now.get(st, 0) + 1
        print(f"check: of the first {min(len(unsettled), 20)} unsettled, "
              f"the API now reads {now}; event stream dropped "
              f"{run.server.events.stats()['DroppedTotal']}", flush=True)
    # the full wire form on a seeded handful of jobs: up to SAMPLED_JOBS,
    # fewer once they hold SAMPLED_ALLOCS allocations
    rng = random.Random(f"sample:{run.seed}")
    sample, held = [], 0
    for job in rng.sample(jobs, min(SAMPLED_JOBS, len(jobs))):
        if held >= SAMPLED_ALLOCS:
            break
        sample.append(job)
        held += job["TaskGroups"][0]["Count"]
    for job in sample:
        rows = [a for a in http_json(
                    address, "GET", f"/v1/job/{job['ID']}/allocations")
                if a["DesiredStatus"] == "run"
                and a["ClientStatus"] not in CLIENT_TERMINAL]
        ask = job["TaskGroups"][0]["Tasks"][0]["Resources"]
        if sorted(a["NodeID"] for a in rows) != sorted(
                by_job.get(job["ID"], [])):
            failures.append(f"{job['ID']}: per-job and columnar reads "
                            f"disagree")
        if not all(a["Resources"]["CPU"] == ask["CPU"]
                   and a["Resources"]["MemoryMB"] == ask["MemoryMB"]
                   for a in rows):
            failures.append(f"{job['ID']}: alloc resources differ from "
                            f"the ask")
    n_allocs = sum(len(v) for _, seen in groups for v in seen.values())
    print(f"check: {n_allocs} allocations of {len(sent)} jobs in "
          f"{len(groups)} groups; the last read back in {t1 - t0:.2f} s, "
          f"all checked in {time.monotonic() - t1:.2f} s; "
          f"{len(failures)} failures", flush=True)
    return failures


def cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             platform: str = "tpu", overrides=None) -> int:
    """`platform` is for benchmark/selftest's CPU rehearsal alone and
    `overrides` ({"config": {...}, "traffic": {...}}) for it and for
    benchmark/sweep.py; the command line can set neither."""
    from benchmark import drivers, taps

    if importlib.util.find_spec("nomad_tpu") is None:
        print("benchmark: the program (nomad_tpu) is not in this checkout",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    cell = load_json("workloads", cell_name)
    cfg = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    if overrides:
        cfg.update(overrides.get("config", {}))
        traffic.update(overrides.get("traffic", {}))
    config_mod = load_module("configs", cell["config"])
    loop = drivers.LOOPS[traffic["loop"]]

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} devices={len(devices)} "
          f"cell={cell_name} seed={seed} seconds={seconds} "
          f"trace={int(trace)}", flush=True)
    if device["platform"] != platform or len(devices) < cell["chips"]:
        print(f"benchmark: {cell_name} needs {cell['chips']} {platform} "
              f"device(s); JAX found {len(devices)} of platform "
              f"{device['platform']!r}", file=sys.stderr)
        return EXIT_NO_DEVICE
    if platform == "tpu":
        from benchmark import peaks
        peaks.peaks_for(device["kind"])       # unknown device: an error

    run = Run(cell, cfg, traffic, seed, seconds, trace)
    run.device = device
    run.compile_log = taps.CompileLog()
    run.compile_log.install()
    run.gc_log = taps.GcLog()
    run.gc_log.install()

    import nomad_tpu.ops  # noqa: F401 - places the compile cache
    from nomad_tpu.agent import Agent
    from nomad_tpu.core.logging import RING

    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)
    ring_level = RING.min_level
    run.tmp = tempfile.mkdtemp(prefix="nomad-bench-")
    agent = None
    try:
        t0 = time.monotonic()
        nodes, fleet = config_mod.build_fleet(cfg, seed)
        # one chip: the single-device path, whatever else the host holds
        agent = Agent(num_clients=cfg["server"]["num_clients"],
                      heartbeat_ttl=float(cfg["server"]["heartbeat_ttl_s"]),
                      num_workers=cfg["server"]["workers"],
                      log_level="warn",
                      mesh=False if cell["chips"] == 1 else None)
        agent.start()
        run.server = agent.server
        run.address = agent.address
        run.server.state.upsert_nodes(nodes)
        if hasattr(config_mod, "install"):
            config_mod.install(
                cfg, nodes, lambda path, body: http_json(
                    agent.address, "PUT", path, body))
        t1 = time.monotonic()
        run.due = loop["plan"](traffic, seed, seconds)
        plan_path = os.path.join(run.tmp, "plan.tsv")
        with open(plan_path, "wb") as f:
            for i, due in enumerate(run.due):
                job = config_mod.make_job(cfg, i)
                run.jobs.append(job)
                f.write(b"%r\t{\"Job\": %s}\n"
                        % (due, json.dumps(job).encode()))
        run.child = Child(agent.address, plan_path,
                          os.path.join(run.tmp, "client.json"),
                          traffic["connections"])
        run.tap = taps.StageTap(run.server)
        if trace:
            run.tap.start()
            for obj_path, span in cell.get("spans", {}).items():
                taps.wrap_span(run, obj_path, span)
        print(f"setup: fleet of {len(nodes)} nodes up in {t1 - t0:.2f} s, "
              f"{len(run.jobs)} jobs planned and the client up in "
              f"{time.monotonic() - t1:.2f} s", flush=True)

        run.result = loop["run"](run)
        run.window_closes()
        run.tap.finish()
        run.records = run.child.finish()
        w0, w1 = run.result["window"]
        print(f"gc in the window: {run.gc_log.describe(w0, w1)}", flush=True)
        if "readings" in loop:
            try:
                loop["readings"](run, run.result, run.records)
            except drivers.RunFailed:
                # say what the API reads before giving up
                verify(run, config_mod, fleet, agent.address)
                raise
        setup_s = w0 - T_START
        settled = [run.records[i]["settled"]
                   for i in run.result["measured_jobs"]
                   if run.records[i]["settled"] is not None]
        until = max([w1] + settled)
        run.compile_events = run.compile_log.between(w0, until)
        run.tap_window = run.tap.window(w0, until)
        longest = max((e[2] for e in run.compile_events), default=0.0)
        print(f"window: {w1 - w0:.2f} s; jax compile events inside it: "
              f"{len(run.compile_events)}, "
              f"{sum(e[2] for e in run.compile_events):.4f} s in all, "
              f"longest {longest:.4f} s; compile cache {cache_dir}: "
              f"{cache_before} entries before, {cache_entries(cache_dir)} "
              f"now; set-up {setup_s:.2f} s", flush=True)
        if longest >= SLOW_COMPILE_S:
            print(f"benchmark: a jax compile event of {longest:.2f} s fell "
                  f"inside the window: warm-up missed a shape",
                  file=sys.stderr)
            return EXIT_COMPILED_IN_WINDOW

        measured = [run.records[i] for i in run.result["measured_jobs"]]
        failed = sum(1 for r in measured
                     if r["status"] != "complete" or r["failed_tg"])
        failures = verify(run, config_mod, fleet, agent.address)
        for f in failures:
            print(f"FAILED {f}", file=sys.stderr)

        metrics = {}
        out = {"correct": not failures, "attempted": len(measured),
               "failed": failed, "metrics": metrics, "device": device}
        if trace:
            run.reduce_trace(cell["chips"])
            # a metric's reader is the file of its name, or the one the
            # cell's file gives it: a per-layer metric names one end-to-end
            # metric, so a reading that moves another cell's goes by
            # another name there
            for name in cell["per_layer"]:
                mod = load_module("layer_metrics",
                                  cell.get("readers", {}).get(name, name))
                value = mod.read(run)
                if value is not None:
                    metrics[name] = {"value": value, "unit": mod.UNIT}
            if run.trace:
                device["busy_s"] = run.trace["busy_s"]
                device["window_s"] = run.trace["window_s"]
                out["breakdown"] = {
                    "device_ops": [[n, s] for n, s
                                   in run.trace["device_ops"]],
                    "idle_gaps": [[n, s] for n, s
                                  in run.trace["idle_gaps"]]}
        else:
            readings = dict(run.result["end_to_end"])
            readings["setup_s"] = (setup_s, "s")
            for name in cell["end_to_end"]:
                value, unit = readings[name]
                metrics[name] = {"value": value, "unit": unit}
        device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices[:cell["chips"]])
        print(json.dumps(out), flush=True)
        return 0
    except drivers.RunFailed as e:
        print(f"benchmark: run failed: {e}", file=sys.stderr)
        return EXIT_RUN_FAILED
    finally:
        if run.child is not None:
            run.child.kill()
        run.abandon_trace()
        if agent is not None:
            agent.shutdown()
        RING.min_level = ring_level
        shutil.rmtree(run.tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
