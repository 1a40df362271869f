#!/usr/bin/env python3
"""The benchmark's HTTP client: a process of its own, stdlib only.

It never imports `jax`, `nomad_tpu` or the `benchmark` package, so it
shares neither the server's interpreter lock nor its chip.  The parent
(benchmark/run.py) writes the job bodies and due times to a plan file
and drives this process over a pipe, one JSON object per line:

    -> {"op": "register", "lo": i, "hi": j}      post jobs[i:j] at once
    <- {"ev": "registered", ...}
    -> {"op": "await", "timeout_s": s}           until every eval of the
    <- {"ev": "settled", "t_last": t, ...}       jobs posted so far settled
    -> {"op": "deregister", "lo": i, "hi": j,    DELETE jobs[i:j] with purge
        "timeout_s": s}                          and wait until each
    <- {"ev": "deregistered", ...}               deregistration eval settled
    -> {"op": "open", "lo": i, "hi": j, "t0": t, "grace_s": g}
    <- {"ev": "open_done", ...}                  each job posted when due
    -> {"op": "quit"}                            write the records, exit

Every time is `time.monotonic()`: CLOCK_MONOTONIC is one clock for all
processes of a machine, so the parent's release stamp and this
process's stamps compare directly.  Completion is read from
`/v1/event/stream?topic=Evaluation`, one line per event, stamped when
the line arrives: no polling, no sleep between looks.
"""

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlsplit

TERMINAL = ("complete", "failed", "canceled")


class Events(threading.Thread):
    """Follows the Evaluation topic; `settled[eval_id] = (t, status,
    failed_tg_allocs)` for every eval seen in a terminal status."""

    def __init__(self, host, port):
        super().__init__(name="events", daemon=True)
        self.host, self.port = host, port
        self.cv = threading.Condition()
        self.settled = {}
        self.connected = threading.Event()
        self.error = None

    def run(self):
        try:
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=600)
            conn.request("GET", "/v1/event/stream?topic=Evaluation")
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"event stream: HTTP {resp.status}")
            self.connected.set()
            while True:
                line = resp.readline()
                if not line:
                    return
                t = time.monotonic()
                if len(line) < 8:          # the idle heartbeat "{}"
                    continue
                for ev in json.loads(line).get("Events", ()):
                    p = ev.get("Payload") or {}
                    if (ev.get("Topic") == "Evaluation"
                            and p.get("Status") in TERMINAL):
                        with self.cv:
                            self.settled.setdefault(
                                ev["Key"], (t, p["Status"],
                                            bool(p.get("FailedTGAllocs"))))
                            self.cv.notify_all()
        except Exception as e:  # noqa: BLE001 - reported to the parent
            self.error = repr(e)
            self.connected.set()
            with self.cv:
                self.cv.notify_all()


class Client:
    def __init__(self, address, plan_path, connections):
        u = urlsplit(address)
        self.host, self.port = u.hostname, u.port
        self.due, self.bodies = [], []
        with open(plan_path, "rb") as f:
            for line in f:
                due, _, body = line.partition(b"\t")
                self.due.append(float(due))
                self.bodies.append(body.rstrip(b"\n"))
        n = len(self.bodies)
        self.sent = [None] * n
        self.acked = [None] * n
        self.http = [0] * n
        self.eval_id = [""] * n
        self.posted = []              # indices with an eval id, in order
        self.awaited = 0              # how many of them _await has taken
        self.unsettled = []           # eval ids it is still waiting for
        self.connections = connections
        self.events = Events(self.host, self.port)
        self.events.start()
        self.events.connected.wait(30)
        if self.events.error or not self.events.connected.is_set():
            raise RuntimeError(f"event stream: {self.events.error}")

    # ------------------------------------------------------------ posting

    def _post(self, conn, i):
        self.sent[i] = time.monotonic()
        try:
            conn.request("PUT", "/v1/jobs", body=self.bodies[i],
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            self.acked[i] = time.monotonic()
            self.http[i] = resp.status
            if resp.status == 200:
                self.eval_id[i] = json.loads(data).get("EvalID", "")
        except (OSError, http.client.HTTPException) as e:
            self.http[i] = -1
            conn.close()
            sys.stderr.write(f"client: job {i}: {e!r}\n")

    def _run_senders(self, lo, hi, t0):
        """Post jobs[lo:hi] over the connections.  `t0` None: as fast as
        they go.  Else job i goes at t0 + due[i]; a sender takes the next
        index, in order, and sleeps until it is due."""
        nxt = [lo]
        lock = threading.Lock()

        def sender():
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=120)
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= hi:
                    break
                if t0 is not None:
                    wait = t0 + self.due[i] - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                self._post(conn, i)
            conn.close()

        threads = [threading.Thread(target=sender, daemon=True)
                   for _ in range(min(self.connections, hi - lo))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ok = [i for i in range(lo, hi) if self.eval_id[i]]
        self.posted.extend(ok)
        return len(ok)

    # ------------------------------------------------------------- waiting

    def _await(self, timeout_s):
        """Until every posted job's eval has settled; returns (the stamp
        of the last one to settle in this call, how many have not).
        Only the evals still open are looked at, so a wake-up costs the
        size of the backlog, not of the run."""
        ev = self.events
        want = self.unsettled + [self.eval_id[i]
                                 for i in self.posted[self.awaited:]]
        self.awaited = len(self.posted)
        asked = want
        deadline = time.monotonic() + timeout_s
        with ev.cv:
            while True:
                want = [e for e in want if e not in ev.settled]
                left = deadline - time.monotonic()
                if not want or ev.error or left <= 0:
                    break
                ev.cv.wait(left)
            t_last = max((ev.settled[e][0] for e in asked
                          if e in ev.settled), default=None)
        self.unsettled = want
        return t_last, len(want)

    # ------------------------------------------------------------ commands

    def register(self, lo, hi):
        t0 = time.monotonic()
        n_ok = self._run_senders(lo, hi, None)
        return {"ev": "registered", "ok": n_ok, "asked": hi - lo,
                "seconds": time.monotonic() - t0}

    def deregister(self, lo, hi, timeout_s):
        """Stops and purges jobs[lo:hi] (`DELETE /v1/job/<id>?purge=true`)
        and waits for each deregistration's evaluation to settle: its
        allocations are then stopped and no longer count against their
        nodes."""
        t0 = time.monotonic()
        conn = http.client.HTTPConnection(self.host, self.port, timeout=120)
        evals = []
        for i in range(lo, hi):
            job_id = json.loads(self.bodies[i])["Job"]["ID"]
            conn.request("DELETE", f"/v1/job/{job_id}?purge=true")
            resp = conn.getresponse()
            data = resp.read()
            if resp.status == 200 and json.loads(data).get("EvalID"):
                evals.append(json.loads(data)["EvalID"])
        conn.close()
        ev = self.events
        deadline = time.monotonic() + timeout_s
        want = evals
        with ev.cv:
            while True:
                want = [e for e in want if e not in ev.settled]
                left = deadline - time.monotonic()
                if not want or ev.error or left <= 0:
                    break
                ev.cv.wait(left)
        bad = [e for e in evals if e in ev.settled
               and ev.settled[e][1] != "complete"]
        return {"ev": "deregistered", "ok": len(evals) - len(bad),
                "asked": hi - lo, "missing": len(want),
                "seconds": time.monotonic() - t0, "error": ev.error}

    def settle(self, timeout_s):
        t_last, missing = self._await(timeout_s)
        return {"ev": "settled", "t_last": t_last, "missing": missing,
                "error": self.events.error}

    def open_loop(self, lo, hi, t0, grace_s):
        n_ok = self._run_senders(lo, hi, t0)
        left = max(t0 + self.due[hi - 1] + grace_s - time.monotonic(), 0.0)
        _, missing = self._await(left)
        return {"ev": "open_done", "ok": n_ok, "asked": hi - lo,
                "missing": missing, "error": self.events.error}

    def records(self):
        settled = self.events.settled
        rows = []
        for i in range(len(self.bodies)):
            t, status, failed_tg = settled.get(self.eval_id[i],
                                               (None, "", False))
            rows.append({"i": i, "due": self.due[i], "sent": self.sent[i],
                         "acked": self.acked[i], "http": self.http[i],
                         "eval_id": self.eval_id[i], "settled": t,
                         "status": status, "failed_tg": failed_tg})
        return rows


def main(argv):
    address, plan_path, out_path, connections = argv
    client = Client(address, plan_path, int(connections))
    out = sys.stdout
    out.write(json.dumps({"ev": "ready", "jobs": len(client.bodies)}) + "\n")
    out.flush()
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd["op"]
        if op == "register":
            reply = client.register(cmd["lo"], cmd["hi"])
        elif op == "await":
            reply = client.settle(cmd["timeout_s"])
        elif op == "deregister":
            reply = client.deregister(cmd["lo"], cmd["hi"],
                                      cmd["timeout_s"])
        elif op == "open":
            reply = client.open_loop(cmd["lo"], cmd["hi"], cmd["t0"],
                                     cmd["grace_s"])
        elif op == "quit":
            with open(out_path, "w") as f:
                json.dump(client.records(), f)
            out.write(json.dumps({"ev": "bye"}) + "\n")
            out.flush()
            return 0
        else:
            raise ValueError(f"unknown op {op!r}")
        out.write(json.dumps(reply) + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
