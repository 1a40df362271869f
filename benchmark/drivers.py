"""The two traffic drivers: `drain` (closed loop on a backlog) and `open`
(open loop at a fixed rate).  A traffic file names one by its `loop` key
and gives its parameters; nothing here knows a cell or a configuration.

A driver gets the run (benchmark/run.py's Run), plans the jobs it may
send, then drives the child client and the server's scheduling hold.  It
returns the end-to-end readings it can take and the timed intervals.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict

from benchmark import schedule, stats


class RunFailed(RuntimeError):
    """The run cannot give a measurement (too few cycles or jobs, a
    starved generator, a cycle that never settled): no result line."""


# ------------------------------------------------------------------ drain

def plan_drain(traffic: dict, seed: int, seconds: float) -> list:
    """Due times are unused in a drain: every job goes as fast as the
    connections take it."""
    del seed, seconds
    return [0.0] * (traffic["jobs_per_cycle"] * traffic["max_cycles"])


def run_drain(run) -> dict:
    tr = run.traffic
    per_cycle = tr["jobs_per_cycle"]
    placements = per_cycle * run.cfg["count_per_job"]
    server, child = run.server, run.child
    cycles = []                       # (t_release, t_last, registered_s)
    resets = []                       # seconds each took, untimed
    window_t0 = None
    n = 0
    while n < tr["max_cycles"]:
        measured = n >= tr["warmup_cycles"]
        now = time.monotonic()
        if measured and window_t0 is None:
            window_t0 = now
            run.window_opens(now)
        if measured and now - window_t0 >= run.seconds:
            break
        if n and tr.get("between_cycles") == "deregister":
            # every cycle starts from the same fleet: read the last
            # cycle's allocations for the check, then stop and purge its
            # jobs; untimed, like registration.  The run's last cycle
            # stays, for the check's reads at the end
            last = range((n - 1) * per_cycle, n * per_cycle)
            run.capture(last)
            gone = child.call({"op": "deregister", "lo": last.start,
                               "hi": last.stop,
                               "timeout_s": tr["cycle_timeout_s"]})
            if gone["ok"] != per_cycle or gone["missing"]:
                raise RunFailed(f"cycle {n - 1}: {gone['ok']}/{per_cycle} "
                                f"jobs deregistered, {gone['missing']} "
                                f"not settled ({gone.get('error')})")
            resets.append(time.monotonic() - now)
        if measured:
            run.trace_tick(time.monotonic())
        server.stop_scheduling()
        reg = child.call({"op": "register", "lo": n * per_cycle,
                          "hi": (n + 1) * per_cycle})
        if reg["ok"] != per_cycle:
            raise RunFailed(f"cycle {n}: {reg['ok']}/{per_cycle} "
                            f"registrations acknowledged")
        t_release = time.monotonic()
        server.start_scheduling()
        done = child.call({"op": "await",
                           "timeout_s": tr["cycle_timeout_s"]})
        if done["missing"] or done.get("error"):
            raise RunFailed(f"cycle {n}: {done['missing']} evaluations "
                            f"not settled after {tr['cycle_timeout_s']} s "
                            f"({done.get('error')})")
        n += 1
        if measured:
            cycles.append((t_release, done["t_last"], reg["seconds"]))
    window_t1 = time.monotonic()
    run.trace_tick(window_t1, closing=True)
    # a traced run reports no end-to-end metric, and stopping the
    # profiler eats its window
    if len(cycles) < (2 if run.trace_on else tr["min_cycles"]):
        raise RunFailed(f"{len(cycles)} drain cycles in the window, "
                        f"fewer than {tr['min_cycles']}")
    rates = [placements / (t1 - t0) for t0, t1, _ in cycles]
    drains = [t1 - t0 for t0, t1, _ in cycles]
    third = max(len(drains) // 3, 1)
    print(f"drain: {len(cycles)} cycles of {per_cycle} jobs x "
          f"{run.cfg['count_per_job']}; drain s median "
          f"{stats.median(drains):.4f} min {min(drains):.4f} max "
          f"{max(drains):.4f}, first third {stats.median(drains[:third]):.4f}"
          f" last third {stats.median(drains[-third:]):.4f} (a cell that "
          f"slows with history shows here); registration s median "
          f"{stats.median([c[2] for c in cycles]):.3f}"
          + (f"; deregistration between cycles s median "
             f"{stats.median(resets):.3f}" if resets else "")
          + "; each drain: " + " ".join(f"{d:.3f}" for d in drains),
          flush=True)
    lo = tr["warmup_cycles"] * per_cycle
    return {
        "window": (window_t0, window_t1),
        "timed": [(t0, t1) for t0, t1, _ in cycles],
        "measured_jobs": range(lo, n * per_cycle),
        "sent_jobs": range(0, n * per_cycle),
        # under the name the traffic file gives: one bound serves a
        # metric in every cell it is in, so cells that differ in noise
        # report their rate as metrics of their own
        "end_to_end": {tr["reports"]: (stats.median(rates),
                                       "placements/s")},
    }


# ------------------------------------------------------------------- open

def plan_open(traffic: dict, seed: int, seconds: float) -> list:
    """The warm-up bursts' jobs first (no due time), then the schedule."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    return ([0.0] * sum(traffic.get("warmup_bursts", ()))
            + schedule.poisson(seed, traffic["rate_per_s"],
                               traffic["warmup_s"] + seconds))


def warm_bursts(run) -> int:
    """Launches every wave shape the traffic can meet before the clock
    starts: with scheduling held, register a burst of consecutive jobs,
    release, wait; one wave per burst.  The sizes are the traffic
    file's.  Returns the number of jobs used."""
    tr, server, child = run.traffic, run.server, run.child
    lo = 0
    t0 = time.monotonic()
    for size in tr.get("warmup_bursts", ()):
        server.stop_scheduling()
        reg = child.call({"op": "register", "lo": lo, "hi": lo + size})
        server.start_scheduling()
        done = child.call({"op": "await", "timeout_s": 300})
        if reg["ok"] != size or done["missing"] or done.get("error"):
            raise RunFailed(f"warm-up burst of {size}: {reg['ok']} "
                            f"acknowledged, {done['missing']} not settled")
        lo += size
    if lo:
        ex = server.executor.stats
        slow = [e for e in run.compile_log.between(t0, time.monotonic())
                if e[2] >= 1.0]
        print(f"open: {len(tr['warmup_bursts'])} warm-up bursts, {lo} jobs, "
              f"{time.monotonic() - t0:.1f} s; executor dispatches "
              f"{ex['dispatches']} resident {ex['resident_waves']} "
              f"invalidations {ex['invalidations']}; compile events of "
              f"1 s or more: {len(slow)}, {sum(e[2] for e in slow):.1f} s",
              flush=True)
    return lo


def run_open(run) -> dict:
    tr = run.traffic
    due = run.due
    warm = tr["warmup_s"]
    burst_jobs = warm_bursts(run)
    first = next((i for i, d in enumerate(due)
                  if i >= burst_jobs and d >= warm), len(due))
    in_window = len(due) - first
    if in_window < tr["min_jobs"]:
        raise RunFailed(f"{in_window} jobs due in the window, fewer than "
                        f"{tr['min_jobs']}: the tail percentile needs them")
    t0 = time.monotonic() + 0.25
    window = (t0 + warm, t0 + warm + run.seconds)
    run.window_opens(window[0], planned=True)
    run.trace_timer(window[0])
    done = run.child.call({"op": "open", "lo": burst_jobs, "hi": len(due),
                           "t0": t0, "grace_s": tr["grace_s"]})
    if done.get("error"):
        raise RunFailed(f"event stream lost: {done['error']}")
    print(f"open: {len(due)} jobs sent ({first} of them warm-up), "
          f"{done['ok']} acknowledged, {done['missing']} not settled "
          f"{tr['grace_s']} s after the last was due", flush=True)
    return {
        "window": window,
        "timed": [window],
        "measured_jobs": range(first, len(due)),
        "sent_jobs": range(0, len(due)),
        "t0": t0,
        "end_to_end": {},            # from the client's records, below
    }


def open_readings(run, result: dict, records: list) -> None:
    """commit_p50_ms / commit_p99_ms from the client's records: settled
    minus DUE, over every job due in the window.  A job that failed or
    never settled counts as missing any limit: it is read as +inf, so
    it sits past every percentile it can reach."""
    tr = run.traffic
    t0 = result["t0"]
    lat, late = [], []
    for i in result["measured_jobs"]:
        r = records[i]
        due_t = t0 + r["due"]
        if r["sent"] is not None:
            late.append((r["sent"] - due_t) * 1e3)
        ok = (r["status"] == "complete" and not r["failed_tg"]
              and r["settled"] is not None)
        lat.append((r["settled"] - due_t) * 1e3 if ok else math.inf)
    try:
        late_p99 = stats.percentile(late, 0.99)
        p50, p99 = stats.median(lat), stats.percentile(lat, 0.99)
    except stats.TooFewSamples as e:
        raise RunFailed(str(e)) from None
    print(f"open: generator lateness ms p50 {stats.median(late):.3f} "
          f"p99 {late_p99:.3f} max {max(late):.3f} "
          f"(limit p99 {tr['late_p99_limit_ms']})", flush=True)
    if math.isinf(p50) or math.isinf(p99):
        print(f"open: {sum(math.isinf(x) for x in lat)} of {len(lat)} jobs "
              f"failed or never settled", flush=True)
        raise RunFailed("so many jobs failed or never settled that the "
                        "percentile itself is a failure")
    third = max(len(lat) // 3, 1)
    print("open: commit ms p50 %.2f p99 %.2f; p50 by thirds of the window "
          "%s (a backlog that grows shows as a rising third); max %.2f"
          % (p50, p99, " ".join(
              f"{stats.median(lat[k:k + third]):.2f}"
              for k in (0, third, 2 * third)), max(lat)), flush=True)
    if late_p99 > tr["late_p99_limit_ms"]:
        raise RunFailed(f"the generator ran late: p99 {late_p99:.3f} ms "
                        f"over the limit {tr['late_p99_limit_ms']} ms")
    result["end_to_end"] = {"commit_p50_ms": (p50, "ms"),
                            "commit_p99_ms": (p99, "ms")}
    result["late_ms"] = late


LOOPS: Dict[str, Dict[str, Callable]] = {
    "drain": {"plan": plan_drain, "run": run_drain},
    "open": {"plan": plan_open, "run": run_open, "readings": open_readings},
}
