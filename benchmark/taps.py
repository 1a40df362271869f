"""What the benchmark reads from the running program: jax's own compile
events, the wave records and stage intervals, and counter snapshots.

Everything here runs in the server's process but only reads; the
StageTap thread exists because the program's rings are bounded
(StageTimers keeps 4,096 intervals a stage, the flight recorder 512
waves) and a window holds more, so it copies them out once a second.
It runs in traced runs only: end-to-end numbers are taken without it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Tuple

STAGES = ("dispatch", "device", "d2h", "materialize", "commit")


class CompileLog:
    """Every trace, lower and backend-compile event jax reports, with the
    monotonic time it ended (jax 0.9.0: jax._src.dispatch's *_EVENT)."""

    def __init__(self) -> None:
        self.events: List[Tuple[str, float, float]] = []

    def install(self) -> None:
        import jax.monitoring
        from jax._src import dispatch

        names = {dispatch.JAXPR_TRACE_EVENT: "trace",
                 dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lower",
                 dispatch.BACKEND_COMPILE_EVENT: "backend_compile"}

        def on_duration(event: str, seconds: float, **_kw) -> None:
            kind = names.get(event)
            if kind is not None:
                self.events.append((kind, time.monotonic(), seconds))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> List[Tuple[str, float, float]]:
        """Events that ended inside [t0, t1]."""
        return [e for e in list(self.events) if t0 <= e[1] <= t1]


class GcLog:
    """The interpreter's garbage collections, timed: a full collection
    over millions of live allocations stops every thread of the server,
    and shows as a stall in whatever was waiting on it."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[float, float, int]] = []   # (t0, t1, gen)
        self._t0 = 0.0

    def install(self) -> None:
        import gc

        def on_gc(phase: str, info: dict) -> None:
            if phase == "start":
                self._t0 = time.monotonic()
            else:
                self.pauses.append((self._t0, time.monotonic(),
                                    info["generation"]))

        gc.callbacks.append(on_gc)

    def between(self, t0: float, t1: float) -> List[Tuple[float, int]]:
        """(seconds, generation) of the collections begun in [t0, t1]."""
        return [(b - a, g) for a, b, g in list(self.pauses) if t0 <= a <= t1]

    def describe(self, t0: float, t1: float) -> str:
        hit = self.between(t0, t1)
        full = [d for d, g in hit if g == 2]
        return (f"{len(hit)} collections, {sum(d for d, _ in hit):.3f} s; "
                f"{len(full)} full, {sum(full):.3f} s, longest "
                f"{max(full, default=0.0):.3f} s")


class StageTap(threading.Thread):
    """Copies the flight recorder's wave records and the StageTimers'
    intervals out of their rings while the run goes on."""

    def __init__(self, server, period_s: float = 1.0) -> None:
        super().__init__(name="stage-tap", daemon=True)
        self.timers = server.stage_timers
        self.period_s = period_s
        self.waves: Dict[int, dict] = {}
        self.intervals: Dict[str, List[Tuple[int, float, float]]] = {
            s: [] for s in STAGES}
        self._seen = {s: 0 for s in STAGES}
        self._halt = threading.Event()
        self.lost = 0

    def poll(self) -> None:
        from nomad_tpu.core.flightrec import FLIGHT

        for rec in FLIGHT.waves():
            self.waves[rec["Wave"]] = rec
        counts = self.timers.counts()
        for stage in STAGES:
            new = counts.get(stage, 0) - self._seen[stage]
            if new <= 0:
                continue
            ring = self.timers.intervals(stage)
            # the count was read first, so the ring's tail holds at least
            # the `new` intervals (it may hold a few newer ones: those
            # are taken next time)
            extra = self.timers.counts().get(stage, 0) - counts[stage]
            tail = ring[:len(ring) - extra] if extra > 0 else ring
            if new > len(tail):
                self.lost += new - len(tail)
                new = len(tail)
            self.intervals[stage].extend(tail[len(tail) - new:])
            self._seen[stage] = counts[stage]

    def run(self) -> None:
        while not self._halt.wait(self.period_s):
            self.poll()

    def finish(self) -> None:
        self._halt.set()
        if self.is_alive():
            self.join(10)
        self.poll()

    def window(self, t0: float, t1: float) -> dict:
        """Waves first seen, and intervals begun, inside [t0, t1]."""
        return {
            "waves": [w for _, w in sorted(self.waves.items())
                      if t0 <= w.get("T", -1.0) <= t1],
            "intervals": {s: [iv for iv in ivs if t0 <= iv[1] <= t1]
                          for s, ivs in self.intervals.items()},
        }


def counters(server) -> dict:
    """One snapshot of the program's own counters (read twice, at the
    window's ends; the readers take differences)."""
    from nomad_tpu.core.telemetry import REGISTRY

    ex = server.executor
    return {
        "t": time.monotonic(),
        "executor": {k: ex.stats[k] for k in (
            "dispatches", "resident_waves", "upload_bytes")},
        "applier": {"plans_refuted":
                    server.plan_applier.stats["plans_refuted"]},
        "stage_totals": server.stage_timers.totals(),
        "stage_counts": server.stage_timers.counts(),
        "broker_wait": REGISTRY.histogram("nomad.broker.wait_s")
        or {"sum": 0.0, "count": 0},
        "planq_latencies": len(server.plan_queue.latencies),
    }


def wrap_span(run, obj_path: str, span: str) -> None:
    """A span of the benchmark's own around one call into a layer:
    `obj_path` is an attribute path below the server (`engine.place`),
    and every call's (start, end) lands in `run.spans[span]`.  Traced
    runs only."""
    *owners, method = obj_path.split(".")
    owner = run.server
    for name in owners:
        owner = getattr(owner, name)
    inner = getattr(owner, method)
    log = run.spans.setdefault(span, [])

    def timed(*args, **kwargs):
        t0 = time.monotonic()
        try:
            return inner(*args, **kwargs)
        finally:
            log.append((t0, time.monotonic()))

    setattr(owner, method, timed)
