"""What the program's spans carry as arguments, read from the trace: a
pass's interpreter budget (whose CPU its wall held) and the collector's
generations.

`host_spans.parse` keeps of a span's arguments only `wave`, so this
reads the `/host:CPU` plane a second time, once a trace, for two names:

`nomad.cpu`: the worker leaves a marker immediately before a
`nomad.pass` span opens and immediately after it closes
(nomad_tpu/core/wavepipe.py `mark_cpu`).  A marker carries the
CUMULATIVE CPU microseconds of the process's Python threads by role
(`worker_us`, `applier_us`, `http_us`, `other_us`), each thread's own
`time.thread_time()` as it last stamped itself, and of the whole process
(`process_us`: every thread, XLA's and the profiler's too).  A pass's
budget is the difference of its two markers.  Where the four roles sum
to less than the pass's wall, the rest is time in which no Python thread
ran (the device, a socket, a condition wait).  They can sum to MORE:
CPU seconds are not seconds of the interpreter lock, and threads run
beside one another outside it (system calls, native code that released
it); no sum of roles can pass the process's.  The thread clock of the
chip's host ticks coarsely (PERF.md section 3 has the measured tick), so
the readers report SUMS over the passes begun in the timed, traced
windows, never one pass.

`nomad.gc`: a collection of generation 1 or 2, with its `generation`
(nomad_tpu/core/telemetry.py).  Held here against `taps.GcLog`, which
times the same collections from outside on the host's clock: the two
must agree.

A program without these names (any commit before they were added) gives
None everywhere.
"""

from __future__ import annotations

import bisect
import os
from typing import Dict, List, Optional, Tuple

from benchmark import host_spans
from benchmark import trace_reduce as tr

MARK = host_spans.PREFIX + "cpu"
PASS = host_spans.PREFIX + host_spans.PASS
GC = host_spans.PREFIX + "gc"
ROLES = ("worker_us", "applier_us", "http_us", "other_us")
PROCESS = "process_us"       # absent from a marker: read as 0

Interval = host_spans.Interval
Mark = Tuple[float, Dict[str, int]]          # (time, role -> cpu us)
Line = Tuple[List[Interval], List[Mark]]

_read: Dict[str, dict] = {}                  # by trace file


def parse(path: str) -> dict:
    """`lines`: per host thread line that holds markers, its
    `nomad.pass` spans and its markers, each sorted by time; `gc`: every
    line's `nomad.gc` spans as (t0, t1, generation); `anchor_s`.
    Seconds on the trace's own timeline, as `host_spans.parse` gives
    them."""
    from jax.profiler import ProfileData

    lines: List[Line] = []
    collections: List[Tuple[float, float, int]] = []
    anchor_s = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            passes, marks = [], []
            for e in line.events:
                name = e.name
                if name == MARK:
                    stats = dict(e.stats)
                    marks.append((e.start_ns * 1e-9, {
                        r: int(stats.get(r, 0)) for r in ROLES + (PROCESS,)}))
                elif name == PASS:
                    lo = e.start_ns * 1e-9
                    passes.append((lo, lo + e.duration_ns * 1e-9))
                elif name == GC:
                    lo = e.start_ns * 1e-9
                    collections.append((
                        lo, lo + e.duration_ns * 1e-9,
                        int(dict(e.stats).get("generation", -1))))
                elif name == tr.ANCHOR and anchor_s is None:
                    anchor_s = e.start_ns * 1e-9
            if marks:
                lines.append((sorted(passes),
                              sorted(marks, key=lambda m: m[0])))
    return {"lines": lines, "gc": sorted(collections), "anchor_s": anchor_s}


def budget(lines: List[Line], windows: List[Interval]) -> Optional[dict]:
    """Over the passes begun in the merged windows that have a marker on
    either side: their count, their wall in seconds and each role's (and
    the process's) CPU microseconds between a pass's two markers,
    summed."""
    out = {"passes": 0, "wall_s": 0.0, **{r: 0 for r in ROLES + (PROCESS,)}}
    for passes, marks in lines:
        times = [t for t, _ in marks]
        for a, b in host_spans.begun_in(passes, windows):
            i = bisect.bisect_right(times, a) - 1     # last one before
            j = bisect.bisect_left(times, b)          # first one after
            if i < 0 or j >= len(marks):
                continue
            out["passes"] += 1
            out["wall_s"] += b - a
            for r in ROLES + (PROCESS,):
                out[r] += marks[j][1].get(r, 0) - marks[i][1].get(r, 0)
    return out if out["passes"] else None


def read(run) -> Optional[dict]:
    """What a traced run's (benchmark/run.py's Run) trace holds of the
    two names, read once: `parse`'s keys and `budget`, the budget of the
    passes begun in the timed windows.  What they say is printed then,
    on lines before the result line."""
    view = host_spans.view(run)
    if view is None:
        return None
    path = tr.find_xplane(os.path.join(run.tmp, "trace"))
    if path not in _read:
        got = _read[path] = parse(path)
        got["budget"] = budget(got["lines"], view.windows)
        if got["budget"] is not None:
            print(describe(got["budget"]), flush=True)
        if got["gc"]:
            print(describe_gc(gc_against_log(run)), flush=True)
    return _read[path]


def of(run) -> Optional[dict]:
    got = read(run)
    return got["budget"] if got is not None else None


def describe(b: dict) -> str:
    n = b["passes"]
    ms = {r: b[r] * 1e-3 / n for r in ROLES}
    wall_ms = b["wall_s"] * 1e3 / n
    roles_ms = sum(ms.values())
    rest = (f"{wall_ms - roles_ms:.1f} ms of a pass no Python thread ran"
            if roles_ms <= wall_ms else
            f"{roles_ms - wall_ms:.1f} ms a pass MORE than its wall: so "
            f"much at least ran beside another thread, outside the "
            f"interpreter lock")
    return (f"cpu marks: {n} passes begun in the timed windows, "
            f"{wall_ms:.1f} ms a pass; CPU ms a pass by thread role "
            f"between its two nomad.cpu markers: worker "
            f"{ms['worker_us']:.1f}, applier {ms['applier_us']:.1f}, http "
            f"{ms['http_us']:.1f}, other {ms['other_us']:.1f}, together "
            f"{roles_ms:.1f} ({100.0 * roles_ms / wall_ms:.1f} % of the "
            f"wall); {rest}; the whole process "
            f"{b[PROCESS] * 1e-3 / n:.1f} (every thread, XLA's and the "
            f"profiler's too); sums over the passes: the thread clock "
            f"ticks coarsely")


def gc_against_log(run) -> Optional[Dict[int, Tuple[int, float, int, float]]]:
    """Per generation 1 and 2, over the collections begun in the traced
    seconds: (the program's `nomad.gc` spans, their seconds,
    `taps.GcLog`'s collections, their seconds)."""
    got = read(run)
    if got is None or got["anchor_s"] is None:
        return None
    offset = run._anchor - got["anchor_s"]
    t0, t1 = run._trace_t0, run._trace_t1
    spans = [(b - a, g) for a, b, g in got["gc"] if t0 <= a + offset <= t1]
    log = run.gc_log.between(t0, t1)
    out = {}
    for gen in (1, 2):
        mine = [d for d, g in spans if g == gen]
        theirs = [d for d, g in log if g == gen]
        out[gen] = (len(mine), sum(mine), len(theirs), sum(theirs))
    return out


def describe_gc(by_gen: Optional[dict]) -> str:
    if by_gen is None:
        return "gc spans: no anchor in the trace to place them by"
    return ("gc spans: nomad.gc in the traced seconds against taps.GcLog "
            "there (host clock, from outside); " + "; ".join(
                f"generation {gen}: {n} spans {s:.4f} s, GcLog {m} "
                f"collections {t:.4f} s"
                for gen, (n, s, m, t) in sorted(by_gen.items())))


def ms_per_pass(run, *roles: str) -> Optional[float]:
    b = of(run)
    if b is None:
        return None
    return sum(b[r] for r in roles) * 1e-3 / b["passes"]


def held_share(run) -> Optional[float]:
    b = of(run)
    if b is None or not b["wall_s"]:
        return None
    return 100.0 * sum(b[r] for r in ROLES) * 1e-6 / b["wall_s"]
