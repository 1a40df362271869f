"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy time, kernel time by program, the ops that took
most, and the idle gaps.  Checked by benchmark/selftest against
selftest/data/small_trace.xplane.pb, recorded on a TPU v5e.

What the TPU's trace holds (read by hand from that file): a plane
`/device:TPU:<n>` per chip whose line `XLA Modules` has one event per
program execution, named `jit_<function>(<fingerprint>)`, and whose line
`XLA Ops` has one event per HLO op inside it (a `%while` spans its
body's ops); a plane `/host:CPU` with one line per host thread, where
`jax.profiler.TraceAnnotation` spans land.  All planes share one
timeline in nanoseconds, the device's leading the host's by about a
millisecond in the recorded trace.

Busy time is the union of the `XLA Modules` intervals: in the recorded
trace it differs from the union of the `XLA Ops` intervals by 0.01 %,
and a four-thousand-step scan writes millions of op events but one
module event.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

ANCHOR = "benchmark_anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# container ops whose interval is their children's, not work of their own
CONTAINERS = ("%while", "%conditional", "%call")
MAX_OP_EVENTS_BYTES = 256 << 20

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: List[Interval], windows: List[Interval]
         ) -> List[Interval]:
    """The parts of merged `intervals` that lie inside merged `windows`."""
    out: List[Interval] = []
    i = j = 0
    while i < len(intervals) and j < len(windows):
        lo = max(intervals[i][0], windows[j][0])
        hi = min(intervals[i][1], windows[j][1])
        if hi > lo:
            out.append((lo, hi))
        if intervals[i][1] < windows[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def gaps(busy: List[Interval], windows: List[Interval]) -> List[Interval]:
    """The idle intervals: each window minus the busy intervals."""
    out: List[Interval] = []
    for w_lo, w_hi in windows:
        at = w_lo
        for lo, hi in clip(busy, [(w_lo, w_hi)]):
            if lo > at:
                out.append((at, lo))
            at = max(at, hi)
        if w_hi > at:
            out.append((at, w_hi))
    return out


def program_name(module_event_name: str) -> str:
    """`jit_place_packed(123...)` -> `jit_place_packed`."""
    return module_event_name.split("(", 1)[0]


def op_name(op_event_name: str) -> str:
    """`%fusion.8 = f32[..] fusion(...)` -> `%fusion.8 fusion`."""
    head, _, rest = op_event_name.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    return f"{head} {m.group(1)}" if m else head


def reduce_trace(path: str, with_ops: bool = True) -> dict:
    """Per chip: merged busy intervals, per-program (launches, seconds),
    per-op seconds; plus the anchor's trace time.  Times in seconds on
    the trace's own timeline."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[int, dict] = {}
    anchor_s = None
    read_ops = with_ops and os.path.getsize(path) <= MAX_OP_EVENTS_BYTES
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = {"busy": [], "programs": {}, "ops": {}}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    spans = []
                    for e in line.events:
                        lo = e.start_ns * 1e-9
                        dur = e.duration_ns * 1e-9
                        spans.append((lo, lo + dur))
                        p = chip["programs"].setdefault(
                            program_name(e.name), [0, 0.0])
                        p[0] += 1
                        p[1] += dur
                    chip["busy"] = union(spans)
                elif line.name == "XLA Ops" and read_ops:
                    ops = chip["ops"]
                    for e in line.events:
                        name = e.name
                        if name.startswith(CONTAINERS):
                            continue
                        ops[name] = ops.get(name, 0.0) + e.duration_ns * 1e-9
            chips[int(m.group(1))] = chip
        elif plane.name == "/host:CPU" and anchor_s is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor_s = e.start_ns * 1e-9
                        break
                if anchor_s is not None:
                    break
    return {"chips": chips, "anchor_s": anchor_s, "ops_read": read_ops}


def summarize(reduced: dict, windows: Optional[List[Interval]] = None,
              offset_s: float = 0.0, n_chips: int = 1) -> dict:
    """Busy and window seconds (averaged over the chips used), kernel
    sums and the ten heaviest ops.  `windows` are the timed intervals on
    the caller's clock and `offset_s` what to add to a trace time to get
    that clock; with no windows, the span of the device events."""
    chips = reduced["chips"]
    if not chips:
        return {}
    used = sorted(chips)[:n_chips]
    busy_s, window_s = 0.0, 0.0
    idle: List[Interval] = []
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    for c in used:
        chip = chips[c]
        busy = [(lo + offset_s, hi + offset_s) for lo, hi in chip["busy"]]
        if windows is None:
            wins = [(busy[0][0], busy[-1][1])] if busy else []
        else:
            wins = union(windows)
        busy_s += total(clip(busy, wins))
        window_s += total(wins)
        if c == used[0]:
            idle = gaps(busy, wins)
        for name, (n, s) in chip["programs"].items():
            p = programs.setdefault(name, [0, 0.0])
            p[0] += n
            p[1] += s
        for name, s in chip["ops"].items():
            ops[name] = ops.get(name, 0.0) + s
    by_op: Dict[str, float] = {}
    for name, s in ops.items():
        short = op_name(name)
        by_op[short] = by_op.get(short, 0.0) + s
    heaviest = by_op or {n: s for n, (_, s) in programs.items()}
    return {
        "busy_s": busy_s / len(used),
        "window_s": window_s / len(used),
        "programs": {n: (int(c), s) for n, (c, s) in programs.items()},
        "device_ops": sorted(heaviest.items(), key=lambda kv: -kv[1])[:10],
        "idle": idle,
    }


def attribute_gaps(idle: List[Interval], stages: Dict[str, List[Interval]],
                   top: int = 10) -> List[Tuple[str, float]]:
    """Names each idle gap by the host stage whose intervals cover most
    of it (`host:<stage>`, or `host:other` when none covers any), sums
    by name and returns the longest.  The stages are StageTimers
    intervals on the host's clock, joined to the trace by the one anchor
    taken at start_trace, so a gap's edges are good to about a
    millisecond."""
    merged = {s: union(iv) for s, iv in stages.items()}
    by_name: Dict[str, float] = {}
    for gap in idle:
        best, best_s = "other", 0.0
        for stage, ivs in merged.items():
            covered = total(clip(ivs, [gap]))
            if covered > best_s:
                best, best_s = stage, covered
        name = f"host:{best}"
        by_name[name] = by_name.get(name, 0.0) + (gap[1] - gap[0])
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
