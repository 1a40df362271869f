"""The program's own spans, read from the profiler trace beside the
device's events: what the host was doing in each idle gap, with no
second clock.

`StageTimers.time` (nomad_tpu/core/wavepipe.py) emits every stage
interval as a `jax.profiler.TraceAnnotation` named `nomad.<stage>`, so
it lands on a thread line of the trace's `/host:CPU` plane, on the same
timeline as the `XLA Modules` events of `/device:TPU:<n>`.  The anchor
annotation (benchmark/run.py `_start_trace`) is used for one thing only:
to place the timed windows, each over a second long, on that timeline.

A worker thread is a line that holds `nomad.pass` spans; the other
`nomad.*` spans on such a line are the worker's stages, which never nest
in one another, so what a pass leaves unnamed is its wall less their
union.  `commit` and `store_upsert` are the applier thread's.

A window closes on the client's stamp of the last evaluation's
`complete`, which the eval-status write emits BEFORE the worker settles
and acks that evaluation.  So per-eval readings count the spans that
begin in a window stretched to the end of the last pass begun inside it;
idle time keeps the window as it is (as `device.idle_share` has it).

A program without these spans (any commit before they were added) gives
every reading here as None.

No cell's file lists the readers yet (a PR that changes the program may
not edit a file the benchmark has), so the benchmark's command never
calls them.  Until a `benchmark` PR moves CELLS into the cell files and
BENCHMARK.json,

    python3 -m benchmark.host_spans --workload <cell> --seed <n> --seconds <s>

makes one traced run of the cell as `benchmark.run` does, with CELLS'
metrics appended, in memory, to the cell's per-layer list.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import stats
from benchmark import trace_reduce as tr

PREFIX = "nomad."
PASS = "pass"

Interval = Tuple[float, float]
Stages = Dict[str, List[Interval]]

_views: Dict[str, Optional["View"]] = {}     # by trace file

# per cell, the metrics read here and each one's reader file under
# benchmark/layer_metrics/ (a metric moves one end-to-end metric, so the
# solo cell's go by `solo.*` names, as its other shared readings do)
CELLS: Dict[str, Dict[str, str]] = {
    "csi50k-drain": {
        "worker.prepare_ms_per_eval": "worker.prepare_ms_per_eval",
        "worker.plan_wait_ms_per_eval": "worker.plan_wait_ms_per_eval",
        "worker.settle_ms_per_eval": "worker.settle_ms_per_eval",
        "store.upsert_ms_per_eval": "store.upsert_ms_per_eval",
        "worker.unnamed_share": "worker.unnamed_share",
        "device.idle_unnamed_share": "device.idle_unnamed_share",
    },
    "spread5k-drain": {
        "solo.place_ms_per_eval": "engine.place_ms_per_eval",
        "solo.materialize_ms_per_eval": "materialize.ms_per_eval",
        "solo.plan_wait_ms_per_eval": "worker.plan_wait_ms_per_eval",
        "solo.settle_ms_per_eval": "worker.settle_ms_per_eval",
        "solo.store_upsert_ms_per_eval": "store.upsert_ms_per_eval",
        "solo.unnamed_share": "worker.unnamed_share",
        "solo.device_idle_unnamed_share": "device.idle_unnamed_share",
    },
}


def parse(path: str) -> dict:
    """The `nomad.*` spans of each host thread line that has any
    ({stage: [(t0, t1, wave)]} per line), the first chip's merged busy
    intervals (None where the trace has no device plane) and the
    anchor's time; seconds on the trace's own timeline."""
    from jax.profiler import ProfileData

    lines: List[Dict[str, list]] = []
    busy: Dict[int, List[Interval]] = {}
    anchor_s = None
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    busy[int(m.group(1))] = tr.union(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                stages: Dict[str, list] = {}
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        lo = e.start_ns * 1e-9
                        stages.setdefault(name[len(PREFIX):], []).append(
                            (lo, lo + e.duration_ns * 1e-9, next(
                                (v for k, v in e.stats if k == "wave"), -1)))
                    elif name == tr.ANCHOR and anchor_s is None:
                        anchor_s = e.start_ns * 1e-9
                if stages:
                    lines.append(stages)
    return {"lines": lines, "anchor_s": anchor_s,
            "busy": busy[min(busy)] if busy else None}


class View:
    """One trace's spans and the timed windows, on the trace's clock."""

    def __init__(self, parsed: dict, windows: List[Interval]) -> None:
        self.windows = tr.union(windows)
        self.busy = parsed["busy"]
        self.all: Stages = {}          # every thread's spans, by stage
        self.worker: Stages = {}       # worker lines' spans but `pass`
        self.waves: set = set()        # the wave ids the spans carry
        for stages in parsed["lines"]:
            for stage, spans in stages.items():
                ivs = [(a, b) for a, b, _ in spans]
                self.waves.update(w for _, _, w in spans if w >= 0)
                self.all.setdefault(stage, []).extend(ivs)
                if PASS in stages and stage != PASS:
                    self.worker.setdefault(stage, []).extend(ivs)
        # what the worker's stages other than `pass` cover, merged
        self.named = tr.union(
            iv for ivs in self.worker.values() for iv in ivs)
        self.passes = begun_in(self.all.get(PASS, []), self.windows)
        self.stretched = stretch(self.windows, self.passes)

    # ------------------------------------------------------- per eval

    def ms_per_eval(self, *stages: str) -> Optional[float]:
        """Seconds of the stages' spans begun in the (stretched) windows
        x 1e3 over the `ack` spans begun there: one ack per eval."""
        acks = begun_in(self.all.get("ack", []), self.stretched)
        spans = [iv for s in stages
                 for iv in begun_in(self.all.get(s, []), self.stretched)]
        if not acks or not spans:
            return None
        return tr.total(spans) * 1e3 / len(acks)

    def stage_table(self) -> Dict[str, Tuple[int, float, float, float]]:
        """Per stage, over its spans begun in the (stretched) windows:
        (count, mean, median, longest), seconds.  A mean well over its
        median is a few long spans: a full collection landing in one."""
        out = {}
        for stage, ivs in self.all.items():
            d = [b - a for a, b in begun_in(ivs, self.stretched)]
            if d:
                out[stage] = (len(d), sum(d) / len(d), stats.median(d),
                              max(d))
        return out

    def unnamed_share(self) -> Optional[float]:
        """100 x (1 - worker stages inside the windows' passes / those
        passes)."""
        wall = tr.total(self.passes)
        if not wall:
            return None
        return 100.0 * (1.0 - tr.total(tr.clip(self.named, tr.union(
            self.passes))) / wall)

    # ------------------------------------------------------ idle time

    def idle(self) -> Optional[List[Interval]]:
        if self.busy is None:
            return None
        return tr.gaps(self.busy, self.windows)

    def idle_unnamed_share(self) -> Optional[float]:
        """100 x idle seconds in the windows that no worker stage other
        than `pass` covers / idle seconds there."""
        idle = self.idle()
        if not idle or not self.worker:
            return None
        return 100.0 * uncovered(idle, self.named) / tr.total(idle)

    def idle_table(self) -> Optional[dict]:
        """Each idle gap split by coverage: for every stage the idle
        seconds its spans cover (stages of different threads overlap,
        so the rows need not sum to the idle time), the seconds only a
        `pass` covers, and those no `nomad.*` span of any thread
        covers."""
        idle = self.idle()
        if not idle or not self.all:
            return None
        by_stage = split_by_coverage(idle, self.all)
        passes = tr.union(self.all.get(PASS, []))
        anything = tr.union(iv for ivs in self.all.values() for iv in ivs)
        return {"idle_s": tr.total(idle),
                "stages": sorted(((s, v) for s, v in by_stage.items()
                                  if s != PASS), key=lambda kv: -kv[1]),
                "pass_alone_s": uncovered(tr.clip(idle, passes), self.named),
                "nothing_s": uncovered(idle, anything)}


# ------------------------------------------------ interval arithmetic

def begun_in(spans: List[Interval], windows: List[Interval]
             ) -> List[Interval]:
    """The spans that begin inside one of the merged windows (whole: a
    span that straddles a window's end counts, one that straddles its
    start does not)."""
    return [(a, b) for a, b in spans
            if any(lo <= a <= hi for lo, hi in windows)]


def stretch(windows: List[Interval], passes: List[Interval]
            ) -> List[Interval]:
    """Each window, its end moved to the end of the last pass begun in
    it."""
    return tr.union(
        (lo, max([hi] + [b for a, b in passes if lo <= a <= hi]))
        for lo, hi in windows)


def split_by_coverage(idle: List[Interval], stages: Stages
                      ) -> Dict[str, float]:
    """For each stage, the seconds of the idle gaps that its spans
    cover: a gap two stages share is split between them by what each
    covers, not given whole to the longer."""
    return {s: tr.total(tr.clip(tr.union(ivs), idle))
            for s, ivs in stages.items()}


def uncovered(idle: List[Interval], cover: List[Interval]) -> float:
    """Seconds of the merged `idle` intervals outside merged `cover`."""
    return tr.total(idle) - tr.total(tr.clip(cover, idle))


# ----------------------------------------------------------- the run

def view(run) -> Optional[View]:
    """The View of a traced run (benchmark/run.py's Run), made once; the
    idle table is printed then, on a line before the result line."""
    if getattr(run, "_trace_state", "off") != "done":
        return None
    path = tr.find_xplane(os.path.join(run.tmp, "trace"))
    if path is None:
        return None
    if path in _views:
        return _views[path]
    parsed = parse(path)
    v = None
    if parsed["anchor_s"] is not None and parsed["lines"]:
        # trace time = host time - offset; good to about a millisecond,
        # and used for the windows' edges alone
        offset = run._anchor - parsed["anchor_s"]
        timed = tr.clip(tr.union(run.result["timed"]),
                        [(run._trace_t0, run._trace_t1)])
        v = View(parsed, [(lo - offset, hi - offset) for lo, hi in timed])
        print(describe(v), flush=True)
    _views[path] = v
    return v


def describe(v: View) -> str:
    t = v.idle_table()
    evals = len(begun_in(v.all.get("ack", []), v.stretched))
    head = (f"host spans: {sum(len(x) for x in v.all.values())} nomad.* "
            f"spans on the trace's own clock, {len(v.waves)} waves named "
            f"on them, {len(v.passes)} passes and {evals} evals begun in "
            f"the timed windows")
    head += ("; spans begun there, stage count mean/median/longest ms: "
             + " ".join(
                 f"{s} {n} {mean * 1e3:.3f}/{med * 1e3:.3f}/{top * 1e3:.3f}"
                 for s, (n, mean, med, top) in sorted(
                     v.stage_table().items(),
                     key=lambda kv: -kv[1][0] * kv[1][1])))
    if t is None:
        return head + "; no device plane, so no idle table"
    rows = " ".join(f"{s} {secs:.4f}" for s, secs in t["stages"])
    return (f"{head}; device idle {t['idle_s']:.4f} s there, each gap "
            f"split by coverage (s covered by each stage; the applier's "
            f"commit and store_upsert run beside the worker's stages): "
            f"{rows}; pass alone {t['pass_alone_s']:.4f}; no span "
            f"{t['nothing_s']:.4f}")


def ms_per_eval(run, *stages: str) -> Optional[float]:
    v = view(run)
    return v.ms_per_eval(*stages) if v is not None else None


def unnamed_share(run) -> Optional[float]:
    v = view(run)
    return v.unnamed_share() if v is not None else None


def idle_unnamed_share(run) -> Optional[float]:
    v = view(run)
    return v.idle_unnamed_share() if v is not None else None


def main(argv=None) -> int:
    from benchmark import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, choices=sorted(CELLS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    extra = CELLS[args.workload]
    plain = bench_run.load_json

    def with_spans(kind: str, name: str) -> dict:
        doc = plain(kind, name)
        if kind == "workloads":
            doc["per_layer"] = doc["per_layer"] + list(extra)
            doc["readers"] = {**doc.get("readers", {}), **extra}
        return doc

    bench_run.load_json = with_spans
    return bench_run.run_cell(args.workload, args.seed, args.seconds, True)


if __name__ == "__main__":
    sys.exit(main())
