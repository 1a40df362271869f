"""The program's stage spans in the profiler's trace, and the reader
that takes them from it (benchmark/host_spans.py).

A small worker pass (tests/stage_pass.py) runs under
`jax.profiler.start_trace` on the CPU backend, anchored the way
benchmark/run.py anchors a traced run; the `.xplane.pb` is then read
with the benchmark's own code and held against `StageTimers.intervals()`
of the same pass.  Plus the reader's interval arithmetic on hand-made
spans.  The CPU trace has no device plane: idle time is checked on the
hand-made intervals alone.
"""

import os
import signal
import time
from types import SimpleNamespace

import pytest

from benchmark import host_spans as hs
from benchmark import span_args, span_cells
from benchmark import trace_reduce as tr

TIME_LIMIT_S = 300


@pytest.fixture(autouse=True)
def time_limit():
    """A time limit of this file's own: a profiler session that hangs
    must fail here, not stall the whole run."""
    def expired(signum, frame):
        raise TimeoutError(f"no result after {TIME_LIMIT_S} s")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(TIME_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(server, run, view): the pass, a stand-in for benchmark/run.py's
    Run holding what `host_spans.view` reads of it, and the View."""
    import gc

    import jax
    from benchmark import taps
    from stage_pass import run_small_pass

    run = SimpleNamespace(tmp=str(tmp_path_factory.mktemp("bench")),
                          _trace_state="off", result={},
                          gc_log=taps.GcLog())
    run.gc_log.install()              # as benchmark/run.py's run_cell does
    gc_log_hook = gc.callbacks[-1]

    def start():                          # as Run._start_trace does
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(os.path.join(run.tmp, "trace"),
                                 profiler_options=opts)
        run._anchor = run._trace_t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            pass
        run._trace_state = "on"
        for generation in (1, 2, 0):      # whatever else the pass brings
            gc.collect(generation)

    try:
        server = run_small_pass(between=start)
        run._trace_t1 = time.monotonic()
    finally:
        if run._trace_state == "on":
            jax.profiler.stop_trace()
        gc.callbacks.remove(gc_log_hook)
    run._trace_state = "done"
    run.result["timed"] = [(run._trace_t0, run._trace_t1)]
    return server, run, hs.view(run)


def _timer_ms_per_eval(server, *stages):
    t = server.stage_timers
    return (sum(t.totals()[s] for s in stages) * 1e3 / t.counts()["ack"])


class TestSpansInTheTrace:
    def test_every_thread_stage_is_a_span_with_its_wave(self, traced):
        server, run, view = traced
        path = tr.find_xplane(os.path.join(run.tmp, "trace"))
        parsed = hs.parse(path)
        assert parsed["anchor_s"] is not None and parsed["busy"] is None
        counts = server.stage_timers.counts()
        seen = {}
        for line in parsed["lines"]:
            for stage, spans in line.items():
                seen[stage] = seen.get(stage, 0) + len(spans)
        # `cpu` (two markers a pass) and `gc` (the collector's hook)
        # are annotations of their own, no stage of the timers
        assert seen.pop("cpu") == 2 * counts["pass"]
        seen.pop("gc", None)
        # `device` is no thread's wall and is never emitted
        assert seen == {s: n for s, n in counts.items() if s != "device"}
        worker = next(ln for ln in parsed["lines"] if "pass" in ln)
        timers = server.stage_timers
        for stage in ("dispatch", "batch_admin", "plan_wait", "finalize",
                      "dequeue"):
            assert ({w for _, _, w in worker[stage]}
                    == {w for w, _, _ in timers.intervals(stage)}), stage
        assert {w for _, _, w in worker["finalize"]} == {-1}
        assert len({w for _, _, w in worker["batch_admin"]}) == 2
        # the stream's spans lie on its handler's line, the applier's
        # on the appliers', and neither on a worker's
        for stage in ("stream_send", "commit"):
            assert stage in view.all and stage not in view.worker

    def test_worker_stages_nest_under_pass(self, traced):
        _, _, view = traced
        assert len(view.passes) == 3
        assert set(view.worker) - {"gc"} == {
            "dequeue", "cpu", "prepare", "dispatch", "device_wait", "d2h",
            "solo_place", "system_place", "materialize", "plan_wait",
            "finalize", "batch_admin", "eval_update", "ack"}
        for stage, spans in view.worker.items():
            for a, b in spans:
                inside = any(lo <= a and b <= hi for lo, hi in view.passes)
                # the dequeue and the markers lie outside every pass
                assert inside != (stage in ("dequeue", "cpu")), stage
        # the applier's stages are on another thread's line
        assert "commit" in view.all and "commit" not in view.worker
        assert "store_upsert" not in view.worker

    @pytest.mark.parametrize("stage", ["finalize", "batch_admin", "dequeue"])
    def test_new_worker_stage_overlaps_no_other(self, traced, stage):
        _, _, view = traced
        others = [iv for s, ivs in view.worker.items()
                  if s not in (stage, "gc") for iv in ivs]
        assert view.worker[stage]
        for a, b in view.worker[stage]:
            assert all(b <= lo or hi <= a for lo, hi in others)

    def test_empty_poll_leaves_no_dequeue(self, traced):
        # the worker polled the broker every 0.1 s between the drains
        # and after the last; a span only where the dequeue returned
        # work: one a pass here (none was prefetched)
        server, _, view = traced
        assert (len(view.all["dequeue"]) == len(view.passes)
                == server.stage_timers.counts()["dequeue"])

    def test_two_cpu_markers_a_pass_that_never_fall(self, traced):
        _, run, view = traced
        path = tr.find_xplane(os.path.join(run.tmp, "trace"))
        parsed = span_args.parse(path)
        ((passes, marks),) = parsed["lines"]      # the worker's line
        assert passes == sorted(view.all["pass"])
        assert len(marks) == 2 * len(passes)
        times = [t for t, _ in marks]
        for k, (a, b) in enumerate(passes):
            # one immediately before it opens, one after it closes
            assert times[2 * k] <= a and b <= times[2 * k + 1]
        for before, after in zip(marks, marks[1:]):
            for role in span_args.ROLES:
                assert before[1][role] <= after[1][role], role
            # no thread's seconds twice: the process has had them all
            assert sum(after[1][r] for r in span_args.ROLES) \
                <= after[1][span_args.PROCESS]
        first, last = marks[0][1], marks[-1][1]
        assert last["worker_us"] > first["worker_us"]
        assert last["applier_us"] > first["applier_us"]
        assert last["http_us"] > first["http_us"]    # the stream's handler

    def test_gc_spans_agree_with_the_gc_log(self, traced):
        # the program's hook and the benchmark's, which times the same
        # collections from outside on the host's clock: the same count,
        # and seconds within 5 % (or the few microseconds between the
        # two hooks' stamps, on collections this short)
        _, run, _ = traced
        by_gen = span_args.gc_against_log(run)
        for gen in (1, 2):
            spans, spans_s, logged, logged_s = by_gen[gen]
            assert spans == logged >= 1, gen
            assert spans_s == pytest.approx(logged_s, rel=0.05,
                                            abs=20e-6 * spans), gen
        assert "generation 2: " in span_args.describe_gc(by_gen)
        # the youngest generation has no span
        assert {g for _, _, g in span_args.read(run)["gc"]} <= {1, 2}
        assert any(g == 0 for _, g in run.gc_log.between(
            run._trace_t0, run._trace_t1))

    def test_budget_of_the_traced_passes(self, traced):
        _, run, view = traced
        b = span_args.of(run)
        assert b["passes"] == 3
        assert b["wall_s"] == pytest.approx(tr.total(view.passes))
        assert "cpu marks: 3 passes" in span_args.describe(b)
        assert 0.0 < span_args.held_share(run)
        # no thread's CPU twice: the roles' within the process's
        assert sum(b[r] for r in span_args.ROLES) <= b[span_args.PROCESS]
        assert span_args.ms_per_pass(run, "worker_us") > 0.0
        # every reader of span_cells gives a value on this trace but
        # the idle ones (no device plane on the CPU backend)
        from benchmark.loader import load_module
        for reader in set(span_cells.EXTRA["csi50k-drain"].values()):
            value = load_module("layer_metrics", reader).read(run)
            assert (value is None) == (reader == "device.idle_gc_share"), \
                reader

    @pytest.mark.parametrize("stages", [
        ("prepare",), ("plan_wait",), ("eval_update", "ack"),
        ("store_upsert",), ("solo_place",), ("system_place",),
        ("materialize",)],
        ids="+".join)
    def test_ms_per_eval_agrees_with_the_timers(self, traced, stages):
        server, run, _ = traced
        counts = server.stage_timers.counts()
        # within 5 %, or within what the annotations cost inside the
        # timers' two stamps: under a microsecond a span, but the first
        # span a thread closes in a session allocates that thread's
        # event buffer (25-330 us here: on the applier's thread it is
        # a `store_upsert` of 0.2 ms, on a run of eight evals)
        spans = sum(counts[s] for s in stages)
        allowed_ms = (0.5 + 0.005 * spans) / counts["ack"]
        assert hs.ms_per_eval(run, *stages) == pytest.approx(
            _timer_ms_per_eval(server, *stages), rel=0.05, abs=allowed_ms)

    def test_unnamed_share_agrees_with_the_timers(self, traced):
        server, run, view = traced
        totals = server.stage_timers.totals()
        named = sum(totals[s] for s in (
            "prepare", "dispatch", "device_wait", "d2h", "solo_place",
            "system_place", "materialize", "plan_wait", "finalize",
            "batch_admin", "eval_update", "ack"))
        expected = 100.0 * (1.0 - named / totals["pass"])
        # a collection that struck the worker between two stages is
        # named in the trace (`nomad.gc`) and in no timer: leave the
        # collector's spans out of this comparison
        parsed = hs.parse(tr.find_xplane(os.path.join(run.tmp, "trace")))
        for line in parsed["lines"]:
            line.pop("gc", None)
        got = hs.View(parsed, view.windows).unnamed_share()
        assert got <= hs.unnamed_share(run) + 1e-9
        # within 5 % of the named share, which is what is measured
        assert 100.0 - got == pytest.approx(100.0 - expected, rel=0.05)

    def test_no_device_plane_no_idle_reading(self, traced):
        _, run, view = traced
        assert hs.idle_unnamed_share(run) is None
        assert view.idle_table() is None
        assert "no device plane" in hs.describe(view)

    def test_untraced_run_reads_nothing(self):
        run = SimpleNamespace(_trace_state="off", tmp="/nonexistent")
        assert hs.ms_per_eval(run, "prepare") is None
        assert hs.unnamed_share(run) is None
        assert hs.idle_unnamed_share(run) is None


def _view(lines, busy, windows):
    parsed = {"lines": [{s: [(a, b, -1) for a, b in ivs]
                         for s, ivs in line.items()} for line in lines],
              "busy": busy, "anchor_s": 0.0}
    return hs.View(parsed, windows)


class TestArithmetic:
    def test_gap_split_between_two_spans(self):
        # one idle gap 1.0-2.0; materialize covers 0.6 of it, dispatch
        # 0.3, a pass alone 0.1; the longer stage does not take it whole
        v = _view([{"pass": [(0.9, 2.5)], "materialize": [(1.0, 1.6)],
                    "dispatch": [(1.7, 2.2)]}],
                  [(0.0, 1.0), (2.0, 3.0)], [(0.5, 2.5)])
        t = v.idle_table()
        assert t["idle_s"] == pytest.approx(1.0 + 0.5 - 0.5)
        assert dict(t["stages"]) == pytest.approx(
            {"materialize": 0.6, "dispatch": 0.3})
        assert t["pass_alone_s"] == pytest.approx(0.1)
        assert t["nothing_s"] == pytest.approx(0.0)
        assert v.idle_unnamed_share() == pytest.approx(10.0)

    def test_gap_nothing_covers(self):
        v = _view([{"pass": [(0.0, 1.0)], "prepare": [(0.0, 0.25)]}],
                  [(3.0, 4.0)], [(0.0, 2.0)])
        t = v.idle_table()
        assert t["idle_s"] == pytest.approx(2.0)
        assert dict(t["stages"]) == pytest.approx({"prepare": 0.25})
        assert t["pass_alone_s"] == pytest.approx(0.75)
        assert t["nothing_s"] == pytest.approx(1.0)
        assert v.idle_unnamed_share() == pytest.approx(87.5)
        assert v.unnamed_share() == pytest.approx(75.0)

    def test_applier_spans_name_no_worker_time(self):
        # commit runs on a line without a pass: it is in the table, and
        # it does not make the worker's time named
        v = _view([{"pass": [(0.0, 1.0)], "plan_wait": [(0.0, 0.5)]},
                   {"commit": [(0.25, 1.0)]}], [], [(0.0, 1.0)])
        assert dict(v.idle_table()["stages"]) == pytest.approx(
            {"plan_wait": 0.5, "commit": 0.75})
        assert v.idle_unnamed_share() == pytest.approx(50.0)
        assert v.unnamed_share() == pytest.approx(50.0)

    def test_span_straddling_a_windows_edge(self):
        # the window closes at 2.0, inside the pass that ends at 2.4:
        # the acks after the close still belong to the window's evals;
        # the pass begun before the window opened does not
        v = _view([{"pass": [(0.2, 0.9), (1.0, 2.4)],
                    "ack": [(0.8, 0.9), (1.9, 1.95), (2.2, 2.3)],
                    "prepare": [(0.3, 0.5), (1.0, 1.2), (2.5, 2.7)]}],
                  [], [(0.5, 2.0)])
        assert v.passes == [(1.0, 2.4)]
        assert v.stretched == [(0.5, 2.4)]
        # acks begun in 0.5-2.4: three; prepare begun there: 1.0-1.2
        assert v.ms_per_eval("prepare") == pytest.approx(200.0 / 3)
        assert v.ms_per_eval("solo_place") is None
        # count, mean, median, longest of the spans begun in 0.5-2.4
        assert v.stage_table()["ack"] == pytest.approx((3, 0.25 / 3, 0.1, 0.1))
        assert v.stage_table()["prepare"] == pytest.approx((1, 0.2, 0.2, 0.2))
        assert hs.begun_in([(0.4, 0.6), (1.9, 2.6)], [(0.5, 2.0)]) == [
            (1.9, 2.6)]

    def test_a_program_without_spans_reads_nothing(self):
        v = _view([], [(0.0, 0.1)], [(0.0, 1.0)])
        assert v.ms_per_eval("prepare") is None
        assert v.unnamed_share() is None
        assert v.idle_unnamed_share() is None
        assert v.idle_table() is None


def _marks(*rows):
    # worker, applier, http, other; the process has had twice the worker
    return [(t, {**dict(zip(span_args.ROLES, us)),
                 span_args.PROCESS: 2 * us[0]}) for t, *us in rows]


class TestBudget:
    """`span_args.budget` on hand-made markers: known differences give
    known milliseconds and a known share."""

    # two passes of 0.100 s on one line; worker, applier, http, other
    LINE = ([(1.0, 1.1), (1.2, 1.3)],
            _marks((0.999, 1000, 500, 10, 0), (1.101, 61000, 30500, 5010, 0),
                   (1.199, 62000, 31500, 5010, 0),
                   (1.301, 142000, 41500, 5010, 10000)))

    def test_known_differences(self, monkeypatch):
        b = span_args.budget([self.LINE], [(0.5, 2.0)])
        assert b == {"passes": 2, "wall_s": pytest.approx(0.2),
                     "worker_us": 140000, "applier_us": 40000,
                     "http_us": 5000, "other_us": 10000,
                     "process_us": 280000}
        run = SimpleNamespace()
        monkeypatch.setattr(span_args, "of", lambda _: b)
        assert span_args.ms_per_pass(run, "worker_us") == 70.0
        assert span_args.ms_per_pass(run, "applier_us") == 20.0
        assert span_args.ms_per_pass(run, "http_us", "other_us") == 7.5
        assert span_args.held_share(run) == pytest.approx(97.5)
        text = span_args.describe(b)
        assert "100.0 ms a pass; " in text
        assert "worker 70.0, applier 20.0, http 2.5, other 5.0" in text
        assert "together 97.5 (97.5 % of the wall)" in text
        assert "2.5 ms of a pass no Python thread ran" in text
        assert "the whole process 140.0" in text

    def test_threads_beside_one_another_outside_the_lock(self):
        # CPU seconds are not seconds of the lock: two threads in system
        # calls or native code at once sum to more than the wall
        line = ([(1.0, 1.1)], _marks((0.99, 0, 0, 0, 0),
                                     (1.11, 80000, 50000, 13000, 0)))
        b = span_args.budget([line], [(0.0, 2.0)])
        assert 100.0 * sum(b[r] for r in span_args.ROLES) * 1e-6 \
            / b["wall_s"] == pytest.approx(143.0)
        assert "43.0 ms a pass MORE than its wall" in span_args.describe(b)

    def test_a_marker_without_the_process_reads_it_as_zero(self):
        passes, marks = self.LINE
        bare = [(t, {r: v for r, v in m.items() if r in span_args.ROLES})
                for t, m in marks]
        b = span_args.budget([(passes, bare)], [(0.5, 2.0)])
        assert (b["worker_us"], b["process_us"]) == (140000, 0)

    def test_only_the_passes_begun_in_the_windows(self):
        # the window opens after the first pass began: the second alone
        b = span_args.budget([self.LINE], [(1.15, 1.25)])
        assert (b["passes"], b["worker_us"]) == (1, 80000)
        assert b["wall_s"] == pytest.approx(0.1)
        assert span_args.budget([self.LINE], [(5.0, 6.0)]) is None

    def test_a_pass_without_both_markers_is_left_out(self):
        # the trace opened inside the first pass: no marker before it
        passes, marks = self.LINE
        b = span_args.budget([(passes, marks[1:])], [(0.5, 2.0)])
        assert (b["passes"], b["worker_us"]) == (1, 80000)

    def test_two_worker_lines_add(self):
        other = ([(1.05, 1.15)], _marks((1.04, 0, 0, 0, 0),
                                        (1.16, 50000, 0, 0, 0)))
        b = span_args.budget([self.LINE, other], [(0.5, 2.0)])
        assert (b["passes"], b["worker_us"]) == (3, 190000)

    def test_a_program_without_markers_reads_nothing(self):
        assert span_args.budget([], [(0.0, 9.0)]) is None
        run = SimpleNamespace(_trace_state="off", tmp="/nonexistent")
        assert span_args.of(run) is None
        assert span_args.held_share(run) is None
        assert span_args.ms_per_pass(run, "worker_us") is None
        assert span_args.gc_against_log(run) is None


def _cells_metrics(cells):
    return [(c, m, r) for c, ms in cells.items() for m, r in ms.items()]


class TestCells:
    """What `python3 -m benchmark.host_spans` and `python3 -m
    benchmark.span_cells` append to a cell: no cell's file lists these
    metrics yet, so the benchmark's own selftest does not hold them to
    their readers."""

    @pytest.mark.parametrize("cell,metric,reader",
                             _cells_metrics(hs.CELLS)
                             + _cells_metrics(span_cells.EXTRA))
    def test_metric_has_its_reader(self, cell, metric, reader):
        from benchmark.loader import load_json, load_module
        mod = load_module("layer_metrics", reader)
        assert mod.UNIT == ("%" if metric.endswith("_share") else "ms")
        assert callable(mod.read)
        assert metric not in load_json("workloads", cell)["per_layer"]
        # a run that was not traced gives nothing and does not raise
        assert mod.read(SimpleNamespace(_trace_state="off")) is None

    def test_extra_names_every_cell_of_the_benchmark(self):
        import json
        with open(os.path.join(os.path.dirname(hs.__file__), os.pardir,
                               "BENCHMARK.json")) as f:
            cells = {w["name"] for w in json.load(f)["workloads"]}
        # the four-chip cell, the ports cell, the groups cell and the
        # MIG cell came with `model_config` PRs, which may not edit
        # benchmark/span_cells.py: a `benchmark` PR's to list (PERF.md
        # section 7)
        assert set(span_cells.EXTRA) == cells - {"csi50k-drain-mesh4",
                                                 "ports50k-drain",
                                                 "groups50k-drain",
                                                 "mig50k-drain"}
        for cell, extra in span_cells.EXTRA.items():
            # a per-layer metric moves one end-to-end metric: bare names
            # in the batched path's cell alone, the cell's prefix elsewhere
            prefixes = {m.split(".")[0] for m in extra}
            if cell == "csi50k-drain":
                assert prefixes == {"lock", "worker", "runtime", "device",
                                    "stream"}
            else:
                assert len(prefixes) == 1, cell
            # nothing PR 24's listing already has
            assert not set(extra) & set(hs.CELLS.get(cell, {}))
            assert len(set(extra.values())) == len(extra)
        # the wave's stages only where a wave exists
        for cell in ("spread5k-drain", "system50k-drain"):
            assert not {"worker.finalize_ms_per_eval",
                        "worker.admin_ms_per_pass"} & set(
                span_cells.EXTRA[cell].values())

    @pytest.mark.parametrize("main,cell,last", [
        (hs.main, "spread5k-drain", None),
        (span_cells.main, "spread5k-drain", "solo.stream_send_ms_per_eval"),
        (span_cells.main, "spread50k-mixed", "mixed.admin_ms_per_pass")],
        ids=["host_spans", "span_cells-spread5k", "span_cells-spread50k"])
    def test_main_appends_them_to_the_cell_in_memory(self, monkeypatch,
                                                     main, cell, last):
        from benchmark import run as bench_run
        from benchmark.loader import load_json
        seen = {}

        def run_cell(cell, seed, seconds, trace):
            seen.update(bench_run.load_json("workloads", cell),
                        args=(seed, seconds, trace))
            return 0

        monkeypatch.setattr(bench_run, "load_json", bench_run.load_json)
        monkeypatch.setattr(bench_run, "run_cell", run_cell)
        monkeypatch.setattr(hs, "CELLS", dict(hs.CELLS))
        before = load_json("workloads", cell)
        assert main(["--workload", cell, "--seed", "2147483659",
                     "--seconds", "30"]) == 0
        extra = dict(hs.CELLS.get(cell, {}))
        if main is span_cells.main:
            assert set(span_cells.EXTRA[cell]) <= set(extra)
            assert seen["per_layer"][-1] == last
        assert seen["per_layer"] == before["per_layer"] + list(extra)
        assert seen["readers"] == {**before.get("readers", {}), **extra}
        assert seen["args"] == (2147483659, 30.0, True)
        # in memory: the cell's file is as it was
        assert load_json("workloads", cell) == before
