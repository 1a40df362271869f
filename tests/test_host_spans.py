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
    import jax
    from stage_pass import run_small_pass

    run = SimpleNamespace(tmp=str(tmp_path_factory.mktemp("bench")),
                          _trace_state="off", result={})

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

    try:
        server = run_small_pass(between=start)
        run._trace_t1 = time.monotonic()
    finally:
        if run._trace_state == "on":
            jax.profiler.stop_trace()
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
        # `device` is no thread's wall and is never emitted
        assert seen == {s: n for s, n in counts.items() if s != "device"}
        worker = next(ln for ln in parsed["lines"] if "pass" in ln)
        waves = {w for _, _, w in worker["dispatch"]}
        assert waves == {w for w, _, _ in
                         server.stage_timers.intervals("dispatch")}
        assert {w for _, _, w in worker["plan_wait"]} == {-1}

    def test_worker_stages_nest_under_pass(self, traced):
        _, _, view = traced
        assert len(view.passes) == 3
        assert set(view.worker) == {
            "prepare", "dispatch", "device_wait", "d2h", "solo_place",
            "system_place", "materialize", "plan_wait", "eval_update",
            "ack"}
        for stage, spans in view.worker.items():
            for a, b in spans:
                assert any(lo <= a and b <= hi for lo, hi in view.passes), \
                    stage
        # the applier's stages are on another thread's line
        assert "commit" in view.all and "commit" not in view.worker
        assert "store_upsert" not in view.worker

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
        server, run, _ = traced
        totals = server.stage_timers.totals()
        named = sum(totals[s] for s in (
            "prepare", "dispatch", "device_wait", "d2h", "solo_place",
            "system_place", "materialize", "plan_wait", "eval_update",
            "ack"))
        expected = 100.0 * (1.0 - named / totals["pass"])
        got = hs.unnamed_share(run)
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


class TestCells:
    """What `python3 -m benchmark.host_spans` appends to a cell: no
    cell's file lists these metrics yet, so the benchmark's own selftest
    does not hold them to their readers."""

    @pytest.mark.parametrize("cell,metric,reader", [
        (c, m, r) for c, ms in hs.CELLS.items() for m, r in ms.items()])
    def test_metric_has_its_reader(self, cell, metric, reader):
        from benchmark.loader import load_json, load_module
        mod = load_module("layer_metrics", reader)
        assert mod.UNIT == ("%" if metric.endswith("_share") else "ms")
        assert callable(mod.read)
        assert metric not in load_json("workloads", cell)["per_layer"]
        # a run that was not traced gives nothing and does not raise
        assert mod.read(SimpleNamespace(_trace_state="off")) is None

    def test_main_appends_them_to_the_cell_in_memory(self, monkeypatch):
        from benchmark import run as bench_run
        seen = {}

        def run_cell(cell, seed, seconds, trace):
            seen.update(bench_run.load_json("workloads", cell),
                        args=(seed, seconds, trace))
            return 0

        monkeypatch.setattr(bench_run, "load_json", bench_run.load_json)
        monkeypatch.setattr(bench_run, "run_cell", run_cell)
        assert hs.main(["--workload", "spread5k-drain", "--seed",
                        "2147483659", "--seconds", "30"]) == 0
        extra = hs.CELLS["spread5k-drain"]
        assert seen["per_layer"][-len(extra):] == list(extra)
        assert seen["readers"]["solo.unnamed_share"] == "worker.unnamed_share"
        assert seen["readers"]["solo.device_idle_share"] == "device.idle_share"
        assert seen["args"] == (2147483659, 30.0, True)
