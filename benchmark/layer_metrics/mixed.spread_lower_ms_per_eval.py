"""Worker-thread time an eval's share of its wave's spread lowering
takes: the `nomad.spread_lower` spans (ops/engine.py
_lower_wave_spreads, one a wave that holds a spread item, inside that
wave's `nomad.dispatch`).  Seconds of those spans begun in the timed,
traced windows over the `nomad.ack` spans begun there
(benchmark/host_spans.py).  A program without the span reads nothing."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "spread_lower")
