"""Share of the device's idle time in which the worker was in no named
stage: 100 x idle seconds inside the timed, traced windows that no
worker-thread `nomad.*` span other than `nomad.pass` covers / idle
seconds there.  Spans and device events share the trace's one clock
(benchmark/host_spans.py)."""

UNIT = "%"


def read(run):
    from benchmark import host_spans
    return host_spans.idle_unnamed_share(run)
