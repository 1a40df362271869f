"""Milliseconds of a pass's time in which the interpreter's collector ran
a collection of generation 1 or 2, on whichever thread it struck: the
seconds of the program's own `nomad.gc` spans (core/telemetry.py)
clipped to the timed, traced windows x 1e3 over the passes begun there
(benchmark/host_spans.py).  On the trace's clock, where
`runtime.gc_s_in_window` takes the host's from outside."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    from benchmark import trace_reduce as tr
    view = host_spans.view(run)
    if view is None or "gc" not in view.all or not view.passes:
        return None
    inside = tr.clip(tr.union(view.all["gc"]), view.windows)
    return tr.total(inside) * 1e3 / len(view.passes)
