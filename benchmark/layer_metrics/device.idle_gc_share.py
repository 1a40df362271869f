"""Share of the device's idle time that a collection covers: 100 x idle
seconds inside the timed, traced windows that a `nomad.gc` span of ANY
thread covers / idle seconds there (a collection stops every thread of
the server, whichever it struck).  Spans and device events share the
trace's one clock (benchmark/host_spans.py)."""

UNIT = "%"


def read(run):
    from benchmark import host_spans
    from benchmark import trace_reduce as tr
    view = host_spans.view(run)
    idle = view.idle() if view is not None else None
    if not idle or "gc" not in view.all:
        return None
    covered = host_spans.split_by_coverage(idle, {"gc": view.all["gc"]})
    return 100.0 * covered["gc"] / tr.total(idle)
