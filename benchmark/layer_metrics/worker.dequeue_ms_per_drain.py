"""The head of a drain: worker-thread time in `nomad.dequeue` spans (the
broker's dequeue BEFORE a pass, from the first eval in hand to the batch
in hand; an empty poll leaves no span), a timed window.  Seconds of the
spans that END inside a timed, traced window x 1e3 over those windows
(benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    from benchmark import trace_reduce as tr
    view = host_spans.view(run)
    if view is None or "dequeue" not in view.all or not view.windows:
        return None
    ended = [(a, b) for a, b in view.all["dequeue"]
             if any(lo <= b <= hi for lo, hi in view.windows)]
    return tr.total(ended) * 1e3 / len(view.windows)
