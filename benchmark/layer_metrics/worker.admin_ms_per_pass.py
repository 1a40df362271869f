"""Worker-thread time a pass spends in `nomad.batch_admin` spans, the
batch's bookkeeping that no other stage covers (`Worker._finish_batch`:
delivery deadlines restarted, the chain's state, the prefetch's dequeue,
the chain parked; two or three spans a pass).  Seconds of those spans
begun in the timed, traced windows (each stretched to the end of its
last pass) x 1e3 over the passes begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    from benchmark import trace_reduce as tr
    view = host_spans.view(run)
    if view is None or "batch_admin" not in view.all or not view.passes:
        return None
    spans = host_spans.begun_in(view.all["batch_admin"], view.stretched)
    return tr.total(spans) * 1e3 / len(view.passes)
