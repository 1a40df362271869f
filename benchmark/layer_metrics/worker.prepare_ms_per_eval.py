"""Worker-thread time an eval spends in `nomad.prepare` spans: waiting for
the store to reach the batch's index, the snapshot, and a scheduler plus
its reconcile per eval (`Worker._start_batch` up to the dispatch).
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "prepare")
