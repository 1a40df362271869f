"""Worker-thread time an eval spends in `nomad.eval_update` (the eval
status write) and `nomad.ack` (`Worker._settle`: a histogram, a flight
record, a trace record, a log line and the broker's ack) spans.
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "eval_update", "ack")
