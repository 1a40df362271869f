"""Worker-thread time an eval spends in `nomad.materialize` spans:
building its plan from the kernel's picks, on the batched path (per wave)
and on the solo path (after `engine.place`).
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "materialize")
