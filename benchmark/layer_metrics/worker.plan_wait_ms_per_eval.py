"""Worker-thread time an eval spends in `nomad.plan_wait` spans: blocked
on the applier's verdict for a plan it submitted (`Worker.wait_plan`).
Near `commit.ms_per_plan`, the applier paces the worker.
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "plan_wait")
