"""Worker-thread time an eval spends on its ports: the
`nomad.port_assign` spans (scheduler/generic.py _materialize_bulk: the
NetworkIndex build of every node the eval's picks touch and the
assignment, columnar or per allocation, inside that eval's
`nomad.materialize`).  Seconds of those spans begun in the timed, traced
windows over the `nomad.ack` spans begun there, so over ALL the window's
evals, the ones without ports too (benchmark/host_spans.py).  A program
without the span reads nothing."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "port_assign")
