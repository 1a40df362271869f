"""The worker thread's own CPU milliseconds a pass: the sum, over the
passes begun in the timed, traced windows, of `worker_us` at the
`nomad.cpu` marker after the pass less that at the marker before it,
over those passes (benchmark/span_args.py).  A sum, because the thread
clock ticks coarsely; CPU, not wall: what a stage's wall holds of OTHER
threads' turns under the one interpreter lock is not in it."""

UNIT = "ms"


def read(run):
    from benchmark import span_args
    return span_args.ms_per_pass(run, "worker_us")
