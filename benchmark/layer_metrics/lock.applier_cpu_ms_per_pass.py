"""The applier thread's CPU milliseconds a pass of the worker: the sum,
over the passes begun in the timed, traced windows, of `applier_us`
between a pass's two `nomad.cpu` markers, over those passes
(benchmark/span_args.py).  The applier stamps its own clock after every
plan, so a commit in flight at a marker counts to the next pass."""

UNIT = "ms"


def read(run):
    from benchmark import span_args
    return span_args.ms_per_pass(run, "applier_us")
