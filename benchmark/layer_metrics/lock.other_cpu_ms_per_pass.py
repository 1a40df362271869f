"""CPU milliseconds a pass of every thread that stamps itself and is
neither the worker nor the applier: `http_us` (the HTTP handlers, the
event stream's among them) plus `other_us`, between a pass's two
`nomad.cpu` markers, summed over the passes begun in the timed, traced
windows, over those passes (benchmark/span_args.py)."""

UNIT = "ms"


def read(run):
    from benchmark import span_args
    return span_args.ms_per_pass(run, "http_us", "other_us")
