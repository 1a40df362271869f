"""Share of the worker's passes that no stage names: 100 x (1 - seconds
of the worker thread's `nomad.*` spans inside the `nomad.pass` spans
begun in the timed, traced windows / seconds of those passes).  Worker
stages never nest in one another (tests/test_wavepipe.py), so this is a
subtraction (benchmark/host_spans.py)."""

UNIT = "%"


def read(run):
    from benchmark import host_spans
    return host_spans.unnamed_share(run)
