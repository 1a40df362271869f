"""Worker-thread time an eval spends in `nomad.solo_place` spans: its
`PlacementEngine.place` calls (input build, the scan's launch, the wait
for it, the fetch and the decision rows), timed by the program itself.
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "solo_place")
