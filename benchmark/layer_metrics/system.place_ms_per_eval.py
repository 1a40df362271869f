"""Worker-thread time an eval spends in `nomad.system_place` spans: its
one `PlacementEngine.place_system` call (input build, the launch, the
wait for it, the fetch of the verdicts), timed by the program itself.
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "system_place")
