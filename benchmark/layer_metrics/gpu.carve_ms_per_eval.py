"""Worker-thread time an eval spends carving device instance ids for its
picks: the `nomad.device_carve` spans (scheduler/device.py carve_block,
inside that eval's `nomad.materialize`).  Seconds of those spans begun in
the timed, traced windows over the `nomad.ack` spans begun there
(benchmark/host_spans.py).  A program without the span reads nothing."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "device_carve")
