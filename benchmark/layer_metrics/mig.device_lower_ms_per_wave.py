"""Worker-thread time a wave's device group slot lowering takes: the
`nomad.device_lower` spans (ops/engine.py _lower_wave_slots, one a wave
that holds a device ask, inside that wave's `nomad.dispatch`), their mean
over the spans begun in the timed, traced windows
(benchmark/host_spans.py).  A program without the span reads nothing."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    v = host_spans.view(run)
    if v is None:
        return None
    spans = host_spans.begun_in(v.all.get("device_lower", []), v.stretched)
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
