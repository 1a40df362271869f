"""Time an eval costs the event stream's HTTP handler thread in
`nomad.stream_send` spans: ONE delivered event of `/v1/event/stream`
encoded (`wire()` + `json.dumps`) and written as a chunk, the wait for
the event left out; on its own thread, under the interpreter lock the
worker and the applier share.  Seconds of those spans begun in the
timed, traced windows over the `nomad.ack` spans begun there
(benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "stream_send")
