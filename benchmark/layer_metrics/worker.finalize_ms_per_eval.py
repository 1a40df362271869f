"""Worker-thread time an eval of a wave spends in `nomad.finalize` spans:
`GenericScheduler.finalize_batched` from the applier's verdict in hand
to the eval done (the carve ledger's settle, the full-commit check, the
repair branch, the eval's completion).  Seconds of those spans begun in
the timed, traced windows over the `nomad.ack` spans begun there
(benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "finalize")
