"""Applier-thread time an eval's plans spend in `nomad.store_upsert`
spans: `StateStore.upsert_plan_results` alone, inside `nomad.commit`.
Seconds of those spans begun in the timed, traced windows over the
`nomad.ack` spans begun there (benchmark/host_spans.py)."""

UNIT = "ms"


def read(run):
    from benchmark import host_spans
    return host_spans.ms_per_eval(run, "store_upsert")
