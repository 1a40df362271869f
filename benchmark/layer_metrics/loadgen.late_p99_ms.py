"""How late the generator ran: 99th percentile of (sent - due) over the jobs
due in the window.  A starved generator must not read as a fast server;
the run fails above the traffic file's `late_p99_limit_ms`."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    late = run.result.get("late_ms")
    return stats.percentile_or_none(late, 0.99) if late else None
