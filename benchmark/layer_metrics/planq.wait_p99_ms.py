"""99th percentile of a plan's enqueue-to-response latency in the window
(BASELINE's 'p99 plan-queue latency'), from the plan queue's own ring;
nothing when fewer than 1,000 plans fell in the window."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    p99 = stats.percentile_or_none(run.planq, 0.99)
    return p99 * 1e3 if p99 is not None else None
