"""Median wall of one `PUT /v1/jobs` as the client saw it (send to response
read), over the jobs due in the window."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    walls = [(r["acked"] - r["sent"]) * 1e3
             for r in (run.records[i] for i in run.result["measured_jobs"])
             if r["acked"] is not None and r["sent"] is not None]
    return stats.median(walls) if walls else None
