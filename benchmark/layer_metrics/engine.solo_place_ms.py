"""Median wall of one `PlacementEngine.place` call on the solo path (input
build, the scan's launch, the wait for it, the fetch and the decision
rows), from the benchmark's own span around the call; the solo path
records no StageTimers interval of its own."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    w0, w1 = run.result["window"]
    ms = [(b - a) * 1e3 for a, b in run.spans.get("engine.solo_place", ())
          if w0 <= a <= w1]
    return stats.median(ms) if ms else None
