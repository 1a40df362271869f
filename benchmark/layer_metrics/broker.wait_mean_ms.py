"""Mean wait of an evaluation in the broker (ready to dequeued) inside the
window: difference of `nomad.broker.wait_s`'s sum over that of its count.
A mean and not a median, because the program keeps a fixed-bucket
histogram whose median is an interpolation inside a 2.5x-wide bucket."""

UNIT = "ms"


def read(run):
    a, b = run.c0["broker_wait"], run.c1["broker_wait"]
    n = b["count"] - a["count"]
    return (b["sum"] - a["sum"]) / n * 1e3 if n > 0 else None
