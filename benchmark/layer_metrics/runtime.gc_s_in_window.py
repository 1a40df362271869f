"""Seconds inside the window in which the interpreter's garbage collector
ran (gc.callbacks, every generation).  A collection stops every thread of
the server; a full one over millions of live allocations takes most of a
second, and lands in whichever drain it interrupts."""

UNIT = "s"


def read(run):
    w0, w1 = run.result["window"]
    return float(sum(d for d, _ in run.gc_log.between(w0, w1)))
