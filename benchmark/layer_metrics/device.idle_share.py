"""Share of the timed intervals inside the traced seconds in which no
program ran on the device: 1 - union of the trace's program intervals
over the timed time."""

UNIT = "%"


def read(run):
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
