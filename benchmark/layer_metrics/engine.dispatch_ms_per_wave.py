"""Median `dispatch` interval in the window: packing one wave's inputs on
the host and enqueueing its launch."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    ms = [(b - a) * 1e3 for _, a, b in
          run.tap_window["intervals"].get("dispatch", ())]
    return stats.median(ms) if ms else None
