"""Median over the window's waves of the host time spent building that
wave's plans from the kernel's picks (the flight recorder's summed
`materialize_s` per wave)."""

UNIT = "ms"


def read(run):
    from benchmark import stats
    ms = [w["materialize_s"] * 1e3 for w in run.tap_window["waves"]
          if "materialize_s" in w]
    return stats.median(ms) if ms else None
