"""Share of launches in the window that started from the device-resident
usage chain instead of a host re-sync: resident_waves / dispatches."""

UNIT = "%"


def read(run):
    a, b = run.c0["executor"], run.c1["executor"]
    n = b["dispatches"] - a["dispatches"]
    return (100.0 * (b["resident_waves"] - a["resident_waves"]) / n
            if n > 0 else None)
