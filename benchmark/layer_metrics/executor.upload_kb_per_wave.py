"""Host-to-device bytes the executor uploaded per launch in the window."""

UNIT = "KiB"


def read(run):
    a, b = run.c0["executor"], run.c1["executor"]
    n = b["dispatches"] - a["dispatches"]
    return ((b["upload_bytes"] - a["upload_bytes"]) / n / 1024.0
            if n > 0 else None)
