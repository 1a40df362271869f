"""Mean over the window's waves of the bytes a device RECEIVES in one
launch's collectives, by the engine's own formula
(`PlacementEngine._note_collective`: rounds x (a [5, k] candidate pack
from every shard + the psum'd round metrics)), from the wave records'
`collective_bytes`.  A formula, not a reading: set it beside
mesh4.collective_share, which is the trace's.  A wave that launched on
one device carries none."""

UNIT = "KiB"


def read(run):
    sent = [w["collective_bytes"] for w in run.tap_window["waves"]
            if w.get("collective_bytes")]
    return sum(sent) / len(sent) / 1024.0 if sent else None
