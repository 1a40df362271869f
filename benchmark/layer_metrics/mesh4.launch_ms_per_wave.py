"""The host's side of one sharded launch: seconds of the program's
`mesh_launch` stage in the window over its count (core/wavepipe.py
STAGES: the call of the wave's node-sharded program, inside `dispatch`,
where the replicated inputs go to every device and one execution a
device is enqueued).  An engine with no mesh records none."""

UNIT = "ms"


def read(run):
    n = (run.c1["stage_counts"].get("mesh_launch", 0)
         - run.c0["stage_counts"].get("mesh_launch", 0))
    if n <= 0:
        return None
    return (run.c1["stage_totals"]["mesh_launch"]
            - run.c0["stage_totals"].get("mesh_launch", 0.0)) / n * 1e3
