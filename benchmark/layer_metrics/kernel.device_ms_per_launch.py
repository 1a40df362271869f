"""Device time of one placement-kernel launch: summed durations of the
trace's `jit_place*` program executions over their count."""

UNIT = "ms"


def read(run):
    progs = {n: v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith("jit_place")}
    launches = sum(c for c, _ in progs.values())
    return (sum(s for _, s in progs.values()) / launches * 1e3
            if launches else None)
