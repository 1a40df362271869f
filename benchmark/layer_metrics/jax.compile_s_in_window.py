"""Seconds inside the window spent in jax's own trace, lower and backend-
compile events (jax.monitoring; not the program's compile ledger).  A
single event of 1 s or more fails the run; what is left is sub-second
glue below the persistent cache's threshold."""

UNIT = "s"


def read(run):
    return float(sum(e[2] for e in run.compile_events))
