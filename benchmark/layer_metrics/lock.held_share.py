"""The CPU the server's threads spend inside the worker's passes, as a
share of the passes' walls: 100 x the CPU seconds of all four roles
between the passes' `nomad.cpu` markers / the passes' walls, over the
passes begun in the timed, traced windows (benchmark/span_args.py).
Under 100 the rest is time in which no Python thread ran, which overlap
could hide.  At 100 or over, a pass is at least the SUM of the work its
threads do under the interpreter lock, and only less work shortens it;
what is over 100 ran beside another thread outside the lock (system
calls, native code): CPU seconds are not seconds of the lock.  No sum of
roles can pass the process's own CPU, which the markers carry too."""

UNIT = "%"


def read(run):
    from benchmark import span_args
    return span_args.held_share(run)
